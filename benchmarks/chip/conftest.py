import os

# Two virtual CPU devices for the tensor-parallel cases (the weights made
# in their shardings, the harness at tp = 2), as tests/conftest.py gives
# the program's tests: set before the first JAX backend starts.
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")
