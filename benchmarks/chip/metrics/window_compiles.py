"""Programs compiled inside the measured window, or loaded there from the
persistent compilation cache: JAX's ``backend_compile_duration`` event,
which fires for both. Each is a step shape the warm-up did not meet.
Layer: launcher warm-up. Moves ``tokens_per_s``: each one stalls every
engine step behind it."""


def read(ctx):
    return ctx["window_compiles"]
