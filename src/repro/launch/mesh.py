"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run forces 512 host devices and must do
so before any jax initialization).
"""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the locally available devices (tests)."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


def make_tp_mesh(tp: int) -> jax.sharding.Mesh:
    """1-D serving mesh: the first ``tp`` local devices on a single
    'model' axis (DESIGN.md §Sharded serving). Each tensor-parallel
    Engine owns one of these; a cluster of engines with different ``tp``
    is a set of disjoint meshes over one host's devices."""
    n = len(jax.devices())
    assert 1 <= tp <= n, f"tp={tp} needs {tp} devices, have {n}"
    return jax.make_mesh((tp,), ("model",), axis_types=_auto(1),
                         devices=jax.devices()[:tp])


def batch_axes(mesh: jax.sharding.Mesh):
    """The (super-)axis batch shards over: ('pod','data') when a pod axis
    exists, else ('data',)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
