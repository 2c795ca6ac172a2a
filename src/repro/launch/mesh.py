"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state — jax locks the device count at first backend init, and smoke tests
must see 1 CPU device while the dry-run sees 512 placeholders.
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dp_mesh(n: int | None = None):
    """Pure data-parallel mesh (the sparse-allreduce setting)."""
    n = n or len(jax.devices())
    return make_mesh((n,), ("data",))


def make_dp_tp_mesh(data: int | None = None, model: int = 1):
    """('data', 'model') mesh for the sparse-DP × TP composition
    (DESIGN.md §8). ``data=None`` takes every local device divided by
    ``model``; model-axis neighbours stay physically adjacent (the dense
    psum_scatter/all_gather legs ride the fast links)."""
    if data is None:
        n = len(jax.devices())
        if n % model:
            raise ValueError(f"{n} devices do not split into model={model}")
        data = n // model
    return make_mesh((data, model), ("data", "model"))


def chips(mesh) -> int:
    import numpy as np
    return int(np.prod(list(mesh.shape.values())))
