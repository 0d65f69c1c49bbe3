"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because smoke tests and
benches must see 1 device while the dry-run forces 512.

Single pod: (data=16, model=16) — 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the pod axis is pure
data parallelism (gradient all-reduce crosses DCN), which is also where
gradient compression applies.

``make_mesh`` is the constructor every caller (and test) uses: it builds
meshes whose axes are all ``AxisType.Auto``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices,
        axis_types=(AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))
