"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis extends
data parallelism across the inter-pod (DCN/ICI) boundary — gradient
all-reduce crosses pods once per step.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh over ``devices`` (default: all of them) with Auto axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh() -> Mesh:
    """Degenerate mesh over however many local devices exist (CPU tests)."""
    n = jax.device_count()
    return make_mesh((n, 1), ("data", "model"))
