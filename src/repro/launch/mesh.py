"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  Shapes: single pod = (data=16, model=16) — 256
chips of TPU v5e; multi-pod = (pod=2, data=16, model=16) = 512 chips, the
"pod" axis carrying inter-pod data parallelism only (gradient all-reduce).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the step code places arrays with with_sharding_constraint,
    # which only accepts Auto axes (make_mesh defaults to Explicit).
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return _auto_mesh((n // mp, mp), ("data", "model"))


# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW_PER_LINK = 50e9         # bytes/s/link (~ per direction)
HBM_BYTES = 16 * 1024**3       # 16 GiB
