"""Oracles for the segagg kernel: pure jnp, and plain numpy."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def segagg_ref(keys: jax.Array, values: jax.Array, num_groups: int) -> jax.Array:
    """keys (N,) int32, values (N, V) -> (num_groups, V) f32 group sums."""
    return jax.ops.segment_sum(
        values.astype(jnp.float32), keys, num_segments=num_groups)


def combine_ref(partials: jax.Array) -> jax.Array:
    """Final aggregation (paper §2.1): sum the per-batch partials.
    partials: (num_batches, G, V) -> (G, V)."""
    return partials.sum(axis=0)


def pane_segagg_ref(keys: jax.Array, values: jax.Array, pane_ids: jax.Array,
                    num_panes: int, num_groups: int) -> jax.Array:
    """Oracle for the pane-partial aggregation op: ONE pass over (N,) keys /
    (N, V) values / (N,) pane assignments -> (num_panes, num_groups, V)
    per-pane group sums (pane sharing, repro.core.panes)."""
    composite = pane_ids.astype(jnp.int32) * num_groups + keys.astype(jnp.int32)
    flat = jax.ops.segment_sum(
        values.astype(jnp.float32), composite,
        num_segments=num_panes * num_groups)
    return flat.reshape(num_panes, num_groups, values.shape[-1])


def segagg_numpy(keys, values, num_groups: int) -> np.ndarray:
    """Plain numpy oracle, independent of JAX: float64 ``np.bincount`` with
    weights per value column -> (num_groups, V) float64 group sums."""
    keys = np.asarray(keys)
    values = np.asarray(values, np.float64)
    if values.ndim == 1:
        values = values[:, None]
    return np.stack([np.bincount(keys, weights=values[:, j],
                                 minlength=num_groups)
                     for j in range(values.shape[1])], axis=1)
