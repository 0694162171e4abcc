"""Pallas TPU kernel: blocked GROUP-BY partial aggregation (the paper's
query-executor hot spot — CQ1..CQ4 / TPC-H COUNT/SUM GROUP BY).

Two formulations of the same segment-sum, selected per call shape by
``ops.segagg`` (``tuning.pick_formulation``); a third, for group domains
too wide for either, is XLA's own scatter-add in ``ops`` (below):

MATMUL (DESIGN.md §2): instead of a hash table (the CPU/Spark formulation —
pointer chasing, no TPU analogue), aggregation is a blocked ONE-HOT MATMUL
on the MXU:

    partial[g, v] = sum_i  [keys_i == g] * values[i, v]

Grid: (num_group_blocks, num_row_blocks).  Each instance builds the
(block_g x block_n) one-hot membership matrix in VMEM from an iota compare
(never in HBM) and contracts it with the (block_n x V) value block on the
MXU, accumulating into the (block_g x V) output block across the row-block
grid dimension (the sequential minor axis on TPU).  Work is O(N·G·V) MXU
FLOPs — cheap for narrow G, quadratic waste for wide G.

SCATTER-ADD: the classic formulation — one sequential pass over the row
block doing ``out[key] += value`` into the full (G, V) accumulator held
on-chip.  Work is O(N·V), independent of G, so it wins once the one-hot's
O(N·G) FLOPs dominate; the price is a serial row loop (VPU, no MXU) and a
resident (G, V) accumulator (must fit VMEM on real hardware — ``ops``
checks before selecting it).

Past that VMEM budget (``SCATTER_VMEM_BYTES``; the width is padded to 128
lanes, so about 16K groups) neither kernel here runs: ``ops.segagg``
hands the call to ``"hbm_scatter"``, a jitted XLA scatter-add whose
(G, V) accumulator stays in HBM at its unpadded width.  The one-hot
matmul at CQ3/CQ4 widths (G = 360K, 1.5M) would spend 1e13-1e14 MXU FLOPs
per 156K rows on an answer that needs O(N) bytes.

Batches of rows become independent partial aggregates; the paper's "final
aggregation" is then a trivial add over partials (`combine`), whose cost
grows with num_groups x num_batches exactly as the paper's §6.2 model says.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 1024   # default rows per block (lane-dim multiple of 128)
BLOCK_G = 256    # default groups per block (sublane-dim multiple of 8)
# value width is padded to the 128-lane MXU boundary by ops.segagg

# VMEM budget for the scatter variant's resident (G, V) accumulator
# (~16 MB/core on TPU; leave headroom for the row block + loop state).
SCATTER_VMEM_BYTES = 8 * 2**20


def _segagg_matmul_kernel(keys_ref, values_ref, out_ref, *, block_g: int):
    gi = pl.program_id(0)
    ni = pl.program_id(1)

    keys = keys_ref[...]                     # (1, block_n) int32, rows on lanes
    vals = values_ref[...]                   # (block_n, V)

    g0 = gi * block_g
    # (block_g, block_n) one-hot membership, built in VMEM.
    gids = g0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_g, keys.shape[1]), 0)
    onehot = (gids == keys).astype(vals.dtype)

    # MXU contraction: (block_g, block_n) @ (block_n, V) -> (block_g, V).
    # HIGHEST keeps f32 values at f32 precision (the default may feed the
    # MXU bf16, which would round float sums such as TPC-Q6's revenue).
    partial = jax.lax.dot(
        onehot, vals,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(ni == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


def _segagg_scatter_kernel(keys_ref, values_ref, out_ref):
    ni = pl.program_id(0)

    @pl.when(ni == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, _):
        # out[key_i] += value_i — the key is a scalar read from SMEM, the
        # row a dynamic single-row accumulate into the VMEM accumulator.
        out_ref[pl.ds(keys_ref[i], 1), :] += (
            values_ref[pl.ds(i, 1), :].astype(jnp.float32))
        return 0

    jax.lax.fori_loop(0, keys_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def segagg_pallas(keys: jax.Array, values: jax.Array, num_groups: int,
                  interpret: bool = True, block_n: int = BLOCK_N,
                  block_g: int = BLOCK_G,
                  formulation: str = "matmul") -> jax.Array:
    """keys: (N,) int32 in [0, num_groups); values: (N, V) float.
    Returns (num_groups, V) f32 partial aggregate.  N must be a block_n
    multiple; for the matmul formulation num_groups must be a block_g
    multiple (ops.segagg handles padding).  ``formulation`` selects the
    one-hot MXU matmul vs the sequential scatter-add variant."""
    N, V = values.shape
    assert N % block_n == 0, (N, block_n)
    # Under shard_map the output varies over the same mesh axes as the rows.
    out_shape = jax.ShapeDtypeStruct((num_groups, V), jnp.float32,
                                     vma=jax.typeof(values).vma)
    if formulation == "scatter":
        return pl.pallas_call(
            _segagg_scatter_kernel,
            grid=(N // block_n,),
            in_specs=[
                # Keys are read one scalar per row: SMEM, one block at a
                # time (the whole key array would not fit SMEM).
                pl.BlockSpec((block_n,), lambda n: (n,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((block_n, V), lambda n: (n, 0)),
            ],
            out_specs=pl.BlockSpec((num_groups, V), lambda n: (0, 0)),
            out_shape=out_shape,
            interpret=interpret,
        )(keys, values)
    assert num_groups % block_g == 0, (num_groups, block_g)
    grid = (num_groups // block_g, N // block_n)
    return pl.pallas_call(
        functools.partial(_segagg_matmul_kernel, block_g=block_g),
        grid=grid,
        in_specs=[
            # Keys as one (1, N) row: rows on the 128 lanes, which Mosaic
            # tiles for any block_n that is a multiple of 128.
            pl.BlockSpec((1, block_n), lambda g, n: (0, n)),
            pl.BlockSpec((block_n, V), lambda g, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((block_g, V), lambda g, n: (g, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(keys.reshape(1, N), values)
