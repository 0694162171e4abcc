"""Public segagg op: backend dispatch, padding, dtype handling, multi-level
combine.

Backend resolution (``backend=``):

* ``"auto"`` (default) — compiled Pallas kernel on TPU/GPU, the jitted XLA
  scatter-add formulation on CPU.  Every call site gets the fastest
  compiled path for the platform it runs on.
* ``"pallas"`` — the compiled Pallas kernel (requires a TPU/GPU backend;
  raises on CPU, where Pallas can only interpret).
* ``"xla"`` — jitted XLA formulation: ``zeros.at[keys].add(values)``
  scatter-add, or a scan-blocked one-hot matmul for narrow G (the measured
  crossover in ``tuning`` selects per call shape).
* ``"interpret"`` — the Pallas kernel body run under the Pallas interpreter
  (the pre-PR-8 default).  Kept for CI parity on CPU: it executes the SAME
  kernel code the TPU path compiles, just slowly.

The legacy ``interpret: bool`` positional is still accepted (``True`` →
``backend="interpret"``, ``False`` → ``backend="pallas"``) so pre-dispatch
callers keep working unchanged.

Formulations (``tuning.pick_formulation``, by call shape alone):

* G up to the measured crossover (``tuning.matmul_max_g``): the blocked
  one-hot matmul, O(N·G·V) MXU work.
* Wider G on the Pallas backends: the Pallas scatter-add while its resident
  (G, 128-lane) f32 accumulator fits VMEM (``SCATTER_VMEM_BYTES``), else
  ``"hbm_scatter"``: XLA's own scatter-add, ``zeros((G, V)).at[keys].add``,
  with the accumulator in HBM and V unpadded.  A 360K- or 1.5M-group
  GROUP-BY padded to 128 lanes needs 184-768 MB of accumulator, which no
  VMEM holds, and the one-hot matmul there is 1e13-1e14 FLOPs per 156K
  rows where the answer needs O(N) bytes; XLA's compiled scatter does
  the O(N·V) work in about 1.7 ms on a TPU v5e.
* Wider G on ``"xla"``: the same XLA scatter-add.

The padding runs inside jitted programs: ``"hbm_scatter"`` is one
program per call; the Pallas kernels are two, the row and lane padding
(one program per row count) and the kernel with its output slice (one per
padded shape, so row counts in one row block share a Mosaic compile).
``tuning.tuned_blocks`` supplies hillclimb-tuned (block_n, block_g) per
(backend, shape-class).  With ``repro.tracing`` on, each eager dispatch
adds one to the counter ``segagg.<formulation>``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ... import tracing
from . import tuning
from .segagg import segagg_pallas

BACKENDS = ("auto", "pallas", "xla", "interpret")

_INT32_MAX = jnp.iinfo(jnp.int32).max


def resolve_backend(backend: Optional[str] = None,
                    interpret: Optional[bool] = None) -> str:
    """Canonical concrete backend for one call.

    ``interpret`` is the legacy knob: when given (not None) it wins, mapping
    ``True`` → ``"interpret"`` and ``False`` → ``"pallas"``.  ``backend``
    is then resolved: ``None``/``"auto"`` picks compiled Pallas on TPU/GPU
    and compiled XLA on CPU; explicit names are validated.
    """
    if interpret is not None:
        if backend not in (None, "auto"):
            raise ValueError(
                "pass either the legacy interpret= bool or backend=, not both")
        backend = "interpret" if interpret else "pallas"
    if backend is None:
        backend = "auto"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown segagg backend: {backend!r} (expected one of {BACKENDS})")
    if backend == "auto":
        return "pallas" if jax.default_backend() in ("tpu", "gpu") else "xla"
    if backend == "pallas" and jax.default_backend() not in ("tpu", "gpu"):
        raise ValueError(
            "backend='pallas' compiles the Pallas kernel and needs a TPU/GPU "
            "jax backend; on CPU use backend='xla' (compiled) or "
            "backend='interpret' (Pallas interpreter, CI parity path)")
    return backend


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


# -- XLA formulations ------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _segagg_xla_scatter(keys: jax.Array, values: jax.Array,
                        num_groups: int, block_n: int = 1) -> jax.Array:
    """Scatter-add into a (num_groups, V) f32 accumulator in HBM: O(N·V)
    work regardless of G.  Rows are padded to a ``block_n`` multiple with
    key ``num_groups``; those, like any key outside [0, num_groups), are
    dropped, as the kernel path's sacrificial padding group is."""
    n = keys.shape[0]
    pad = _pad_to(n, block_n) - n
    with jax.named_scope("segagg.pad"):
        keys = jnp.pad(keys.astype(jnp.int32), (0, pad),
                       constant_values=num_groups)
        values = jnp.pad(values.astype(jnp.float32), ((0, pad), (0, 0)))
    with jax.named_scope("segagg.kernel"):
        return jnp.zeros((num_groups, values.shape[1]), jnp.float32).at[
            keys].add(values, mode="drop")


_XLA_MM_BLOCK_N = 16_384  # rows per scan step: bounds the one-hot to ~G*64KB


@functools.partial(jax.jit, static_argnums=(2,))
def _segagg_xla_matmul(keys: jax.Array, values: jax.Array,
                       num_groups: int) -> jax.Array:
    """Scan-blocked one-hot matmul: same formulation the Pallas kernel runs
    on the MXU, expressed as XLA ops.  O(N·G·V) FLOPs — only selected for
    narrow G (below the measured crossover)."""
    N, V = values.shape
    keys = keys.astype(jnp.int32)
    block = min(_XLA_MM_BLOCK_N, _pad_to(N, 8))
    Np = _pad_to(N, block)
    # Padding rows carry key == num_groups: outside every gid, so their
    # one-hot row is all zero.
    keys_p = jnp.full((Np,), num_groups, jnp.int32).at[:N].set(keys)
    vals_p = jnp.zeros((Np, V), jnp.float32).at[:N].set(
        values.astype(jnp.float32))
    gids = jnp.arange(num_groups, dtype=jnp.int32)
    keys_b = keys_p.reshape(-1, block)
    vals_b = vals_p.reshape(-1, block, V)

    def block_sum(k, v):
        onehot = (k[:, None] == gids[None, :]).astype(jnp.float32)
        return onehot.T @ v

    # The first block's partial seeds the carry, so the carry has the type
    # of the data (under shard_map: varying over the mesh axis), which a
    # zeros seed would not.
    out, _ = jax.lax.scan(
        lambda acc, kv: (acc + block_sum(*kv), None),
        block_sum(keys_b[0], vals_b[0]), (keys_b[1:], vals_b[1:]))
    return out


# -- Pallas formulations --------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _pad_rows(keys: jax.Array, values: jax.Array, num_groups: int,
              rows: int, lanes: int) -> Tuple[jax.Array, jax.Array]:
    """Keys and values padded to ``rows`` rows, as one program: padded
    rows carry key ``num_groups`` (the kernels' sacrificial group) and
    values are zero-padded to ``lanes`` lanes."""
    N, V = values.shape
    with jax.named_scope("segagg.pad"):
        return (jnp.pad(keys.astype(jnp.int32), (0, rows - N),
                        constant_values=num_groups),
                jnp.pad(values, ((0, rows - N), (0, lanes - V))))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _segagg_pallas_sliced(keys_p: jax.Array, vals_p: jax.Array,
                          num_groups: int, width: int, interpret: bool,
                          block_n: int, block_g: int,
                          formulation: str) -> jax.Array:
    """The Pallas kernel on padded rows, sliced back to (num_groups,
    width): one program per padded shape, whatever the unpadded row count."""
    Gp = _pad_to(num_groups + 1, block_g)   # +1 sacrificial group for padding
    with jax.named_scope("segagg.kernel"):
        out = segagg_pallas(keys_p, vals_p, Gp, interpret, block_n, block_g,
                            formulation)
    with jax.named_scope("segagg.pad"):
        return out[:num_groups, :width]


# -- dispatch --------------------------------------------------------------

def segagg(keys: jax.Array, values: jax.Array, num_groups: int,
           interpret: Optional[bool] = None, *,
           backend: Optional[str] = None,
           formulation: Optional[str] = None) -> jax.Array:
    """GROUP-BY partial aggregation: (N,) keys + (N, V) values ->
    (num_groups, V) f32 sums.

    ``backend=`` selects the execution path (see module docstring);
    ``formulation=`` overrides the per-shape choice ("matmul" | "scatter" |
    "hbm_scatter").  The legacy positional ``interpret`` bool still works:
    True → the interpreter path, False → compiled Pallas.
    """
    be = resolve_backend(backend, interpret)
    if num_groups <= 0:
        raise ValueError(f"num_groups must be positive, got {num_groups}")
    if values.ndim == 1:
        values = values[:, None]
    N = keys.shape[0]
    V = values.shape[1]
    if N == 0:
        return jnp.zeros((num_groups, V), jnp.float32)
    form = tuning.pick_formulation(be, N, num_groups, V, formulation)
    if not isinstance(keys, jax.core.Tracer):  # under a jit: not a dispatch
        tracing.count(f"segagg.{form}")
    if form == "hbm_scatter":
        block_n, _ = tuning.tuned_blocks(be, N, num_groups)
        return _segagg_xla_scatter(keys, values, num_groups, block_n)
    if be == "xla":
        if form == "scatter":
            return _segagg_xla_scatter(keys, values, num_groups)
        return _segagg_xla_matmul(keys, values, num_groups)
    # Pallas paths: the padding is one program per row count, the kernel
    # one per padded shape, so the Mosaic compiles are shared across row
    # counts in one row block.
    block_n, block_g = tuning.tuned_blocks(be, N, num_groups)
    keys_p, vals_p = _pad_rows(keys, values, num_groups, _pad_to(N, block_n),
                               _pad_to(V, 128))
    return _segagg_pallas_sliced(keys_p, vals_p, num_groups, V,
                                 be == "interpret", block_n, block_g, form)


def group_count(keys: jax.Array, num_groups: int,
                interpret: Optional[bool] = None, *,
                backend: Optional[str] = None) -> jax.Array:
    """COUNT(*) GROUP BY — values = ones."""
    ones = jnp.ones((keys.shape[0], 1), jnp.float32)
    return segagg(keys, ones, num_groups, interpret, backend=backend)[:, 0]


def combine(partials: jax.Array) -> jax.Array:
    """Final aggregation step over per-batch partials: (B, G, V) -> (G, V)."""
    return partials.sum(axis=0)


def pane_composite_groups(num_panes: int, num_groups: int) -> int:
    """Composite segment count for the pane x group key space, guarded
    against int32 overflow: pane_segagg keys are ``pane * num_groups +
    group`` in int32, so the product must stay addressable."""
    total = num_panes * num_groups  # Python ints: no silent wraparound
    if total > _INT32_MAX:
        raise ValueError(
            f"pane_segagg composite key space num_panes*num_groups = "
            f"{num_panes}*{num_groups} = {total} exceeds int32 "
            f"({_INT32_MAX}); split the pane run into "
            f"<= {_INT32_MAX // max(num_groups, 1)} panes per scan")
    return total


def pane_segagg(keys: jax.Array, values: jax.Array, pane_ids: jax.Array,
                num_panes: int, num_groups: int,
                interpret: Optional[bool] = None, *,
                backend: Optional[str] = None) -> jax.Array:
    """Pane-partial aggregation for shared execution (repro.core.panes):
    one scan over (N,) keys + (N, V) values with per-row pane assignments
    ``pane_ids`` -> (num_panes, num_groups, V) f32 per-pane group sums.

    Runs through the SAME blocked segagg kernel via composite keys
    ``pane * num_groups + group`` — the pane axis is just more segments, so
    one kernel pass produces every pane's partial at once, ready to be
    cached in a ``PaneStore`` and fanned out to subscribed windows with
    ``merge_panes``.  ``backend=`` dispatches exactly like ``segagg``.
    """
    if values.ndim == 1:
        values = values[:, None]
    total = pane_composite_groups(num_panes, num_groups)
    composite = pane_ids.astype(jnp.int32) * num_groups + keys.astype(jnp.int32)
    flat = segagg(composite, values, total, interpret, backend=backend)
    return flat.reshape(num_panes, num_groups, values.shape[1])


def merge_panes(pane_partials: jax.Array) -> jax.Array:
    """Fan-out merge of cached pane partials into one window aggregate:
    (P, G, V) -> (G, V).  The merge side of "one scan + k merges" — same
    combine as the final aggregation, over panes instead of batches."""
    return pane_partials.sum(axis=0)


def flops_bytes(n: int, num_groups: int, v: int, formulation: str,
                backend: str = "xla") -> Tuple[float, float]:
    """Analytic (FLOPs, HBM bytes) of one segagg call — the numerators of
    the roofline terms (benchmarks/bench_roofline.py).  The Pallas paths
    pad rows/groups/width to kernel blocks and that padded work really
    runs, so their counts use padded extents; ``"hbm_scatter"`` pads only
    rows, to the Pallas row block; the XLA paths only pad rows for the
    matmul scan.  Matmul counts the one-hot contraction; scatter one
    multiply-accumulate per row element.  Bytes: keys + values read,
    (G, V) f32 partial written."""
    if formulation == "hbm_scatter":
        bn, _ = tuning.tuned_blocks(backend, n, num_groups)
        np_, gp, vp = _pad_to(n, bn), num_groups, v
    elif backend in ("pallas", "interpret"):
        vp = _pad_to(v, 128)
        bn, bg = tuning.tuned_blocks(backend, n, num_groups)
        np_, gp = _pad_to(n, bn), _pad_to(num_groups + 1, bg)
    else:
        vp, gp = v, num_groups
        np_ = _pad_to(n, min(_XLA_MM_BLOCK_N, _pad_to(n, 8))) \
            if formulation == "matmul" else n
    if formulation == "matmul":
        flops = 2.0 * np_ * gp * vp
    else:
        flops = 2.0 * np_ * vp
    bytes_ = 4.0 * np_ + 4.0 * np_ * vp + 4.0 * gp * vp
    return flops, bytes_
