"""Kernel micro-benchmarks.

segagg / pane_segagg are timed PER BACKEND across an (N, G) grid:

* ``ref``       — the pure-jnp oracle (jitted ``jax.ops.segment_sum``),
* ``xla``       — the compiled dispatch path on CPU (scatter-add /
                  blocked one-hot matmul, crossover-selected),
* ``interpret`` — the Pallas kernel body under the interpreter (the
                  pre-PR-8 default execution path),
* ``pallas``    — the compiled Pallas kernel (only when a TPU/GPU jax
                  backend is present; skipped on CPU).

Every timed shape asserts output parity between the compiled path and the
interpreter before timing, and the PR-8 acceptance gate — compiled CPU
>= 5x over interpret at (N=200k, G=10k) — is checked in full mode.  Rows
carry analytic FLOPs/bytes (``ops.flops_bytes``) so
``benchmarks.bench_roofline`` can report achieved-vs-roofline fractions
from the committed ``results/kernels.json``.

    python -m benchmarks.bench_kernels            # full grid, commits results
    python -m benchmarks.bench_kernels --smoke    # tiny shapes, parity gate
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segagg import tuning
from repro.kernels.segagg.ops import flops_bytes, pane_segagg, resolve_backend, segagg
from repro.kernels.segagg.ref import pane_segagg_ref, segagg_ref

from .common import Timer, emit, write_result

# Full-mode segagg grid: (N, G, which backends to time).  The interpreter
# is only timed where the acceptance gate needs it or it stays affordable —
# a full interpret sweep of the wide-G shapes costs minutes for no signal.
_SEGAGG_GRID = (
    (50_000, 1_000, ("ref", "xla", "interpret")),
    (200_000, 100, ("ref", "xla")),
    (200_000, 10_000, ("ref", "xla", "interpret")),   # acceptance-gate shape
    (20_000, 50_000, ("ref", "xla")),                 # wide G: scatter regime
)
_PANE_GRID = (
    (100_000, 8, 500, ("ref", "xla", "interpret")),
)
_SMOKE_SEGAGG = ((2_000, 64, ("ref", "xla", "interpret")),)
_SMOKE_PANE = ((1_500, 4, 32, ("ref", "xla", "interpret")),)

_GATE_SHAPE = (200_000, 10_000)
_GATE_SPEEDUP = 5.0


def _time(fn, *args, reps=5):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _segagg_fn(backend):
    if backend == "ref":
        return jax.jit(segagg_ref, static_argnums=(2,))
    return lambda k, v, g: segagg(k, v, g, backend=backend)


def _pane_fn(backend):
    if backend == "ref":
        return jax.jit(pane_segagg_ref, static_argnums=(3, 4))
    return lambda k, v, p, np_, g: pane_segagg(k, v, p, np_, g,
                                               backend=backend)


def _formulation(backend, n, g, v=1):
    if backend == "ref":
        return "scatter"  # segment_sum IS a scatter-add
    return tuning.pick_formulation(backend, n, g, v)


def bench_segagg(grid, reps, rows, compiled):
    rng = np.random.default_rng(0)
    for n, g, backends in grid:
        keys = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
        vals = jnp.ones((n, 1), jnp.float32)
        outs = {}
        for backend in backends:
            fn = _segagg_fn(backend)
            r = 1 if backend == "interpret" else reps
            dt = _time(fn, keys, vals, g, reps=r)
            outs[backend] = np.asarray(fn(keys, vals, g))
            form = _formulation(backend, n, g)
            fl, by = flops_bytes(n, g, 1, form,
                                 "xla" if backend == "ref" else backend)
            rows.append({
                "kernel": "segagg", "backend": backend, "formulation": form,
                "n": n, "groups": g, "us": dt * 1e6, "rows_per_s": n / dt,
                "flops": fl, "bytes": by,
            })
        # parity gate: every backend must agree with the oracle
        for backend, got in outs.items():
            np.testing.assert_allclose(
                got, np.asarray(segagg_ref(keys, vals, g)),
                rtol=1e-5, atol=1e-5,
                err_msg=f"segagg {backend} diverges at (n={n}, g={g})")
        if compiled in outs and "interpret" in outs:
            t_c = next(r["us"] for r in rows
                       if r["kernel"] == "segagg" and r["n"] == n
                       and r["groups"] == g and r["backend"] == compiled)
            t_i = next(r["us"] for r in rows
                       if r["kernel"] == "segagg" and r["n"] == n
                       and r["groups"] == g and r["backend"] == "interpret")
            rows.append({
                "kernel": "segagg", "backend": f"{compiled}/interpret",
                "n": n, "groups": g, "speedup": t_i / t_c,
            })


def bench_pane(grid, reps, rows):
    rng = np.random.default_rng(1)
    for n, p, g, backends in grid:
        keys = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
        pane_ids = jnp.sort(jnp.asarray(rng.integers(0, p, n).astype(np.int32)))
        vals = jnp.ones((n, 1), jnp.float32)
        want = np.asarray(pane_segagg_ref(keys, vals, pane_ids, p, g))
        for backend in backends:
            fn = _pane_fn(backend)
            r = 1 if backend == "interpret" else reps
            dt = _time(fn, keys, vals, pane_ids, p, g, reps=r)
            np.testing.assert_allclose(
                np.asarray(fn(keys, vals, pane_ids, p, g)), want,
                rtol=1e-5, atol=1e-5,
                err_msg=f"pane_segagg {backend} diverges at "
                        f"(n={n}, panes={p}, g={g})")
            form = _formulation(backend, n, p * g)
            fl, by = flops_bytes(n, p * g, 1, form,
                                 "xla" if backend == "ref" else backend)
            rows.append({
                "kernel": "pane_segagg", "backend": backend,
                "formulation": form, "n": n, "panes": p, "groups": g,
                "us": dt * 1e6, "rows_per_s": n / dt,
                "flops": fl, "bytes": by,
            })


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + parity gate only (CI)")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    compiled = resolve_backend()          # "xla" on CPU, "pallas" on TPU/GPU
    rows = []
    with Timer() as t:
        if args.smoke:
            bench_segagg(_SMOKE_SEGAGG, args.reps, rows, compiled)
            bench_pane(_SMOKE_PANE, args.reps, rows)
        else:
            backends_avail = ["ref", "xla", "interpret"]
            if compiled == "pallas":
                backends_avail.append("pallas")
            grid = tuple(
                (n, g, tuple(b for b in bes if b in backends_avail)
                 + (("pallas",) if compiled == "pallas" else ()))
                for n, g, bes in _SEGAGG_GRID)
            bench_segagg(grid, args.reps, rows, compiled)
            bench_pane(_PANE_GRID, args.reps, rows)
            gate = next(
                (r for r in rows if r.get("speedup") is not None
                 and (r["n"], r["groups"]) == _GATE_SHAPE), None)
            assert gate is not None and gate["speedup"] >= _GATE_SPEEDUP, (
                f"compiled segagg must be >= {_GATE_SPEEDUP}x over interpret "
                f"at {_GATE_SHAPE}, got {gate}")

        # flash attention (jnp path)
        from repro.layers.attention import AttnSpec, chunked_attention

        B, S, H, D = 1, (256 if args.smoke else 1024), 4, 64
        q = jnp.ones((B, S, H, D), jnp.bfloat16)
        fn = jax.jit(lambda q: chunked_attention(
            q, q, q, AttnSpec(causal=True, chunk=256)))
        dt = _time(fn, q, reps=args.reps)
        flops = 4 * B * S * S * H * D * 0.5
        rows.append({"kernel": "flash_attention", "n": S, "us": dt * 1e6,
                     "gflops_s": flops / dt / 1e9})
        # ssd (jnp path)
        from repro.layers.ssd import ssd_chunked

        S2 = 256 if args.smoke else 1024
        x = jnp.ones((1, S2, 4, 64), jnp.float32)
        dtm = jnp.ones((1, S2, 4), jnp.float32) * 0.1
        A = -jnp.ones((4,))
        Bm = jnp.ones((1, S2, 4, 32), jnp.float32) * 0.1
        fn = jax.jit(lambda x, d, B_: ssd_chunked(x, d, A, B_, B_,
                                                  jnp.ones((4,)), 128)[0])
        dt = _time(fn, x, dtm, Bm, reps=args.reps)
        rows.append({"kernel": "ssd", "n": S2, "us": dt * 1e6})

    name = "kernels_smoke" if args.smoke else "kernels"
    write_result(name, {"compiled_backend": compiled, "rows": rows})
    seg = [r for r in rows if r["kernel"] == "segagg" and "us" in r]
    speedups = [r for r in rows if r.get("speedup") is not None]
    emit("kernel_micro", t.seconds * 1e6 / max(len(rows), 1),
         "; ".join(f"{r['backend']}@{r['n']}x{r['groups']}:{r['us']:.0f}us"
                   for r in seg)
         + "".join(f"; {r['backend']}@{r['n']}x{r['groups']}:"
                   f"{r['speedup']:.0f}x" for r in speedups))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
