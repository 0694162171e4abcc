"""Smoke run of the deadline-scheduled TPC-H session path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --mesh 4      # four chips: the DeviceMesh path only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny CPU rehearsal

One chip: the paper's stream (§7.1) at ``StreamScale(1.0)`` -- 3,300
orders and 13,000 lineitem rows per file -- in 3 windows of 60 files.
CQ1-CQ4, TPC-Q6-like and TPC-Q4-like each run as a recurring query
through

    Session -> llf-dynamic -> AnalyticsRuntimeExecutor
            -> segagg (compiled Pallas) -> partials -> final aggregation

with a cost model calibrated by ``measure_cost_model`` on the same
backend.  Every window is compared with a float64 numpy reference: counts
exactly, TPC-Q6-like's revenue within the worst-case f32 rounding of the
kernel's blocked sum.  Direct ``segagg`` checks then cover the scatter
formulation (picked by dispatch at a mid-width group count) and the
precision of the one-hot matmul on float values.

``--mesh 4``: CQ2 and CQ3 through ``MeshAnalyticsBackend`` on a
``DeviceMesh(4)`` with ``shard_across=4``, compared exactly with the same
windows on one chip and with numpy; every device must have received rows.

The script needs a TPU: on any other platform it exits non-zero, naming
the platform, and prints no result.  ``--rehearse`` is the one exception,
an explicit CPU rehearsal at a tiny scale (Pallas interpreter; XLA on the
mesh, where the interpreter cannot run under ``shard_map``) that is never a
chip result.  The last line of stdout is the result, one JSON object.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

NUM_WINDOWS = 3
FILES_PER_WINDOW = 60
CAL_FILES = 4            # calibration runs on the first files of window 0
CAL_BATCH_SIZES = (1, 4)
SCATTER_GROUPS = 4096    # mid-width G at which dispatch picks scatter
MESH_QUERIES = ("CQ2", "CQ3")
F32_EPS = 2.0 ** -24     # unit roundoff of float32


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (a persistent
    cache hit is counted as its retrieval time)."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated stream and check data")
    ap.add_argument("--mesh", type=int, choices=(4,), default=None,
                    help="run only the DeviceMesh phase on this many chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny scale; not a chip result")
    return ap.parse_args(argv)


def windows_of(stream, n_windows, per_window):
    """{stream name: [window files]} and each window's arrival instants."""
    files = {"orders": [], "lineitem": []}
    stamps = []
    for w in range(n_windows):
        chunk = stream[w * per_window:(w + 1) * per_window]
        files["orders"].append([o for _, o, _ in chunk])
        files["lineitem"].append([li for _, _, li in chunk])
        stamps.append([t for t, _, _ in chunk])
    return files, stamps


def numpy_reference(query, files, scale):
    from repro.kernels.segagg.ref import segagg_numpy
    from repro.serve.analytics import concat_files

    records = concat_files(files)
    keys, values = query.key_fn(records), query.value_fn(records)
    groups = query.num_groups(scale)
    return (segagg_numpy(keys, values, groups),
            segagg_numpy(keys, abs(values), groups))


def blocked_sum_chain(backend, rows, groups, batches):
    """Longest chain of f32 roundings a group's sum can take in the
    kernel: a tree within a row block, a chain across the blocks of a
    batch, then one across the batches of the window."""
    from repro.kernels.segagg import tuning

    blocks = [tuning.tuned_blocks(backend, n, groups)[0] for n in (1, rows)]
    return max(blocks) + math.ceil(rows / min(blocks)) + batches + 1


def check_window(query, got, ref, abs_sum, chain):
    """Counts exactly; float sums within ``chain`` f32 roundings."""
    if query.query_id == "TPC-Q6-like":
        tol = chain * F32_EPS * abs_sum
        return bool(got.shape == ref.shape and (abs(got - ref) <= tol).all())
    return bool(got.shape == ref.shape and (got == ref).all())


def session_phase(args, backend, scale, per_window, clock):
    import numpy as np

    from repro.data.tpch import PAPER_QUERIES, stream_files
    from repro.kernels.segagg import tuning
    from repro.serve.analytics import measure_cost_model, run_session

    stream = list(stream_files(args.seed, NUM_WINDOWS * per_window, scale))
    files, stamps = windows_of(stream, NUM_WINDOWS, per_window)
    print(f"stream: StreamScale({scale.scale}) {NUM_WINDOWS} windows x "
          f"{per_window} files = "
          f"{per_window * scale.lineitems_per_file} lineitem / "
          f"{per_window * scale.orders_per_file} orders rows per window")
    ok = True
    for query in PAPER_QUERIES:
        groups = query.num_groups(scale)
        windows = files[query.stream]
        rows = sum(len(f["ts"]) for f in windows[0])
        t0, c0 = time.perf_counter(), clock.seconds
        cm = measure_cost_model(query, windows[0][:CAL_FILES], scale,
                                batch_sizes=CAL_BATCH_SIZES, backend=backend)
        results, trace = run_session(query, windows, stamps, scale, cm,
                                     period=float(per_window),
                                     backend=backend)
        wall, comp = time.perf_counter() - t0, clock.seconds - c0
        series = trace.outcome_series(query.query_id)
        batches = sum(o.num_batches for o in series)
        exact = []
        for w in range(NUM_WINDOWS):
            ref, abs_sum = numpy_reference(query, windows[w], scale)
            chain = blocked_sum_chain(backend, rows, groups,
                                      series[w].num_batches
                                      if w < len(series) else 0)
            exact.append(w in results and check_window(
                query, np.asarray(results[w], np.float64), ref, abs_sum,
                chain))
        ok &= all(exact) and len(series) == NUM_WINDOWS
        print(f"query {query.query_id}: groups={groups} rows/window={rows} "
              f"windows={len(results)} batches={batches} "
              f"formulation={tuning.pick_formulation(backend, rows, groups, 128)} "
              f"deadlines_met={sum(o.met_deadline for o in series)}/"
              f"{len(series)} wall_s={wall} compile_s={comp} "
              f"equal_to_numpy={exact}")
    return ok


def kernel_phase(args, backend, rows, clock):
    """Direct segagg checks at G = SCATTER_GROUPS: counts and float sums
    through the dispatched (scatter) formulation, float sums through the
    one-hot matmul."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.segagg import tuning
    from repro.kernels.segagg.ops import segagg
    from repro.kernels.segagg.ref import segagg_numpy

    rng = np.random.default_rng(args.seed + 1)
    G = SCATTER_GROUPS
    ok = True
    for form, n in ((None, rows), ("matmul", 2 * G)):
        keys = rng.integers(0, G, n).astype(np.int32)
        ones = np.ones((n, 1), np.float32)
        prices = rng.gamma(2.0, 30.0, (n, 1)).astype(np.float32)
        picked = tuning.pick_formulation(backend, n, G, 128, form)
        t0, c0 = time.perf_counter(), clock.seconds
        counts = np.asarray(segagg(jnp.asarray(keys), jnp.asarray(ones), G,
                                   backend=backend, formulation=form))
        sums = np.asarray(segagg(jnp.asarray(keys), jnp.asarray(prices), G,
                                 backend=backend, formulation=form))
        wall, comp = time.perf_counter() - t0, clock.seconds - c0
        ref_counts = segagg_numpy(keys, ones, G)
        ref_sums = segagg_numpy(keys, prices, G)
        # A group's sum of c values takes at most c + 1 f32 roundings here
        # (one row per step, then the store); bf16 inputs would miss by ~2^-9.
        tol = (ref_counts + 1) * F32_EPS * ref_sums
        counts_ok = bool((counts == ref_counts).all())
        sums_ok = bool((abs(sums - ref_sums) <= tol).all())
        rel = float(np.max(abs(sums - ref_sums) / np.maximum(ref_sums, 1e-30)))
        good = counts_ok and sums_ok and (form is not None or picked == "scatter")
        ok &= good
        print(f"kernel segagg: groups={G} rows={n} formulation={picked} "
              f"counts_exact={counts_ok} f32_sums_within_bound={sums_ok} "
              f"max_rel_err={rel} wall_s={wall} compile_s={comp}")
    return ok


def mesh_phase(args, backend, scale, per_window, clock):
    import jax
    import numpy as np

    from repro.core import ShardedCostModel
    from repro.data.tpch import PAPER_QUERIES, stream_files
    from repro.dist import DeviceMesh
    from repro.serve.analytics import measure_cost_model, run_session

    stream = list(stream_files(args.seed, NUM_WINDOWS * per_window, scale))
    files, stamps = windows_of(stream, NUM_WINDOWS, per_window)
    mesh = DeviceMesh(args.mesh)
    ok = True
    for query in (q for q in PAPER_QUERIES if q.query_id in MESH_QUERIES):
        windows = files[query.stream]
        t0, c0 = time.perf_counter(), clock.seconds
        cm = measure_cost_model(query, windows[0][:CAL_FILES], scale,
                                batch_sizes=CAL_BATCH_SIZES, backend=backend)
        tc = time.perf_counter()
        offset = 2.0 * cm.cost(per_window)
        one, _ = run_session(query, windows, stamps, scale, cm,
                             period=float(per_window),
                             deadline_offset=offset, backend=backend)
        t1 = time.perf_counter()
        four, trace = run_session(
            query, windows, stamps, scale, ShardedCostModel(cm, args.mesh),
            period=float(per_window), deadline_offset=offset,
            backend=backend, mesh=mesh, shard_across=args.mesh)
        wall4, comp = time.perf_counter() - t1, clock.seconds - c0
        equal = []
        for w in range(NUM_WINDOWS):
            ref, _ = numpy_reference(query, windows[w], scale)
            equal.append(w in one and w in four
                         and bool((np.asarray(four[w]) == ref).all())
                         and bool((np.asarray(one[w]) == ref).all()))
        series = trace.outcome_series(query.query_id)
        ok &= all(equal)
        print(f"mesh query {query.query_id}: devices={args.mesh} "
              f"windows={len(four)} batches={sum(o.num_batches for o in series)} "
              f"deadlines_met={sum(o.met_deadline for o in series)}/"
              f"{len(series)} wall_s(mesh)={wall4} "
              f"wall_s(one chip)={t1 - tc} calibrate_s={tc - t0} "
              f"compile_s={comp} "
              f"mesh_eq_one_chip_eq_numpy={equal}")
    for dev in mesh.mesh.devices.flat:
        stats = dev.memory_stats() or {}
        rows = mesh.rows_placed.get(dev.id, 0)
        ok &= rows > 0
        print(f"mesh device {dev.id}: rows_received={rows} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return ok


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.data.tpch import StreamScale

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} compile_cache={cache_dir}")
    if args.rehearse:
        if device["platform"] != "cpu":
            print("chip_smoke: --rehearse is a CPU rehearsal; found platform "
                  f"{device['platform']!r}", file=sys.stderr)
            return 2
        # The Pallas interpreter cannot run under shard_map (it evaluates
        # the kernel without the mesh axes its inputs vary over).
        backend = "xla" if args.mesh else "interpret"
        scale, per_window = StreamScale(0.001), 6
    else:
        if device["platform"] != "tpu":
            print("chip_smoke: needs a TPU; jax found platform "
                  f"{device['platform']!r}", file=sys.stderr)
            return 2
        backend, scale, per_window = "pallas", StreamScale(1.0), FILES_PER_WINDOW
    if args.mesh and device["count"] < args.mesh:
        print(f"chip_smoke: --mesh {args.mesh} needs {args.mesh} devices; "
              f"jax found {device['count']}", file=sys.stderr)
        return 2
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    if args.mesh:
        ok = mesh_phase(args, backend, scale, per_window, clock)
    else:
        ok = session_phase(args, backend, scale, per_window, clock)
        ok &= kernel_phase(args, backend,
                           per_window * scale.lineitems_per_file, clock)
    print(f"total: wall_s={time.perf_counter() - t0} "
          f"compile_s={clock.seconds} ok={ok}")
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
