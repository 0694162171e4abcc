"""Real multi-device execution: modelled-vs-measured speedup curves.

Everything before this PR timed the scheduler on MODELLED clocks (cost
units == time units).  This bench runs the same burst workload on a REAL
jax device mesh (``repro.dist.DeviceMesh`` + ``MeshAnalyticsBackend``:
worker clocks stitched from measured wall seconds, shard groups fused into
one ``shard_map`` call) and reports, per W in {1, 2, 4, 8}:

* measured wall seconds + speedup vs W=1 (median of ``REPS`` runs);
* the modelled twin (same workload on a simulated ``ExecutorPool(W)``) so
  the modelled speedup curve can be compared against the real one;
* dispatch counts — the mechanism: ``ShardedCostModel`` makes planned
  MinBatches ~W x larger, so W x fewer logical batches reach the mesh and
  per-dispatch overhead is paid once per GROUP (the paper's
  overhead-amortization argument applied to dispatch fan-out).

Gates (assertions; ``--smoke`` keeps them except the speedup floor):

* parity  — every W's aggregate results exactly equal W=1's
  (integer-valued f32: sums are exact under any sharding);
* identity — with no mesh anywhere, ``ExecutorPool(workers=1)`` traces are
  byte-identical to the bare single-executor loop for EVERY registered
  policy on BOTH dynamic runtimes (scan + heap) — the WorkerBackend
  refactor changed no modelled decision;
* speedup — the committed full run shows > 1.5x measured speedup at W=8.

CPU note: the container exposes one socket; XLA_FLAGS (set below, before
jax initializes) force-splits it into 8 host devices.  The speedup is real
wall-clock but comes from dispatch amortization, not extra silicon.
"""
from __future__ import annotations

import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

import argparse  # noqa: E402
import hashlib  # noqa: E402
import statistics  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    DynamicQuerySpec,
    ExecutorPool,
    LinearCostModel,
    Query,
    ShardedCostModel,
    SimulatedExecutor,
    TraceArrival,
    get_policy,
    list_policies,
    run,
)
from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files  # noqa: E402
from repro.dist import DeviceMesh  # noqa: E402
from repro.serve.analytics import MeshAnalyticsBackend  # noqa: E402

from .common import Timer, emit, write_result  # noqa: E402

WORKER_COUNTS = (1, 2, 4, 8)
SCALE = StreamScale(scale=0.005)
POLICY = "llf-dynamic"


# ---------------------------------------------------------------------------
# burst workload: every file present at t=0, deadlines far out
# ---------------------------------------------------------------------------


# Count-shaped queries only (value_fn == ones): integer-valued f32 sums
# are EXACT under any sharding/association, so the parity gate can assert
# exact equality.  TPC-Q6-like's float revenue reassociates differently
# across shards and is excluded on purpose.
COUNT_QUERIES = [q for q in PAPER_QUERIES if q.query_id != "TPC-Q6-like"]


def burst_workload(num_queries: int, num_files: int):
    """(jobs, base specs): ``num_queries`` analytics queries over disjoint
    seeds of the §7.1 stream, all files arrived at t=0 (the heavy-traffic
    regime where dispatch overhead, not arrival, bounds the makespan)."""
    jobs, queries = {}, []
    for i in range(num_queries):
        aq = COUNT_QUERIES[i % len(COUNT_QUERIES)]
        files = [(line if aq.stream == "lineitem" else o)
                 for _, o, line in
                 stream_files(seed=100 + i, num_files=num_files, sc=SCALE)]
        qid = f"{aq.query_id}~{i}"
        jobs[qid] = (aq, files)
        cm = LinearCostModel(tuple_cost=1.0, overhead=1.0, agg_per_batch=0.2)
        queries.append(Query(
            query_id=qid,
            wind_start=0.0,
            wind_end=0.0,
            deadline=50.0 * cm.cost(num_files),
            num_tuples_total=num_files,
            cost_model=cm,
            arrival=TraceArrival(timestamps=(0.0,) * num_files),
        ))
    return jobs, queries


def with_sharded_costs(queries, ways: int):
    import dataclasses

    if ways <= 1:
        return list(queries)
    return [dataclasses.replace(
        q, cost_model=ShardedCostModel(q.cost_model, ways)) for q in queries]


# ---------------------------------------------------------------------------
# measured mesh runs
# ---------------------------------------------------------------------------


def run_mesh(jobs, queries, workers: int, reps: int):
    mesh = DeviceMesh(workers)
    wb = MeshAnalyticsBackend(jobs, SCALE, mesh)
    pool = ExecutorPool(worker_backend=wb)
    policy = get_policy(POLICY, shard_across=workers)
    specs = [DynamicQuerySpec(query=q)
             for q in with_sharded_costs(queries, workers)]
    trace = run(policy, specs, pool)           # warmup: jit compiles here
    results = {qid: np.array(r) for qid, r in wb.results.items()}
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        trace = run(policy, specs, pool)
        walls.append(time.perf_counter() - t0)
    batches = [e for e in trace.executions if e.kind == "batch"]
    return {
        "workers": workers,
        "wall_s": statistics.median(walls),
        "wall_s_all": walls,
        "dispatches": len({(e.query_id, e.start) for e in batches}),
        "shard_executions": len(batches),
        "complete": all(trace.outcome(q.query_id).complete for q in queries),
        "backend_wall_s": sum(wb.wall_seconds.values()),
    }, {qid: np.array(r) for qid, r in wb.results.items()} or results


def run_modelled(queries, workers: int):
    pool = ExecutorPool(workers=workers,
                        names=tuple(f"d{i}" for i in range(workers)))
    policy = get_policy(POLICY, shard_across=workers)
    specs = [DynamicQuerySpec(query=q)
             for q in with_sharded_costs(queries, workers)]
    trace = run(policy, specs, pool)
    return {
        "workers": workers,
        "makespan": max(o.completion_time for o in trace.outcomes),
        "complete": all(o.complete for o in trace.outcomes),
    }


# ---------------------------------------------------------------------------
# identity gate: no mesh anywhere -> the refactor changed no trace
# ---------------------------------------------------------------------------


def _digest(trace) -> str:
    h = hashlib.sha256()
    for e in trace.executions:
        h.update(repr(e).encode())
    for o in trace.outcomes:
        h.update(repr(o).encode())
    return h.hexdigest()[:16]


def identity_gate():
    """Pool(workers=1) == bare executor, byte-identical, for every policy
    on both dynamic runtimes."""
    arr = TraceArrival(timestamps=tuple(float(i) for i in range(8)))
    cm = LinearCostModel(tuple_cost=0.4, overhead=0.3, agg_per_batch=0.2)

    def workload():
        return [DynamicQuerySpec(query=Query(
            f"q{i}", arr.wind_start, arr.wind_end,
            arr.wind_end + 5.0 * cm.cost(8), 8, cm, arr))
            for i in range(4)]

    digests = {}
    for name in sorted(list_policies()):
        policy = get_policy(name)
        runtimes = ((None,) if getattr(policy, "kind", "static") != "dynamic"
                    else ("scan", "heap"))
        for rt in runtimes:
            kw = {} if rt is None else {"runtime": rt}
            bare = run(get_policy(name), workload(), SimulatedExecutor(), **kw)
            pooled = run(get_policy(name), workload(),
                         ExecutorPool(workers=1), **kw)
            assert bare.executions == pooled.executions, (name, rt)
            assert bare.outcomes == pooled.outcomes, (name, rt)
            digests[f"{name}/{rt or 'static'}"] = _digest(pooled)
    return digests


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload, no speedup floor (CI)")
    args = ap.parse_args()

    num_queries, num_files, reps = (2, 16, 2) if args.smoke else (6, 64, 5)

    with Timer() as t_id:
        digests = identity_gate()
    emit("mesh_identity", t_id.seconds * 1e6,
         f"{len(digests)} policy/runtime traces pool==bare")

    jobs, queries = burst_workload(num_queries, num_files)
    import jax
    avail = jax.device_count()
    counts = [w for w in WORKER_COUNTS if w <= avail]

    rows, modelled, results_by_w = [], [], {}
    for w in counts:
        row, results = run_mesh(jobs, queries, w, reps)
        rows.append(row)
        results_by_w[w] = results
        modelled.append(run_modelled(queries, w))
        emit("mesh_measured", row["wall_s"] * 1e6,
             f"W={w} wall={row['wall_s']:.3f}s dispatches={row['dispatches']} "
             f"complete={row['complete']}")

    # parity gate: every W's aggregates exactly equal W=1's
    base = results_by_w[counts[0]]
    for w in counts[1:]:
        for qid, ref in base.items():
            assert np.array_equal(results_by_w[w][qid], ref), (w, qid)

    base_wall = rows[0]["wall_s"]
    base_make = modelled[0]["makespan"]
    for row, m in zip(rows, modelled):
        row["speedup"] = base_wall / row["wall_s"] if row["wall_s"] else 0.0
        m["speedup"] = base_make / m["makespan"] if m["makespan"] else 0.0

    assert all(r["complete"] for r in rows), "mesh run missed tuples"

    payload = {
        "policy": POLICY,
        "devices_available": avail,
        "num_queries": num_queries,
        "num_files": num_files,
        "reps": reps,
        "measured": rows,
        "modelled": modelled,
        "parity": "exact",
        "identity_digests": digests,
    }
    name = "mesh_smoke" if args.smoke else "mesh"
    write_result(name, payload)

    top = rows[-1]
    emit("mesh_speedup", top["wall_s"] * 1e6,
         f"W={top['workers']} measured={top['speedup']:.2f}x "
         f"modelled={modelled[-1]['speedup']:.2f}x")
    if not args.smoke and 8 in counts:
        w8 = next(r for r in rows if r["workers"] == 8)
        assert w8["speedup"] > 1.5, (
            f"W=8 measured speedup {w8['speedup']:.2f}x <= 1.5x floor")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
