"""Benchmark harness: one function per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --policy llf-dynamic

Prints ``name,us_per_call,derived`` CSV rows (one per artifact) and writes
detailed JSON under benchmarks/results/.  With ``--policy`` the harness
instead runs ONE registered scheduling policy (``repro.core.get_policy``)
over the paper's §7.1 query set end to end on the shared runtime loop and
reports per-query deadline outcomes.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def run_policy_bench(policy_name: str, deadline_frac: float, num_files: int,
                     workers: int = 1, num_queries: int = 0,
                     runtime: str = None) -> int:
    from repro.core import InfeasibleDeadline, Planner

    from .common import all_paper_queries, emit, tile_queries, write_result

    try:
        planner = Planner(policy=policy_name)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2
    if workers > 1 and getattr(planner.policy, "kind", "static") != "dynamic":
        print("error: --workers applies to dynamic policies only (static "
              "runs give each query its own timeline)", file=sys.stderr)
        return 2
    if runtime and getattr(planner.policy, "kind", "static") != "dynamic":
        print("error: --runtime applies to dynamic policies only (static "
              "plans have no decision loop)", file=sys.stderr)
        return 2
    queries = all_paper_queries(deadline_frac=deadline_frac,
                                num_files=num_files)
    if num_queries and num_queries > len(queries):
        # Scale the paper's 13-query set up by tiling window-shifted
        # replicas (one window length apart) — pairs with --runtime heap
        # to exercise the event-heap core at registered-query scale.
        queries = tile_queries(queries, num_queries, float(num_files))
    # Like deadline misses, infeasibility is a measured outcome: record
    # per-query infeasible rows and still run the feasible remainder
    # (static policies raise at plan time; dynamic policies always run).
    infeasible = []
    if getattr(planner.policy, "kind", "static") == "static":
        from repro.core import execute_plan

        feasible, trace = [], None
        t0 = time.perf_counter()
        for q in queries:
            try:
                plan = planner.schedule(q)  # plan once, execute below
            except InfeasibleDeadline as e:
                infeasible.append((q, str(e)))
                continue
            feasible.append(q)
            trace = execute_plan(q, plan, trace=trace)
        dt = time.perf_counter() - t0
        queries = feasible
        if trace is None:
            from repro.core import ExecutionTrace

            trace = ExecutionTrace()
    else:
        t0 = time.perf_counter()
        trace = planner.run(queries, workers=workers if workers > 1 else None,
                            runtime=runtime)
        dt = time.perf_counter() - t0

    rows = []
    for q, reason in infeasible:
        rows.append({
            "query_id": q.query_id,
            "num_batches": 0,
            "completion_time": None,
            "deadline": q.deadline,
            "met_deadline": False,
            "infeasible": reason,
        })
        emit(f"policy_{policy_name}_{q.query_id}", 0.0,
             "batches=0;met=False;infeasible")
    for o in trace.outcomes:
        rows.append({
            "query_id": o.query_id,
            "num_batches": o.num_batches,
            "completion_time": o.completion_time,
            "deadline": o.deadline,
            "met_deadline": o.met_deadline,
            "total_cost": o.total_cost,
        })
        # us_per_call = the query's OWN modelled executor time (cost units
        # == seconds in the paper's regime); harness wall time is in summary.
        emit(f"policy_{policy_name}_{o.query_id}", o.total_cost * 1e6,
             f"batches={o.num_batches};met={o.met_deadline}")
    met = sum(1 for r in rows if r["met_deadline"])
    emit(f"policy_{policy_name}_summary", dt * 1e6,
         f"met={met}/{len(rows)};policy={policy_name}")
    # workers>1 / scaled runs get their own results file so they never
    # clobber the single-worker 13-query baseline record.
    result_name = f"policy_{policy_name}" + (
        f"_w{workers}" if workers > 1 else "") + (
        f"_q{num_queries}" if num_queries and num_queries > 13 else "")
    write_result(result_name, {
        "policy": policy_name,
        "deadline_frac": deadline_frac,
        "num_files": num_files,
        "workers": workers,
        "num_queries": len(queries),
        "runtime": runtime,
        "outcomes": rows,
        "stragglers": trace.stragglers,
        "wall_seconds": dt,
    })
    # Deadline misses are a measured outcome, not a harness failure.
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--policy",
        help="run ONE registered scheduling policy over the paper query set "
             "(see repro.core.list_policies())",
    )
    ap.add_argument("--deadline-frac", type=float, default=2.0,
                    help="deadline slack as a fraction of single-batch cost")
    ap.add_argument("--num-files", type=int, default=900,
                    help="stream length in files (paper full scale: 4500)")
    ap.add_argument("--workers", type=int, default=1,
                    help="ExecutorPool width for --policy runs (dynamic "
                         "policies only; 1 = bare executor)")
    ap.add_argument("--queries", type=int, default=0,
                    help="scale --policy runs to N queries by tiling the "
                         "paper set with window-shifted replicas (0 = the "
                         "plain 13-query set)")
    ap.add_argument("--runtime", choices=("scan", "heap"), default=None,
                    help="dynamic decision core for --policy runs: 'heap' "
                         "= O(log n) event-heap core, 'scan' = reference "
                         "full-walk core (default)")
    ap.add_argument("--list-policies", action="store_true",
                    help="print registered policy names and exit")
    args = ap.parse_args()

    if args.list_policies:
        from repro.core import list_policies

        print("\n".join(list_policies()))
        sys.exit(0)

    print("name,us_per_call,derived")
    if args.policy:
        sys.exit(run_policy_bench(args.policy, args.deadline_frac,
                                  args.num_files, args.workers,
                                  args.queries, args.runtime))

    from . import (
        bench_single_query,      # Fig 2 + Fig 6
        bench_cost_vs_batches,   # Fig 4
        bench_batch_vs_streaming,# Fig 5
        bench_multi_query,       # Fig 7 (both calibration regimes)
        bench_pool_scaling,      # makespan vs W (ExecutorPool scale-out)
        bench_session,           # continuous sessions: recurrence + drift
        bench_input_modes,       # Table 2 analogue (real executor)
        bench_memory,            # §7.2 OOM analysis
        bench_kernels,           # kernel micro-benches
        bench_roofline,          # deliverable (g): dry-run roofline table
    )

    failures = 0
    for mod in (bench_single_query, bench_cost_vs_batches,
                bench_batch_vs_streaming, bench_multi_query,
                bench_pool_scaling, bench_session, bench_input_modes,
                bench_memory, bench_kernels, bench_roofline):
        try:
            mod.main()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{mod.__name__},0,FAILED")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
