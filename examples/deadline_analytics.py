"""Single-query intermittent analytics, end to end with REAL JAX execution:

  1. generate a TPC-H-like record stream (reduced scale),
  2. calibrate the cost model from measured batch runs (paper Section 6.2),
  3. plan batches with the "single" policy (Algorithm 1) against a deadline,
  4. execute the plan on-device (segagg partial aggregation, host spill),
  5. final aggregation; verify the result equals a numpy reference.

Execution uses the dispatched segagg kernel (``backend="auto"``: compiled
Pallas on TPU/GPU, compiled XLA scatter-add on CPU — docs/API.md "Kernel
backends"), so the calibrated cost model describes the compiled kernel's
wall clock, not interpreter overhead.

    PYTHONPATH=src python examples/deadline_analytics.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Planner, Query, TraceArrival, plan_cost
from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
from repro.kernels.segagg.ops import resolve_backend
from repro.kernels.segagg.ref import segagg_numpy
from repro.serve.analytics import concat_files, measure_cost_model, run_plan

enable_compile_cache()
SCALE = StreamScale(scale=0.01)
NUM_FILES = 96

query = PAPER_QUERIES[2]  # CQ3: count(*) GROUP BY suppKey
files, times = [], []
for t, orders, lineitem in stream_files(seed=11, num_files=NUM_FILES, sc=SCALE):
    files.append(lineitem if query.stream == "lineitem" else orders)
    times.append(t)

print(f"query {query.query_id}: {query.description} "
      f"(segagg backend: {resolve_backend()})")
cost_model = measure_cost_model(query, files, SCALE)
print(f"calibrated cost model: cost(1 file)={cost_model.cost(1)*1e3:.2f} ms, "
      f"cost({NUM_FILES})={cost_model.cost(NUM_FILES)*1e3:.1f} ms")

arrival = TraceArrival(timestamps=tuple(times))
# 0.6x a one-shot run past window close, plus room for a last 1-file batch
deadline = (arrival.wind_end + 0.6 * cost_model.cost(NUM_FILES)
            + cost_model.cost(1))
q = Query("CQ3-deadline", arrival.wind_start, arrival.wind_end, deadline,
          NUM_FILES, cost_model, arrival)
plan = Planner(policy="single").schedule(q)
print(f"deadline {deadline:.2f}s -> plan: {plan.sch_tuples} files per batch "
      f"at t={[round(p, 2) for p in plan.sch_points]} "
      f"(modelled cost {plan_cost(q, plan)*1e3:.1f} ms)")

result, log, agg_s = run_plan(query, files, plan, SCALE)
records = concat_files(files)
reference = segagg_numpy(query.key_fn(records), query.value_fn(records),
                         query.num_groups(SCALE))
assert np.array_equal(result, reference)
print(f"executed {len(log)} real batches "
      f"({[b.num_records for b in log]} records), final agg {agg_s*1e3:.1f} ms")
print("result identical to the numpy reference — partial aggregation exact.")
print(f"total rows: {int(result.sum())}, groups touched: "
      f"{int((result > 0).sum())}")
