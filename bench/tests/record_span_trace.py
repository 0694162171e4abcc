"""Record a small profiler trace with the program's own spans on, for
``test_program_spans.py``.

    python3 bench/tests/record_span_trace.py bench/tests/span_trace.json

Two windows of the TPC-Q6-like query (one group) run through the program's
session path (``repro.serve.analytics.run_session`` on the Pallas
backend) with ``repro.tracing`` enabled, under
``program_spans.ScopedProfile``.  Written as JSON, on the ``perf_counter``
clock relative to the first program span: the program spans, each device
operation with the scope read from its stats, and the string stats of one
event per operation name (what the scope was read from).
"""
from __future__ import annotations

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

KEPT_STATS = ("tf_op", "long_name", "name", "hlo_module", "hlo_op",
              "scope_range_id", "source")


def main(out: str) -> int:
    import jax

    import program_spans
    from repro import tracing
    from repro.core import LinearCostModel
    from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
    from repro.serve.analytics import run_session

    if jax.devices()[0].platform != "tpu":
        print("record_span_trace: needs a TPU", file=sys.stderr)
        return 2
    scale = StreamScale(scale=0.1)
    aq = next(q for q in PAPER_QUERIES if q.query_id == "TPC-Q6-like")
    windows, stamps = [], []
    for w in range(2):
        files, times = [], []
        for t, o, line in stream_files(seed=7 + w, num_files=4, sc=scale):
            files.append(line if aq.stream == "lineitem" else o)
            times.append(t + 10.0 * w)
        windows.append(files)
        stamps.append(times)
    cm = LinearCostModel(tuple_cost=0.4, overhead=0.3, agg_per_batch=0.2)

    def session():
        return run_session(aq, windows, stamps, scale, cm, period=10.0,
                           calibrate=False, backend="pallas")

    session()                                  # compile outside the trace
    profile = program_spans.ScopedProfile("record-spans")
    tracing.enable()
    profile.start()
    session()
    profile.stop()
    tracing.disable()
    spans = tracing.drain()
    ops, _ = profile.read()
    t0 = min(s.start for s in spans)
    t1 = max(s.end for s in spans)
    dev = jax.devices()[0].id
    record = {
        "query": aq.query_id,
        "window": [0.0, t1 - t0],
        "spans": [[s.name, s.start - t0, s.end - t0, s.id, s.parent, s.request]
                  for s in spans],
        "ops": [[s - t0, e - t0, n, sc] for (s, e, n), sc
                in zip(ops.get(dev, []), profile.scopes.get(dev, []))
                if t0 <= s <= t1],
        "stats": {n: {k: v for k, v in st.items() if k in KEPT_STATS}
                  for n, st in sorted(profile.stats_by_op.items())},
    }
    for n, st in sorted(profile.stats_by_op.items()):
        print(f"stats of {n}: {st}", file=sys.stderr)
    pathlib.Path(out).write_text(json.dumps(record, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
