"""Table 2 analogue: REAL measured cost of processing modes on the JAX
analytics executor (CPU wall-clock, reduced scale).

Modes: per-file (tuple-ish streaming), micro-batch (every 8 files),
one-shot / single batch (ours).  The paper's Table 2 shows batch-mode
processing beating streaming regardless of transport; here the same holds
for actual executor time because the per-batch dispatch overhead is paid
4500x vs 1x."""
from __future__ import annotations

import numpy as np

from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
from repro.serve.analytics import run_batched

from .common import Timer, emit, write_result

SCALE = StreamScale(scale=0.01)
NUM_FILES = 128


def main() -> None:
    files_by_stream = {"orders": [], "lineitem": []}
    for _, o, l in stream_files(seed=7, num_files=NUM_FILES, sc=SCALE):
        files_by_stream["orders"].append(o)
        files_by_stream["lineitem"].append(l)

    rows = []
    with Timer() as t:
        for q in PAPER_QUERIES[:4]:          # CQ1..CQ4, like Table 2
            files = files_by_stream[q.stream]
            ref = None
            for mode, bs in (("per_file", 1), ("micro_batch_8", 8),
                             ("single_batch", NUM_FILES)):
                result, secs, nb = run_batched(q, files, bs, SCALE)
                if ref is None:
                    ref = result
                else:
                    np.testing.assert_allclose(result, ref, rtol=1e-5,
                                               atol=1e-5)
                rows.append({"query": q.query_id, "mode": mode,
                             "seconds": secs, "num_batches": nb})
    write_result("input_modes", {"rows": rows})
    by = {}
    for r in rows:
        by.setdefault(r["query"], {})[r["mode"]] = r["seconds"]
    ratios = {q: round(m["per_file"] / m["single_batch"], 1)
              for q, m in by.items()}
    emit("table2_input_modes", t.seconds * 1e6 / len(rows),
         f"per-file/single-batch cost ratio: {ratios} (results identical "
         "across modes)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
