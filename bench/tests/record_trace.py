"""Record a small profiler trace on the chip for ``test_reduction.py``.

    python3 bench/tests/record_trace.py bench/tests/small_trace.json

Three GROUP-BYs of the program (``repro.kernels.segagg``), each inside a
``batch`` annotation and separated by ``decide`` and ``wait`` pauses, are
traced with ``device_trace.Profile``; the device operations and the host
spans, on the ``perf_counter`` clock relative to the first span, are written
as JSON.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import device_trace
    from repro.kernels.segagg.ops import segagg

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 4096, 65536), jnp.int32)
    vals = jnp.asarray(rng.gamma(2.0, 30.0, (65536, 1)), jnp.float32)
    segagg(keys, vals, 4096).block_until_ready()       # compile outside
    profile = device_trace.Profile("record")
    profile.start()
    spans = []
    for name, pause in (("decide", 0.002), ("batch", None), ("wait", 0.003),
                        ("batch", None), ("decide", 0.001), ("batch", None)):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            if pause is None:
                np.asarray(segagg(keys, vals, 4096))
            else:
                time.sleep(pause)
        spans.append((name, t, time.perf_counter()))
    profile.stop()
    ops, _ = profile.read()
    t0 = spans[0][1]
    record = {
        "window": [0.0, spans[-1][2] - t0],
        "spans": [[n, s - t0, e - t0] for n, s, e in spans],
        "ops": {str(d): [[s - t0, e - t0, n] for s, e, n in evs
                         if spans[0][1] <= s <= spans[-1][2]]
                for d, evs in ops.items()},
    }
    pathlib.Path(out).write_text(json.dumps(record, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
