"""Milliseconds of the serving loop outside the program's executor calls
(``decide`` spans: session bookkeeping and the policy's decisions) per
batch started in the measured window."""
import readings


def read(run):
    n = len(readings.batches(run))
    if n == 0:
        return None
    return 1e3 * run.rec.span_seconds("decide", run.lo, run.hi) / n
