"""The paper's cost: wall seconds of the measured window that the serving
loop did not spend waiting for input, over the million query-rows that the
batches started in the window processed (a row read by two queries counts
twice)."""
import readings


def read(run):
    rows = sum(b.rows for b in readings.batches(run))
    if rows == 0:
        return None
    busy = (run.hi - run.lo) - run.rec.span_seconds("wait", run.lo, run.hi)
    return busy / (rows / 1e6)
