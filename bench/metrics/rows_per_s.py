"""Query-rows of the windows whose answers were emitted inside the measured
window, over the measured seconds from the window's start to the last such
emission: each round from the start of its set-up to its last answer, the
reference checks between rounds left out."""
import readings


def read(run):
    done = [w for w in run.windows
            if w.emitted is not None and w.emitted <= run.hi]
    if not done:
        return None
    last = max(w.emitted for w in done)
    seconds = sum(min(e, last) - s for s, e in readings.measured(run) if s < last)
    return sum(w.rows for w in done) / seconds
