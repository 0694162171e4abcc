"""Query-rows of the windows whose answers were emitted inside the measured
window, over the seconds from the window's start to the last such
emission."""


def read(run):
    done = [w for w in run.windows
            if w.emitted is not None and w.emitted <= run.hi]
    if not done:
        return None
    return sum(w.rows for w in done) / (max(w.emitted for w in done) - run.lo)
