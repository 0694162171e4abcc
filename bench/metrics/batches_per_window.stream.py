"""Batches the policy ran per window, over the windows due in the measured
window (the session trace's ``num_batches``)."""


def read(run):
    if not run.windows:
        return None
    return sum(w.batches for w in run.windows) / len(run.windows)
