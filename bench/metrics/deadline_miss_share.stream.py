"""% of the windows due in the measured window whose answer was not emitted
by their wall deadline (answered late or never)."""


def read(run):
    if not run.windows:
        return None
    missed = sum(1 for w in run.windows
                 if w.emitted is None or w.emitted > w.deadline)
    return 100.0 * missed / len(run.windows)
