"""% of the measured window in which the device ran no operation (from the
profiler's trace, averaged over the chips)."""
import readings


def read(run):
    return readings.idle_share(run, without_waits=False)
