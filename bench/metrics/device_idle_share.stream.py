"""% of the measured window, less the pacing waits, in which the device ran
no operation (from the profiler's trace, averaged over the chips)."""
import readings


def read(run):
    return readings.idle_share(run, without_waits=True)
