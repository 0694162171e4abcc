"""% of the executor's batch time (``batch`` spans in the measured window)
in which the device trace shows no operation: host record preparation,
transfer and spill."""
import readings


def read(run):
    return readings.host_share(run)
