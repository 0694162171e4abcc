"""% of the GROUP-BY roofline: the least time the work of every batch
started in the measured window needs on the chip's peaks
(``roofline.least_seconds``: N, G and V alone), over all device busy time
inside those ``batch`` spans, summed over the chips.  The denominator is
every device operation inside the spans, not events matched by kernel
name."""
import readings
import roofline


def read(run):
    dev = readings.device_seconds(run, readings.spans(run, "batch"))
    if not dev:
        return None
    need = sum(roofline.least_seconds(b.rows, b.groups, 1, run.device_kind)
               for b in readings.batches(run))
    return 100.0 * need / dev
