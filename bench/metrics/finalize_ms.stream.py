"""Milliseconds of the final aggregation (``finalize`` spans) per window
finalized in the measured window."""
import readings


def read(run):
    spans = readings.spans(run, "finalize")
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
