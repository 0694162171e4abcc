"""95th percentile, over every window of every query whose close falls in
the measured window, of result emitted (final aggregation returned, the
answer on the host) minus window close (the wall instant its last tick was
due).  Windows never answered are left to ``failed`` and ``correct``."""
import numpy as np


def read(run):
    lat = [w.emitted - w.close for w in run.windows if w.emitted is not None]
    return float(np.percentile(lat, 95)) if lat else None
