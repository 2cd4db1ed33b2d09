"""95th percentile, over every request due in the window, of the time
from when it was due until its output was a host array (the client's
clock)."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_s()
    return float(np.percentile(lat, 95) * 1e3) if lat else None
