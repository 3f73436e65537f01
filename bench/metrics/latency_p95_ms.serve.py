"""95th percentile, over every request due in the traced window, of due
time to result claimed: the open loop's tail, which swings with every
stall of the host at four fifths of capacity."""

import numpy as np


def read(ctx):
    lat = [x for x in ctx["record"]["counters"].get("latency_s", [])
           if x is not None]
    if not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3
