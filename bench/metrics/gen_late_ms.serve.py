"""95th percentile of how late the load generator submitted each request
after its due time (a starved generator would flatter the server)."""

import numpy as np


def read(ctx):
    late = [x for x in ctx["record"]["counters"].get("gen_late_s", [])
            if x is not None]
    if not late:
        return None
    return float(np.percentile(late, 95)) * 1e3
