"""Device time of the collectives a train step (all-gather, reduce-scatter,
all-reduce, collective-permute, all-to-all, their async halves),
overlapped with compute or not: the reduction's ``collective_s`` (ops whose
stable path, name or HLO category names a collective, averaged over the
devices) over the steps of the traced window.

``bench/trace.py`` divides ``collective_s`` by 1e9 twice (each op's
duration is already in seconds when it is divided again), so the reading is
scaled back by 1e9 here; a repair of the reduction drops that factor."""


def read(ctx):
    steps = ctx["record"]["counters"].get("steps")
    if not steps:
        return None
    return 1e3 * ctx["trace"]["collective_s"] * 1e9 / steps
