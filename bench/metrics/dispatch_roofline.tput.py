"""The dispatch programs' share of their HBM roofline: one read and one
write of each payload completed in the traced window at the chip's HBM
bandwidth, over the device seconds of every program that is not the
benchmark's own (``bench_*``: payload making)."""


def read(ctx):
    mods = ctx["trace"]["modules"]
    busy = sum(m["s"] for name, m in mods.items() if "bench_" not in name)
    moved = 2 * ctx["record"]["counters"]["payload_bytes"]
    if not busy or not moved:
        return None
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / busy
