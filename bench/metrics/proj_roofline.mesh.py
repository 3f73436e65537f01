"""The mesh-native projection's share of its HBM roofline: one read and one
write of each chip's shard of every projected leaf
(``counts.projection_bytes_per_step`` over the chips) at the chip's HBM
bandwidth, over the device seconds a step of the ops whose stable path
contains ``codegen_``: the kernels ``codegen_reduce``, ``codegen_solve_*``
and ``codegen_apply`` that the sharded codegen body runs
(``kernels/codegen/distributed.py``)."""

import counts


def read(ctx):
    rec, cfg = ctx["record"], ctx["config"]
    steps = rec["counters"].get("steps")
    kernel_s = sum(s for path, s in ctx["trace"]["paths"].items()
                   if "codegen_" in path)
    if not steps or not kernel_s:
        return None
    moved = (counts.projection_bytes_per_step(cfg, cfg["program"]["param_dtype"])
             / rec["counters"]["chips"])
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / (kernel_s / steps)
