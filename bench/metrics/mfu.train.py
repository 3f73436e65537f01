"""Model FLOPs per step over the step period and the chips' bf16 peak.

FLOPs come from the configuration's shapes (``counts.train_flops_per_token``,
no remat recompute); the period from the device trace: the spacing of the
train step program's starts across the traced window."""

import counts


def read(ctx):
    runs = ctx["trace"]["modules"].get("jit_train_step", {})
    if runs.get("runs", 0) < 2:
        return None
    period = (runs["last_start_s"] - runs["first_start_s"]) / (runs["runs"] - 1)
    rec, cfg = ctx["record"], ctx["config"]
    flops = (counts.train_flops_per_token(cfg, ctx["traffic"]["seq"])
             * rec["counters"]["tokens_per_step"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * rec["counters"]["chips"]
    return 100.0 * flops / period / peak
