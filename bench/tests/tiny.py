"""Tiny copies of the configurations and mixes, for the CPU: the same
files with every size cut, the limits kept."""

import harness

TRAIN_SIZES = dict(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=4,
                   num_hidden_layers=2, vocab_size=256)
KEY_SHAPES = {"w_up_f32": [16, 40], "w_up_bf16": [16, 40],
              "heads_tri_f32": [4, 8, 16], "fig1_f32": [10, 100]}


def cell(name):
    manifest = harness.load_manifest()
    w = harness.workload(manifest, name)
    cfg = harness.config(manifest, w["config"])
    mix = harness.traffic(w["traffic"])
    if cfg["driver"] == "train":
        cfg.update(TRAIN_SIZES)
        cfg["projection"]["radius"] = 4.0        # binds at the tiny widths
        mix.update(batch=4, seq=16, trace_seconds=0.5)
    else:
        for k in cfg["keys"]:
            k["shape"] = KEY_SHAPES[k["name"]]
        cfg["engine"].update(max_batch=4)
        if mix["kind"] == "open":
            mix["rate_per_s"] = 40
        else:
            mix.update(inflight=4, sample_range=40, sample=8)
        mix["trace_seconds"] = 0.5
    return manifest, w, cfg, mix
