"""BENCHMARK.json against the contract, the files it names, the traffic
generator, and each driver run for a moment at a tiny size on the CPU."""

import json
import os
import re
import subprocess
import sys
import time

import jax
import pytest

import harness
import load
import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = harness.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"] and M["command"][1] == "bench/run.py"
    assert 1 <= M["run_seconds"] <= 51


def test_names_and_units():
    names = [c["name"] for c in M["configs"]] + CELLS + \
        [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in M["workloads"]]:
        assert NAME.match(n), n
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_cells_name_existing_files():
    used = set()
    for w in M["workloads"]:
        cfg = harness.config(M, w["config"])
        assert cfg["chips"] == w["chips"]
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (harness.BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
        used.add(w["config"])
    assert used == {c["name"] for c in M["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs_state_their_cuts():
    for c in M["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert cfg[key] != cfg["published"][key]
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) <= 4
    assert e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for cell in CELLS:
        e2e = [m["name"] for m in harness.end_to_end(M, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.per_layer(M, cell)


def test_per_layer_metrics_move_what_their_cells_report():
    for m in M["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert hasattr(harness.metric_reader(m["name"]), "read")
        for cell in m["workloads"]:
            assert cell in CELLS
            reported = [e["name"] for e in harness.end_to_end(M, cell)]
            assert m["moves"] in reported, (m["name"], cell)


def test_layers_are_named_alike():
    for m in M["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]


@pytest.mark.parametrize("mix", ["zipf4_steady", "fig1_closed64"])
def test_traffic_is_the_same_for_the_same_seed(mix):
    t = harness.traffic(mix)
    seed = 2 ** 31 + 12345
    if t["kind"] == "open":
        a, b = (load.open_schedule(t, seed, 10) for _ in range(2))
        c = load.open_schedule(t, seed + 1, 10)
    else:
        a, b = load.closed_pool(t, seed), load.closed_pool(t, seed)
        c = load.closed_pool(t, seed + 1)
    assert a == b and a != c
    # the same arrivals and keys for every seed
    assert [x[:-2] for x in a] == [x[:-2] for x in c]
    # another seed: the same work in another order
    for i in (-3, -2, -1):
        assert sorted(x[i] for x in a) == sorted(x[i] for x in c)


def test_open_schedule_rate_and_keys():
    t = harness.traffic("zipf4_steady")
    s = load.open_schedule(t, 7, 10)
    assert len(s) == round(t["rate_per_s"] * 10)
    assert 9.0 < s[-1][0] < 11.0
    keys = [x[1] for x in s]
    assert keys.count("w_up_f32") > keys.count("w_up_bf16") > \
        keys.count("fig1_f32")


def test_train_tokens_are_seeded():
    t = harness.traffic("b8s256")
    a = load.train_tokens(t, 5, 0, 100352)
    assert a.shape == (1, 8, 257)
    assert (a == load.train_tokens(t, 5, 0, 100352)).all()
    assert (a != load.train_tokens(t, 6, 0, 100352)).any()
    assert (a != load.train_tokens(t, 5, 1, 100352)).any()


def _measure(name, **kw):
    import run

    manifest, w, cfg, mix = tiny.cell(name)
    return run.measure(manifest, w, cfg, mix, 2 ** 32 + 99, 0.5, trace=False,
                       devices=jax.devices()[:1], t0=time.perf_counter(),
                       **kw)


def test_train_driver_runs_tiny():
    result, checks = _measure("train_stablelm4l_b8s256")
    assert result["attempted"] > 3 and result["failed"] == 0
    m = result["metrics"]
    assert m["train_step_ms"]["value"] > 0 and m["setup_s"]["value"] > 0
    _, _, cfg, _ = tiny.cell("train_stablelm4l_b8s256")
    assert set(checks) == set(cfg["limits"]) and checks


@pytest.mark.parametrize("name", ["serve_zipf4_steady", "serve_fig1_closed64"])
def test_serve_driver_runs_tiny(name):
    result, checks = _measure(name, interpret=True)
    assert "compiles_in_window" not in checks, checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert "err_float32" in checks


def test_buckets_reach_what_the_mix_can_fill():
    drv = harness.driver("serve")
    closed = {"kind": "closed", "inflight": 64}
    assert drv.reachable_buckets(closed, 64) == 7            # 1 .. 64
    assert drv.reachable_buckets(dict(closed, inflight=4), 64) == 3
    assert drv.reachable_buckets(dict(closed, inflight=3), 64) == 3  # 1 .. 4
    assert drv.reachable_buckets(dict(closed, inflight=5), 64) == 4
    assert drv.reachable_buckets(dict(closed, inflight=1), 64) == 1
    assert drv.reachable_buckets({"kind": "open"}, 64) == 7


def test_radius_is_rounded_on_the_host_as_the_device_rounds_it():
    import jax.numpy as jnp

    drv = harness.driver("serve")
    for r in (0.1, 3.3, 451.77, 12345.678):
        for dt in ("float32", "bfloat16"):
            assert drv.round_radius(r, dt) == float(jnp.asarray(r, dt))


def _run_py(env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=harness.ROOT)


def test_run_refuses_the_cpu():
    p = _run_py({})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_interpret_mode():
    p = _run_py({"REPRO_FORCE_INTERPRET": "1"})
    assert p.returncode != 0 and p.stdout.strip() == ""
