"""The mesh training cell at a tiny size on four forced CPU devices (in a
subprocess: this process keeps its one device), its sharded reference
against the one-device one, and the cell's metric readers on a CPU
trace."""

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

import harness
import trace

CELL = "train_stablelm24l_2x2_b32s256"
FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"

PROG = """
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{bench!r}, {src!r}]
import jax, jax.numpy as jnp
import counts, harness, load, reference, reference_mesh, run

SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)
SEED = 2 ** 32 + 515
m = harness.load_manifest()
w = harness.workload(m, {cell!r})
cfg = harness.config(m, w["config"])
cfg.update(SIZES)
cfg["projection"]["radius"] = 4.0           # binds at the tiny widths
mix = harness.traffic(w["traffic"])
mix.update(batch=8, seq=16, trace_seconds=0.5)
devices = jax.devices()[:4]
out = {{"limits": cfg["limits"]}}
result, checks = run.measure(m, w, cfg, mix, SEED, 0.5, trace=False,
                             devices=devices, t0=time.perf_counter())
out["untraced"] = {{"result": result, "checks": checks}}
# the CPU has no entry in the peaks table
real = counts.peaks
counts.peaks = lambda _kind: real("TPU v5 lite")
result, _ = run.measure(m, w, cfg, mix, SEED, 0.5, trace=True,
                        devices=devices, t0=time.perf_counter(),
                        device_pattern=r"^/host:CPU$")
out["traced"] = result["metrics"]
# a planted cross-chip fault: every shard projects its own block alone
# (no combine of the partial reduces, no gather for the outer solve)
from repro.core import schedule, sharded
body = sharded.make_schedule_body
def shard_local(sched, names, **kw):
    local = tuple(d // 2 if n else d for d, n in zip(sched.shape, names))
    return body(schedule.compile_schedule(local, sched.levels,
                                          sched.batch_dims),
                (None,) * len(local), **kw)
sharded.make_schedule_body = shard_local
result, checks = run.measure(m, w, cfg, mix, SEED, 0.5, trace=False,
                             devices=devices, t0=time.perf_counter())
sharded.make_schedule_body = body
out["fault"] = {{"result": result, "checks": checks}}

batches = [load.train_tokens(mix, SEED, i, cfg["vocab_size"])[0]
           for i in range(mix["setup_steps"])]
for name, kw in (("f32", {{}}), ("half", {{"rows": 4}}),
                 ("fp8", {{"compute": jnp.float8_e4m3fn}})):
    one = reference.train_reference(cfg, cfg["optimizer"], SEED, batches,
                                    4.0, **kw)
    mesh = reference_mesh.train_reference(
        cfg, cfg["optimizer"], SEED, batches, 4.0, devices=devices,
        mesh="2x2", block_rows=2, **kw)
    out[name] = {{"loss": max(abs(a - b) / abs(b) for a, b in
                            zip(mesh["losses"], one["losses"])),
                 "grad": max(abs(mesh["grad_norms"][p] - r) / r
                             for p, r in one["grad_norms"].items()),
                 "delta": max(abs(mesh["delta_norms"][p] - r) / r
                              for p, r in one["delta_norms"].items())}}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def tiny_run():
    code = PROG.format(bench=str(harness.BENCH),
                       src=str(harness.ROOT / "src"), cell=CELL)
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.split("RESULT", 1)[1])


def test_mesh_driver_runs_tiny(tiny_run):
    result = tiny_run["untraced"]["result"]
    checks = tiny_run["untraced"]["checks"]
    assert result["correct"] is True, checks
    assert result["attempted"] > 3 and result["failed"] == 0
    assert result["device"]["count"] == 4
    m = result["metrics"]
    assert m["train_step_ms"]["value"] > 0 and m["setup_s"]["value"] > 0
    # its checks are the configuration's limits, and nothing compiled in
    # the window
    assert set(checks) == set(tiny_run["limits"]) and checks


def test_checks_see_a_shard_local_projection(tiny_run):
    # the cell's own comparison catches an exchange between chips left
    # out: with each shard solving alone, the projected leaves' change
    # departs from the reference's
    fault = tiny_run["fault"]
    assert fault["result"]["correct"] is False, fault["checks"]
    assert (fault["checks"]["delta_gap"]["value"]
            > fault["checks"]["delta_gap"]["limit"])


def test_traced_mesh_run_reads_its_cpu_metrics(tiny_run):
    m = tiny_run["traced"]
    # off the TPU the hook runs the jnp body under shard_map: both leaves
    assert m["proj_native_leaves.mesh"]["value"] == 100.0
    assert 0 <= m["device_idle.train"]["value"] < 100
    assert m["collective_ms.mesh"]["value"] >= 0
    # a CPU trace has no program line and no codegen kernel to read
    assert "mfu.train" not in m and "proj_roofline.mesh" not in m


@pytest.mark.parametrize("kind,limit", [("f32", 1e-4), ("half", 1e-4),
                                        ("fp8", 0.05)])
def test_sharded_reference_is_the_one_device_reference(tiny_run, kind,
                                                       limit):
    # the same steps, over four devices in row blocks of 2 and a scan over
    # the layers. In float32, and with half the batch left out, only the
    # order of the sums differs: each leaf's norms agree relatively to
    # 1.6e-5 or better here (the smallest leaves' changes round most), so
    # 1e-4. The float8 control takes each tensor's power-of-two scale per
    # row block (a block's activations are its tensors): its rounding, not
    # its size, differs from the whole batch's, by 1.2% here, so 5%.
    gaps = tiny_run[kind]
    assert max(gaps.values()) < limit, gaps


@pytest.fixture(scope="module")
def cpu_ctx():
    tr = trace.reduce(FIXTURE, device_pattern=r"^/host:CPU$")
    manifest = harness.load_manifest()
    w = harness.workload(manifest, CELL)
    return {"trace": tr, "config": harness.config(manifest, w["config"]),
            "traffic": harness.traffic(w["traffic"]),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "record": {"counters": {
                "steps": 2, "chips": 4, "tokens_per_step": 32 * 256,
                "projection_leaves": {"shard_map_codegen": 2}}}}


def _read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_mesh_readers_on_the_cpu_trace(cpu_ctx):
    tr = cpu_ctx["trace"]
    idle = _read("device_idle.train", cpu_ctx)
    assert idle == pytest.approx(100 * (1 - tr["busy_s"] / tr["window_s"]))
    assert 50 < idle < 100                      # a 50 ms sleep in the window
    assert _read("collective_ms.mesh", cpu_ctx) == 0.0   # one CPU device
    assert _read("proj_native_leaves.mesh", cpu_ctx) == 100.0
    # neither a train step program nor a codegen kernel in this trace
    assert _read("mfu.train", cpu_ctx) is None
    assert _read("proj_roofline.mesh", cpu_ctx) is None


def test_mesh_readers_arithmetic(cpu_ctx):
    import copy

    import counts

    ctx = copy.deepcopy(cpu_ctx)
    tr = ctx["trace"]
    # what a TPU trace adds: the step program's runs, collectives, and the
    # codegen kernels by their stable names
    tr["modules"]["jit_train_step"] = {"s": 0.8, "runs": 3,
                                       "first_start_s": 0.0,
                                       "last_start_s": 0.8}
    tr["collective_s"] = 0.06e-9        # as the reduction divides it
    tr["paths"]["codegen_reduce f32[24,1024,2816]"] = 0.002
    tr["paths"]["codegen_apply f32[24,1024,2816]"] = 0.004
    cfg = ctx["config"]
    flops = counts.train_flops_per_token(cfg, 256) * 32 * 256
    assert flops == pytest.approx(72e12, rel=0.02)    # about 72 TFLOP a step
    assert _read("mfu.train", ctx) == pytest.approx(
        100 * flops / 0.4 / (4 * 197e12))
    assert _read("collective_ms.mesh", ctx) == pytest.approx(30.0)
    moved = 2 * 2 * 24 * 2048 * 5632 * 4 / 4          # 1.107 GB a chip
    assert moved == pytest.approx(1.107e9, rel=1e-3)
    assert _read("proj_roofline.mesh", ctx) == pytest.approx(
        100 * moved / 819e9 / (0.006 / 2))
    ctx["record"]["counters"]["projection_leaves"] = {
        "shard_map_codegen": 1, "vmapped": 1}
    assert _read("proj_native_leaves.mesh", ctx) == 50.0
    ctx["record"]["counters"]["projection_leaves"] = {}
    assert _read("proj_native_leaves.mesh", ctx) is None
