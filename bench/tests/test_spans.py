"""The engine's span metrics, read from a traced tiny run of each serve
cell on the CPU (the CPU's host plane stands in for the device plane)."""

import time

import jax
import pytest

import harness
import spans
import tiny

SEED = 2 ** 32 + 4242
NEW = {"serve_zipf4_steady": ["engine_submit_us.serve",
                              "engine_dispatch_us.serve"],
       "serve_fig1_closed64": ["engine_submit_us.tput",
                               "engine_dispatch_us.tput",
                               "engine_launch_us.tput"]}


@pytest.fixture()
def registry():
    from repro.obs import metrics

    fresh = metrics.Registry()
    prev = metrics.set_registry(fresh)
    yield fresh
    metrics.set_registry(prev)


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_serve_cell_reads_the_span_metrics(registry, monkeypatch,
                                                  name):
    import counts
    import run

    # the CPU has no entry in the peaks table; the span metrics read none
    real = counts.peaks
    monkeypatch.setattr(counts, "peaks", lambda _kind: real("TPU v5 lite"))
    manifest, w, cfg, mix = tiny.cell(name)
    result, _ = run.measure(manifest, w, cfg, mix, SEED, 0.3, trace=True,
                            devices=jax.devices()[:1],
                            t0=time.perf_counter(),
                            device_pattern=r"^/host:CPU$", interpret=True)
    assert result["failed"] == 0
    declared = {m["name"] for m in harness.per_layer(manifest, name)
                if m["source"] == "program_span"}
    assert set(NEW[name]) <= declared
    for metric in NEW[name]:
        assert result["metrics"][metric]["value"] > 0, metric
        assert result["metrics"][metric]["unit"] == "us"
    # every submit the client made in the window, and none of set-up's
    _, submits = spans.totals("serving/submit")
    assert submits == result["attempted"]
    _, dispatches = spans.totals("serving/dispatch")
    _, launches = spans.totals("serving/launch")
    assert 1 <= launches <= dispatches <= submits


def test_no_tally_reads_as_none(registry):
    assert spans.totals("serving/submit") == (0.0, 0)
    for names in NEW.values():
        for metric in names:
            assert harness.metric_reader(metric).read({}) is None
