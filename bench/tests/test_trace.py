"""The trace reduction, on a small trace recorded on a CPU
(``make_cpu_trace.py``)."""

import pathlib

import pytest

import trace

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"
CPU = r"^/host:CPU$"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(FIXTURE, device_pattern=CPU)


def test_window_is_the_bench_span(reduced):
    # the window holds a 50 ms sleep between two small programs
    assert 0.05 <= reduced["window_s"] < 0.5


def test_busy_is_inside_the_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"] - 0.045


def test_longest_gap_is_labelled_with_its_host_span(reduced):
    label, seconds = reduced["idle_gaps"][0]
    assert label == "bench/sleep"
    assert seconds >= 0.045
    lengths = [s for _, s in reduced["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)


def test_ops_and_modules(reduced):
    ops = dict(reduced["device_ops"])
    assert "dot_general" in ops                # suffix ".1" removed
    assert reduced["collective_s"] == 0.0


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(FIXTURE)


def test_stable_names():
    assert trace.stable("fusion.123") == "fusion"
    assert trace.stable("jit_train_step(1234)") == "jit_train_step"
    tpu = ("%maximum_reduce_fusion.4 = f32[4]{0:T(128)S(1)} fusion(f32[4,5632]"
           "{1,0:T(4,128)S(1)} %get-tuple-element.3211), kind=kLoop")
    assert trace._op_path(tpu, {}) == "maximum_reduce_fusion f32[4]"
    tup = ("%fusion = (f32[100352,2048]{1,0:T(8,128)}, bf16[4]{0}) fusion("
           "f32[100352,2048]{1,0:T(8,128)} %p), kind=kLoop")
    assert trace._op_path(tup, {}) == "fusion (f32[100352,2048], bf16[4])"
    path = trace._op_path("fusion.7", {
        "tf_op": "jit(train_step)/jit(main)/proj/reduce0_inf/reduce_max.3"})
    assert path == "jit(train_step)/jit(main)/proj/reduce0_inf/reduce_max"


def test_seconds_under_and_union():
    tr = {"paths": {"a/proj/reduce0": 1.0, "a/proj/apply0": 2.0, "b": 4.0}}
    assert trace.seconds_under(tr, "proj/") == 3.0
    assert trace._union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def test_collectives_are_recognised():
    for name in ("all-gather.3", "reduce-scatter", "all-reduce-start",
                 "collective-permute-done"):
        assert trace.COLLECTIVE.search(name)
    assert not trace.COLLECTIVE.search("fusion.12")
