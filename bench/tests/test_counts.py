"""FLOP and byte counts from shapes, against the hand counts."""

import json

import pytest

import counts
import harness

CELL1 = json.loads((harness.BENCH / "configs" / "stablelm-1.6b-4L.json")
                   .read_text())


def test_train_flops_per_token_cell1():
    # 6 × 411,041,792 matmul params + 12 × 4 × 256 × 2048
    assert counts.train_flops_per_token(CELL1, 256) == 2_491_416_576


def test_train_flops_per_token_full_depth():
    full = dict(CELL1, num_hidden_layers=24)
    assert counts.train_flops_per_token(full, 256) == 8_782_872_576


def test_params_cell1():
    assert counts.lm_params(CELL1) == 616_581_120


def test_projection_bytes_cell1():
    # w_up and w_gate, 4 layers of 2048 × 5632 f32, read once, written once
    assert counts.projection_bytes_per_step(CELL1) == 738_197_504


def test_fig1_payload_bytes():
    assert counts.payload_bytes((1000, 10000), "float32") == 40_000_000
    assert counts.projection_bytes((1000, 10000), "float32") == 80_000_000


def test_peaks_v5e():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
