"""Operation and byte counts from shapes: the yardstick of the rooflines.

Every count here is the least work any implementation of the operation must
do, so that a faster implementation reads a higher share and never more than
the whole: model FLOPs without recomputation, and one read plus one write of
each projected tensor.
"""

from __future__ import annotations

import json
import math
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; a kind that is not in the
    table is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def dtype_bytes(dtype: str) -> int:
    return _DTYPE_BYTES[dtype]


def lm_matmul_params(m: dict) -> int:
    """Parameters that enter a matmul per token: the layers' attention and
    MLP weights and the output head (the embedding is a gather)."""
    d, f, n = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    hd = d // m["num_attention_heads"]
    q_o = 2 * d * m["num_attention_heads"] * hd
    k_v = 2 * d * m["num_key_value_heads"] * hd
    mlp = 3 * d * f
    return n * (q_o + k_v + mlp) + d * m["vocab_size"]


def lm_params(m: dict) -> int:
    """Every parameter of the dense decoder: matmul weights, the embedding,
    two norm scales per layer and the final norm."""
    d, n = m["hidden_size"], m["num_hidden_layers"]
    return lm_matmul_params(m) + m["vocab_size"] * d + 2 * n * d + d


def train_flops_per_token(m: dict, seq: int) -> int:
    """Model FLOPs of forward and backward per token, no remat recompute:
    6 × matmul params + 12 × layers × seq × d_model (attention scores and
    values, forward and backward)."""
    return (6 * lm_matmul_params(m)
            + 12 * m["num_hidden_layers"] * seq * m["hidden_size"])


def projected_leaf_elements(m: dict) -> int:
    """Elements of the leaves the train step projects: every layer's w_up
    and w_gate, (hidden, intermediate) each."""
    return 2 * m["num_hidden_layers"] * m["hidden_size"] * m["intermediate_size"]


def projection_bytes_per_step(m: dict, param_dtype: str = "float32") -> int:
    """One read and one write of every projected leaf."""
    return 2 * projected_leaf_elements(m) * dtype_bytes(param_dtype)


def payload_bytes(shape, dtype: str) -> int:
    return math.prod(shape) * dtype_bytes(dtype)


def projection_bytes(shape, dtype: str) -> int:
    """One read and one write of a projected payload."""
    return 2 * payload_bytes(shape, dtype)
