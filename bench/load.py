"""The one generator of every traffic mix (``bench/traffic/<mix>.json``).

A mix is data: its ``kind`` says which of the shapes below it takes, and the
rest are its parameters. Every seed gets the same work: the same number of
requests, the same keys in the same order and, in an open loop, the same
arrival times; the seed shuffles the scale factors and radii over them and
makes the payloads. So the spread between runs measures the system, not the
draw.

* ``train``: token batches (``batch`` rows of ``seq + 1`` tokens) from a
  counter-based hash of (seed, step, position), the launcher's synthetic
  stream with the seed folded in;
* ``open``: Poisson arrivals at ``rate_per_s`` (exponential gaps taken at
  evenly spaced quantiles), keys in the proportions of a Zipf law over the
  mix's key list, radius fractions and payload factors log-uniform;
* ``closed``: ``inflight`` requests kept outstanding, drawn in turn from a
  pool of ``pool`` request specs built the same way.
"""

from __future__ import annotations

import math

import numpy as np

_MIX = 0x9E3779B97F4A7C15
_ARRIVALS = 0x5EED        # the one stream that orders the gaps and keys


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def train_tokens(traffic: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """(1, batch, seq + 1) int32 tokens of one step."""
    n = traffic["batch"] * (traffic["seq"] + 1)
    with np.errstate(over="ignore"):
        idx = (np.arange(n, dtype=np.uint64)
               + np.uint64(step) * np.uint64(n)
               + np.uint64(int(seed) % 2 ** 63) * np.uint64(_MIX))
        z = idx + np.uint64(_MIX)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    tok = (z % np.uint64(vocab)).astype(np.int32)
    return tok.reshape(1, traffic["batch"], traffic["seq"] + 1)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _log_uniform(lo: float, hi: float, n: int) -> np.ndarray:
    return np.exp(math.log(lo) + _quantiles(n) * (math.log(hi) - math.log(lo)))


def _counts(weights, n: int) -> list:
    """Integer counts in proportion to ``weights`` summing to ``n``
    (largest remainder)."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(raw - out)[::-1][: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def key_weights(traffic: dict) -> list:
    keys = traffic["keys"]
    if traffic.get("zipf_s") is None:
        return [1.0] * len(keys)
    return [1.0 / (k + 1) ** traffic["zipf_s"] for k in range(len(keys))]


def _specs(traffic: dict, seed: int, n: int):
    """n (key, factor, radius fraction) triples: the keys in one order for
    every seed (the key sets a request's cost), the factors and fractions
    shuffled by the seed."""
    keys = []
    for name, c in zip(traffic["keys"], _counts(key_weights(traffic), n)):
        keys += [name] * c
    factors = _log_uniform(*traffic["factor"], n)
    fracs = _log_uniform(*traffic["radius_fraction"], n)
    keys = [keys[i] for i in _rng(_ARRIVALS, 1).permutation(n)]
    rng = _rng(seed, 1)
    factors = factors[rng.permutation(n)]
    fracs = fracs[rng.permutation(n)]
    return [(k, float(f), float(r)) for k, f, r in zip(keys, factors, fracs)]


def open_schedule(traffic: dict, seed: int, seconds: float) -> list:
    """[(due_s, key, factor, radius_fraction)] of an open loop: rate ×
    seconds requests, due from 0 to about ``seconds``. The due times and
    the keys due at them are the same for every seed, so no seed draws
    burstier arrivals or heavier runs of keys than another."""
    rate = traffic["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log(1.0 - _quantiles(n)) / rate
    gaps = gaps[_rng(_ARRIVALS, 2).permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [(float(t),) + s for t, s in zip(due, _specs(traffic, seed, n))]


def closed_pool(traffic: dict, seed: int) -> list:
    """The closed loop's pool of (key, factor, radius_fraction), used in
    turn."""
    return _specs(traffic, seed, traffic["pool"])


def sample(seed: int, n: int, k: int) -> set:
    """k distinct request indices in range(n), drawn from the seed."""
    return {int(i) for i in
            _rng(seed, 3).choice(n, size=min(k, n), replace=False)}
