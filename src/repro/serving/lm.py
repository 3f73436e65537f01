"""LM serving helpers: batched prefill + single-token decode steps (the
``serve_step`` of the decode_* cells) and eager greedy
generation, used by examples/serve_lm.py. The projection serving engine —
the async continuous-batching tier — lives in ``serving/engine.py``.

Decode semantics per family:
  dense/moe/vlm : KV (or MLA latent) cache, seq sharded over 'model'
  audio         : decoder self-cache + precomputed cross K/V
  ssm / hybrid  : O(1) recurrent state
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.types import ArchConfig
from repro import models


def make_decode_step(cfg: ArchConfig, api, *, n_groups: int = 1):
    """(params, tokens (B,), cache, pos) -> (next_tokens, logits, cache)."""

    def step(params, tokens, cache, pos):
        kw = {}
        if cfg.family in ("dense", "moe", "vlm"):
            kw["n_groups"] = n_groups
        logits, cache = api.decode_step(params, tokens, cache, pos, cfg, **kw)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, cache

    return step


def make_prefill(cfg: ArchConfig, api, *, impl="chunked", act_spec=None):
    """Teacher-forced pass returning last-position logits (+cache for LMs)."""

    def prefill(params, tokens):
        kw = {"remat": True, "act_spec": act_spec}
        if cfg.family not in ("ssm", "hybrid"):
            kw["impl"] = impl
        logits, _ = api.forward(params, tokens, cfg, **kw)
        return logits[:, -1]

    return prefill


def generate(params, cfg: ArchConfig, prompt, max_new: int, *,
             n_groups: int = 1, max_len: Optional[int] = None):
    """Eager greedy generation for the examples: prefill by replaying the
    prompt through decode_step (simple + exact), then greedy continue."""
    api = models.get(cfg)
    b, s = prompt.shape
    max_len = max_len or (s + max_new)
    cache = api.make_cache(cfg, b, max_len, dtype=jnp.float32)
    step = jax.jit(make_decode_step(cfg, api, n_groups=n_groups),
                   static_argnames=())
    toks = prompt
    nxt = None
    for i in range(s):  # traced pos -> one compile for all steps
        nxt, _, cache = step(params, toks[:, i], cache, jnp.int32(i))
    out = [nxt]
    for j in range(max_new - 1):
        nxt, _, cache = step(params, out[-1], cache, jnp.int32(s + j))
        out.append(nxt)
    return jnp.stack(out, axis=1)
