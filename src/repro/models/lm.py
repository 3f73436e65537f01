"""Unified decoder-only LM: covers stablelm / danube / granite / qwen3 /
chameleon (dense, GQA, SWA, qk-norm) and deepseek-v3 / kimi-k2 (MLA + MoE).

Layers are *stacked* (leading 'layers' axis) and applied with lax.scan — one
layer body in the HLO regardless of depth (compile time does not grow with
the layer count). MoE models have two stacks: the leading dense layers and the
MoE layers.

Three entry points:
  forward(params, tokens)                          -> logits       (training)
  prefill(params, tokens, cache)                   -> logits, cache
  decode_step(params, token, cache, pos)           -> logits, cache

MLA decode uses weight absorption: only the compressed c_kv / k_rope are
cached (573 floats/token for deepseek-v3 instead of 32k — the whole point of
MLA), and W_kv_b is folded into the query/output projections.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.types import ArchConfig
from . import layers as L
from .params import ParamDef


# ------------------------------------------------------------------ templates
def _attn_template(cfg: ArchConfig, n: int):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        t = {
            "wq_a": ParamDef((n, d, m.q_lora_rank), ("layers", "embed", None), "scaled"),
            "q_norm": ParamDef((n, m.q_lora_rank), ("layers", None), "ones"),
            "wq_b": ParamDef((n, m.q_lora_rank, cfg.n_heads, qk),
                             ("layers", None, "heads", None), "scaled"),
            "wkv_a": ParamDef((n, d, m.kv_lora_rank + m.qk_rope_dim),
                              ("layers", "embed", None), "scaled"),
            "kv_norm": ParamDef((n, m.kv_lora_rank), ("layers", None), "ones"),
            "wkv_b": ParamDef((n, m.kv_lora_rank, cfg.n_heads,
                               m.qk_nope_dim + m.v_head_dim),
                              ("layers", None, "heads", None), "scaled"),
            "wo": ParamDef((n, cfg.n_heads, m.v_head_dim, d),
                           ("layers", "heads", None, "embed"), "scaled"),
        }
        return t
    t = {
        "wq": ParamDef((n, d, cfg.n_heads, hd), ("layers", "embed", "heads", None),
                       "scaled"),
        "wk": ParamDef((n, d, cfg.n_kv_heads, hd),
                       ("layers", "embed", "kv_heads", None), "scaled"),
        "wv": ParamDef((n, d, cfg.n_kv_heads, hd),
                       ("layers", "embed", "kv_heads", None), "scaled"),
        "wo": ParamDef((n, cfg.n_heads, hd, d), ("layers", "heads", None, "embed"),
                       "scaled"),
    }
    if cfg.qk_norm:
        t["qn"] = ParamDef((n, hd), ("layers", None), "ones")
        t["kn"] = ParamDef((n, hd), ("layers", None), "ones")
    return t


def _stack_mlp(cfg: ArchConfig, n: int):
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_up": ParamDef((n, d, f), ("layers", "embed", "ffn"), "scaled"),
        "w_gate": ParamDef((n, d, f), ("layers", "embed", "ffn"), "scaled"),
        "w_down": ParamDef((n, f, d), ("layers", "ffn", "embed"), "scaled"),
    }


def _stack_moe(cfg: ArchConfig, n: int):
    mo = cfg.moe
    d, e, f = cfg.d_model, mo.n_experts, mo.d_expert
    t = {
        "router": ParamDef((n, d, e), ("layers", "embed", None), "scaled"),
        "w_gate": ParamDef((n, e, d, f), ("layers", "experts", "embed", "expert_ff"),
                           "scaled"),
        "w_up": ParamDef((n, e, d, f), ("layers", "experts", "embed", "expert_ff"),
                         "scaled"),
        "w_down": ParamDef((n, e, f, d), ("layers", "experts", "expert_ff", "embed"),
                           "scaled"),
    }
    if mo.n_shared:
        ds = (mo.d_shared or mo.d_expert) * mo.n_shared
        t["shared"] = {
            "w_up": ParamDef((n, d, ds), ("layers", "embed", "ffn"), "scaled"),
            "w_gate": ParamDef((n, d, ds), ("layers", "embed", "ffn"), "scaled"),
            "w_down": ParamDef((n, ds, d), ("layers", "ffn", "embed"), "scaled"),
        }
    return t


def _block_template(cfg: ArchConfig, n: int, moe: bool):
    t = {
        "ln1": ParamDef((n, cfg.d_model), ("layers", None), "ones"),
        "ln2": ParamDef((n, cfg.d_model), ("layers", None), "ones"),
        "attn": _attn_template(cfg, n),
        "mlp": _stack_moe(cfg, n) if moe else _stack_mlp(cfg, n),
    }
    return t


def template(cfg: ArchConfig):
    d = cfg.d_model
    t = {
        "embed": ParamDef((cfg.vocab, d), ("vocab", "embed"), "normal", 0.02),
        "final_norm": ParamDef((d,), (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        t["unembed"] = ParamDef((d, cfg.vocab), ("embed", "vocab"), "scaled")
    if cfg.moe is not None:
        nd, nm = cfg.moe.first_dense, cfg.n_layers - cfg.moe.first_dense
        if nd:
            t["dense_blocks"] = _block_template(
                dataclasses.replace(cfg), nd, moe=False)
        t["moe_blocks"] = _block_template(cfg, nm, moe=True)
    else:
        t["blocks"] = _block_template(cfg, cfg.n_layers, moe=False)
    return t


# ------------------------------------------------------------------ attention
def _attn_dense(lp, h, cfg: ArchConfig, *, positions, impl, cache=None,
                cache_pos=None, window):
    """Standard (GQA) attention. h (B,S,D). Returns out, (k,v) for caching."""
    b, s, _ = h.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["qn"], cfg.norm_eps)
        k = L.rms_norm(k, lp["kn"], cfg.norm_eps)
    freqs = L.rope_frequencies(hd, cfg.rope_pct, cfg.rope_theta, positions)
    q = L.apply_rope(q, freqs)
    k = L.apply_rope(k, freqs)
    out = L.attention(q, k, v, causal=True, window=window, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out, lp["wo"]), (k, v)


def _attn_dense_decode(lp, h, cfg: ArchConfig, *, pos, cache, window):
    """h (B,1,D); cache dict with k/v (B,T,KV,hd) (ring buffer when windowed)."""
    b = h.shape[0]
    hd = cfg.resolved_head_dim
    hq = h[:, 0]
    q = jnp.einsum("bd,dhk->bhk", hq, lp["wq"])
    k = jnp.einsum("bd,dhk->bhk", hq, lp["wk"])
    v = jnp.einsum("bd,dhk->bhk", hq, lp["wv"])
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["qn"], cfg.norm_eps)
        k = L.rms_norm(k, lp["kn"], cfg.norm_eps)
    posv = jnp.full((b,), pos, jnp.int32)
    freqs = L.rope_frequencies(hd, cfg.rope_pct, cfg.rope_theta, posv)
    q = L.apply_rope(q[:, None], (freqs[0][:, None], freqs[1][:, None], freqs[2])
                     if freqs else None)[:, 0]
    k = L.apply_rope(k[:, None], (freqs[0][:, None], freqs[1][:, None], freqs[2])
                     if freqs else None)[:, 0]
    t = cache["k"].shape[1]
    slot = pos % t if window is not None else pos
    kc = jax.lax.dynamic_update_slice(cache["k"], k[:, None].astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v[:, None].astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    cur = jnp.full((b,), pos + 1, jnp.int32)
    out = L.attention_decode(q, kc, vc, cur, window=window)
    return jnp.einsum("bhk,hkd->bd", out, lp["wo"])[:, None], {"k": kc, "v": vc}


def _attn_mla(lp, h, cfg: ArchConfig, *, positions, impl, window):
    """MLA training/prefill path (full expansion). Returns out, (c_kv, k_rope)."""
    m = cfg.mla
    b, s, _ = h.shape
    q_lat = L.rms_norm(jnp.einsum("bsd,dr->bsr", h, lp["wq_a"]), lp["q_norm"],
                       cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, lp["wq_b"])
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    kv_a = jnp.einsum("bsd,dr->bsr", h, lp["wkv_a"])
    c_kv = L.rms_norm(kv_a[..., : m.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]                       # (B,S,rope)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, lp["wkv_b"])
    k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]

    freqs = L.rope_frequencies(m.qk_rope_dim, 1.0, cfg.rope_theta, positions)
    q_rope = L.apply_rope(q_rope, freqs)
    k_rope_r = L.apply_rope(k_rope[:, :, None, :], freqs)     # single kv head
    k_rope_b = jnp.broadcast_to(k_rope_r, (b, s, cfg.n_heads, m.qk_rope_dim))

    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = L.attention(q_full, k_full, v, causal=True, window=window, impl=impl)
    proj = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
    return proj, (c_kv, L.apply_rope(k_rope[:, :, None, :], freqs)[:, :, 0])


def _attn_mla_decode(lp, h, cfg: ArchConfig, *, pos, cache):
    """MLA decode with weight absorption; cache holds c_kv (B,T,r), k_rope."""
    m = cfg.mla
    b = h.shape[0]
    hq = h[:, 0]
    q_lat = L.rms_norm(hq @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
    q = jnp.einsum("br,rhk->bhk", q_lat, lp["wq_b"])
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    kv_a = hq @ lp["wkv_a"]
    c_kv = L.rms_norm(kv_a[..., : m.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]

    posv = jnp.full((b,), pos, jnp.int32)
    freqs = L.rope_frequencies(m.qk_rope_dim, 1.0, cfg.rope_theta, posv)
    fq = (freqs[0][:, None], freqs[1][:, None], freqs[2])
    q_rope = L.apply_rope(q_rope[:, None], fq)[:, 0]
    k_rope = L.apply_rope(k_rope[:, None, None, :], fq)[:, 0, 0]

    ck = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv[:, None].astype(cache["c_kv"].dtype), (0, pos, 0))
    kr = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, None].astype(cache["k_rope"].dtype), (0, pos, 0))

    # absorption: q_eff = q_nope @ W_kvb[:, :, :nope]ᵀ  -> latent space
    wk = lp["wkv_b"][..., : m.qk_nope_dim]                     # (r, H, nope)
    q_eff = jnp.einsum("bhk,rhk->bhr", q_nope, wk)             # (B,H,r)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    lat = jnp.einsum("bhr,btr->bht", q_eff, ck.astype(jnp.float32))
    rop = jnp.einsum("bhk,btk->bht", q_rope, kr.astype(jnp.float32))
    logits = (lat + rop) * scale
    valid = jnp.arange(ck.shape[1])[None, :] <= pos
    logits = jnp.where(valid[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    lat_out = jnp.einsum("bht,btr->bhr", w, ck.astype(jnp.float32))  # (B,H,r)
    wv = lp["wkv_b"][..., m.qk_nope_dim:]                       # (r, H, v)
    out = jnp.einsum("bhr,rhk->bhk", lat_out.astype(h.dtype), wv)
    proj = jnp.einsum("bhk,hkd->bd", out, lp["wo"])
    return proj[:, None], {"c_kv": ck, "k_rope": kr}


# --------------------------------------------------------------------- blocks
def _block(lp, x, cfg: ArchConfig, *, moe: bool, positions, impl, n_groups,
           collect=None):
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        a, _ = _attn_mla(lp["attn"], h, cfg, positions=positions, impl=impl,
                         window=cfg.window)
    else:
        a, _ = _attn_dense(lp["attn"], h, cfg, positions=positions, impl=impl,
                           window=cfg.window)
    x = x + a
    h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if moe:
        b, s, d = h2.shape
        y, aux = L.moe_apply(lp["mlp"], h2.reshape(b * s, d), cfg.moe,
                             n_groups=n_groups, act=cfg.act)
        y = y.reshape(b, s, d)
    else:
        y, aux = L.mlp_apply(lp["mlp"], h2, cfg.act), 0.0
    out = x + y
    # harvest sites (data/activations.py): the post-block residual stream or
    # the MLP branch output (pre-residual-add) — the two streams SAEs are
    # trained on in the interpretability literature
    cap = None if collect is None else (out if collect == "resid" else y)
    return out, aux, cap


def _scan_blocks(blocks, x, cfg, *, moe, positions, impl, n_groups, remat=True,
                 collect=None):
    def body(carry, lp):
        x, aux = carry
        fn = functools.partial(_block, cfg=cfg, moe=moe, positions=positions,
                               impl=impl, n_groups=n_groups, collect=collect)
        if remat:
            fn = jax.checkpoint(fn)
        y, a, cap = fn(lp, x)
        return (y, aux + a), cap

    (x, aux), caps = jax.lax.scan(body, (x, 0.0), blocks)
    return x, aux, caps


def forward(params, tokens, cfg: ArchConfig, *, impl="chunked", n_groups=1,
            remat=True, act_spec=None, collect=None):
    """tokens (B, S) int32 -> logits (B, S, V). aux returned for MoE balance.

    ``act_spec``: PartitionSpec for (B, S, D) activations. The embedding
    gather otherwise inherits the table's FSDP sharding (batch replicated!) —
    constraining here pins activations to batch-sharded layout for the whole
    stack.

    ``collect``: None | "resid" | "mlp" — when set, also return the per-layer
    activations stacked on a leading layer axis, shape (L, B, S, D): the
    post-block residual stream or the MLP branch output. This is the capture
    point of the SAE activation-harvesting stage (data/activations.py);
    ``remat`` is usually off for harvesting (no backward pass)."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(params["final_norm"].dtype)
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    aux = 0.0
    caps = []
    if cfg.moe is not None:
        if cfg.moe.first_dense:
            x, a1, c1 = _scan_blocks(params["dense_blocks"], x, cfg, moe=False,
                                     positions=positions, impl=impl,
                                     n_groups=n_groups, remat=remat,
                                     collect=collect)
            aux += a1
            caps.append(c1)
        x, a2, c2 = _scan_blocks(params["moe_blocks"], x, cfg, moe=True,
                                 positions=positions, impl=impl,
                                 n_groups=n_groups, remat=remat,
                                 collect=collect)
        aux += a2
        caps.append(c2)
    else:
        x, _, c = _scan_blocks(params["blocks"], x, cfg, moe=False,
                               positions=positions, impl=impl,
                               n_groups=n_groups, remat=remat, collect=collect)
        caps.append(c)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    un = params.get("unembed")
    logits = x @ un if un is not None else x @ params["embed"].T
    if collect is not None:
        acts = caps[0] if len(caps) == 1 else jnp.concatenate(caps, axis=0)
        return logits, aux, acts
    return logits, aux


# -------------------------------------------------------------------- serving
def make_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-layer cache pytree. Windowed archs get ring buffers."""
    t = min(max_len, cfg.window) if cfg.window else max_len
    n = cfg.n_layers
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((n, batch, t, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((n, batch, t, m.qk_rope_dim), dtype),
        }
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((n, batch, t, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((n, batch, t, cfg.n_kv_heads, hd), dtype),
    }


def cache_specs(cfg: ArchConfig, rules, mesh_shape):
    """PartitionSpecs for the cache: batch -> data, seq -> model (flash-decode)."""
    from jax.sharding import PartitionSpec as P
    batch_ax = rules.get("batch")
    seq_ax = rules.get("cache_seq")
    if cfg.mla is not None:
        return {"c_kv": P(None, batch_ax, seq_ax, None),
                "k_rope": P(None, batch_ax, seq_ax, None)}
    return {"k": P(None, batch_ax, seq_ax, None, None),
            "v": P(None, batch_ax, seq_ax, None, None)}


def _stacked_blocks_for_decode(params, cfg):
    """(blocks_tree, moe_flags) — blocks concatenated dense-first for MoE."""
    if cfg.moe is None:
        return [(params["blocks"], False, cfg.n_layers)]
    out = []
    if cfg.moe.first_dense:
        out.append((params["dense_blocks"], False, cfg.moe.first_dense))
    out.append((params["moe_blocks"], True, cfg.n_layers - cfg.moe.first_dense))
    return out


def decode_step(params, tokens, cache, pos, cfg: ArchConfig, *, n_groups=1):
    """One token for the whole batch. tokens (B,) int32; pos: python/traced int.
    Returns (logits (B, V), new_cache)."""
    b = tokens.shape[0]
    x = params["embed"][tokens][:, None].astype(params["final_norm"].dtype)
    layer_off = 0
    new_cache = {k: [] for k in cache}

    for blocks, moe, n in _stacked_blocks_for_decode(params, cfg):
        cache_slice = {k: v[layer_off:layer_off + n] for k, v in cache.items()}

        def body(x, xs, moe=moe):
            lp, cl = xs
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            if cfg.mla is not None:
                a, cnew = _attn_mla_decode(lp["attn"], h, cfg, pos=pos, cache=cl)
            else:
                a, cnew = _attn_dense_decode(lp["attn"], h, cfg, pos=pos,
                                             cache=cl, window=cfg.window)
            x = x + a
            h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if moe:
                y, _ = L.moe_apply(lp["mlp"], h2[:, 0], cfg.moe,
                                   n_groups=n_groups, act=cfg.act)
                y = y[:, None]
            else:
                y = L.mlp_apply(lp["mlp"], h2, cfg.act)
            return x + y, cnew

        x, upd = jax.lax.scan(body, x, (blocks, cache_slice))
        for k in cache:
            new_cache[k].append(upd[k])
        layer_off += n

    merged = {k: jnp.concatenate(v, axis=0) if len(v) > 1 else v[0]
              for k, v in new_cache.items()}
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    un = params.get("unembed")
    logits = (x[:, 0] @ un) if un is not None else x[:, 0] @ params["embed"].T
    return logits, merged
