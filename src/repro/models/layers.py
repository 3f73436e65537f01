"""Neural-net building blocks shared by all 10 assigned architectures.

Everything is a pure function of (params, inputs). Attention comes in three
implementations selected by ``impl``:

  * "naive"   — materializes S×S logits (tiny smoke tests only)
  * "chunked" — lax.scan online softmax over KV chunks: memory-bounded, pure
                jnp, the default path (flash semantics, XLA-lowered)
  * "pallas"  — repro.kernels flash kernel (real TPUs)

All attention math accumulates in f32 regardless of compute dtype.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.types import MLAConfig, MoEConfig, SSMConfig
from .params import ParamDef

_NEG = -1e30

# tuning knobs for the chunked attention path (set by launch/specs.py
# before lowering; trace-time constants)
ATTN_TUNE = {"chunk": 1024, "probs_dtype": None}  # None -> f32 probs


# ---------------------------------------------------------------------- norms
def rms_norm(x, scale, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


# ----------------------------------------------------------------------- rope
def rope_frequencies(head_dim: int, rope_pct: float, theta: float, positions):
    """positions (…,) int32 -> (cos, sin) of shape (…, rot_dim//2)."""
    rot = int(head_dim * rope_pct)
    rot -= rot % 2
    if rot == 0:
        return None
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang), rot


def apply_rope(x, freqs):
    """x (..., S, H, D); freqs from rope_frequencies with positions (..., S)."""
    if freqs is None:
        return x
    cos, sin, rot = freqs
    xf = x.astype(jnp.float32)
    xr, xp = xf[..., :rot], xf[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


# ------------------------------------------------------------------ attention
def _gqa_logits(q, k):
    """q (B,S,KV,G,D) × k (B,T,KV,D) -> (B,KV,G,S,T) in f32."""
    return jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def attention_naive(q, k, v, *, causal=True, window=None, q_offset=0):
    """q (B,S,H,Dqk), k (B,T,KV,Dqk), v (B,T,KV,Dv). Returns (B,S,H,Dv)."""
    b, s, h, d = q.shape
    t, kv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kv
    qg = q.reshape(b, s, kv, g, d) * (d ** -0.5)
    logits = _gqa_logits(qg, k)
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, _NEG)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, dv).astype(q.dtype)


def attention_chunked(q, k, v, *, causal=True, window=None, q_offset=0,
                      chunk=1024):
    """Online-softmax over KV chunks (flash semantics in pure jnp)."""
    b, s, h, d = q.shape
    t, kv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kv
    chunk = min(chunk, t)
    n_chunks = -(-t // chunk)
    t_pad = n_chunks * chunk
    if t_pad != t:
        pad = [(0, 0), (0, t_pad - t), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    kc = k.reshape(b, n_chunks, chunk, kv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kv, dv).transpose(1, 0, 2, 3, 4)

    qg = (q.astype(jnp.float32) * (d ** -0.5)).reshape(b, s, kv, g, d)
    qpos = (jnp.arange(s) + q_offset)[:, None]

    m0 = jnp.full((b, kv, g, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, kv, g, s), jnp.float32)
    a0 = jnp.zeros((b, kv, g, s, dv), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, idx = xs
        logits = _gqa_logits(qg, kb)  # (b,kv,g,s,chunk)
        kpos = (idx * chunk + jnp.arange(chunk))[None, :]
        mask = kpos < t
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None, None], logits, _NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pd = ATTN_TUNE.get("probs_dtype")
        pv = p.astype(pd) if pd is not None else p
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", pv, vb.astype(pd or jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.where(l == 0, 1.0, l)[..., None]          # (b,kv,g,s,dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dv).astype(q.dtype)


def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              impl="chunked", chunk=None):
    if impl == "naive":
        return attention_naive(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    if impl == "chunked":
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset,
                                 chunk=chunk or ATTN_TUNE["chunk"])
    if impl == "pallas":
        from repro.kernels import ops
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        o = ops.attention(qt, kt, vt, causal=causal, window=window)
        return o.transpose(0, 2, 1, 3)
    raise ValueError(f"unknown attention impl {impl!r}")


def attention_decode(q, k_cache, v_cache, cur_len, *, window=None):
    """Single-token decode. q (B,H,D); caches (B,T,KV,D); cur_len int32.

    Pure reductions over the cache axis — GSPMD keeps the cache sharded over
    'model' (sequence dim) and inserts partial-softmax all-reduces
    (flash-decode). ``window`` caches are ring buffers: every slot is valid
    once the ring wraps, and positions are handled by the caller.
    """
    b, h, d = q.shape
    t, kv, dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[-1]
    g = h // kv
    qg = (q.astype(jnp.float32) * (d ** -0.5)).reshape(b, kv, g, d)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache.astype(jnp.float32))
    pos = jnp.arange(t)[None, :]
    if window is None:
        valid = pos < cur_len[:, None]                    # (B, T)
    else:
        valid = pos < jnp.minimum(cur_len, t)[:, None]    # ring: all once full
    logits = jnp.where(valid[:, None, None], logits, _NEG)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgt,btkd->bkgd", p / l, v_cache.astype(jnp.float32))
    return out.reshape(b, h, dv).astype(q.dtype)


# ------------------------------------------------------------------------ mlp
def _act(x, kind):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(kind)


def mlp_template(d_model: int, d_ff: int, act: str = "silu"):
    t = {
        "w_up": ParamDef((d_model, d_ff), ("embed", "ffn"), "scaled"),
        "w_down": ParamDef((d_ff, d_model), ("ffn", "embed"), "scaled"),
    }
    if act != "gelu":  # gated (SwiGLU-style) for silu/relu families
        t["w_gate"] = ParamDef((d_model, d_ff), ("embed", "ffn"), "scaled")
    return t


def mlp_apply(p, x, act="silu"):
    up = x @ p["w_up"]
    if "w_gate" in p:
        up = _act(x @ p["w_gate"], act) * up
    else:
        up = _act(up, act)
    return up @ p["w_down"]


# ------------------------------------------------------------------------ moe
def moe_template(d_model: int, cfg: MoEConfig):
    e, f = cfg.n_experts, cfg.d_expert
    t = {
        "router": ParamDef((d_model, e), ("embed", None), "scaled"),
        "w_gate": ParamDef((e, d_model, f), ("experts", "embed", "expert_ff"), "scaled"),
        "w_up": ParamDef((e, d_model, f), ("experts", "embed", "expert_ff"), "scaled"),
        "w_down": ParamDef((e, f, d_model), ("experts", "expert_ff", "embed"), "scaled"),
    }
    if cfg.n_shared:
        ds = cfg.d_shared or cfg.d_expert
        t["shared"] = mlp_template(d_model, ds * cfg.n_shared, "silu")
    return t


def moe_apply(p, x, cfg: MoEConfig, *, n_groups: int, act="silu"):
    """GShard-style capacity-dispatch MoE. x (T, M) flattened tokens.

    Tokens are split into ``n_groups`` groups (≈ one per data shard); dispatch
    is per-group so the position-cumsum never crosses shards. ``einsum``
    dispatch is the robust GSPMD path; ``scatter`` (cfg.dispatch) is the
    gather-based variant.
    """
    tkns, m = x.shape
    e, k = cfg.n_experts, cfg.top_k
    g = math.gcd(n_groups, tkns)
    tg = tkns // g
    cap = int(max(1, math.ceil(tg * k / e * cfg.capacity_factor)))
    xg = x.reshape(g, tg, m)

    logits = (xg.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (g, tg, e)
    top_v, top_i = jax.lax.top_k(probs, k)                     # (g, tg, k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)       # (g, tg, k, e)
    slot_mask = onehot                                         # k slots in priority order
    # position of each (token, slot) in its expert queue
    flat = slot_mask.reshape(g, tg * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                      # (g, tg*k, e)
    pos = jnp.sum(pos.reshape(g, tg, k, e) * onehot, axis=-1)  # (g, tg, k)
    expert_of = top_i
    keep = pos < cap
    gate = top_v * keep

    if cfg.dispatch == "einsum":
        # collapse the k slots: a token holds at most one slot per expert
        oh_e = jax.nn.one_hot(expert_of, e, dtype=jnp.float32)   # (g, tg, k, e)
        mask_te = jnp.einsum("gtke,gtk->gte", oh_e, keep.astype(jnp.float32))
        pos_te = jnp.einsum("gtke,gtk->gte", oh_e, pos)
        gate_te = jnp.einsum("gtke,gtk->gte", oh_e, gate)
        oh_c = jax.nn.one_hot(pos_te.astype(jnp.int32), cap, dtype=jnp.float32)
        disp_te = (mask_te[..., None] * oh_c).astype(x.dtype)   # (g, tg, e, cap)
        xe = jnp.einsum("gtec,gtm->gecm", disp_te, xg)          # (g, e, cap, m)
        h = jnp.einsum("gecm,emf->gecf", xe, p["w_up"])
        hg = _act(jnp.einsum("gecm,emf->gecf", xe, p["w_gate"]), act)
        ye = jnp.einsum("gecf,efm->gecm", h * hg, p["w_down"])
        comb = (gate_te[..., None].astype(x.dtype) * disp_te)   # (g, tg, e, cap)
        out = jnp.einsum("gtec,gecm->gtm", comb, ye)
    else:  # scatter: gather-based dispatch (no one-hot matmul FLOPs)
        slot_idx = (expert_of * cap + pos.astype(jnp.int32))   # (g, tg, k)
        slot_idx = jnp.where(keep, slot_idx, e * cap)          # overflow -> dropped row
        buf = jnp.zeros((g, e * cap + 1, m), x.dtype)
        src = jnp.repeat(xg[:, :, None, :], k, axis=2)         # (g, tg, k, m)
        buf = buf.at[jnp.arange(g)[:, None, None],
                     slot_idx, :].add(src, mode="drop")
        xe = buf[:, : e * cap, :].reshape(g, e, cap, m)
        h = jnp.einsum("gecm,emf->gecf", xe, p["w_up"])
        hg = _act(jnp.einsum("gecm,emf->gecf", xe, p["w_gate"]), act)
        ye = jnp.einsum("gecf,efm->gecm", h * hg, p["w_down"]).reshape(g, e * cap, m)
        ye = jnp.concatenate([ye, jnp.zeros((g, 1, m), x.dtype)], axis=1)
        gath = ye[jnp.arange(g)[:, None, None], slot_idx, :]   # (g, tg, k, m)
        out = jnp.sum(gath * gate[..., None].astype(x.dtype), axis=2)

    if cfg.n_shared:
        out = out + mlp_apply(p["shared"], xg, act)
    # aux load-balance loss (Switch): mean fraction * mean prob per expert
    me = jnp.mean(jnp.sum(onehot, axis=2), axis=1)             # (g, e) token frac
    pe = jnp.mean(probs, axis=1)
    aux = e * jnp.mean(jnp.sum(me * pe, axis=-1))
    return out.reshape(tkns, m), aux


# --------------------------------------------------------------------- mamba2
def mamba2_template(d_model: int, cfg: SSMConfig):
    di = cfg.expand * d_model
    h = di // cfg.head_dim
    gn = cfg.n_groups * cfg.d_state
    return {
        # fused input projection: [z(di), x(di), B(gn), C(gn), dt(h)]
        "w_in": ParamDef((d_model, 2 * di + 2 * gn + h), ("embed", "ssm_in"), "scaled"),
        "conv_w": ParamDef((cfg.d_conv, di + 2 * gn), (None, None), "scaled", 0.1),
        "a_log": ParamDef((h,), (None,), "zeros"),
        "d_skip": ParamDef((h,), (None,), "ones"),
        "dt_bias": ParamDef((h,), (None,), "zeros"),
        "norm": ParamDef((di,), (None,), "ones"),
        "w_out": ParamDef((di, d_model), ("ssm_in", "embed"), "scaled"),
    }


def _ssd_chunked(x, dt, A, B, C, *, chunk: int):
    """Mamba2 SSD, chunked-parallel. x (b,s,h,p), dt (b,s,h), A (h,),
    B/C (b,s,g,n) with h % g == 0. Returns (b,s,h,p). f32 internally."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # chunked views (b, nc, c, ...)
    xc = x.reshape(b, nc, c, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, c, h).astype(jnp.float32)
    Bc = jnp.repeat(B.reshape(b, nc, c, g, n), rep, axis=3).astype(jnp.float32)
    Cc = jnp.repeat(C.reshape(b, nc, c, g, n), rep, axis=3).astype(jnp.float32)

    dA = dtc * (-jnp.exp(A.astype(jnp.float32)))[None, None, None, :]  # ≤ 0
    cum = jnp.cumsum(dA, axis=2)                                # (b,nc,c,h)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]          # (b,nc,i,j,h)
    ii = jnp.arange(c)
    causal = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    L = jnp.where(causal, jnp.exp(li), 0.0)
    scores = jnp.einsum("bzihn,bzjhn->bzijh", Cc, Bc) * L
    y_diag = jnp.einsum("bzijh,bzjhp->bzihp", scores, xc * dtc[..., None])
    # chunk end-states: S_z = sum_j exp(cum_end - cum_j) * B_j x_j dt_j
    decay_out = jnp.exp(cum[:, :, -1:, :] - cum)                # (b,nc,c,h)
    S = jnp.einsum("bzjhn,bzjhp->bzhnp",
                   Bc * (decay_out * dtc)[..., None], xc)
    # cross-chunk recurrence over z
    chunk_decay = jnp.exp(cum[:, :, -1, :])                     # (b,nc,h)

    def scan_fn(hprev, inp):
        Sz, dz = inp
        hnew = hprev * dz[..., None, None] + Sz
        return hnew, hprev

    h0 = jnp.zeros((b, h, n, p), jnp.float32)
    _, h_in = jax.lax.scan(scan_fn, h0,
                           (S.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_in = h_in.transpose(1, 0, 2, 3, 4)                        # (b,nc,h,n,p) state entering chunk
    y_off = jnp.einsum("bzihn,bzhnp->bzihp", Cc * jnp.exp(cum)[..., None], h_in)
    y = (y_diag + y_off).reshape(b, nc * c, h, p)
    return y[:, :s]


def mamba2_apply(p, x, cfg: SSMConfig, *, state=None):
    """Mamba2 block. x (b, s, d). If ``state`` is given (decode), s must be 1
    and the returned aux is the updated (conv_state, ssm_state)."""
    b, s, d = x.shape
    di = cfg.expand * d
    h = di // cfg.head_dim
    gn = cfg.n_groups * cfg.d_state
    proj = x @ p["w_in"]
    z, xs, Bf, Cf, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + gn, 2 * di + 2 * gn], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    conv_in = jnp.concatenate([xs, Bf, Cf], axis=-1)            # (b, s, di+2gn)
    if state is None:
        # causal depthwise conv over time
        ci = jnp.pad(conv_in, ((0, 0), (cfg.d_conv - 1, 0), (0, 0)))
        win = jnp.stack([ci[:, i:i + s] for i in range(cfg.d_conv)], axis=-1)
        conv = jnp.einsum("bsdk,kd->bsd", win, p["conv_w"])
        conv_state_new = None
    else:
        conv_state, ssm_state = state
        roll = jnp.concatenate([conv_state[:, 1:], conv_in], axis=1)
        conv = jnp.einsum("bkd,kd->bd", roll, p["conv_w"])[:, None, :]
        conv_state_new = roll
    conv = jax.nn.silu(conv)
    xs2, Bf2, Cf2 = jnp.split(conv, [di, di + gn], axis=-1)
    xh = xs2.reshape(b, s, h, cfg.head_dim)
    Bm = Bf2.reshape(b, s, cfg.n_groups, cfg.d_state)
    Cm = Cf2.reshape(b, s, cfg.n_groups, cfg.d_state)

    if state is None:
        y = _ssd_chunked(xh, dt, p["a_log"], Bm, Cm, chunk=cfg.chunk)
        new_state = None
    else:
        # single-step recurrence: h' = h * exp(dt·A) + dt·B⊗x ; y = C·h'
        rep = h // cfg.n_groups
        dA = jnp.exp(dt[:, 0] * (-jnp.exp(p["a_log"].astype(jnp.float32))))  # (b,h)
        Br = jnp.repeat(Bm[:, 0], rep, axis=1).astype(jnp.float32)           # (b,h,n)
        Cr = jnp.repeat(Cm[:, 0], rep, axis=1).astype(jnp.float32)
        xf = xh[:, 0].astype(jnp.float32)                                    # (b,h,p)
        upd = (dt[:, 0, :, None, None] * Br[..., None]) * xf[:, :, None, :]
        hnew = ssm_state * dA[..., None, None] + upd                         # (b,h,n,p)
        y = jnp.einsum("bhn,bhnp->bhp", Cr, hnew)[:, None]
        new_state = (conv_state_new, hnew)

    y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    return y @ p["w_out"], new_state
