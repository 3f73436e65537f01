"""Pallas TPU flash attention — blockwise online softmax, forward AND backward.

Used by the serving/prefill path on real TPUs (the CPU tests use
the pure-jnp chunked oracle; see models/layers.py `attention_impl`) and, now
that it carries a custom VJP, by LM *training* on TPU — gradients no longer
fall back to the jnp oracle.

Layout: q (B, Hq, Sq, D), k/v (B, Hkv, Sk, D) with GQA group = Hq // Hkv
resolved inside the BlockSpec index maps (no kv repetition in HBM!).

Forward grid: (B, Hq, Sq/block_q, Sk/block_k) — the k axis is last
(sequential on TPU), carrying the running max/denominator/accumulator in VMEM
scratch. Causal/windowed blocks that are fully masked are skipped with
pl.when — for causal attention this halves the compute (FlashAttention-2
behaviour). The forward also emits the log-sum-exp rows ``lse = m + log(l)``,
the only softmax statistic the backward needs.

Backward (FlashAttention-2 style, two kernels + one elementwise jnp pass):

* ``delta = rowsum(dO ∘ O)`` — elementwise, jnp;
* **dQ kernel** — same grid as the forward (k sequential), recomputes the
  P-tile from (q, k, lse), accumulates ``scale · Σ_j P∘(dOVᵀ − delta) k_j``
  in VMEM scratch;
* **dK/dV kernel** — grid (B, Hkv, Sk/block_k, G·Sq/block_q) with the fused
  (group, q-block) axis last (sequential): each kv head accumulates its dk/dv
  block across all G query heads of its group and every q block in scratch,
  so GQA needs no gradient reshuffle in HBM.

Alignment: block_q/block_k multiples of 128 (lane), head dim is the minor-most
axis of every tile; pad D to a multiple of 128 outside for peak MXU mapping.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30
_LANES = 128


def _block_mask(q_start, k_start, shape, *, causal, window, sk):
    """The (block_q, block_k) validity mask shared by forward and backward."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = jnp.logical_and(kpos < sk, qpos < sk)   # ragged k AND q tails
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    return mask


def _block_live(q_start, k_start, *, causal, window, block_q, block_k):
    """Trace-time predicate: does this (q-block, k-block) pair contribute?"""
    run = True
    if causal:
        run = jnp.asarray(k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(
            run, jnp.asarray(k_start + block_k - 1 > q_start - window))
    if not causal and window is None:
        run = jnp.asarray(True)
    return run


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, window, block_q: int,
                  block_k: int, sq: int, sk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = iq * block_q + (sk - sq)  # right-aligned absolute q positions
    k_start = ik * block_k
    run = _block_live(q_start, k_start, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        # zero the ragged k/v tail: p is 0 there, but 0 * pad-NaN would poison acc
        kv_valid = (k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)) < sk
        v = jnp.where(kv_valid, v, 0.0)
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        mask = _block_mask(q_start, k_start, s.shape, causal=causal,
                           window=window, sk=sk)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, 0:1]                      # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # (block_q, block_k)
        corr = jnp.exp(m_prev - m_new)              # (block_q, 1)
        l_new = corr * l_ref[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _fin():
        l = l_ref[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        # lse rows for the backward; fully-masked rows get -inf (their p
        # recomputation is then 0 under the mask, never NaN)
        lse_ref[0, 0] = m_ref[:, 0:1] + jnp.log(denom)


def _fwd_call(q, k, v, *, causal, window, scale, block_q, block_k, interpret):
    """Forward pallas call: returns (o, lse) with lse (B, Hq, Sq) in f32."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (b, hq, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, sq=sq, sk=sk,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# --------------------------------------------------------------------------- #
# Backward kernels (FlashAttention-2)
# --------------------------------------------------------------------------- #


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc_ref,
               *, scale: float, causal: bool, window, block_q: int,
               block_k: int, sq: int, sk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = iq * block_q + (sk - sq)
    k_start = ik * block_k
    run = _block_live(q_start, k_start, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                # (block_q, 1)
        delta = dl_ref[0, 0]                               # (block_q, 1)
        # zero ragged k/v tails: the matmuls below would turn pad-NaN into
        # NaN rows of dq even where p == 0 (0 * NaN)
        kv_valid = (k_start + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)) < sk
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _block_mask(q_start, k_start, s.shape, causal=causal,
                           window=window, sk=sk)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)         # (block_q, block_k)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = jnp.where(mask, p * (dp - delta), 0.0)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _fin():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, causal: bool, window,
                block_q: int, block_k: int, sq: int, sk: int, n_q: int):
    jk = pl.program_id(2)
    t = pl.program_id(3)       # fused (group, q-block) sequential axis
    nt = pl.num_programs(3)
    iq = t % n_q
    q_start = iq * block_q + (sk - sq)
    k_start = jk * block_k
    run = _block_live(q_start, k_start, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = dl_ref[0, 0]
        # ragged tails on BOTH axes feed the accumulating matmuls here: a
        # pad-NaN q/do row (or k/v row) would poison the whole dk/dv block
        # through 0 * NaN, so zero them before any contraction
        qrow_valid = (q_start + jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)) < sk
        q = jnp.where(qrow_valid, q, 0.0)
        do = jnp.where(qrow_valid, do, 0.0)
        kv_valid = (k_start + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)) < sk
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _block_mask(q_start, k_start, s.shape, causal=causal,
                           window=window, sk=sk)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = jnp.where(mask, p * (dp - delta), 0.0)
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _fin():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, *, causal, window, scale, block_q, block_k,
              interpret):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = pl.cdiv(sq, block_q)
    n_k = pl.cdiv(sk, block_k)

    # delta = rowsum(dO ∘ O): one elementwise pass, no attention recompute
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse4 = lse[..., None]                      # (B, Hq, Sq, 1) f32
    delta4 = delta[..., None]

    common = dict(scale=scale, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, sq=sq, sk=sk)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b, h, iq, ik, g=group: (b, h // g, ik, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, iq, ik: (b, h, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b, hq, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse4, delta4)

    # dK/dV: each kv head walks its whole query group (G heads × n_q blocks)
    # on the sequential axis, accumulating in scratch — GQA sums in VMEM
    def qmap(b, h, jk, t, g=group, nq=n_q):
        return (b, h * g + t // nq, t % nq, 0)

    qg_spec = pl.BlockSpec((1, 1, block_q, d), qmap)
    rowg_spec = pl.BlockSpec((1, 1, block_q, 1), qmap)
    kvg_spec = pl.BlockSpec((1, 1, block_k, d),
                            lambda b, h, jk, t: (b, h, jk, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common, n_q=n_q),
        grid=(b, hkv, n_k, group * n_q),
        in_specs=[qg_spec, kvg_spec, kvg_spec, qg_spec, rowg_spec, rowg_spec],
        out_specs=[kvg_spec, kvg_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse4, delta4)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp plumbing + public entry
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_k, interpret):
    return _fwd_call(q, k, v, causal=causal, window=window, scale=scale,
                     block_q=block_q, block_k=block_k, interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, causal=causal, window=window, scale=scale,
                       block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, do, causal=causal, window=window,
                     scale=scale, block_q=block_q, block_k=block_k,
                     interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """Blockwise attention, differentiable. q (B,Hq,Sq,D); k,v (B,Hkv,Sk,D)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    if scale is None:
        scale = d ** -0.5
    return _flash(q, k, v, causal, window, float(scale), int(block_q),
                  int(block_k), bool(interpret))
