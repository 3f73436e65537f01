"""Fused tri-level ℓ1,∞,∞ Pallas kernels (paper Algorithm 5, DESIGN.md §4).

GOLDEN REFERENCE: since the kernel code generator landed
(``kernels/codegen``), this hand-written kernel is no longer a planner
backend — it pins the generated tri-level kernel in ``tests/test_codegen.py``.

``TP^{1,∞,∞}_η(Y)`` for Y ∈ R^{c,n,m} decomposes into

  pass 1  reduce:  v2[i,j] = max_c |Y[c,i,j]|   AND   v1[j] = max_i v2[i,j]
                   (ONE streaming pass over Y; the slice-∞ and column-∞
                   reductions are fused — v2 is produced as a byproduct of
                   accumulating v1, grid-reduced over row blocks)
  (tiny)  outer :  u1 = P¹_η(v1)                (jnp or the l1ball kernel)
  pass 2  apply :  X = clip(Y, ±min(v2, u1))    (the grouped threshold apply:
                   min(v2, u1) IS the per-(i,j) ∞-radius of the recursion)

Y is read exactly twice — same information-theoretic minimum as the bi-level
kernel; the naive composition (multilevel_project) reads Y twice *and* v2
twice more in separate dispatches. Blocks are (c, block_n, block_m) with the
whole (small) slice axis resident: c is experts/heads (≤ a few hundred) in
every assigned architecture, so a (c, 8, 128) f32 tile fits VMEM comfortably.

Grid layout mirrors bilevel_l1inf.py: the sequential row-block axis is LAST so
the v1 accumulation is legal (PARALLEL over column blocks, ARBITRARY over row
blocks); ragged row edges are masked in-kernel, ragged lane edges are dropped
on write-back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_N = 256   # rows per tile (sublane axis)
DEFAULT_BLOCK_M = 512   # cols per tile (lane axis)


def _reduce_kernel(y_ref, v2_ref, v1_ref, *, n_total: int, block_n: int):
    """v2 tile = max over the slice axis; v1 row = running max over row blocks."""
    i = pl.program_id(1)  # sequential row-block index (last grid axis)
    a = jnp.abs(y_ref[...])                       # (c, block_n, block_m)
    v2 = jnp.max(a, axis=0)                       # (block_n, block_m)
    # mask rows past the true edge with 0 (|.| >= 0 so 0 is the max identity)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, v2.shape, 0) + i * block_n
    v2 = jnp.where(row_ids < n_total, v2, 0.0)
    v2_ref[...] = v2
    part = jnp.max(v2, axis=0, keepdims=True)     # (1, block_m)

    @pl.when(i == 0)
    def _init():
        v1_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        v1_ref[...] = jnp.maximum(v1_ref[...], part)


def _apply_kernel(y_ref, v2_ref, u1_ref, out_ref):
    """out = clip(y, ±min(v2, u1)) — the grouped threshold apply in one tile."""
    u2 = jnp.minimum(v2_ref[...], u1_ref[...])    # (block_n, block_m), u1 bcast
    out_ref[...] = jnp.clip(y_ref[...], -u2[None], u2[None])


def trilevel_reduce_pallas(y: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                           block_m: int = DEFAULT_BLOCK_M,
                           interpret: bool = False):
    """(v2, v1) = (max_c |Y|, max_{c,i} |Y|) in one streaming pass over Y."""
    c, n, m = y.shape
    block_n = min(block_n, max(8, n))
    block_m = min(block_m, max(128, m))
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    v2, v1 = pl.pallas_call(
        functools.partial(_reduce_kernel, n_total=n, block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((c, block_n, block_m), lambda j, i: (0, i, j))],
        out_specs=[
            pl.BlockSpec((block_n, block_m), lambda j, i: (i, j)),
            pl.BlockSpec((1, block_m), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), y.dtype),
            jax.ShapeDtypeStruct((1, m), y.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(y)
    return v2, v1[0]


def trilevel_apply_pallas(y: jax.Array, v2: jax.Array, u1: jax.Array, *,
                          block_n: int = DEFAULT_BLOCK_N,
                          block_m: int = DEFAULT_BLOCK_M,
                          interpret: bool = False) -> jax.Array:
    """X = clip(Y, ±min(v2, u1)) — per-column ∞-radius u1, per-slice max v2."""
    c, n, m = y.shape
    block_n = min(block_n, max(8, n))
    block_m = min(block_m, max(128, m))
    grid = (pl.cdiv(n, block_n), pl.cdiv(m, block_m))
    return pl.pallas_call(
        _apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, block_n, block_m), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((c, block_n, block_m), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((c, n, m), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(y, v2, u1.reshape(1, m).astype(y.dtype))


def trilevel_l1infinf_pallas(y: jax.Array, radius, *, method: str = "bisect",
                             block_n: int = DEFAULT_BLOCK_N,
                             block_m: int = DEFAULT_BLOCK_M,
                             interpret: bool = False) -> jax.Array:
    """Fused tri-level ℓ1,∞,∞ projection: reduce → outer P¹ → apply.

    ``method`` selects the outer-step θ kernel ("bisect" | "filter" run the
    VMEM kernel; anything else — or a vector past the single-block VMEM
    limit — the jnp backend); see kernels.l1ball.
    """
    from .l1ball import outer_l1_solve

    if y.ndim != 3:
        raise ValueError("trilevel_l1infinf_pallas expects an order-3 tensor")
    v2, v1 = trilevel_reduce_pallas(y, block_n=block_n, block_m=block_m,
                                    interpret=interpret)
    u1 = outer_l1_solve(v1, radius, method=method, interpret=interpret)
    return trilevel_apply_pallas(y, v2, u1, block_n=block_n, block_m=block_m,
                                 interpret=interpret)
