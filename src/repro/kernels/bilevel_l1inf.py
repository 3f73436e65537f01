"""Pallas TPU kernels for the bi-level ℓ1,∞ projection (paper Algorithm 2).

GOLDEN REFERENCE: since the kernel code generator landed
(``kernels/codegen``, DESIGN.md §4 "IR → Pallas lowering"), this hand-written
kernel is no longer a planner backend — it pins the generated bi-level kernel
in ``tests/test_codegen.py``.

The projection is bandwidth-bound (O(1) FLOP/byte), so the kernels are tiled
HBM→VMEM streaming passes (DESIGN.md §4):

  pass 1  colmax:  v[j]   = max_i |Y[i, j]|        (grid-reduced over row blocks)
  (tiny)  outer :  u      = P¹_η(v)                (jnp or the l1ball kernel)
  pass 2  clip  :  X[i,j] = clip(Y[i,j], ±u[j])    (elementwise, broadcast u)

Y is read exactly twice — the information-theoretic minimum for the split.
Blocks are (block_n, block_m) with the lane dimension a multiple of 128 and the
sublane dimension a multiple of 8 (f32) for MXU/VPU alignment; ragged edges are
handled by index-map clamping + masking in the kernel.

On TPU the grid's *last* axis is the sequential one: we place row-blocks last
so the colmax accumulation into ``out_ref`` is legal (PARALLEL over column
blocks, ARBITRARY over row blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_N = 256   # rows per tile (sublane axis)
DEFAULT_BLOCK_M = 512   # cols per tile (lane axis)


def _colmax_kernel(y_ref, out_ref, *, n_total: int, block_n: int):
    """out[0, j] = max over row-blocks of max_i |y[i, j]| (accumulated)."""
    i = pl.program_id(1)  # sequential row-block index (last grid axis)
    rows_done = i * block_n
    # mask rows past the true edge with 0 (|.| >= 0 so 0 is the identity here)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 0) + rows_done
    valid = row_ids < n_total
    block = jnp.where(valid, jnp.abs(y_ref[...]), 0.0)
    part = jnp.max(block, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = jnp.maximum(out_ref[...], part)


def _clip_kernel(y_ref, u_ref, out_ref):
    """out = clip(y, -u, u) with u broadcast down the rows of the tile."""
    u = u_ref[...]  # (1, block_m)
    out_ref[...] = jnp.clip(y_ref[...], -u, u)


def bilevel_l1inf_pallas(y: jax.Array, radius, *, method: str = "bisect",
                         block_n: int = DEFAULT_BLOCK_N,
                         block_m: int = DEFAULT_BLOCK_M,
                         interpret: bool = False) -> jax.Array:
    """Fused bi-level ℓ1,∞ projection: colmax → outer P¹ → clip, all Pallas.

    ``method`` selects the outer-step threshold kernel ("bisect" or the
    linear-time "filter"); anything else — or a vector past the single-block
    VMEM limit — runs the outer solve on the jnp backend instead.
    """
    from .l1ball import outer_l1_solve

    v = colmax_pallas(y, block_n=block_n, block_m=block_m, interpret=interpret)
    u = outer_l1_solve(v, radius, method=method, interpret=interpret)
    return clip_pallas(y, u, block_n=block_n, block_m=block_m, interpret=interpret)


def colmax_pallas(y: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                  block_m: int = DEFAULT_BLOCK_M, interpret: bool = False) -> jax.Array:
    """Per-column max|·| of a 2-D array via a tiled grid reduction."""
    n, m = y.shape
    block_n = min(block_n, max(8, n))
    block_m = min(block_m, max(128, m))
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    out = pl.pallas_call(
        functools.partial(_colmax_kernel, n_total=n, block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, block_m), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((1, block_m), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, m), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(y)
    return out[0]


def clip_pallas(y: jax.Array, u: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                block_m: int = DEFAULT_BLOCK_M, interpret: bool = False) -> jax.Array:
    """X = clip(Y, ±u) with u a per-column radius vector."""
    n, m = y.shape
    block_n = min(block_n, max(8, n))
    block_m = min(block_m, max(128, m))
    grid = (pl.cdiv(n, block_n), pl.cdiv(m, block_m))
    u2 = u.reshape(1, m).astype(y.dtype)
    return pl.pallas_call(
        _clip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(y, u2)
