"""Pallas TPU kernels: ℓ1-ball projection of a vector.

The outer step of the bi-level projection. Two in-VMEM algorithms:

* ``bisect`` — k fixed iterations of soft-threshold + tree reduction. Serial
  depth k·log n, fully VPU-shaped (DESIGN.md §4). Accuracy ~2^-k.
* ``filter`` — Michelot/Condat filtering: a ``lax.while_loop`` fixed point on
  the threshold θ over a shrinking active set (masking, no sorting). Converges
  exactly in a handful of sweeps on typical data — O(n) expected work versus
  the bisect kernel's fixed 64 sweeps.

Serial-optimal heap/partition variants do not map to the VPU; both kernels use
only elementwise ops + reductions. Single-block kernels: the whole (padded)
vector lives in VMEM. That covers the aggregate vectors of every assigned
architecture (d_ff ≤ 25600, experts ≤ 384, vocab ≤ 163840 → ≤ 640 KB f32).
``ops.py`` falls back to the jnp path for anything larger.

The kernels compute in float32 whatever the input dtype: the radius reaches
SMEM as a 32-bit scalar (the only scalar width Mosaic reads from SMEM), and
the wrappers cast the result back to the input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ITERS = 64
_LANE = 128


# Both threshold kernels read their radius as ``radius_ref[program_id(0)]``:
# the whole f32 radius vector sits in SMEM (a 1-element block of it is not a
# tiling Mosaic accepts), one entry per program of the batch grid — entry 0
# for the single-item call, whose grid is (1,).


def _masked_abs(v_ref, n_total: int):
    v = v_ref[...]  # (1, n_pad)
    ids = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    a = jnp.where(ids < n_total, jnp.abs(v), 0.0)
    return v, a


def _l1ball_bisect_kernel(v_ref, radius_ref, out_ref, *, n_total: int, iters: int):
    v, a = _masked_abs(v_ref, n_total)
    radius = radius_ref[pl.program_id(0)]
    inside = jnp.sum(a) <= radius

    def body(_, loh):
        lo, hi = loh
        mid = 0.5 * (lo + hi)
        phi = jnp.sum(jnp.maximum(a - mid, 0.0))
        too_small = phi > radius
        lo = jnp.where(too_small, mid, lo)
        hi = jnp.where(too_small, hi, mid)
        return lo, hi

    lo0 = jnp.zeros((), v.dtype)
    hi0 = jnp.max(a)
    lo, hi = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    theta = jnp.where(inside, jnp.zeros((), v.dtype), 0.5 * (lo + hi))
    out_ref[...] = jnp.sign(v) * jnp.maximum(a - theta, 0.0)


def _l1ball_filter_kernel(v_ref, radius_ref, out_ref, *, n_total: int, iters: int):
    """Michelot filtering in VMEM: θ ← (Σ_{aᵢ>θ} aᵢ - r)/#{aᵢ>θ} to fixpoint.

    Outside the ball θ is strictly positive and non-decreasing, so the zero
    padding (and true zeros) can never enter the active set — the mask IS the
    shrinking active set, no compaction needed. ``iters`` caps the sweep count
    (termination is guaranteed in ≤ n sweeps; typical data needs < 10).
    """
    v, a = _masked_abs(v_ref, n_total)
    radius = radius_ref[pl.program_id(0)]
    s0 = jnp.sum(a)
    inside = s0 <= radius
    theta0 = (s0 - radius) / n_total

    def cond(state):
        _, _, changed, it = state
        return jnp.logical_and(changed, it < iters)

    def body(state):
        theta, count, _, it = state
        active = a > theta
        new_count = jnp.sum(active.astype(jnp.int32))
        ssum = jnp.sum(jnp.where(active, a, 0.0))
        new_theta = jnp.where(
            new_count > 0,
            (ssum - radius) / jnp.maximum(new_count, 1).astype(a.dtype),
            theta,
        )
        changed = jnp.logical_and(new_count != count, new_count > 0)
        return new_theta, new_count, changed, it + 1

    theta, _, _, _ = jax.lax.while_loop(
        cond, body, (theta0, jnp.int32(n_total), jnp.bool_(True), jnp.int32(0)))
    theta = jnp.where(inside, jnp.zeros((), v.dtype), jnp.maximum(theta, 0.0))
    out_ref[...] = jnp.sign(v) * jnp.maximum(a - theta, 0.0)


# threshold-kernel dispatch — keyed by the core.ball backend names ("sort" has
# no VPU mapping; outer_l1_solve routes it to the jnp oracle instead)
_THRESHOLD_KERNELS = {
    "bisect": _l1ball_bisect_kernel,
    "filter": _l1ball_filter_kernel,
}

KERNEL_METHODS = tuple(sorted(_THRESHOLD_KERNELS))

# vectors larger than this stay on the jnp path (single-block VMEM kernel limit)
L1_KERNEL_MAX = 512 * 1024


def outer_l1_solve(v: jax.Array, radius, *, method: str = "bisect",
                   interpret: bool = False) -> jax.Array:
    """The fused kernels' outer θ-solve: VMEM kernel when ``method`` has one
    and ``v`` fits a single block, jnp backend otherwise."""
    if v.shape[0] <= L1_KERNEL_MAX and method in KERNEL_METHODS:
        return project_l1_pallas(v, radius, method=method, interpret=interpret)
    from .ref import project_l1_ref
    return project_l1_ref(v, radius, method=method)


def project_l1_pallas(v: jax.Array, radius, *, method: str = "bisect",
                      iters: int | None = None, interpret: bool = False) -> jax.Array:
    """Project a 1-D vector onto the ℓ1 ball of ``radius`` in VMEM.

    ``method`` ∈ {"bisect", "filter"} selects the threshold kernel.
    """
    if method not in _THRESHOLD_KERNELS:
        raise ValueError(
            f"no pallas threshold kernel for method {method!r}; "
            f"available: {sorted(_THRESHOLD_KERNELS)}"
        )
    (n,) = v.shape
    if iters is None:
        # filter terminates in <= n sweeps; bisect needs its fixed budget
        iters = n + 2 if method == "filter" else _ITERS
    n_pad = max(_LANE, ((n + _LANE - 1) // _LANE) * _LANE)
    v2 = jnp.zeros((1, n_pad), jnp.float32).at[0, :n].set(v)
    r = jnp.asarray(radius, jnp.float32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_THRESHOLD_KERNELS[method], n_total=n, iters=iters),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(v2, r)
    return out[0, :n].astype(v.dtype)


def project_l1_pallas_batched(v: jax.Array, radii: jax.Array, *,
                              method: str = "bisect", iters: int | None = None,
                              interpret: bool = False) -> jax.Array:
    """Project every row of ``v`` (B, n) onto its own ℓ1 ball, batched grid.

    The serving-bucket form of :func:`project_l1_pallas`: the batch axis is a
    PARALLEL Pallas grid dimension (one program per request) and the per-item
    radii ride in SMEM, indexed by the batch grid index — the same kernel
    bodies as the single-item version, no vmap lifting. Each program keeps its
    whole (padded) row in VMEM, so the single-block size limit
    (``L1_KERNEL_MAX``) applies per item, not to the batch. The rows are laid
    out as ``(B, 1, n_pad)`` with the batch axis squeezed out of the block, so
    every program sees the ``(1, n_pad)`` row the single-item kernels read —
    a block whose last two dimensions equal the array's, for any ``B``.
    """
    if method not in _THRESHOLD_KERNELS:
        raise ValueError(
            f"no pallas threshold kernel for method {method!r}; "
            f"available: {sorted(_THRESHOLD_KERNELS)}"
        )
    b, n = v.shape
    if iters is None:
        iters = n + 2 if method == "filter" else _ITERS
    n_pad = max(_LANE, ((n + _LANE - 1) // _LANE) * _LANE)
    v2 = jnp.zeros((b, 1, n_pad), jnp.float32).at[:, 0, :n].set(v)
    r = jnp.asarray(radii, jnp.float32).reshape(b)
    row = pl.BlockSpec((pl.squeezed, 1, n_pad), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_THRESHOLD_KERNELS[method], n_total=n, iters=iters),
        grid=(b,),
        in_specs=[
            row,
            pl.BlockSpec(memory_space=pltpu.SMEM),  # all B radii
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((b, 1, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(v2, r)
    return out[:, 0, :n].astype(v.dtype)
