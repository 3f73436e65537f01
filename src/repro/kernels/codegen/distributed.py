"""Sharded codegen lowering — fused Pallas shard-local stages in shard_map.

The mesh executor (``core/sharded.py``) runs a compiled schedule as local
stages stitched by DESIGN.md §3's collective plan: one psum/pmax combine per
sharded ReduceLevel, a tiny all-gather + replicated θ-solve + re-slice for
the OuterSolve, local applies (with a distributed bisection for a
mesh-spanning ℓ1 group). Its local stages are plain jnp. This module builds
the *same* body with the local stages lowered through ``kernels/codegen``:

* the shard's reduce sweep is ONE streaming Pallas pass (``_reduce_call`` on
  the local schedule's tile plan), producing every intermediate aggregate and
  the final level's RAW accumulator;
* when the final reduce level spans the mesh, its combine splices between the
  kernels on the raw accumulator (psum for ℓ1/ℓ2 — ℓ2 accumulates squares —
  pmax for ℓ∞) BEFORE the monoid's finalize, so the collective payload is
  exactly the jnp body's (the already-reduced aggregate);
* the OuterSolve gathers the finalized aggregate over surviving sharded axes
  in the *uncollapsed* surviving-axes view, solves replicated with the
  codegen θ-solve, and slices the local radii back out — the jnp body's plan
  verbatim;
* the apply sweep is ONE fused Pallas epilogue (``_apply_call``) — unless the
  final level is an ℓ1 whose group spans the mesh, in which case the
  distributed bisection (``core.sharded._grouped_l1_collective``) runs on the
  last intermediate aggregate and the epilogue *resumes* one level down
  (``_partial_apply_call``).

The collective sequence is identical to the jnp body's by construction —
``sharded_collective_bytes`` is a function of (schedule, spec) alone, and the
equality tests assert the traced collective primitives match.

Eligibility (:func:`shardable`): a sharded tensor axis must be a batch axis,
a surviving (solve) axis, or an axis of the FINAL reduce level. An axis of an
*intermediate* reduce level folds inside the reduce mega-kernel's VMEM tile —
there is no splice point for its combine — so those designs stay on the jnp
body. The local (per-shard) schedule must also tile (``plan_tiles``).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import schedule as sched_mod
from repro.core.schedule import Schedule
from repro.obs import profile as obs_profile

from .lowering import (MONOIDS, _apply_call, _partial_apply_call,
                       _reduce_call, _solve_outer_vec)
from .tiling import TilePlan, plan_tiles


def _level_of_axis(levels, batch_dims: int, axis: int) -> int:
    """The (0-indexed) level owning tensor axis ``axis``; levels consume
    contiguous axis runs left to right after the batch prefix."""
    off = batch_dims
    for t, (_, k) in enumerate(levels):
        if axis < off + k:
            return t
        off += k
    raise ValueError(f"axis {axis} not covered by levels {levels}")


def local_shape(shape: Sequence[int], axis_names: Sequence[Optional[str]],
                mesh) -> Tuple[int, ...]:
    """Per-shard shape of ``shape`` under ``axis_names`` — ceil division, to
    match the executor's zero-padding of uneven shards."""
    return tuple(-(-d // mesh.shape[n]) if n else d
                 for d, n in zip(shape, axis_names))


def shardable(shape, levels, axis_names: Sequence[Optional[str]], mesh,
              dtype, batch_dims: int = 0) -> bool:
    """Can this design's shard-local stages lower through codegen?

    False when an *intermediate* reduce level's axis is sharded (its fold is
    in-tile — no splice point for the combine) or when the local per-shard
    schedule has no VMEM-resident tiling.
    """
    levels = sched_mod.canonical_levels(levels)
    L = len(levels)
    b = batch_dims
    for a, n in enumerate(axis_names):
        if n is None or a < b:
            continue
        if _level_of_axis(levels, b, a) < L - 2:
            return False
    lshape = local_shape(shape, axis_names, mesh)
    lsched = sched_mod.compile_schedule(lshape[b:], levels)
    return plan_tiles(lsched, dtype) is not None


def make_codegen_schedule_body(sched: Schedule,
                               axis_names: Sequence[Optional[str]], mesh,
                               dtype, *, method: str = "bisect",
                               interpret: bool = False,
                               tile_plan: Optional[TilePlan] = None
                               ) -> Callable:
    """Build the shard_map body ``(y_local, radius) -> x_local`` with the
    shard-local stages lowered through the fused Pallas kernels.

    ``sched`` is the GLOBAL schedule on the (padded, evenly-divisible) shape;
    the local schedule and its tile plan derive from the per-shard shape.
    ``tile_plan`` overrides the block sizes; by default they are the local
    workload's heuristic plan (``plan_tiles``), the same in every process:
    no host-timed search, whose pick at a train step's shard (tens of µs a
    kernel) would vary with the host. Leading batch axes vmap the
    batch-free body — collectives batch through vmap unchanged.

    Gate with :func:`shardable` first; raises ``ValueError`` when the design
    has no codegen lowering on this mesh.
    """
    from repro.core.sharded import _grouped_l1_collective

    b = sched.batch_dims
    levels = sched.levels
    L = len(levels)
    names = tuple(axis_names)
    if not shardable(sched.shape, levels, names, mesh, dtype, b):
        raise ValueError(
            f"no sharded codegen lowering for levels={levels} on "
            f"shape={sched.shape} with axes {names}: an intermediate reduce "
            "axis is sharded, or the local shard does not tile")
    lshape = local_shape(sched.shape, names, mesh)
    if any(d % mesh.shape[n] for d, n in zip(sched.shape, names) if n):
        raise ValueError(
            "make_codegen_schedule_body needs even shards — the executor "
            "zero-pads and recompiles before building the body")
    lsched = sched_mod.compile_schedule(lshape[b:], levels)
    norms = [q for q, _ in levels]
    tp = tile_plan if tile_plan is not None else plan_tiles(lsched, dtype)

    # final reduce level (index L-2): mesh axes its combine spans. Levels
    # consume contiguous ORIGINAL-tensor axis runs left to right (ReduceLevel
    # .axes are stage-relative, so recompute the original run here).
    n_reduced = sum(k for _, k in levels[:-1])
    n_before_fin = sum(k for _, k in levels[:-2])
    fin_coll = tuple(names[a] for a in range(b + n_before_fin, b + n_reduced)
                     if names[a]) if L > 1 else ()
    # surviving (solve) axes: the last level's run — gather/slice positions
    # are relative to the batch-free reduced tensor (stage_shapes[-1])
    surv_names = names[b + n_reduced:]
    surv_loc = lsched.stage_shapes[-1]
    surv_glob = tuple(d * mesh.shape[n] if n else d
                      for d, n in zip(surv_loc, surv_names))

    def _gather(g):
        for ax, n in enumerate(surv_names):
            if n:
                g = jax.lax.all_gather(g, n, axis=ax, tiled=True)
        return g

    def _slice_back(w):
        for ax, n in enumerate(surv_names):
            if n:
                idx = jax.lax.axis_index(n)
                w = jax.lax.dynamic_slice_in_dim(
                    w, idx * surv_loc[ax], surv_loc[ax], axis=ax)
        return w

    def _solve_sliced(v, norm, radius):
        """Replicated outer solve with the surviving-axes gather/re-slice."""
        if not any(surv_names):
            return _solve_outer_vec(v, norm, radius, method, interpret)
        g = _gather(v.reshape(surv_loc))
        u = _solve_outer_vec(g.reshape(-1), norm, radius, method, interpret)
        return _slice_back(u.reshape(surv_glob)).reshape(v.shape)

    def inner(y, radius):
        if L == 1:
            # degenerate flat solve: the whole design IS the OuterSolve
            with obs_profile.scope(f"codegen_solve_{norms[0]}"):
                return _solve_sliced(y.reshape(-1), norms[0],
                                     radius).reshape(y.shape)
        yc = y.reshape(tp.canon_shape)
        with obs_profile.scope("codegen_partial_reduce"):
            aggs, acc = _reduce_call(yc, tp, norms[:-1], interpret)
            if fin_coll:
                # splice the final level's combine on the RAW accumulator (ℓ2
                # is still in the squared domain here), then finalize
                acc = jax.lax.pmax(acc, fin_coll) if norms[-2] == "inf" \
                    else jax.lax.psum(acc, fin_coll)
            vfin = MONOIDS[norms[-2]].finalize(acc)
        with obs_profile.scope(f"codegen_solve_{norms[-1]}"):
            u = _solve_sliced(vfin, norms[-1], radius)
        with obs_profile.scope("codegen_apply"):
            if norms[-2] == "1" and fin_coll:
                # the final level's ℓ1 groups span the mesh: distributed
                # θ-solve on the last resident stage, then resume the
                # epilogue below it
                src = yc if L == 2 else aggs[-1]
                w = _grouped_l1_collective(src, u, (0,), fin_coll, vfin)
                x = w if L == 2 else _partial_apply_call(yc, aggs, w, tp,
                                                         norms[:-1],
                                                         interpret)
            else:
                x = _apply_call(yc, aggs, vfin, u, tp, norms[:-1], interpret)
        return x.reshape(y.shape)

    fn = inner
    for _ in range(b):
        fn = jax.vmap(fn, in_axes=(0, None))

    def body(y_loc, radius):
        return fn(y_loc, jnp.asarray(radius, y_loc.dtype))

    return body
