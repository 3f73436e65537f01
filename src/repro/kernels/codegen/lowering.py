"""Schedule-IR → fused Pallas kernel lowering (DESIGN.md §4).

A compiled ``Schedule`` is ``ReduceLevel* → OuterSolve → ApplyGroup*``. The
generator lowers that to the same three-stage structure the hand-written
golden kernels (``bilevel_l1inf.py`` / ``trilevel_l1infinf.py``) use, but for
*any* norm design the tiler accepts:

* **reduce mega-kernel** — ONE streaming pass over Y produces every forward
  aggregate: each intermediate ``ReduceLevel`` folds its (VMEM-resident) axis
  with the norm's monoid inside the tile, and the final level accumulates
  across the sequential grid axis (``max`` for ℓ∞, ``add`` for ℓ1, ``add`` of
  squares for ℓ2 — finalized after the pass). Y is read exactly once here.
* **outer stage** — the tiny θ-solve on the (m,)-vector: the VPU-shaped
  bisect/filter VMEM kernels from ``kernels/l1ball.py`` for an ℓ1 solve
  (jnp fallback past the single-block limit or for ``method="sort"``), a
  rescale/clip for ℓ2/ℓ∞.
* **apply epilogue** — ONE elementwise pass over Y replays the backward
  sweep per tile: the radii chain starts at the solved aggregate and walks
  down through the saved per-tile aggregates (ℓ∞ → clip, ℓ2 → rescale by the
  saved *global* aggregate, ℓ1 → an in-tile batched bisection θ-solve per
  group), writing X. Y is read exactly twice end-to-end — the same
  information-theoretic minimum as the golden kernels.

Reverse-mode: generated kernels carry a ``custom_vjp`` whose backward is the
*generated* residual VJP (``backward.py``): the forward pipeline already
materializes every stage aggregate, the solved radii, and the projected
output, so the backward is one streaming elementwise+group-reduction pass
over (y, x, g) — the apply Jacobians are diagonal-plus-rank-one per group —
with the tiny radii chain replayed on aggregate-sized tensors. No sort-oracle
recompute, no ``schedule.execute`` call, no second reduce over ``y``.

Serving buckets (B stacked items, per-item radii) lower through
:func:`generate_batched` instead: the batch axis joins the Pallas grid as its
leading parallel dimension and the per-item radii ride in SMEM for the
θ-solve stage (DESIGN.md §5) — one dispatch per pipeline stage for the whole
bucket, versus one vmap-lifted kernel per stage per item.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ball, schedule as sched_mod
from repro.core.schedule import Schedule
from repro.obs import profile as obs_profile

from . import backward as bwd_mod
from .tiling import TilePlan, plan_tiles

_GROUP_SOLVE_ITERS = 64  # in-tile grouped θ-solves: fixed-budget bisection


class Monoid(NamedTuple):
    """In-VMEM staged reduction for one norm, on non-negative inputs.

    ``tile`` folds an axis inside one tile and finalizes (what intermediate
    reduces use); ``part``/``combine``/``finalize`` split the same reduction
    into a raw per-block accumulator + cross-grid-step combine + a post-pass
    finalizer (what the sequential-axis reduce uses: ℓ2 accumulates in the
    squared domain, so its finalize is the √ applied after the last step).
    """

    tile: Callable[[jax.Array, int], jax.Array]
    part: Callable[[jax.Array, int], jax.Array]
    combine: Callable[[jax.Array, jax.Array], jax.Array]
    finalize: Callable[[jax.Array], jax.Array]


MONOIDS = {
    "1": Monoid(
        tile=lambda a, ax: jnp.sum(a, axis=ax),
        part=lambda a, ax: jnp.sum(a, axis=ax),
        combine=jnp.add,
        finalize=lambda acc: acc,
    ),
    "2": Monoid(
        tile=lambda a, ax: jnp.sqrt(jnp.sum(a * a, axis=ax)),
        part=lambda a, ax: jnp.sum(a * a, axis=ax),
        combine=jnp.add,
        finalize=jnp.sqrt,
    ),
    "inf": Monoid(
        tile=lambda a, ax: jnp.max(a, axis=ax),
        part=lambda a, ax: jnp.max(a, axis=ax),
        combine=jnp.maximum,
        finalize=lambda acc: acc,
    ),
}


def _grouped_l1_tile(x: jax.Array, radii_b: jax.Array,
                     iters: int = _GROUP_SOLVE_ITERS) -> jax.Array:
    """Project every axis-0 slice of ``x`` onto its own ℓ1 ball, in-tile.

    ``radii_b`` broadcasts against ``x`` with a size-1 group axis. Batched
    bisection on θ — elementwise ops + axis-0 reductions only, so it stays
    VPU-shaped whatever the surrounding tile shape is.
    """
    a = jnp.abs(x)
    hi = jnp.max(a, axis=0, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, loh):
        lo, hi = loh
        mid = 0.5 * (lo + hi)
        phi = jnp.sum(jnp.maximum(a - mid, 0.0), axis=0, keepdims=True)
        too_small = phi > radii_b
        return jnp.where(too_small, mid, lo), jnp.where(too_small, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    inside = jnp.sum(a, axis=0, keepdims=True) <= radii_b
    theta = jnp.where(inside, jnp.zeros_like(lo), 0.5 * (lo + hi))
    return jnp.sign(x) * jnp.maximum(a - theta, 0.0)


# --------------------------------------------------------------------------- #
# Reduce mega-kernel
# --------------------------------------------------------------------------- #


def _make_reduce_kernel(norms: Sequence[str], n_total: int, block_n: int):
    """Kernel body: every forward aggregate of the schedule in one pass.

    ``norms`` are the reduce norms q_1 … q_{L-1}. Outputs are
    ``[v_1, …, v_{L-2}, acc]`` where v_t keeps the (block_n, block_m) tile
    structure and ``acc`` is the raw (1, block_m) accumulator of the final
    level, combined across sequential grid steps.
    """
    inter, last = tuple(norms[:-1]), norms[-1]

    def kernel(y_ref, *out_refs):
        i = pl.program_id(1)  # sequential row-block index (last grid axis)
        cur = jnp.abs(y_ref[...])
        for t, q in enumerate(inter):
            cur = MONOIDS[q].tile(cur, 0)   # fold the resident axis g_{t+1}
            out_refs[t][...] = cur
        # cur is (block_n, block_m): mask rows past the true edge with 0 —
        # the identity of every monoid here (values are non-negative)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0) \
            + i * block_n
        cur = jnp.where(row_ids < n_total, cur, 0.0)
        part = MONOIDS[last].part(cur, 0)[None]          # (1, block_m)
        acc_ref = out_refs[-1]

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = part

        @pl.when(i > 0)
        def _acc():
            acc_ref[...] = MONOIDS[last].combine(acc_ref[...], part)

    return kernel


def _y_spec(tp: TilePlan):
    k = len(tp.lead)
    return pl.BlockSpec(tp.lead + (tp.block_n, tp.block_m),
                        lambda j, i, k=k: (0,) * k + (i, j))


def _agg_specs_shapes(tp: TilePlan, dtype):
    """BlockSpecs + ShapeDtypeStructs of the intermediate aggregates v_t."""
    specs, shapes = [], []
    for t in range(1, len(tp.lead) + 1):
        ld = tp.lead[t:]
        specs.append(pl.BlockSpec(ld + (tp.block_n, tp.block_m),
                                  lambda j, i, k=len(ld): (0,) * k + (i, j)))
        shapes.append(jax.ShapeDtypeStruct(ld + (tp.n, tp.m), dtype))
    return specs, shapes


def _row_spec(tp: TilePlan):
    return pl.BlockSpec((1, tp.block_m), lambda j, i: (0, j))


def _reduce_call(y: jax.Array, tp: TilePlan, norms: Sequence[str],
                 interpret: bool):
    grid = (pl.cdiv(tp.m, tp.block_m), pl.cdiv(tp.n, tp.block_n))
    agg_specs, agg_shapes = _agg_specs_shapes(tp, y.dtype)
    outs = pl.pallas_call(
        _make_reduce_kernel(norms, n_total=tp.n, block_n=tp.block_n),
        grid=grid,
        in_specs=[_y_spec(tp)],
        out_specs=agg_specs + [_row_spec(tp)],
        out_shape=agg_shapes + [jax.ShapeDtypeStruct((1, tp.m), y.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(y)
    return list(outs[:-1]), outs[-1][0]   # ([v_1, …, v_{L-2}], raw acc (m,))


# --------------------------------------------------------------------------- #
# Apply epilogue
# --------------------------------------------------------------------------- #


def _f32(ref) -> jax.Array:
    """Load a kernel operand for the apply epilogue's math, in float32.

    The apply epilogues compute in f32 whatever the storage dtype and cast
    on store: the chip's vector unit has no bf16 compare, which the clip /
    rescale / θ-solve selects need (an f32 input is unchanged)."""
    return ref[...].astype(jnp.float32)


def _apply_chain(norms: Sequence[str], stages, w):
    """Levels L-2 … 1 of the backward sweep on one resident tile.

    ``w`` is the radii tensor produced by the level-L-1 step (shaped like the
    last intermediate aggregate); the group axis of each step is the leading
    resident axis of its stage input, radii/aggregates live one stage up.
    Factored out of :func:`_apply_tile` so the sharded splice can resume the
    sweep here after a mesh-spanning level-L-1 ℓ1 apply ran collectively.
    """
    L = len(norms) + 1
    for lvl in range(L - 2, 0, -1):
        x, agg, q = stages[lvl - 1], stages[lvl], norms[lvl - 1]
        if q == "inf":
            w = jnp.clip(x, -w[None], w[None])
        elif q == "2":
            scale = jnp.where(agg > w, w / jnp.maximum(agg, 1e-30), 1.0)
            w = x * scale[None]
        else:
            w = _grouped_l1_tile(x, w[None])
    return w


def _apply_tile(norms: Sequence[str], stages, vfin, u):
    """The backward sweep on one resident tile (pure array form).

    ``stages`` are ``[y_tile, v_1, …, v_{L-2}]``; ``u`` the solved-aggregate
    row; ``vfin`` the saved global final aggregate (ℓ2 last reduce only). The
    radii chain ``w`` starts at the solved aggregate and walks levels L-1 → 1;
    every stage input it needs is a saved forward aggregate already resident
    in the tile. Shared by the single-item and batched-grid apply kernels.
    """
    # level L-1: its group runs along the sublane axis of the 2-D tile
    x, q, w = stages[-1], norms[-1], u
    if q == "inf":
        w = jnp.clip(x, -w, w)
    elif q == "2":
        scale = jnp.where(vfin > w, w / jnp.maximum(vfin, 1e-30), 1.0)
        w = x * scale
    else:  # "1" — tiling pinned the whole group axis into this block
        w = _grouped_l1_tile(x, w)
    return _apply_chain(norms, stages, w)


def _make_apply_kernel(norms: Sequence[str]):
    """Kernel body: the backward sweep fused into one elementwise pass.

    Inputs: ``y, v_1, …, v_{L-2}, [v_final_row,] u_row``; output: the
    projected tile (the final-aggregate row rides along only for an ℓ2 last
    reduce level, whose rescale needs the saved *global* norm).
    """
    L = len(norms) + 1
    has_vfin = norms[-1] == "2"

    def kernel(*refs):
        y_ref, v_refs = refs[0], refs[1:L - 1]
        vfin_ref = refs[L - 1] if has_vfin else None
        u_ref, out_ref = refs[-2], refs[-1]
        stages = [_f32(y_ref)] + [_f32(v) for v in v_refs]  # s_0 … s_{L-2}
        vfin = _f32(vfin_ref) if has_vfin else None
        out_ref[...] = _apply_tile(norms, stages, vfin,
                                   _f32(u_ref)).astype(out_ref.dtype)

    return kernel


def _apply_call(y: jax.Array, aggs, vfin: jax.Array, u: jax.Array,
                tp: TilePlan, norms: Sequence[str], interpret: bool):
    grid = (pl.cdiv(tp.m, tp.block_m), pl.cdiv(tp.n, tp.block_n))
    agg_specs, _ = _agg_specs_shapes(tp, y.dtype)
    row = lambda v: v.reshape(1, tp.m).astype(y.dtype)  # noqa: E731
    rows = ([row(vfin)] if norms[-1] == "2" else []) + [row(u)]
    return pl.pallas_call(
        _make_apply_kernel(norms),
        grid=grid,
        in_specs=[_y_spec(tp)] + agg_specs
                 + [_row_spec(tp)] * len(rows),
        out_specs=_y_spec(tp),
        out_shape=jax.ShapeDtypeStruct(tp.canon_shape, y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(y, *aggs, *rows)


def _make_partial_apply_kernel(norms: Sequence[str]):
    """Apply epilogue that *resumes* at level L-2: the level-L-1 radii tensor
    ``w`` (shaped like the last intermediate aggregate v_{L-2}) arrives as an
    input instead of being computed in-tile — the sharded splice computed it
    with the distributed grouped-ℓ1 solve when level L-1 spans the mesh.

    Inputs: ``y, v_1, …, v_{L-2}, w``; output: the projected tile.
    """
    L = len(norms) + 1

    def kernel(*refs):
        y_ref, v_refs = refs[0], refs[1:L - 1]
        w_ref, out_ref = refs[-2], refs[-1]
        stages = [_f32(y_ref)] + [_f32(v) for v in v_refs]
        out_ref[...] = _apply_chain(norms, stages,
                                    _f32(w_ref)).astype(out_ref.dtype)

    return kernel


def _partial_apply_call(y: jax.Array, aggs, w: jax.Array, tp: TilePlan,
                        norms: Sequence[str], interpret: bool):
    """Run the resumed apply epilogue; ``w`` is blocked exactly like the last
    intermediate aggregate (same BlockSpec as ``aggs[-1]``)."""
    grid = (pl.cdiv(tp.m, tp.block_m), pl.cdiv(tp.n, tp.block_n))
    agg_specs, _ = _agg_specs_shapes(tp, y.dtype)
    return pl.pallas_call(
        _make_partial_apply_kernel(norms),
        grid=grid,
        in_specs=[_y_spec(tp)] + agg_specs + [agg_specs[-1]],
        out_specs=_y_spec(tp),
        out_shape=jax.ShapeDtypeStruct(tp.canon_shape, y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(y, *aggs, w.astype(y.dtype))


# --------------------------------------------------------------------------- #
# Outer stage + the generator
# --------------------------------------------------------------------------- #


def _solve_outer_vec(v: jax.Array, norm: str, radius, method: str,
                     interpret: bool) -> jax.Array:
    """Project the finalized (m,) aggregate onto the outer ball."""
    if norm == "1":
        from ..l1ball import outer_l1_solve

        if ball.resolve_method(method) in ("bisect", "filter"):
            return outer_l1_solve(v, radius, method=method,
                                  interpret=interpret)
        return ball.project_l1(v, radius, method=method)
    if norm == "2":
        return ball.project_l2(v, radius)
    return jnp.minimum(v, jnp.asarray(radius, v.dtype))  # ℓ∞ on v ≥ 0


def _resolve_tile_plan(sched: Schedule, dtype,
                       tile_plan: TilePlan | None) -> TilePlan:
    """The generator's tiling: an explicit (autotuned) plan, validated against
    the schedule, or the heuristic default from :func:`plan_tiles`."""
    if tile_plan is not None:
        if tile_plan.canon_shape != sched.canonical_shape:
            raise ValueError(
                f"tile plan built for canonical shape {tile_plan.canon_shape} "
                f"cannot lower schedule with canonical shape "
                f"{sched.canonical_shape}")
        return tile_plan
    tp = plan_tiles(sched, dtype)
    if tp is None:
        raise ValueError(
            f"codegen cannot lower levels={sched.levels} on shape="
            f"{sched.shape}: no VMEM-resident tiling (or flat non-l1 solve)")
    return tp


def generate(sched: Schedule, dtype, *, method: str = "bisect",
             interpret: bool = False,
             tile_plan: TilePlan | None = None) -> Callable:
    """Compile ``sched`` into a fused ``(y, radius) -> x`` callable.

    ``method`` picks the *outer* θ-solve backend (the in-tile grouped solves
    are always the fixed-budget bisection — stable latency, VPU-shaped).
    Leading batch axes lower as vmaps of the batch-free kernel (the batch
    axes join the Pallas grid). ``tile_plan`` overrides the heuristic block
    sizes (the measured autotuner's winner). Raises ``ValueError`` when the
    tiler rejects the design — gate with :func:`tiling.plan_tiles` first.
    """
    if sched.batch_dims:
        base_sched = sched_mod.compile_schedule(
            sched.shape[sched.batch_dims:], sched.levels)
        fn = generate(base_sched, dtype, method=method, interpret=interpret,
                      tile_plan=tile_plan)
        for _ in range(sched.batch_dims):
            fn = jax.vmap(fn, in_axes=(0, None))
        return fn
    tp = _resolve_tile_plan(sched, dtype, tile_plan)
    norms = [q for q, _ in sched.levels]

    def raw(y, radius):
        """Forward pipeline; also returns the VJP residual aggregates."""
        yc = y.reshape(tp.canon_shape)
        if len(norms) == 1:
            with obs_profile.scope(f"codegen_solve_{norms[0]}"):
                out = _solve_outer_vec(yc, norms[0], radius, method,
                                       interpret)
            return out.reshape(y.shape), ()
        # the three lowering boundaries of the fused pipeline — one scope
        # each, so a captured trace attributes device time to the streaming
        # reduce pass, the VMEM θ-solve, and the fused apply epilogue
        with obs_profile.scope("codegen_reduce"):
            aggs, acc = _reduce_call(yc, tp, norms[:-1], interpret)
            vfin = MONOIDS[norms[-2]].finalize(acc)
        with obs_profile.scope(f"codegen_solve_{norms[-1]}"):
            u = _solve_outer_vec(vfin, norms[-1], radius, method, interpret)
        with obs_profile.scope("codegen_apply"):
            x = _apply_call(yc, aggs, vfin, u, tp, norms[:-1], interpret)
        return x.reshape(y.shape), (tuple(aggs), vfin, u)

    @jax.custom_vjp
    def fused(y, radius):
        return raw(y, radius)[0]

    def fwd(y, radius):
        x, internals = raw(y, radius)
        return x, (y, x, internals, radius)

    def bwd(res, g):
        # the generated residual VJP (backward.py): one streaming pass over
        # (y, x, g) + the aggregate-sized radii chain — the schedule executor
        # is NEVER re-run (tests stub it out to prove that)
        y, x, internals, radius = res
        yc = y.reshape(tp.canon_shape)
        gc = g.reshape(tp.canon_shape)
        if len(norms) == 1:
            stages = [yc]
            u = x.reshape(tp.canon_shape)
        else:
            aggs, vfin, u = internals
            stages = [yc, *aggs, vfin]
        dy, dr = bwd_mod.schedule_vjp(norms, stages, u,
                                      x.reshape(tp.canon_shape), radius, gc)
        return dy.reshape(y.shape), jnp.asarray(dr, y.dtype)

    fused.defvjp(fwd, bwd)

    @functools.wraps(fused)
    def entry(y, radius):
        y = jnp.asarray(y)
        return fused(y, jnp.asarray(radius, y.dtype))

    return entry


# --------------------------------------------------------------------------- #
# Batched-grid lowering (serving buckets)
# --------------------------------------------------------------------------- #
#
# A serving bucket is B stacked instances of ONE schedule with per-item radii.
# Items share no aggregates, so the batch axis never enters the schedule — it
# becomes the LEADING (parallel) Pallas grid dimension instead of a vmap
# around the batch-free kernel: one dispatch walks B × grid(base) programs,
# per-item rows are block-sliced and radii indexed by the batch grid index
# (radii ride in SMEM for the θ-solve stage), and per-step VMEM residency
# stays the per-item plan's.


def _y_spec_batched(tp: TilePlan):
    k = len(tp.lead)
    return pl.BlockSpec((1,) + tp.lead + (tp.block_n, tp.block_m),
                        lambda b, j, i, k=k: (b,) + (0,) * k + (i, j))


def _agg_specs_shapes_batched(tp: TilePlan, dtype, batch: int):
    specs, shapes = [], []
    for t in range(1, len(tp.lead) + 1):
        ld = tp.lead[t:]
        specs.append(pl.BlockSpec(
            (1,) + ld + (tp.block_n, tp.block_m),
            lambda b, j, i, k=len(ld): (b,) + (0,) * k + (i, j)))
        shapes.append(jax.ShapeDtypeStruct((batch,) + ld + (tp.n, tp.m), dtype))
    return specs, shapes


def _row_spec_batched(tp: TilePlan):
    return pl.BlockSpec((1, 1, tp.block_m), lambda b, j, i: (b, 0, j))


def _make_batched_reduce_kernel(norms: Sequence[str], n_total: int,
                                block_n: int):
    """The reduce mega-kernel with the batch axis as grid dimension 0.

    Identical per-item math to :func:`_make_reduce_kernel`; every block gains
    a leading size-1 batch axis (squeezed on read, restored on write) and the
    sequential row-block index moves to ``program_id(2)``.
    """
    inter, last = tuple(norms[:-1]), norms[-1]

    def kernel(y_ref, *out_refs):
        i = pl.program_id(2)  # sequential row-block index (last grid axis)
        cur = jnp.abs(y_ref[...])[0]
        for t, q in enumerate(inter):
            cur = MONOIDS[q].tile(cur, 0)
            out_refs[t][...] = cur[None]
        row_ids = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0) \
            + i * block_n
        cur = jnp.where(row_ids < n_total, cur, 0.0)
        part = MONOIDS[last].part(cur, 0)[None]          # (1, block_m)
        acc_ref = out_refs[-1]

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = part[None]

        @pl.when(i > 0)
        def _acc():
            acc_ref[...] = MONOIDS[last].combine(acc_ref[...], part[None])

    return kernel


def _reduce_call_batched(y: jax.Array, tp: TilePlan, norms: Sequence[str],
                         interpret: bool):
    batch = y.shape[0]
    grid = (batch, pl.cdiv(tp.m, tp.block_m), pl.cdiv(tp.n, tp.block_n))
    agg_specs, agg_shapes = _agg_specs_shapes_batched(tp, y.dtype, batch)
    outs = pl.pallas_call(
        _make_batched_reduce_kernel(norms, n_total=tp.n, block_n=tp.block_n),
        grid=grid,
        in_specs=[_y_spec_batched(tp)],
        out_specs=agg_specs + [_row_spec_batched(tp)],
        out_shape=agg_shapes
        + [jax.ShapeDtypeStruct((batch, 1, tp.m), y.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(y)
    return list(outs[:-1]), outs[-1][:, 0]  # ([v_1, …], raw acc (B, m))


def _make_batched_apply_kernel(norms: Sequence[str]):
    """The apply epilogue with the batch axis as grid dimension 0."""
    L = len(norms) + 1
    has_vfin = norms[-1] == "2"

    def kernel(*refs):
        y_ref, v_refs = refs[0], refs[1:L - 1]
        vfin_ref = refs[L - 1] if has_vfin else None
        u_ref, out_ref = refs[-2], refs[-1]
        stages = [_f32(y_ref)[0]] + [_f32(v)[0] for v in v_refs]
        vfin = _f32(vfin_ref)[0] if has_vfin else None
        out_ref[...] = _apply_tile(norms, stages, vfin,
                                   _f32(u_ref)[0])[None].astype(out_ref.dtype)

    return kernel


def _apply_call_batched(y: jax.Array, aggs, vfin: jax.Array, u: jax.Array,
                        tp: TilePlan, norms: Sequence[str], interpret: bool):
    batch = y.shape[0]
    grid = (batch, pl.cdiv(tp.m, tp.block_m), pl.cdiv(tp.n, tp.block_n))
    agg_specs, _ = _agg_specs_shapes_batched(tp, y.dtype, batch)
    row = lambda v: v.reshape(batch, 1, tp.m).astype(y.dtype)  # noqa: E731
    rows = ([row(vfin)] if norms[-1] == "2" else []) + [row(u)]
    return pl.pallas_call(
        _make_batched_apply_kernel(norms),
        grid=grid,
        in_specs=[_y_spec_batched(tp)] + agg_specs
                 + [_row_spec_batched(tp)] * len(rows),
        out_specs=_y_spec_batched(tp),
        out_shape=jax.ShapeDtypeStruct((batch,) + tp.canon_shape, y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(y, *aggs, *rows)


def _solve_outer_batched(v: jax.Array, norm: str, radii: jax.Array,
                         method: str, interpret: bool) -> jax.Array:
    """Per-item outer solves on the (B, m) finalized aggregates.

    The ℓ1 case runs the batched single-block θ kernel (batch in the grid,
    per-item radii in SMEM); ℓ2/ℓ∞ are a batched rescale/clip.
    """
    radii = jnp.asarray(radii, v.dtype)
    if norm == "1":
        from ..l1ball import L1_KERNEL_MAX, project_l1_pallas_batched

        resolved = ball.resolve_method(method)
        if v.shape[-1] <= L1_KERNEL_MAX and resolved in ("bisect", "filter"):
            return project_l1_pallas_batched(v, radii, method=resolved,
                                             interpret=interpret)
        return jax.vmap(
            lambda vv, rr: ball.project_l1(vv, rr, method=method))(v, radii)
    if norm == "2":
        return jax.vmap(ball.project_l2)(v, radii)
    return jnp.minimum(v, radii[:, None])  # ℓ∞ on v ≥ 0


def generate_batched(sched: Schedule, dtype, *, method: str = "bisect",
                     interpret: bool = False,
                     tile_plan: TilePlan | None = None) -> Callable:
    """Compile ``sched`` into a fused batched ``(ys, radii) -> xs`` callable.

    ``ys`` stacks B instances of ``sched.shape`` along a leading axis with a
    per-item ``radii`` vector of length B — the serving-bucket execution
    shape. Unlike :func:`generate` (whose plan backend is vmapped by the
    planner for ``radius_kind="batch"`` keys), the batch axis here IS a Pallas
    grid dimension, so the whole bucket is one reduce dispatch + one θ-solve
    dispatch + one apply dispatch. B is read from ``ys`` at trace time (each
    new bucket size traces once — serving pads to pow-2 buckets).
    """
    if sched.batch_dims:
        raise ValueError(
            "generate_batched takes a batch-free schedule; the stacked "
            "serving axis is the callable's leading axis, not a schedule "
            "batch dim")
    tp = _resolve_tile_plan(sched, dtype, tile_plan)
    norms = [q for q, _ in sched.levels]

    def raw(ys, radii):
        """Forward pipeline; also returns the VJP residual aggregates."""
        batch = ys.shape[0]
        yc = ys.reshape((batch,) + tp.canon_shape)
        if len(norms) == 1:
            with obs_profile.scope(f"codegen_solve_{norms[0]}"):
                out = _solve_outer_batched(yc, norms[0], radii, method,
                                           interpret)
            return out.reshape(ys.shape), ()
        with obs_profile.scope("codegen_reduce"):
            aggs, acc = _reduce_call_batched(yc, tp, norms[:-1], interpret)
            vfin = MONOIDS[norms[-2]].finalize(acc)
        with obs_profile.scope(f"codegen_solve_{norms[-1]}"):
            u = _solve_outer_batched(vfin, norms[-1], radii, method,
                                     interpret)
        with obs_profile.scope("codegen_apply"):
            x = _apply_call_batched(yc, aggs, vfin, u, tp, norms[:-1],
                                    interpret)
        return x.reshape(ys.shape), (tuple(aggs), vfin, u)

    @jax.custom_vjp
    def fused(ys, radii):
        return raw(ys, radii)[0]

    def fwd(ys, radii):
        x, internals = raw(ys, radii)
        return x, (ys, x, internals, radii)

    def bwd(res, g):
        # per-item generated residual VJP, vmapped over the stacked batch —
        # same no-re-execution property as the single-item path
        ys, x, internals, radii = res
        batch = ys.shape[0]
        yc = ys.reshape((batch,) + tp.canon_shape)
        xc = x.reshape((batch,) + tp.canon_shape)
        gc = g.reshape((batch,) + tp.canon_shape)
        if len(norms) == 1:
            def item(y1, x1, g1, r1):
                return bwd_mod.schedule_vjp(norms, [y1], x1, x1, r1, g1)
            dy, dr = jax.vmap(item)(yc, xc, gc, radii)
        else:
            aggs, vfin, u = internals

            def item(y1, aggs1, vfin1, u1, x1, g1, r1):
                return bwd_mod.schedule_vjp(norms, [y1, *aggs1, vfin1],
                                            u1, x1, r1, g1)
            dy, dr = jax.vmap(item)(yc, aggs, vfin, u, xc, gc, radii)
        return dy.reshape(ys.shape), dr.astype(radii.dtype)

    fused.defvjp(fwd, bwd)

    @functools.wraps(fused)
    def entry(ys, radii):
        ys = jnp.asarray(ys)
        radii = jnp.asarray(radii, ys.dtype)
        if ys.ndim != len(sched.shape) + 1:
            raise ValueError(
                f"batched kernel built for item shape {sched.shape} expects "
                f"rank {len(sched.shape) + 1} stacked input, got {ys.shape}")
        if radii.ndim != 1 or radii.shape[0] != ys.shape[0]:
            raise ValueError(
                f"radii must be one scalar per stacked item: got "
                f"{radii.shape} for batch {ys.shape[0]}")
        return fused(ys, radii)

    return entry
