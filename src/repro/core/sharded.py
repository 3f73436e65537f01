"""Mesh-native schedule executor — Proposition 6.4 on a device mesh, for ANY ν.

The compiled schedule of ``core.schedule`` maps onto a mesh step by step
(DESIGN.md §3 derives the collective-bytes bound):

    ReduceLevel  — local norm-reduce; ONE collective combine (psum / pmax)
                   only when the level aggregates a sharded axis, and the
                   payload is the already-reduced aggregate, not the tensor
    OuterSolve   — all-gather of the FINAL aggregate (tiny, and only if a
                   sharded axis survives every reduce), replicated θ-solve,
                   local re-slice of the per-group radii
    ApplyGroup   — local: ℓ∞ is a clip, ℓ2 rescales by the saved (already
                   global) group norm; an ℓ1 apply whose group spans the mesh
                   runs a distributed bisection on θ (64 tiny φ-psums)

``multilevel_project_sharded`` is the full-array entry point: it pads uneven
shards with zeros (exact for every supported norm — zero entries are fixed
points of all three projections), runs the schedule under ``shard_map``, and
slices the result back. ``bilevel_project_sharded`` /
``trilevel_project_sharded`` — the two hand-written specials this module used
to consist of — survive as thin wrappers that build the equivalent schedule
body for use inside an existing ``shard_map``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.obs import profile as obs_profile

from . import ball
from . import schedule as sched_mod

_BISECT_ITERS = 64


def parse_spec(spec, ndim: int, mesh) -> Optional[Tuple[Optional[str], ...]]:
    """THE parser of PartitionSpec entries for the schedule executor (the
    planner's ``canonical_sharding`` and the projection hook delegate here).

    Returns the per-tensor-axis mesh axis name padded to ``ndim``, or ``None``
    when an entry shards one tensor axis over several mesh axes — supported
    by GSPMD but not by this executor, so callers fall back. A name that is
    not a mesh axis at all is a caller bug and raises immediately.
    """
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    names = []
    for entry in entries[:ndim]:
        if entry is None:
            names.append(None)
            continue
        if isinstance(entry, (tuple, list)):
            if len(entry) != 1:
                return None  # one mesh axis per tensor axis only
            entry = entry[0]
        if entry not in mesh.shape:
            raise ValueError(
                f"spec names mesh axis {entry!r} but mesh has "
                f"{tuple(mesh.shape)}")
        names.append(str(entry))
    return tuple(names)


def _spec_axis_names(spec, ndim: int, mesh) -> Tuple[Optional[str], ...]:
    """Strict :func:`parse_spec`: multi-mesh-axis entries are an error here
    (the executor cannot run them and has nothing to fall back to)."""
    names = parse_spec(spec, ndim, mesh)
    if names is None:
        raise ValueError(
            f"spec {tuple(spec)!r} shards a tensor axis over multiple mesh "
            "axes: the schedule executor supports one mesh axis per tensor "
            "axis")
    return names


def _grouped_l1_collective(y: jax.Array, radii: jax.Array, axes,
                           axis_names: Tuple[str, ...],
                           group_sum: jax.Array) -> jax.Array:
    """Distributed grouped-ℓ1 apply: each group spans mesh axes ``axis_names``.

    Bisection on the soft-threshold θ (DESIGN.md §4's VPU-shaped solver) where
    every φ(θ) evaluation is a local partial sum plus one tiny psum over the
    group count — the group's data never moves. ``group_sum`` is the saved
    global ℓ1 aggregate, giving the inside-the-ball test for free.
    """
    a = jnp.abs(y)
    hi = jax.lax.pmax(jnp.max(a, axis=axes), axis_names)
    # == 0 (hi >= 0), but derived from hi so shard_map's replication checker
    # sees the same rep type for both loop carries
    lo = jnp.minimum(hi, 0.0)

    def body(_, loh):
        lo, hi = loh
        mid = 0.5 * (lo + hi)
        phi = jnp.sum(jnp.maximum(a - jnp.expand_dims(mid, axes), 0.0),
                      axis=axes)
        phi = jax.lax.psum(phi, axis_names)
        too_small = phi > radii
        return jnp.where(too_small, mid, lo), jnp.where(too_small, hi, mid)

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    theta = jnp.where(group_sum <= radii, 0.0,
                      jnp.maximum(0.5 * (lo + hi), 0.0))
    return jnp.sign(y) * jnp.maximum(a - jnp.expand_dims(theta, axes), 0.0)


def make_schedule_body(sched: sched_mod.Schedule,
                       axis_names: Sequence[Optional[str]],
                       method: str = "sort"):
    """Build the shard_map body ``(y_local, radius) -> x_local`` for a schedule.

    ``axis_names[a]`` is the mesh axis the a-th tensor axis is sharded over
    (None = unsharded/local). The body is pure collective-and-local code —
    method resolution happens here, at build time, never inside the trace.
    """
    method = ball.resolve_method(method)
    b = sched.batch_dims

    def body(y_loc, radius):
        inputs = [y_loc]
        aggs = []
        stage_names = [tuple(axis_names)]
        for t, red in enumerate(sched.reduces):
            cur, names = inputs[-1], stage_names[-1]
            coll = tuple(names[a] for a in red.axes if names[a])
            with obs_profile.stage_scope(red, t):
                if red.norm == "1":
                    v = jnp.sum(jnp.abs(cur), axis=red.axes)
                    v = jax.lax.psum(v, coll) if coll else v
                elif red.norm == "2":
                    s = jnp.sum(jnp.square(cur), axis=red.axes)
                    v = jnp.sqrt(jax.lax.psum(s, coll) if coll else s)
                else:
                    v = jnp.max(jnp.abs(cur), axis=red.axes)
                    v = jax.lax.pmax(v, coll) if coll else v
            aggs.append(v)
            inputs.append(v)
            stage_names.append(tuple(
                n for a, n in enumerate(names) if a not in red.axes))

        # ---- OuterSolve: gather the surviving sharded axes (tiny), solve
        # replicated, slice the local radii back out ---------------------- #
        top, names = inputs[-1], stage_names[-1]
        local_sizes = top.shape
        with obs_profile.stage_scope(sched.solve):
            g = top
            for ax in range(b, len(names)):
                if names[ax]:
                    g = jax.lax.all_gather(g, names[ax], axis=ax, tiled=True)
            w = sched_mod.solve_outer(g, sched.solve.norm, radius, b, method)
            for ax in range(b, len(names)):
                if names[ax]:
                    idx = jax.lax.axis_index(names[ax])
                    w = jax.lax.dynamic_slice_in_dim(
                        w, idx * local_sizes[ax], local_sizes[ax], axis=ax)

        # ---- backward sweep: applies stay local (clip / saved-norm rescale);
        # only a mesh-spanning l1 group needs the distributed θ-solve ------ #
        for i, app in zip(reversed(range(len(aggs))), sched.applies):
            names = stage_names[i]
            coll = tuple(names[a] for a in app.axes if names[a])
            with obs_profile.stage_scope(app, i):
                if app.norm == "1" and coll:
                    w = _grouped_l1_collective(inputs[i], w, app.axes, coll,
                                               aggs[i])
                else:
                    w = sched_mod.apply_group(inputs[i], app.norm, w,
                                              app.axes, aggs[i], method)
        return w

    return body


def _resolve_sharded_method(method: str, sched: sched_mod.Schedule,
                            dtype) -> str:
    """``method="auto"`` for the mesh executor: autotune the replicated outer
    θ-solve on the gathered final-aggregate length (generic backends only —
    resolved at build time, outside shard_map; memoised by the planner)."""
    if method != "auto":
        return ball.resolve_method(method)
    from . import plan as _plan

    return _plan.best_l1_method(sched.solve_size, dtype)


def multilevel_project_sharded(y: jax.Array, levels, radius, *, mesh, spec,
                               method: str = "sort",
                               batch_dims: int = 0,
                               backend: str = "jnp",
                               interpret: bool = False) -> jax.Array:
    """MP^ν on a mesh: execute the compiled schedule under ``shard_map``.

    ``spec`` is the PartitionSpec of ``y`` over ``mesh`` (any sharded-axis
    position — aggregated, group, or batch axes may all be sharded; at most
    one mesh axis per tensor axis). The leading ``batch_dims`` axes are
    carried through as independent projections (the training hook's stacked
    layers/experts). Mesh axes that do not divide their tensor axis are
    handled by zero-padding (exact: zeros are fixed points of every level).

    ``method`` picks the θ-solver for the replicated outer solve and any
    local ℓ1 applies (``"auto"`` autotunes on the gathered aggregate length);
    a mesh-spanning ℓ1 group always uses the distributed bisection.

    ``backend`` picks the shard-local stage implementation: ``"jnp"`` (the
    schedule body above) or ``"codegen"`` — the fused Pallas kernels of
    ``kernels/codegen`` running inside the shard_map body, with the same
    collective plan spliced between them (``interpret`` lowers those kernels
    in interpreter mode off-TPU). Gate ``"codegen"`` with
    ``kernels.codegen.distributed.shardable`` — ineligible designs raise.
    """
    if backend not in ("jnp", "codegen"):
        raise ValueError(f"unknown sharded backend {backend!r}: "
                         "expected 'jnp' or 'codegen'")
    y = jnp.asarray(y)
    sched = sched_mod.compile_schedule(y.shape, levels, batch_dims)
    if not isinstance(spec, P):
        spec = P(*spec)
    names = _spec_axis_names(spec, y.ndim, mesh)
    meth = _resolve_sharded_method(method, sched, y.dtype)

    pad = [(0, (-d) % mesh.shape[n] if n else 0) for d, n in zip(y.shape, names)]
    padded = jnp.pad(y, pad) if any(p for _, p in pad) else y
    if padded.shape != y.shape:
        sched = sched_mod.compile_schedule(padded.shape, levels, batch_dims)

    if backend == "codegen":
        from repro.kernels.codegen import distributed as _dist

        body = _dist.make_codegen_schedule_body(
            sched, names, mesh, y.dtype, method=meth, interpret=interpret)
    else:
        body = make_schedule_body(sched, names, method=meth)
    in_spec = P(*names)
    # check_vma=False: the replicated outer solve runs the generic θ-solvers'
    # while/fori loops (filter's active-set sweep, bisect's fixed iteration)
    # on the gathered aggregate, and the Pallas kernels of the codegen body
    # carry no varying-manual-axes annotations, so the checker cannot type
    # them although every carry is in fact uniformly replicated after the
    # gather. Correctness is pinned by the sharded-vs-single-device equality
    # tests across all registered methods.
    out = jax.shard_map(body, mesh=mesh, in_specs=(in_spec, P()),
                        out_specs=in_spec,
                        check_vma=False)(padded, jnp.asarray(radius, y.dtype))
    if out.shape != y.shape:
        out = out[tuple(slice(0, d) for d in y.shape)]
    return out


# --------------------------------------------------------------------------- #
# The two historical specials — thin wrappers over the schedule body/executor
# --------------------------------------------------------------------------- #


def bilevel_project_sharded(y_local: jax.Array, radius, p=1, q=jnp.inf,
                            *, axis_name: str, method: str = "sort") -> jax.Array:
    """Bi-level body to run under shard_map; ``y_local`` is the (n, m_local)
    shard, columns sharded over ``axis_name``. Wrapper over the schedule body
    for ν = [(q, 1), (p, 1)]; requires even shards (the full-array
    ``multilevel_project_sharded`` pads uneven ones). The filter/bisect
    θ-solvers need the enclosing shard_map built with ``check_vma=False``
    (their while/fori loops carry values the varying-manual-axes checker
    cannot type — the executor does this for you)."""
    sched = sched_mod.compile_schedule(y_local.shape, [(q, 1), (p, 1)])
    body = make_schedule_body(sched, (None, axis_name), method=method)
    return body(y_local, radius)


def trilevel_project_sharded(y_local: jax.Array, radius, *, axis_name: str,
                             method: str = "sort") -> jax.Array:
    """Sharded tri-level ℓ1,∞,∞ body for (c, n, m_local) tensors (experts or
    heads last). Wrapper over the schedule body; even shards only."""
    sched = sched_mod.compile_schedule(
        y_local.shape, [(jnp.inf, 1), (jnp.inf, 1), (1, 1)])
    body = make_schedule_body(sched, (None, None, axis_name), method=method)
    return body(y_local, radius)


def _check_divides(m: int, mesh, axis_name: str, what: str) -> None:
    size = mesh.shape[axis_name]
    if m % size:
        raise ValueError(
            f"{what}: sharded axis of extent {m} is not divisible by mesh "
            f"axis {axis_name!r} of size {size} — the per-device slice of the "
            "outer solve would silently be wrong. Use "
            "multilevel_project_sharded, which zero-pads uneven shards.")


def make_sharded_bilevel(mesh, axis_name: str, p=1, q=jnp.inf,
                         method: str = "sort"):
    """shard_map'd bi-level projection: columns (axis 1) sharded over
    ``axis_name``. Delegates to the schedule executor, so ``method="auto"``
    autotunes the replicated outer θ-solve exactly like every other design.
    Validates shard evenness with a clear error at call time."""
    if method != "auto":
        method = ball.resolve_method(method)  # fail at build time

    def fn(y, radius):
        _check_divides(y.shape[1], mesh, axis_name, "make_sharded_bilevel")
        return multilevel_project_sharded(
            y, [(q, 1), (p, 1)], radius, mesh=mesh, spec=P(None, axis_name),
            method=method)

    return fn


def make_sharded_trilevel(mesh, axis_name: str, method: str = "sort"):
    """shard_map'd tri-level ℓ1,∞,∞: last axis sharded over ``axis_name``.
    The ``method="auto"`` path resolves through the planner like the bi-level
    builder (the historical asymmetry is gone — both are schedule wrappers)."""
    if method != "auto":
        method = ball.resolve_method(method)

    def fn(y, radius):
        _check_divides(y.shape[-1], mesh, axis_name, "make_sharded_trilevel")
        return multilevel_project_sharded(
            y, [(jnp.inf, 1), (jnp.inf, 1), (1, 1)], radius, mesh=mesh,
            spec=P(None, None, axis_name), method=method)

    return fn


def sharded_collective_bytes(shape, levels, spec, mesh,
                             itemsize: int = 4) -> dict:
    """Collective payload of this design on this mesh vs gather-and-project
    (the generalized DESIGN.md §3 argument)."""
    if not isinstance(spec, P):
        spec = P(*spec)
    names = _spec_axis_names(spec, len(shape), mesh)
    return sched_mod.sharded_collective_bytes(
        tuple(shape), levels, names,
        {n: mesh.shape[n] for n in mesh.axis_names}, itemsize)
