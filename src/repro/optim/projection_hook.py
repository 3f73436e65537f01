"""The paper's technique as a first-class training feature.

``apply_projection(params, spec, step)`` applies the multi-level projection
(core.multilevel) to every parameter whose path matches ``spec.pattern``,
every ``spec.every`` steps (lax.cond — regex matching is trace-time static).

The projection operates on the TRAILING ``sum(k for _, k in levels)`` axes of
each matched leaf; leading axes ('layers', 'super', 'experts' stacks) are
vmapped — e.g. a stacked MoE weight (L, E, d, f) with bi-level ν projects each
(d, f) expert matrix independently, and ν=((inf,1),(inf,1),(1,1)) projects the
(E, d, f) tensor tri-level per layer (head/expert-structured sparsity, §6 of
the paper).

Passing ``mesh=`` and ``param_specs=`` to :func:`make_projection_hook` makes
the projection *explicitly* mesh-native: every matched leaf whose projected
(trailing) axes are sharded executes the compiled schedule under shard_map in
place — collective reduces of the aggregates, a gathered tiny outer solve,
local applies (DESIGN.md §3) — instead of trusting GSPMD to discover the same
decomposition; leading stacked axes become the executor's batch dims. Leaves
with unsharded trailing axes (or without specs) keep the vmapped single-device
path, which under pjit is still communication-minimal by construction.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.types import ProjectionSpec
from repro.core import ball, multilevel, sharded
from repro.core.masks import sparsity


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def _method_resolver(spec: ProjectionSpec):
    """Per-leaf θ-solver resolution, done ONCE per hook (not per step/trace).

    Fixed names validate through the registry immediately; ``"auto"`` is
    resolved per distinct final-level vector length via the planner's
    ``best_l1_method`` (shape-only, so it works while tracing too) and
    memoised — the micro-benchmark runs once per (length, dtype), ever.
    """
    if spec.method != "auto":
        method = ball.resolve_method(spec.method)  # config errors surface once
        return lambda shape, dtype: method

    need = sum(k for _, k in spec.levels)
    cache = {}

    def resolve(shape, dtype):
        trailing = shape[-need:]
        if spec.transpose:
            trailing = tuple(reversed(trailing))
        n_final = multilevel._final_level_size(trailing, spec.levels)
        key = (n_final, np.dtype(dtype).name)
        if key not in cache:
            from repro.core import plan
            cache[key] = plan.best_l1_method(n_final, dtype)
        return cache[key]

    return resolve


def _sharded_leaf_key(mesh, pspec, ndim: int, need: int):
    """The leaf's canonical ShardingKey IF the schedule executor should run
    it: some trailing (projected) axis sharded and the spec
    executor-representable — ``plan.canonical_sharding`` is the single parser
    of spec entries (multi-axis entries like ``("pod", "data")`` make it
    return None → the leaf falls back to the GSPMD path)."""
    if pspec is None:
        return None
    from repro.core import plan as planmod

    key = planmod.canonical_sharding((mesh, pspec), ndim)
    if key is None or not any(n is not None for n in key.spec[ndim - need:]):
        return None
    return key


def _resolve_shard_backend(backend: str, shape, levels, names, mesh, dtype,
                           batch_dims: int) -> str:
    """Pick the shard_map body implementation for one sharded leaf.

    ``"auto"`` lowers the shard-local stages through the fused codegen
    kernels (kernels/codegen/distributed) when the design is eligible and
    the kernels compile natively (TPU); everywhere else — or for designs
    ``shardable`` rejects — it keeps the jnp schedule body, which is the
    same collective plan without the fusion."""
    if backend != "auto":
        return backend
    if jax.default_backend() != "tpu":
        return "jnp"
    from repro.kernels.codegen import distributed as _dist

    try:
        ok = _dist.shardable(shape, list(levels), names, mesh, dtype,
                             batch_dims)
    except ValueError:  # a design the schedule compiler rejects
        ok = False
    return "codegen" if ok else "jnp"


def _project_leaf_sharded(w, spec: ProjectionSpec, radius, method, mesh,
                          names, backend: str = "auto"):
    """Project one sharded leaf in place via the schedule executor: leading
    stacked axes are batch dims, no gather of the weight ever happens.
    ``names`` is the canonical per-axis mesh-axis tuple (ShardingKey.spec)."""
    need = sum(k for _, k in spec.levels)
    batch = w.ndim - need
    if spec.transpose:
        # reverse the trailing (projected) axes — an involution, so the same
        # permutation restores the layout (and permutes the spec with it)
        perm = tuple(range(batch)) + tuple(reversed(range(batch, w.ndim)))
        pnames = tuple(names[a] for a in perm)
        be = _resolve_shard_backend(backend, tuple(w.shape[a] for a in perm),
                                    spec.levels, pnames, mesh, w.dtype, batch)
        kw = {} if be == "jnp" else dict(
            backend="codegen", interpret=jax.default_backend() != "tpu")
        out = sharded.multilevel_project_sharded(
            jnp.transpose(w, perm), list(spec.levels), radius, mesh=mesh,
            spec=P(*pnames), method=method, batch_dims=batch, **kw)
        return jnp.transpose(out, perm)
    be = _resolve_shard_backend(backend, tuple(w.shape), spec.levels, names,
                                mesh, w.dtype, batch)
    kw = {} if be == "jnp" else dict(
        backend="codegen", interpret=jax.default_backend() != "tpu")
    return sharded.multilevel_project_sharded(
        w, list(spec.levels), radius, mesh=mesh, spec=P(*names),
        method=method, batch_dims=batch, **kw)


def _project_leaf(w, levels, radius, method, transpose=False):
    need = sum(k for _, k in levels)

    def core(x):
        if transpose:
            x = jnp.swapaxes(x, 0, -1) if need == 2 else jnp.transpose(
                x, tuple(reversed(range(x.ndim))))
        x = multilevel.multilevel_project(x, list(levels), radius, method)
        if transpose:
            x = jnp.swapaxes(x, 0, -1) if need == 2 else jnp.transpose(
                x, tuple(reversed(range(x.ndim))))
        return x

    fn = core
    for _ in range(w.ndim - need):
        fn = jax.vmap(fn)
    return fn(w)


def _spec_table(param_specs):
    """Flatten a PartitionSpec tree into a path-string → spec lookup."""
    table = {}
    if param_specs is None:
        return table

    def collect(path, s):
        table[_path_str(path)] = s
        return s

    jax.tree_util.tree_map_with_path(collect, param_specs,
                                     is_leaf=lambda x: isinstance(x, P))
    return table


def make_projection_hook(spec: ProjectionSpec | None, *, mesh=None,
                         param_specs=None, backend: str = "auto"):
    """Build the training-time projection hook ONCE (planner lifecycle,
    DESIGN.md §2): compile the regex, validate/resolve the θ-solver backend
    (including ``method="auto"`` via the planner — autotuned per distinct leaf
    workload, memoised forever), and return ``hook(params, step)`` for the
    train step to call every iteration. Per-step/per-trace cost is zero beyond
    the projection itself.

    With ``mesh`` and ``param_specs`` (the params' PartitionSpec tree), every
    matched leaf whose projected trailing axes are sharded runs the schedule
    executor under shard_map in place — no weight gather (DESIGN.md §3).

    ``backend`` selects the shard-local stage implementation for those
    leaves: ``"auto"`` (default) lowers eligible designs through the fused
    codegen kernels on TPU and keeps the jnp schedule body elsewhere;
    ``"jnp"`` / ``"codegen"`` force one — both execute the identical
    collective plan.
    """
    if spec is None or not spec.enabled:
        return lambda params, step: params
    pat = re.compile(spec.pattern)
    need = sum(k for _, k in spec.levels)
    resolve = _method_resolver(spec)
    specs_by_path = _spec_table(param_specs) if mesh is not None else {}

    def project_all(params):
        def one(path, w):
            name = _path_str(path)
            if w.ndim >= need and pat.search(name):
                method = resolve(w.shape, w.dtype)
                skey = None
                if mesh is not None:
                    skey = _sharded_leaf_key(mesh, specs_by_path.get(name),
                                             w.ndim, need)
                if skey is not None:
                    return _project_leaf_sharded(
                        w, spec, spec.radius, method, mesh, skey.spec,
                        backend=backend,
                    ).astype(w.dtype)
                return _project_leaf(w, spec.levels, spec.radius, method,
                                     transpose=spec.transpose).astype(w.dtype)
            return w

        return jax.tree_util.tree_map_with_path(one, params)

    def hook(params, step):
        if spec.every <= 1:
            return project_all(params)
        return jax.lax.cond(step % spec.every == 0, project_all,
                            lambda p: p, params)

    return hook


def project_tree(params, spec: ProjectionSpec):
    """Unconditionally project matched leaves (jit-safe)."""
    pat = re.compile(spec.pattern)
    need = sum(k for _, k in spec.levels)
    resolve = _method_resolver(spec)  # config errors surface here once

    def one(path, w):
        name = _path_str(path)
        if w.ndim >= need and pat.search(name):
            return _project_leaf(w, spec.levels, spec.radius,
                                 resolve(w.shape, w.dtype),
                                 transpose=spec.transpose).astype(w.dtype)
        return w

    return jax.tree_util.tree_map_with_path(one, params)


def apply_projection(params, spec: ProjectionSpec, step):
    """Project every ``spec.every`` steps (cheap lax.cond otherwise).

    One-shot form of :func:`make_projection_hook` — prefer the hook in loops
    so the regex/method resolution happens once at build.
    """
    return make_projection_hook(spec)(params, step)


def matched_names(params, spec: ProjectionSpec):
    """Static list of projected parameter paths (for logging/tests)."""
    pat = re.compile(spec.pattern)
    need = sum(k for _, k in spec.levels)
    names = []

    def one(path, w):
        name = _path_str(path)
        if hasattr(w, "ndim") and w.ndim >= need and pat.search(name):
            names.append(name)
        return w

    jax.tree_util.tree_map_with_path(one, params)
    return names


def tree_sparsity(params, spec: ProjectionSpec):
    """Column-sparsity % of each projected leaf (paper's metric, per tensor)."""
    pat = re.compile(spec.pattern)
    need = sum(k for _, k in spec.levels)
    out = {}

    def one(path, w):
        name = _path_str(path)
        if w.ndim >= need and pat.search(name):
            out[name] = sparsity(w.reshape(-1, w.shape[-1]), axis=0)
        return w

    jax.tree_util.tree_map_with_path(one, params)
    return out
