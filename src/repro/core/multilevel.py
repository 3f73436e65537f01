"""Multi-level projection MP^ν (paper §6, Definitions 6.1/6.2, Algorithms 5/6/10).

A *level* is ``(norm, n_axes)``: aggregate the leading ``n_axes`` axes of the
current tensor with ``norm``. The norm list ν runs innermost→outermost; the
LAST entry is the final vector projection (its n_axes must flatten whatever
remains). Examples for Y ∈ R^{c,n,m}:

    ν = [(inf, 1), (1, 2)]            — bi-level ℓ1,∞ over a matrix-like view
    ν = [(inf, 1), (inf, 1), (1, 1)]  — tri-level ℓ1,∞,∞ of Definition 6.1
    ν = [(1, 3)]                      — |ν| = 1 → the usual flat ℓ1 projection
                                        (Proposition 6.3: MP generalizes P)

Algorithm 6's recursion is compiled to a flat reduce → solve → apply schedule
(``core.schedule``) and executed from that — the same schedule the mesh
executor (``core.sharded``) runs under shard_map and the fused Pallas planner
backends pattern-match.

Complexity: work = O(Π d) (one touch per element per level boundary it lives
under), depth with infinite parallelism = O(Σ levels' reduction depths) —
Proposition 6.4's exponential speedup; on a TPU mesh the outer levels shrink
the data by the aggregated dims, so only the innermost level touches the full
tensor (see core/sharded.py for the mesh mapping).
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from . import ball, schedule as sched_mod

Level = Tuple[object, int]  # (norm ∈ {1,2,'inf',jnp.inf}, number of leading axes)


def _check_levels(shape, levels: Sequence[Level]):
    sched_mod.check_levels(shape, levels)


def _final_level_size(shape, levels: Sequence[Level]) -> int:
    """Length of the vector the LAST level's θ-solver sees (autotune key)."""
    return sched_mod.compile_schedule(shape, levels).solve_size


def multilevel_project(y: jax.Array, levels: Sequence[Level], radius,
                       method: str = "sort") -> jax.Array:
    """MP^ν_radius(Y) — Algorithm 6 via the compiled schedule.

    ``method="auto"`` routes through the projection planner (``core.plan``):
    on a concrete array the cached, autotuned plan executes directly (a
    committed mesh-sharded array routes to the sharded schedule executor);
    under a trace (inside an enclosing jit/vmap) the shape-autotuned best
    *generic* θ-solver is inlined instead (specialized fused backends can't
    be embedded in someone else's trace).
    """
    if method == "auto":
        from . import plan as _plan

        out = _plan.maybe_plan_call(y, levels, radius)
        if out is not None:
            return out
        method = _plan.best_l1_method(_final_level_size(y.shape, levels), y.dtype)
    sched = sched_mod.compile_schedule(y.shape, levels)
    return sched_mod.execute(y, sched, radius, method=method)


def trilevel_l1infinf(y: jax.Array, radius, method: str = "sort") -> jax.Array:
    """Paper Algorithm 5: TP^{1,∞,∞} for an order-3 tensor (c, n, m)."""
    if y.ndim != 3:
        raise ValueError("trilevel_l1infinf expects an order-3 tensor")
    return multilevel_project(y, [(jnp.inf, 1), (jnp.inf, 1), (1, 1)], radius, method)


def trilevel_l111(y: jax.Array, radius, method: str = "sort") -> jax.Array:
    """ℓ1,1,1 tri-level used in the paper's Figure 3 benchmark."""
    if y.ndim != 3:
        raise ValueError("trilevel_l111 expects an order-3 tensor")
    return multilevel_project(y, [(1, 1), (1, 1), (1, 1)], radius, method)


def multilevel_norm(x: jax.Array, levels: Sequence[Level]) -> jax.Array:
    """The mixed norm induced by ν: aggregate each level in turn.

    The feasibility invariant of the multi-level projection is
    ``multilevel_norm(MP^ν_η(Y), ν) <= η`` (checked by the property tests).
    """
    _check_levels(x.shape, levels)
    cur = x
    for q, k in levels[:-1]:
        cur = ball.norm_reduce(cur, q, axes=tuple(range(k)))
    q, _ = levels[-1]
    return ball.norm_reduce(cur.reshape(-1), q, axes=0)


def work_depth(shape, levels: Sequence[Level]):
    """(work, depth) model of Prop 6.4.

    work  = sequential element touches; depth = longest dependency chain with
    unbounded parallelism (tree reductions = log2 of the reduced extent).
    """
    _check_levels(shape, levels)
    work = 0
    depth = 0.0
    cur = list(shape)
    for q, k in levels[:-1]:
        red = math.prod(cur[:k])
        rest = math.prod(cur[k:])
        work += red * rest          # aggregation pass
        work += red * rest          # final per-group projection pass
        depth += math.log2(max(red, 2))  # tree-reduce the aggregated axes
        depth += 1                  # the elementwise apply
        cur = cur[k:]
    n = math.prod(cur)
    work += n * int(math.log2(max(n, 2)))  # final vector projection (sort-based)
    depth += math.log2(max(n, 2))
    return work, depth
