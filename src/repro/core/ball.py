"""Vector norm-ball projections — the primitives every level of the multi-level
projection is built from.

All functions are pure JAX (jit/vmap/grad-safe unless noted), operate on the
*last* axis of the input unless stated otherwise, and accept a scalar or
broadcastable ``radius``.

Three ℓ1 algorithms are provided (see DESIGN.md §4 — hardware adaptation):

* ``project_l1_sort``   — sort + prefix-sum threshold (Duchi et al. / Held et al.).
  O(n log n) work, O(log n) depth. Exact.
* ``project_l1_bisect`` — bisection on the soft-threshold θ. O(k·n) work with k fixed
  iterations, O(k log n) depth, only elementwise ops + reductions: the TPU/Pallas
  friendly variant. Accurate to ~2^-k of the value range.
* ``project_l1_filter`` — Michelot/Condat filtering: a fixed-point iteration on θ
  over a shrinking active set (masking, no sorting). O(n) expected work, converges
  in a handful of sweeps on typical data. Exact at the fixed point. The
  ``lax.while_loop`` only finds the active set (on stopped gradients); θ is
  recomputed from it in closed form, so the backend is reverse-mode
  differentiable like the others.

All reduce to the simplex projection of |y| followed by sign restoration.

Backend registry
----------------
The θ-solvers live in a registry keyed by method name; ``resolve_method()``
canonicalizes (and validates) a user-supplied name, and ``register_l1_method()``
adds a backend in one call — downstream modules (bilevel, multilevel, sharded,
kernels, optim) never enumerate method names themselves. Likewise the per-norm
projection/reduction dispatch lives in tables here (``canonical_norm`` +
``project_ball`` / ``norm_reduce`` / ``project_grouped``) instead of being
copy-pasted ``if q in (...)`` chains across modules.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, NamedTuple, Sequence, Union

import jax
import jax.numpy as jnp

Scalar = Union[float, jax.Array]

_BISECT_ITERS = 64  # enough for float32 exactness on well-scaled data


def _soft_threshold(a: jax.Array, theta: jax.Array) -> jax.Array:
    return jnp.maximum(a - theta, 0.0)


# --------------------------------------------------------------------------- #
# θ solvers: sum(max(a - θ, 0)) == radius for non-negative a.
#
# Ball contract  : return θ <= 0 when sum(a) <= radius (projection = identity).
# Simplex contract: always solve the equality (θ may be negative).
# --------------------------------------------------------------------------- #


def simplex_threshold_sort(a: jax.Array, radius: Scalar) -> jax.Array:
    """Threshold θ s.t. sum(max(a - θ, 0)) == radius, for non-negative ``a``.

    Sort-based exact evaluation over the last axis. Returns θ with the same
    leading (batch) shape as ``a`` minus the last axis. If ``sum(a) <= radius``
    the returned θ is <= 0 so that soft-thresholding is the identity on a >= 0.
    """
    radius = jnp.asarray(radius, a.dtype)
    r = radius[..., None]  # broadcast over the reduced axis (works for 0-d too)
    a_sorted = jnp.sort(a, axis=-1)[..., ::-1]  # descending
    csum = jnp.cumsum(a_sorted, axis=-1)
    n = a.shape[-1]
    ks = jnp.arange(1, n + 1, dtype=a.dtype)
    # candidate thresholds if exactly k entries stay positive
    thetas = (csum - r) / ks
    # k is valid while a_sorted[k-1] > theta_k ; pick the largest valid k
    valid = a_sorted > thetas
    k = jnp.sum(valid, axis=-1)  # >= 1 when sum(a) > radius (radius > 0)
    k = jnp.maximum(k, 1)
    theta = jnp.take_along_axis(thetas, k[..., None] - 1, axis=-1)[..., 0]
    # already feasible -> no shrink
    inside = csum[..., -1] <= radius
    return jnp.where(inside, jnp.zeros_like(theta) - 1.0, theta)


def simplex_threshold_bisect(
    a: jax.Array, radius: Scalar, iters: int = _BISECT_ITERS
) -> jax.Array:
    """Bisection evaluation of the simplex threshold (fully data-parallel).

    φ(θ) = sum(max(a-θ,0)) is continuous, strictly decreasing on [0, max(a)]
    wherever positive; we bisect φ(θ) = radius. Matches the sort variant to
    ~machine precision after 64 iterations.
    """
    radius = jnp.asarray(radius, a.dtype)
    hi = jnp.max(a, axis=-1)
    lo = jnp.zeros_like(hi)

    def body(_, loh):
        lo, hi = loh
        mid = 0.5 * (lo + hi)
        phi = jnp.sum(_soft_threshold(a, mid[..., None]), axis=-1)
        too_small = phi > radius  # θ too small -> raise lo
        lo = jnp.where(too_small, mid, lo)
        hi = jnp.where(too_small, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    theta = 0.5 * (lo + hi)
    inside = jnp.sum(a, axis=-1) <= radius
    return jnp.where(inside, jnp.full_like(theta, -1.0), theta)


def _filter_theta(a: jax.Array, radius: jax.Array) -> jax.Array:
    """Michelot fixed-point θ for the *equality* constraint, batched.

    θ₀ = (Σa - r)/n; repeat θ ← (Σ_{aᵢ>θ} aᵢ - r)/#{aᵢ>θ} until the active set
    stops shrinking. θ is non-decreasing and the active set is monotone, so the
    loop terminates in at most n sweeps (a handful on typical data — expected
    O(n) total work, Michelot 1986 / Condat 2016). Rows that have converged are
    at a fixed point, so the batched loop runs until ALL rows converge without
    disturbing finished ones.
    """
    n = a.shape[-1]
    s0 = jnp.sum(a, axis=-1)
    r = jnp.broadcast_to(jnp.asarray(radius, a.dtype), s0.shape)
    theta0 = (s0 - r) / n
    count0 = jnp.full(s0.shape, n, dtype=jnp.int32)
    done0 = jnp.zeros(s0.shape, dtype=bool)

    def cond(state):
        _, _, done, it = state
        return jnp.logical_and(jnp.logical_not(jnp.all(done)), it < n + 2)

    def body(state):
        theta, count, done, it = state
        active = a > theta[..., None]
        new_count = jnp.sum(active, axis=-1, dtype=jnp.int32)
        ssum = jnp.sum(jnp.where(active, a, 0.0), axis=-1)
        new_theta = (ssum - r) / jnp.maximum(new_count, 1).astype(a.dtype)
        # empty active set (radius ~0 edge): current θ already clips everything
        new_theta = jnp.where(new_count > 0, new_theta, theta)
        converged = (new_count == count) | (new_count == 0)
        theta = jnp.where(done, theta, new_theta)
        count = jnp.where(done, count, new_count)
        return theta, count, done | converged, it + 1

    theta, _, _, _ = jax.lax.while_loop(cond, body, (theta0, count0, done0, 0))
    return theta


def _filter_theta_diff(a: jax.Array, radius: jax.Array) -> jax.Array:
    """``_filter_theta`` made reverse-mode differentiable.

    The ``while_loop`` (not transposable) runs entirely on stopped gradients —
    it only has to FIND the active set. θ is then recomputed from that set as
    a closed-form expression of ``(a, radius)``: θ = (Σ_{active} aᵢ - r)/#active.
    The active set is locally constant in ``a``, so autodiff through the
    recomputation yields the exact projection Jacobian (the same one the
    ``sort`` backend's differentiable graph produces).
    """
    theta0 = _filter_theta(jax.lax.stop_gradient(a),
                           jax.lax.stop_gradient(radius))
    active = jax.lax.stop_gradient(a > theta0[..., None])
    count = jnp.sum(active, axis=-1)
    ssum = jnp.sum(jnp.where(active, a, 0.0), axis=-1)
    r = jnp.broadcast_to(jnp.asarray(radius, a.dtype), ssum.shape)
    theta = (ssum - r) / jnp.maximum(count, 1).astype(a.dtype)
    # empty active set (radius ~ 0 edge): keep the loop's θ, it clips everything
    return jnp.where(count > 0, theta, theta0)


def simplex_threshold_filter(a: jax.Array, radius: Scalar) -> jax.Array:
    """Michelot/Condat filtering θ (ball contract: θ = -1 when inside)."""
    radius = jnp.asarray(radius, a.dtype)
    theta = _filter_theta_diff(a, radius)
    inside = jnp.sum(a, axis=-1) <= radius
    return jnp.where(inside, jnp.full_like(theta, -1.0), theta)


# --------------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------------- #


class L1Method(NamedTuple):
    """One ℓ1/simplex θ-solver backend.

    ``ball_theta``    — θ with the ball contract (θ <= 0 ⇒ identity inside).
    ``simplex_theta`` — θ for the equality constraint (may be negative).
    ``complexity``    — human-readable work bound (docs/benchmarks).
    ``differentiable``— safe under reverse-mode autodiff.
    """

    ball_theta: Callable[[jax.Array, Scalar], jax.Array]
    simplex_theta: Callable[[jax.Array, Scalar], jax.Array]
    complexity: str
    differentiable: bool


def _simplex_theta_sort(a: jax.Array, radius: Scalar) -> jax.Array:
    a_sorted = jnp.sort(a, axis=-1)[..., ::-1]
    csum = jnp.cumsum(a_sorted, axis=-1)
    n = a.shape[-1]
    ks = jnp.arange(1, n + 1, dtype=a.dtype)
    thetas = (csum - jnp.asarray(radius, a.dtype)[..., None]) / ks
    valid = a_sorted > thetas
    k = jnp.maximum(jnp.sum(valid, axis=-1), 1)
    return jnp.take_along_axis(thetas, k[..., None] - 1, axis=-1)[..., 0]


def _simplex_theta_bisect(a: jax.Array, radius: Scalar) -> jax.Array:
    # bisection over [min(a)-radius/n, max(a)] (θ may be negative)
    radius = jnp.asarray(radius, a.dtype)
    hi = jnp.max(a, axis=-1)
    lo = jnp.min(a, axis=-1) - radius / a.shape[-1]

    def body(_, loh):
        lo, hi = loh
        mid = 0.5 * (lo + hi)
        phi = jnp.sum(jnp.maximum(a - mid[..., None], 0.0), axis=-1)
        too_small = phi > radius
        return jnp.where(too_small, mid, lo), jnp.where(too_small, hi, mid)

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    return 0.5 * (lo + hi)


def _simplex_theta_filter(a: jax.Array, radius: Scalar) -> jax.Array:
    return _filter_theta_diff(a, jnp.asarray(radius, a.dtype))


_L1_METHODS: Dict[str, L1Method] = {}
_L1_ALIASES: Dict[str, str] = {}

DEFAULT_METHOD = "sort"


def register_l1_method(name: str, method: L1Method, *,
                       aliases: Sequence[str] = ()) -> None:
    """Register an ℓ1 θ-solver backend. One call makes it available everywhere
    a ``method=`` kwarg exists (core, kernels dispatch, optim hook)."""
    _L1_METHODS[name] = method
    for alias in aliases:
        _L1_ALIASES[alias] = name


def resolve_method(method: str | None, *, default: str = DEFAULT_METHOD) -> str:
    """Canonicalize a backend name (None → default, aliases → canonical).

    Raises ``ValueError`` for unknown names — the single place config errors
    about projection backends surface.
    """
    if method is None:
        method = default
    name = _L1_ALIASES.get(method, method)
    if name not in _L1_METHODS:
        raise ValueError(
            f"unknown l1 method {method!r}; available: {sorted(_L1_METHODS)}"
        )
    return name


def available_methods() -> tuple:
    """Canonical names of all registered ℓ1 backends."""
    return tuple(sorted(_L1_METHODS))


def method_info(method: str) -> L1Method:
    """Registry record for a (possibly aliased) backend name."""
    return _L1_METHODS[resolve_method(method)]


register_l1_method("sort", L1Method(
    simplex_threshold_sort, _simplex_theta_sort,
    complexity="O(n log n)", differentiable=True))
register_l1_method("bisect", L1Method(
    simplex_threshold_bisect, _simplex_theta_bisect,
    complexity="O(k n), k=64 fixed", differentiable=True))
register_l1_method("filter", L1Method(
    simplex_threshold_filter, _simplex_theta_filter,
    complexity="O(n) expected", differentiable=True),
    aliases=("michelot", "condat"))


# --------------------------------------------------------------------------- #
# Projections
# --------------------------------------------------------------------------- #


def project_simplex(y: jax.Array, radius: Scalar = 1.0, method: str = "sort") -> jax.Array:
    """Euclidean projection onto {x >= 0, sum(x) == radius} over the last axis."""
    # equality constraint: always apply the threshold, even inside the l1 ball.
    theta = _L1_METHODS[resolve_method(method)].simplex_theta(y, radius)
    return jnp.maximum(y - theta[..., None], 0.0)


def project_l1(y: jax.Array, radius: Scalar, method: str = "sort") -> jax.Array:
    """Euclidean projection onto the ℓ1 ball of ``radius`` over the last axis."""
    a = jnp.abs(y)
    theta = _L1_METHODS[resolve_method(method)].ball_theta(a, radius)
    return jnp.sign(y) * _soft_threshold(a, jnp.maximum(theta, 0.0)[..., None])


# convenience aliases, exported by repro.core
project_l1_sort = functools.partial(project_l1, method="sort")
project_l1_bisect = functools.partial(project_l1, method="bisect")
project_l1_filter = functools.partial(project_l1, method="filter")


def project_l2(y: jax.Array, radius: Scalar) -> jax.Array:
    """Projection onto the ℓ2 ball over the last axis: pure rescale."""
    radius = jnp.asarray(radius, y.dtype)
    nrm = jnp.linalg.norm(y, axis=-1, keepdims=True)
    scale = jnp.where(nrm > radius[..., None], radius[..., None] / jnp.maximum(nrm, 1e-30), 1.0)
    return y * scale


def project_linf(y: jax.Array, radius: Scalar) -> jax.Array:
    """Projection onto the ℓ∞ ball: elementwise clip. ``radius`` broadcasts."""
    radius = jnp.asarray(radius, y.dtype)
    if radius.ndim:
        radius = radius[..., None]
    return jnp.clip(y, -radius, radius)


# --------------------------------------------------------------------------- #
# Per-norm dispatch tables
# --------------------------------------------------------------------------- #

_NORM_NAMES = {1: "1", "1": "1", 2: "2", "2": "2",
               jnp.inf: "inf", float("inf"): "inf", "inf": "inf"}


def canonical_norm(norm) -> str:
    """Canonical name ('1' | '2' | 'inf') of a norm spec, or ValueError."""
    try:
        return _NORM_NAMES[norm]
    except (KeyError, TypeError):
        raise ValueError(f"unsupported norm {norm!r}") from None


def project_ball(y: jax.Array, norm, radius: Scalar, method: str = "sort") -> jax.Array:
    """Dispatch: project the last axis of ``y`` onto the ``norm``-ball.

    ``norm`` ∈ {1, 2, jnp.inf, 'inf'}.
    """
    q = canonical_norm(norm)
    if q == "1":
        return project_l1(y, radius, method=method)
    if q == "2":
        return project_l2(y, radius)
    return project_linf(y, radius)


def norm_reduce(y: jax.Array, norm, axes) -> jax.Array:
    """Aggregate ``y`` over ``axes`` with the given norm (the v_q of the paper)."""
    q = canonical_norm(norm)
    if q == "1":
        return jnp.sum(jnp.abs(y), axis=axes)
    if q == "2":
        return jnp.sqrt(jnp.sum(jnp.square(y), axis=axes))
    return jnp.max(jnp.abs(y), axis=axes)


def project_grouped(y: jax.Array, norm, radii: jax.Array, inner_axes,
                    method: str = "sort") -> jax.Array:
    """Project every group of ``y`` onto its own ``norm``-ball.

    A group is a slice over ``inner_axes``; ``radii`` has the shape of the
    remaining (outer) axes. This is the shared inner step of the bi-/multi-level
    projections — the single home of the per-norm group dispatch that used to be
    copy-pasted across bilevel.py / multilevel.py / sharded.py.
    """
    inner_axes = tuple(a % y.ndim for a in inner_axes)
    outer_axes = tuple(a for a in range(y.ndim) if a not in inner_axes)
    q = canonical_norm(norm)
    u_b = jnp.expand_dims(radii, inner_axes)  # broadcast radii over the groups
    if q == "inf":
        return jnp.clip(y, -u_b, u_b)
    if q == "2":
        nrm = jnp.sqrt(jnp.sum(jnp.square(y), axis=inner_axes, keepdims=True))
        scale = jnp.where(nrm > u_b, u_b / jnp.maximum(nrm, 1e-30), 1.0)
        return y * scale
    # q == "1": move the group axes last, flatten, batched l1 projection
    perm = outer_axes + inner_axes
    yt = jnp.transpose(y, perm)
    outer_shape = yt.shape[: len(outer_axes)]
    inner_size = math.prod(yt.shape[len(outer_axes):])
    proj = project_l1(yt.reshape((-1, inner_size)), radii.reshape(-1), method=method)
    proj = proj.reshape(outer_shape + yt.shape[len(outer_axes):])
    inv = tuple(perm.index(i) for i in range(y.ndim))
    return jnp.transpose(proj, inv)


def ball_norm(x: jax.Array, norm, axis=-1) -> jax.Array:
    """Vector norm along ``axis`` (thin wrapper used by tests/invariants)."""
    return norm_reduce(x, norm, axis)
