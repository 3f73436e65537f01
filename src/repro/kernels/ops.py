"""Dispatch wrappers for the Pallas kernels.

Dispatch is planner-routed, not platform-hand-rolled: the projection entry
points run the **generated fused kernels** (``kernels/codegen``) when the
workload's device is a TPU (or when forced), and otherwise execute through a
cached ``core.plan`` projection plan — the jitted jnp schedule path.

``use_pallas(y)`` gates on the committed device of the *input* array when it
has one (a CPU-committed array on a TPU host keeps the jnp path and vice
versa), falling back to the default backend device. Setting
``REPRO_FORCE_INTERPRET=1`` flips every kernel path into Pallas interpret
mode, so CPU debugging of kernels does not require threading
``interpret=True`` through each call site by hand.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention

_BILEVEL_LEVELS = (("inf", 1), ("1", 1))
_TRILEVEL_LEVELS = (("inf", 1), ("inf", 1), ("1", 1))


def force_interpret() -> bool:
    """True when ``REPRO_FORCE_INTERPRET`` asks for Pallas interpret mode."""
    return os.environ.get("REPRO_FORCE_INTERPRET", "").strip().lower() in (
        "1", "true", "yes", "on")


def use_pallas(y=None) -> bool:
    """True when the workload should run the Pallas kernels.

    Gates on the committed device of ``y`` when it is a concrete array (the
    workload's actual placement), the default backend device otherwise —
    never on the bare ``jax.devices()[0]`` of whatever backend loaded first.
    """
    if isinstance(y, jax.Array) and not isinstance(y, jax.core.Tracer):
        return next(iter(y.devices())).platform == "tpu"
    return jax.devices()[0].platform == "tpu"


def _projection(y, levels, radius, method: str, interpret: bool, force: bool):
    interpret = bool(interpret) or force_interpret()
    if force or use_pallas(y):
        from .codegen import codegen_project

        return codegen_project(y, list(levels), radius, method=method,
                               interpret=interpret)
    from repro.core import plan as planmod

    p = planmod.make_plan(jnp.shape(y), jnp.result_type(y), list(levels),
                          method=method)
    return p(y, radius)


def bilevel_l1inf(y: jax.Array, radius, *, method: str = "bisect",
                  interpret: bool = False, force: bool = False) -> jax.Array:
    """Bi-level ℓ1,∞ projection — generated fused kernel on TPU, planner-cached
    jnp schedule elsewhere.

    ``method`` selects the outer ℓ1 solve ("bisect" | "filter" have VMEM
    kernels; anything else — e.g. "sort" — runs the outer step on the jnp
    backend). ``force=True`` routes through the kernels regardless of platform
    (with ``interpret=True`` — or ``REPRO_FORCE_INTERPRET=1`` — on CPU: the
    per-kernel correctness tests).
    """
    return _projection(y, _BILEVEL_LEVELS, radius, method, interpret, force)


def trilevel_l1infinf(y: jax.Array, radius, *, method: str = "bisect",
                      interpret: bool = False, force: bool = False) -> jax.Array:
    """Tri-level ℓ1,∞,∞ projection — same contract as ``bilevel_l1inf``."""
    if jnp.ndim(y) != 3:
        raise ValueError("trilevel_l1infinf expects an order-3 tensor")
    return _projection(y, _TRILEVEL_LEVELS, radius, method, interpret, force)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "interpret", "use_kernel"))
def _attention(q, k, v, *, causal, window, interpret, use_kernel):
    if use_kernel:
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              interpret: bool = False, force: bool = False):
    """Flash attention fwd — Pallas on TPU, chunked-jnp oracle elsewhere."""
    return _attention(q, k, v, causal=causal, window=window,
                      interpret=bool(interpret) or force_interpret(),
                      use_kernel=bool(force or use_pallas(q)))
