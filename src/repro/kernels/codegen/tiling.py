"""Tile/grid planning for generated projection kernels (DESIGN.md §4).

The lowering (``lowering.py``) tiles the *canonical* view of a compiled
schedule — ``Schedule.canonical_shape = (g_1, …, g_{L-1}, m)`` where g_t is
the aggregated extent of reduce level t and m the flattened surviving axes —
with the layout the hand-written golden kernels proved out:

* the lane axis is ``m`` (the solve axis), blocked by ``block_m`` and walked
  by a PARALLEL grid dimension;
* the sublane axis is ``g_{L-1}`` (the *last* reduced axis), blocked by
  ``block_n`` and walked by the SEQUENTIAL (``arbitrary``) grid dimension —
  the only reduce that crosses grid steps accumulates over it;
* every earlier reduced axis ``g_1 … g_{L-2}`` stays fully VMEM-resident in
  the tile (experts/heads/slices: small in every assigned architecture).

One rule forces full residency of the sublane axis: an ℓ1 ApplyGroup needs
its whole group for the per-group θ-solve, so when level L-1 (whose group
runs along ``g_{L-1}``) is ℓ1 the axis cannot be split across sequential
blocks — ``plan_tiles`` then pins ``block_n = g_{L-1}`` and lets the VMEM
check decide eligibility. ℓ∞/ℓ2 applies are elementwise given the solved
radii (and the saved *global* final aggregate), so they split freely.

``plan_tiles`` returns ``None`` when no block assignment fits the VMEM
budget — the planner backend's ``available()`` gate, which routes the design
back to the jnp schedule executor.

``plan_tiles``'s default block sizes are heuristics, not measurements.
``candidate_tile_plans`` enumerates the small measured-search grid around the
default (halved/doubled block sizes, VMEM-filtered, the ℓ1 residency pin
respected) that ``kernels.codegen.autotune_tiles`` shoots out the same way
``method="auto"`` shoots out planner backends — the winner is cached per
(canonical shape, dtype, device, interpret).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import numpy as np

from repro.core.schedule import Schedule

DEFAULT_BLOCK_N = 256       # sublane-axis rows per tile
DEFAULT_BLOCK_M = 512       # lane-axis columns per tile
MIN_BLOCK_N = 8             # f32 sublane granule
MIN_BLOCK_M = 128           # lane granule

# per-step VMEM residency ceiling (~half a 16 MB core: leave the compiler
# slack for double buffering and the θ-solve stage)
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


class TilePlan(NamedTuple):
    """Grid/block assignment for one compiled schedule (batch axes excluded).

    ``canon_shape`` is the collapsed ``(g_1, …, g_{L-1}, m)`` view the
    kernels operate on; ``lead`` its VMEM-resident prefix ``(g_1 … g_{L-2})``;
    ``n``/``m`` the two gridded extents (sequential sublane / parallel lane);
    ``n_resident`` records that the whole sublane axis sits in one block
    (required for an ℓ1 apply over it); ``vmem_bytes`` the estimated
    double-buffered per-step residency the budget was checked against.
    """

    canon_shape: Tuple[int, ...]
    lead: Tuple[int, ...]
    n: int
    m: int
    block_n: int
    block_m: int
    n_resident: bool
    vmem_bytes: int


def _tile_bytes(lead: Tuple[int, ...], block_n: int, block_m: int,
                itemsize: int) -> int:
    """Worst-case per-grid-step VMEM residency of the generated kernels.

    The apply pass is the high-water mark: the y tile, the output tile, one
    tile per intermediate aggregate (suffix products of ``lead``), and the
    two (1, block_m) rows; ×2 for pipelined double buffering.
    """
    lead_elems = math.prod(lead) if lead else 1
    elems = 2 * lead_elems * block_n * block_m          # y tile + out tile
    suffix = 1
    for g in reversed(lead):                            # aggregate v_t tiles
        elems += suffix * block_n * block_m
        suffix *= g
    elems += 2 * block_m                                # v-final + u rows
    return 2 * elems * itemsize


class BatchedTilePlan(NamedTuple):
    """Grid/block assignment for a serving bucket: ``batch`` stacked items.

    ``base`` is the per-item :class:`TilePlan`; the generated batched kernels
    prepend the batch extent as the LEADING (parallel) Pallas grid dimension,
    so one dispatch walks ``batch × grid(base)`` programs with per-item radii
    indexed in SMEM by the batch grid index. Per-grid-step VMEM
    residency equals the per-item plan's (the batch block size is 1), so the
    budget check is the base plan's check.
    """

    base: TilePlan
    batch: int

    @property
    def grid_prefix(self) -> Tuple[int, ...]:
        return (self.batch,)


def plan_batched_tiles(sched: Schedule, dtype, batch: int) -> Optional[BatchedTilePlan]:
    """Pick the batched-grid assignment for ``batch`` stacked instances of
    ``sched``, or ``None`` when the per-item design cannot be generated.

    ``sched`` is the batch-free per-item schedule (the serving plan's
    ``key.shape``); the batch axis never enters the schedule because items do
    not share aggregates — it is purely a grid dimension.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    base = plan_tiles(sched, dtype)
    if base is None:
        return None
    return BatchedTilePlan(base, int(batch))


def plan_tiles(sched: Schedule, dtype) -> Optional[TilePlan]:
    """Pick VMEM-resident block sizes for ``sched``, or ``None`` if the
    design cannot be generated (flat non-ℓ1 solve, or no fitting blocks)."""
    if sched.batch_dims:
        raise ValueError(
            "plan_tiles takes a batch-free schedule; the generator strips "
            "batch axes (vmap) before tiling")
    dims = sched.canonical_shape
    itemsize = np.dtype(dtype).itemsize
    if len(sched.levels) == 1:
        # Prop 6.3 degenerate case: the whole design IS the outer solve.
        # Only l1 has a VMEM θ-solver kernel worth generating.
        if sched.solve.norm != "1":
            return None
        m = dims[-1]
        return TilePlan(dims, (), 1, m, 1, m, True, m * itemsize)
    lead, n, m = dims[:-2], dims[-2], dims[-1]
    # an l1 apply over the sequential axis needs its whole group in one block
    n_resident = sched.levels[-2][0] == "1"
    block_n = n if n_resident else min(DEFAULT_BLOCK_N, max(MIN_BLOCK_N, n))
    block_m = min(DEFAULT_BLOCK_M, max(MIN_BLOCK_M, m))
    while _tile_bytes(lead, block_n, block_m, itemsize) > VMEM_BUDGET_BYTES:
        if not n_resident and block_n > MIN_BLOCK_N:
            block_n = max(MIN_BLOCK_N, block_n // 2)
        elif block_m > MIN_BLOCK_M:
            block_m = max(MIN_BLOCK_M, block_m // 2)
        else:
            return None
    return TilePlan(dims, lead, n, m, block_n, block_m, n_resident,
                    _tile_bytes(lead, block_n, block_m, itemsize))


def candidate_tile_plans(sched: Schedule, dtype) -> Tuple[TilePlan, ...]:
    """The measured-search grid for one schedule: the default plan first, then
    every VMEM-fitting neighbor with halved/doubled block sizes.

    The grid is deliberately small (≤ 9 plans): the autotuner times each
    candidate's full fused pipeline, so the search must stay cheap enough to
    run at plan-build time. An ℓ1 apply over the sublane axis keeps its
    residency pin (``block_n = n`` is the only legal choice there), so those
    designs search ``block_m`` only. Returns ``()`` when the design cannot be
    generated at all, and a single plan for the degenerate flat solve.
    """
    default = plan_tiles(sched, dtype)
    if default is None:
        return ()
    if len(sched.levels) == 1:
        return (default,)
    itemsize = np.dtype(dtype).itemsize
    if default.n_resident:
        ns = (default.block_n,)
    else:
        ns = {default.block_n,
              max(MIN_BLOCK_N, default.block_n // 2),
              min(max(MIN_BLOCK_N, default.n), default.block_n * 2)}
    ms = {default.block_m,
          max(MIN_BLOCK_M, default.block_m // 2),
          min(max(MIN_BLOCK_M, default.m), default.block_m * 2)}
    plans = [default]
    for bn in sorted(ns):
        for bm in sorted(ms):
            vb = _tile_bytes(default.lead, bn, bm, itemsize)
            if vb > VMEM_BUDGET_BYTES:
                continue
            tp = TilePlan(default.canon_shape, default.lead, default.n,
                          default.m, bn, bm, default.n_resident, vb)
            if tp not in plans:
                plans.append(tp)
    return tuple(plans)
