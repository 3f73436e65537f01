"""Parameter templates: one declaration drives init, sharding, and counting.

A model is declared as a pytree of ``ParamDef`` (shape + logical axes + init).
From the same template we derive:

  * ``init_params``  — materialized arrays (deterministic per-path RNG folds)
  * ``param_specs``  — PartitionSpec tree via a logical→mesh axis rule map
                       (with divisibility checks → replicate when they fail)
  * ``count_params`` — exact parameter count without allocation

This is the MaxText-style "logical axis" pattern, reduced to the essentials.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]        # logical axis names (len == len(shape))
    init: str = "normal"                   # normal | zeros | ones | scaled
    scale: float = 0.02                    # stddev for 'normal'; 'scaled' -> 1/sqrt(fan_in)

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _tree_map(f, template):
    return jax.tree_util.tree_map(f, template, is_leaf=is_def)


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def init_params(template, key: jax.Array, dtype=jnp.float32):
    """Materialize a template. Each leaf's RNG is folded from its path string
    so layouts can be refactored without changing unrelated leaves."""

    def one(path, pd: ParamDef):
        if pd.init == "zeros":
            return jnp.zeros(pd.shape, dtype)
        if pd.init == "ones":
            return jnp.ones(pd.shape, dtype)
        # crc32, NOT hash(): python string hashing is per-process randomized
        k = jax.random.fold_in(
            key, np.uint32(zlib.crc32(_path_str(path).encode())))
        if pd.init == "scaled":
            fan_in = pd.shape[0] if len(pd.shape) == 1 else int(np.prod(pd.shape[:-1]))
            std = 1.0 / max(np.sqrt(fan_in), 1.0)
        else:
            std = pd.scale
        return (jax.random.normal(k, pd.shape, jnp.float32) * std).astype(dtype)

    return jax.tree_util.tree_map_with_path(one, template, is_leaf=is_def)


def abstract_params(template, dtype=jnp.float32):
    """ShapeDtypeStruct tree (for AOT lowering — no allocation)."""
    return _tree_map(lambda pd: jax.ShapeDtypeStruct(pd.shape, dtype), template)


def param_specs(template, rules: Dict[str, Optional[str]], mesh_shape: Dict[str, int]):
    """PartitionSpec tree. ``rules`` maps logical axis -> mesh axis (or None).

    A dim is sharded only when the mapped mesh axis divides it; otherwise that
    dim replicates (correct-by-construction for ragged head counts etc.).
    A mesh axis is used at most once per param (first logical axis wins).
    """

    def one(pd: ParamDef):
        used = set()
        parts = []
        for dim, ax in zip(pd.shape, pd.axes):
            mesh_ax = rules.get(ax) if ax else None
            if mesh_ax is None:
                parts.append(None)
                continue
            # tuples of mesh axes allowed, e.g. ("pod", "data")
            axes_tuple = mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,)
            size = 1
            for a in axes_tuple:
                size *= mesh_shape[a]
            if dim % size == 0 and not (set(axes_tuple) & used):
                used.update(axes_tuple)
                parts.append(mesh_ax)
            else:
                parts.append(None)
        return P(*parts)

    return _tree_map(one, template)


def count_params(template) -> int:
    leaves = jax.tree_util.tree_leaves(template, is_leaf=is_def)
    return int(sum(int(np.prod(pd.shape)) for pd in leaves))


def cast_tree(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)
