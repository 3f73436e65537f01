"""The float32 reference of the projected AdamW steps, sharded over a mesh.

The same mathematics as ``reference.train_reference``, jitted over the
cell's chips with ``NamedSharding``s, under
``jax.default_matmul_precision("highest")``: the reference of a model whose
state no one chip holds (stablelm-2-1.6b at 24 layers: 26 GB of float32
parameters, two moments and a gradient) fits four. Its placement is its
own, not the program's: d_model over ``data`` (FSDP), heads, the MLP width
and the vocabulary over ``model``.

Two departures in form, none in the mathematics, both pinned to
``reference.train_reference`` by ``bench/tests/test_train_mesh.py``:

* row-block gradient accumulation, to fit the activations beside 6.6 GB of
  state a chip: each step's gradient is accumulated over equal row blocks
  of the batch (``block_rows`` rows each, 2 by default); the loss is a
  mean over rows, so the mean of the blocks' losses and gradients is the
  batch's, exact up to float32 rounding (only the order of the sums
  changes). Nothing is rematerialised. The control (``compute=``) takes
  each tensor's power-of-two scale per row block, a block's activations
  being its tensors;
* ``reference.forward``'s layer loop runs as a ``lax.scan`` over the stacked
  layers (``forward`` below), built from ``reference.py``'s own pieces
  (``_mm``, ``_rms``, ``_rope``, ``_round``): one layer body to compile
  instead of 24 unrolled ones (compiled for a described v5e:2x2 on the
  CPU, the unrolled gradient program took 198 s, the scan 31 s).

The AdamW update and the projection are ``reference.adamw`` and
``reference.project_params`` themselves. Nothing here imports the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import reference

# leaf path -> the mesh axis of each of its axes
_AXES = {
    "embed": ("model", "data"), "unembed": ("data", "model"),
    "final_norm": (None,), "blocks/ln1": (None, None),
    "blocks/ln2": (None, None),
    "blocks/attn/wq": (None, "data", "model", None),
    "blocks/attn/wk": (None, "data", "model", None),
    "blocks/attn/wv": (None, "data", "model", None),
    "blocks/attn/wo": (None, "model", None, "data"),
    "blocks/mlp/w_up": (None, "data", "model"),
    "blocks/mlp/w_gate": (None, "data", "model"),
    "blocks/mlp/w_down": (None, "model", "data"),
}


def make_mesh(devices, mesh: str) -> Mesh:
    """A ``("data", "model")`` mesh of ``"DxM"`` over ``devices``."""
    d, m = (int(x) for x in mesh.split("x"))
    return Mesh(np.asarray(devices[:d * m]).reshape(d, m), ("data", "model"))


def leaf_shardings(m: dict, mesh: Mesh) -> dict:
    """{leaf path: NamedSharding}; an axis the mesh axis does not divide
    stays whole."""
    out = {}
    for path, shape in reference.lm_shapes(m).items():
        names = [n if n and shape[i] % mesh.shape[n] == 0 else None
                 for i, n in enumerate(_AXES[path])]
        out[path] = NamedSharding(mesh, P(*names))
    return out


def forward(params, tokens, m: dict, compute=None):
    """``reference.forward`` with its layer loop as a scan: logits (B, S, V)
    of tokens (B, S)."""
    eps = m["layer_norm_eps"]
    hd = m["hidden_size"] // m["num_attention_heads"]
    rot = int(hd * m["partial_rotary_factor"])
    rot -= rot % 2
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    c = functools.partial(reference._round, compute=compute)
    mm = functools.partial(reference._mm, compute=compute)

    def layer(x, lp):
        h = c(reference._rms(x, lp["ln1"], eps))
        q = mm("bsd,dhk->bshk", h, lp["attn"]["wq"])
        k = mm("bsd,dhk->bshk", h, lp["attn"]["wk"])
        v = mm("bsd,dhk->bshk", h, lp["attn"]["wv"])
        q = c(reference._rope(q, rot, m["rope_theta"]))
        k = c(reference._rope(k, rot, m["rope_theta"]))
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        logits = mm("bshk,bthk->bhst", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        o = mm("bhst,bthk->bshk", p, v)
        x = c(x + mm("bshk,hkd->bsd", o, lp["attn"]["wo"]))
        h2 = c(reference._rms(x, lp["ln2"], eps))
        g = c(jax.nn.silu(mm("bsd,df->bsf", h2, lp["mlp"]["w_gate"])))
        u = mm("bsd,df->bsf", h2, lp["mlp"]["w_up"])
        return c(x + mm("bsf,fd->bsd", c(g * u), lp["mlp"]["w_down"])), None

    x, _ = jax.lax.scan(layer, c(params["embed"][tokens]), params["blocks"])
    x = c(reference._rms(x, params["final_norm"], eps))
    return mm("bsd,dv->bsv", x, params["unembed"])


def loss(params, tokens, m: dict, compute=None):
    """``reference.loss`` over :func:`forward`: the mean next-token
    cross-entropy of tokens (B, S + 1)."""
    logits = forward(params, tokens[:, :-1], m, compute)
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - hit)


def programs(m: dict, opt: dict, shard: dict, compute=None) -> dict:
    """The jitted pieces of one reference step over the shardings
    ``shard`` ({leaf path: NamedSharding}): zeros like the parameters, a row
    block's loss with its gradient added to a running sum, the mean and its
    clip factor, the clipped norms, and the AdamW update with the
    projection."""
    rep = NamedSharding(next(iter(shard.values())).mesh, P())
    names = list(shard)

    @functools.partial(jax.jit, donate_argnums=(1,),
                       out_shardings=(rep, shard))
    def grad_block(flat, acc, tokens):
        l, g = jax.value_and_grad(lambda f: loss(
            reference.nest(f), tokens, m, compute))(flat)
        return l, {p: acc[p] + g[p] for p in names}

    zeros = jax.jit(lambda f: {p: jnp.zeros_like(x) for p, x in f.items()},
                    out_shardings=shard)

    @functools.partial(jax.jit, donate_argnums=(0,),
                       out_shardings=(shard, rep))
    def mean_and_clip(acc, n):
        g = {p: acc[p] / n for p in names}
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        return g, jnp.minimum(1.0, opt["grad_clip"]
                              / jnp.maximum(gnorm, 1e-12))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3),
                       out_shardings=(shard, shard, shard))
    def update(flat, g, mom, vel, lr, bc1, bc2, clip, radius):
        new = {}
        for p in names:
            new[p], mom[p], vel[p] = reference.adamw(
                flat[p], g[p] * clip, mom[p], vel[p], lr, bc1, bc2, opt)
        return reference.project_params(new, radius), mom, vel

    norms = jax.jit(lambda g, c: reference.leaf_norms(
        {p: x * c for p, x in g.items()}))
    return {"zeros": zeros, "grad_block": grad_block,
            "mean_and_clip": mean_and_clip, "update": update,
            "norms": norms}


def train_reference(m: dict, opt: dict, seed: int, batches, radius: float,
                    *, devices, mesh: str, compute=None, rows=None,
                    block_rows: int = 2) -> dict:
    """``reference.train_reference`` over ``devices``: each step's loss,
    each leaf's norm of the first (clipped) gradient, and each leaf's norm
    of the change over all the steps. ``rows`` keeps only that many rows
    of each batch (a fault: part of the batch left out); ``compute`` is the
    control's lower precision."""
    mesh = make_mesh(devices, mesh)
    shard = leaf_shardings(m, mesh)
    tok = NamedSharding(mesh, P("data", None))
    names = list(shard)

    with jax.default_matmul_precision("highest"):
        f = programs(m, opt, shard, compute)
        flat = reference.flatten(reference.init_lm(
            m, seed, out_shardings=reference.nest(shard)))
        mom, vel = f["zeros"](flat), f["zeros"](flat)
        losses, first = [], None
        for i, tokens in enumerate(batches, start=1):
            tokens = np.asarray(tokens)[:rows]
            step = min(block_rows, tokens.shape[0])
            if tokens.shape[0] % step:
                raise ValueError(f"{tokens.shape[0]} rows do not split into "
                                 f"blocks of {step}")
            acc, loss = f["zeros"](flat), 0.0
            for b in range(0, tokens.shape[0], step):
                l, acc = f["grad_block"](
                    flat, acc, jax.device_put(tokens[b:b + step], tok))
                loss += float(l)
            n = tokens.shape[0] // step
            losses.append(loss / n)
            g, clip = f["mean_and_clip"](acc, jnp.float32(n))
            if first is None:
                first = {p: float(v) for p, v in f["norms"](g, clip).items()}
            flat, mom, vel = f["update"](flat, g, mom, vel,
                                         reference.lr_at(i, opt),
                                         1 - opt["beta1"] ** i,
                                         1 - opt["beta2"] ** i, clip, radius)
            del g
        del mom, vel
        init = reference.flatten(reference.init_lm(
            m, seed, out_shardings=reference.nest(shard)))
        delta = jax.jit(lambda a, b: {p: jnp.sqrt(jnp.sum(jnp.square(
            a[p] - b[p]))) for p in names})(flat, init)
    return {"losses": losses, "grad_norms": first,
            "delta_norms": {p: float(v) for p, v in delta.items()}}
