"""Plain float32 references, independent of the program under test.

Nothing here imports the program. Two references:

* the dense decoder and its projected AdamW step, as the configuration file
  states them (``bench/configs/*.json``: RMSNorm, full multi-head attention
  with interleaved partial rotary embeddings, a SiLU-gated MLP, an untied
  output head; AdamW with a global-norm clip, warm-up and cosine decay; the
  bi-level ℓ1,∞ projection of every ``w_up``/``w_gate`` layer slice);
* the multi-level ℓ1,∞(,∞) projection of a tensor, by sorting.

Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32 matmul
otherwise rounds its operands to bfloat16. ``compute=`` rounds every
activation the forward pass keeps, every matmul operand and every cotangent
the backward pass feeds a matmul to a lower precision, each tensor under a
power-of-two scale of its own (accumulating in float32): that is the
control, the reference in the precision one step below the one the
configuration states. ``project_stored`` is the projection's control: the
payload and the answer held in the lower precision.

The benchmark also makes the weights and payloads here, from the seed, on
the device, in one jitted call; the program and the reference both start
from them.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any whole seed, the bits above 32 folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# --------------------------------------------------------------- the model
def lm_shapes(m: dict) -> dict:
    """{leaf path: shape} of the dense decoder's parameters, layers stacked
    on a leading axis."""
    d, f, n, v = (m["hidden_size"], m["intermediate_size"],
                  m["num_hidden_layers"], m["vocab_size"])
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // h
    return {
        "embed": (v, d), "final_norm": (d,), "unembed": (d, v),
        "blocks/ln1": (n, d), "blocks/ln2": (n, d),
        "blocks/attn/wq": (n, d, h, hd), "blocks/attn/wk": (n, d, kv, hd),
        "blocks/attn/wv": (n, d, kv, hd), "blocks/attn/wo": (n, h, hd, d),
        "blocks/mlp/w_up": (n, d, f), "blocks/mlp/w_gate": (n, d, f),
        "blocks/mlp/w_down": (n, f, d),
    }


def _fan_in(path: str, shape) -> int:
    if path.endswith("/wo"):
        return shape[1] * shape[2]
    return shape[-2] if len(shape) >= 2 and path != "unembed" else shape[0]


def init_leaf(key, path: str, shape):
    """The benchmark's initialisation of one leaf: norm scales 1, the
    embedding N(0, 0.02²), every matrix N(0, 1/fan_in)."""
    if path.endswith(("ln1", "ln2", "final_norm")):
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    std = 0.02 if path == "embed" else 1.0 / math.sqrt(_fan_in(path, shape))
    return jax.random.normal(k, shape, jnp.float32) * std


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = x
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def init_lm(m: dict, seed: int, *, out_shardings=None):
    """All parameters, nested as the program takes them, in one jitted call
    on the device."""
    shapes = lm_shapes(m)

    def make(key):
        return nest({p: init_leaf(key, p, s) for p, s in shapes.items()})

    return jax.jit(make, out_shardings=out_shardings)(seed_key(seed))


def _scaled_round(a, dtype):
    """``a`` rounded to ``dtype`` under a per-tensor power-of-two scale that
    puts its largest magnitude near the top of the type's range, as
    low-precision training scales its tensors (no overflow to NaN, no flush
    of small tensors to zero); returned in float32."""
    _, top = jnp.frexp(jnp.float32(jnp.finfo(dtype).max))
    _, e = jnp.frexp(jnp.max(jnp.abs(a)))
    scale = jnp.ldexp(jnp.float32(1), jnp.clip(top - e - 1, -100, 100))
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_both(a, compute):
    return _scaled_round(a, compute)


def _round_both_fwd(a, compute):
    return _scaled_round(a, compute), None


def _round_both_bwd(compute, _, g):
    return (_scaled_round(g, compute),)


_round_both.defvjp(_round_both_fwd, _round_both_bwd)


def _round(a, compute):
    """``a`` rounded to ``compute`` in the forward pass, and its cotangent
    rounded to ``compute`` in the backward pass: a matmul whose operands
    and output pass through here computes its forward and its backward
    products from ``compute`` operands, accumulating in float32."""
    if compute is None:
        return a
    return _round_both(a, compute)


def _mm(spec, a, b, compute):
    return _round(jnp.einsum(spec, _round(a, compute), _round(b, compute),
                             precision=HIGHEST), compute)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, rot: int, theta: float):
    """Interleaved rotary embedding of the first ``rot`` dims of each head;
    x (B, S, H, hd)."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, rot/2)
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)
    return jnp.concatenate([out.reshape(xr.shape), xp], axis=-1)


def forward(params, tokens, m: dict, compute=None):
    """Logits (B, S, V) of tokens (B, S)."""
    eps = m["layer_norm_eps"]
    hd = m["hidden_size"] // m["num_attention_heads"]
    rot = int(hd * m["partial_rotary_factor"])
    rot -= rot % 2
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    c = functools.partial(_round, compute=compute)
    x = c(params["embed"][tokens])
    b = params["blocks"]
    for i in range(m["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], b)
        h = c(_rms(x, lp["ln1"], eps))
        q = _mm("bsd,dhk->bshk", h, lp["attn"]["wq"], compute)
        k = _mm("bsd,dhk->bshk", h, lp["attn"]["wk"], compute)
        v = _mm("bsd,dhk->bshk", h, lp["attn"]["wv"], compute)
        q = c(_rope(q, rot, m["rope_theta"]))
        k = c(_rope(k, rot, m["rope_theta"]))
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        logits = _mm("bshk,bthk->bhst", q, k, compute) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        o = _mm("bhst,bthk->bshk", p, v, compute)
        x = c(x + _mm("bshk,hkd->bsd", o, lp["attn"]["wo"], compute))
        h2 = c(_rms(x, lp["ln2"], eps))
        g = c(jax.nn.silu(_mm("bsd,df->bsf", h2, lp["mlp"]["w_gate"],
                              compute)))
        u = _mm("bsd,df->bsf", h2, lp["mlp"]["w_up"], compute)
        x = c(x + _mm("bsf,fd->bsd", c(g * u), lp["mlp"]["w_down"], compute))
    x = c(_rms(x, params["final_norm"], eps))
    return _mm("bsd,dv->bsv", x, params["unembed"], compute)


def loss(params, tokens, m: dict, compute=None):
    """Mean next-token cross-entropy of tokens (B, S + 1)."""
    logits = forward(params, tokens[:, :-1], m, compute)
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - hit)


# ---------------------------------------------------------- the optimizer
def lr_at(step: int, opt: dict) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * prog)))


def decays(shape, opt: dict) -> bool:
    return len(shape) >= 2 and min(shape[-2:]) >= opt["decay_min_dim"]


def adamw(p, g, mom, vel, lr, bc1, bc2, opt: dict):
    """One AdamW update of one leaf; ``g`` is the clipped gradient, ``bc1``
    and ``bc2`` the bias corrections 1 - β^step."""
    mom = opt["beta1"] * mom + (1 - opt["beta1"]) * g
    vel = opt["beta2"] * vel + (1 - opt["beta2"]) * g * g
    upd = (mom / bc1) / (jnp.sqrt(vel / bc2) + opt["eps"])
    if decays(p.shape, opt):
        upd = upd + opt["weight_decay"] * p
    return p - lr * upd, mom, vel


# --------------------------------------------------------- the projection
def project_l1(v, radius):
    """Euclidean projection of a non-negative vector onto the ℓ1 ball, by
    sorting (Duchi et al. 2008)."""
    if v.dtype != jnp.float32:
        radius = jnp.asarray(radius, v.dtype)
    s = jnp.sort(v)[::-1]
    c = jnp.cumsum(s)
    k = jnp.arange(1, v.shape[0] + 1, dtype=v.dtype)
    ok = s - (c - radius) / k > 0
    rho = jnp.max(jnp.where(ok, jnp.arange(v.shape[0]), 0))
    theta = (c[rho] - radius) / (rho + 1).astype(v.dtype)
    inside = jnp.sum(v) <= radius
    return jnp.where(inside, v, jnp.maximum(v - theta, 0))


def levels_ok(levels) -> bool:
    """The designs this reference computes: ℓ∞ over one leading axis at a
    time, then ℓ1 over what remains."""
    *inner, last = [tuple(lv) for lv in levels]
    return (all(q in ("inf", float("inf")) and k == 1 for q, k in inner)
            and last[0] in (1, "1"))


def multilevel_norm(y, levels):
    if not levels_ok(levels):
        raise ValueError(f"unsupported design {levels}")
    a = jnp.abs(y)
    for _ in levels[:-1]:
        a = jnp.max(a, axis=0)
    return jnp.sum(a)


def project(y, levels, radius, dtype=jnp.float32):
    """Multi-level ℓ1,∞(,∞) projection of y, computed in ``dtype``."""
    if not levels_ok(levels):
        raise ValueError(f"unsupported design {levels}")
    y = y.astype(dtype)
    aggs = [jnp.abs(y)]
    for _ in levels[:-1]:
        aggs.append(jnp.max(aggs[-1], axis=0))
    u = project_l1(aggs[-1].reshape(-1), radius).reshape(aggs[-1].shape)
    for agg in reversed(aggs[1:-1]):
        u = jnp.minimum(agg, u[None])
    return jnp.clip(y, -u[None], u[None])


def project_stored(y, levels, radius, lower):
    """The reference with its payload and its answer held in ``lower``."""
    y = y.astype(lower).astype(jnp.float32)
    return project(y, levels, radius).astype(lower)


def min_norm(params, names=("w_up", "w_gate")) -> float:
    """The smallest bi-level norm over the projected leaves' layer slices."""
    norms = []
    for path, w in flatten(params).items():
        if path.rsplit("/", 1)[-1] in names:
            norms.append(jax.jit(jax.vmap(
                lambda x: multilevel_norm(x, (("inf", 1), ("1", 1)))))(w))
    return float(jnp.min(jnp.concatenate(norms)))


def project_params(flat: dict, radius, names=("w_up", "w_gate")) -> dict:
    bi = (("inf", 1), ("1", 1))
    return {p: jax.vmap(lambda x: project(x, bi, radius))(w)
            if p.rsplit("/", 1)[-1] in names else w for p, w in flat.items()}


# ------------------------------------------------------------ train steps
def leaf_norms(flat: dict) -> dict:
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat.items()}


def train_reference(m: dict, opt: dict, seed: int, batches, radius: float,
                    *, compute=None, rows=None) -> dict:
    """The reference run of ``len(batches)`` projected AdamW steps from the
    benchmark's initialisation. Returns each step's loss, each leaf's norm of
    the first (clipped) gradient, and each leaf's norm of the change of the
    parameters over all the steps. ``rows`` keeps only that many rows of
    each batch (a fault: part of the batch left out)."""
    flat0 = flatten(init_lm(m, seed))
    names = list(flat0)

    @jax.jit
    def grad_step(flat, tokens):
        l, g = jax.value_and_grad(
            lambda f: loss(nest(f), tokens, m, compute))(flat)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
        return l, g, clip

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(flat, g, mom, vel, lr, bc1, bc2, clip, radius):
        new = {}
        for p in names:
            new[p], mom[p], vel[p] = adamw(flat[p], g[p] * clip, mom[p],
                                           vel[p], lr, bc1, bc2, opt)
        return project_params(new, radius), mom, vel

    flat = {p: x for p, x in flat0.items()}
    del flat0
    mom = {p: jnp.zeros_like(x) for p, x in flat.items()}
    vel = {p: jnp.zeros_like(x) for p, x in flat.items()}
    losses, first = [], None
    for i, tokens in enumerate(batches, start=1):
        tokens = jnp.asarray(tokens)
        if rows is not None:
            tokens = tokens[:rows]
        l, g, clip = grad_step(flat, tokens)
        losses.append(float(l))
        if first is None:
            first = {p: float(v) for p, v in
                     leaf_norms({p: x * clip for p, x in g.items()}).items()}
        flat, mom, vel = update(flat, g, mom, vel, lr_at(i, opt),
                                1 - opt["beta1"] ** i, 1 - opt["beta2"] ** i,
                                clip, radius)
        del g
    del mom, vel
    init = flatten(init_lm(m, seed))
    delta = {p: float(jnp.sqrt(jnp.sum(jnp.square(flat[p] - init[p]))))
             for p in names}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}


# ------------------------------------------------------------ comparisons
def gap(prog: dict, ref: dict, floor_share: float = 1e-3) -> tuple:
    """Worst leaf's gap between two norms, over the larger of the
    reference's norm of that leaf and the median leaf's. Leaves whose
    reference norm is under ``floor_share`` × the median are left out: they
    move by round-off alone. Returns (gap, leaf)."""
    med = float(np.median(list(ref.values())))
    worst, leaf = 0.0, None
    for p, r in ref.items():
        if r < floor_share * med:
            continue
        g = abs(prog[p] - r) / max(r, med)
        if g > worst or leaf is None:
            worst, leaf = g, p
    return worst, leaf


def rel_err(x, ref) -> float:
    """max |x - ref| over max |ref|, in float32."""
    x = jnp.asarray(x, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(x - ref))
                 / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))
