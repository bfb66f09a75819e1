"""The plain reference: a dense decoder-only transformer and three AdamW
steps, in float32 ``jax.numpy`` at the highest matmul precision.

It imports nothing of the program.  It follows the configuration file
(RMSNorm, rotary on the whole head in the half-split convention, SwiGLU,
grouped-query attention, tied or separate vocabulary projection) and the
optimizer the traffic file states.  It runs one sequence at a time (a
``lax.map`` whose body is rematerialised), one layer at a time (a
checkpointed ``lax.scan``) and the vocabulary projection in blocks of rows,
so that it fits beside nothing else on one chip.

``matmul`` is the one place the precision lives.  :func:`fp8_matmul` is the
control: the same reference with every matmul operand rounded to float8
(e4m3, one scale per tensor), in the forward and in both backward products.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weights import change_norms, leaf_norms, reference_tree

HIGHEST = jax.lax.Precision.HIGHEST


def f32_matmul(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor (amax to 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_matmul(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    # fp8 values are exact in bfloat16, so one bf16 pass loses nothing more
    return jnp.einsum(eq, _fp8(a), _fp8(b), preferred_element_type=jnp.float32)


def _fp8_fwd(eq, a, b):
    return fp8_matmul(eq, a, b), (a, b)


def _fp8_bwd(eq, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, preferred_element_type=jnp.float32),
                     _fp8(a), _fp8(b))
    return vjp(_fp8(g))


fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)

MATMULS = {"float32": f32_matmul, "fp8": fp8_matmul}


# ------------------------------------------------------------------ model


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, heads, D): rotate the two halves of each head by position."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(m: Dict, mm: Callable, h: jax.Array, p: Dict) -> jax.Array:
    """One decoder block on one sequence, h (S, d)."""
    S = h.shape[0]
    H, KV, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    x = rmsnorm(h, p["ln1"], eps)
    q = rope(mm("sd,dh->sh", x, p["wq"]).reshape(S, H, D), theta)
    k = rope(mm("sd,dh->sh", x, p["wk"]).reshape(S, KV, D), theta)
    v = mm("sd,dh->sh", x, p["wv"]).reshape(S, KV, D)
    q = q.reshape(S, KV, H // KV, D)  # query head kv*G + g reads kv head kv
    s = mm("qkgd,tkd->kgqt", q, k) / math.sqrt(D)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = mm("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(S, H * D)
    h = h + mm("sz,zd->sd", a, p["wo"])
    x = rmsnorm(h, p["ln2"], eps)
    u = jax.nn.silu(mm("sd,df->sf", x, p["w_gate"])) * mm("sd,df->sf", x, p["w_up"])
    return h + mm("sf,fd->sd", u, p["w_down"])


def sequence_loss(m: Dict, mm: Callable, params: Dict, tokens: jax.Array,
                  rows: int = 512) -> jax.Array:
    """Mean next-token cross-entropy of one sequence, tokens (S,)."""
    h = params["embed"][tokens]
    body = jax.checkpoint(lambda h, p: (block(m, mm, h, p), None))
    h, _ = jax.lax.scan(body, h, params["layers"])
    h = rmsnorm(h, params["final_norm"], m["rms_norm_eps"])
    out = params.get("head", params["embed"])
    S = tokens.shape[0]
    rows = min(rows, S)
    gold = jnp.concatenate([tokens[1:], tokens[:1]])  # the last row is dropped

    @jax.checkpoint
    def chunk(args):
        hc, gc = args
        logits = mm("sd,vd->sv", hc, out)
        pick = jnp.take_along_axis(logits, gc[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - pick

    nll = jax.lax.map(chunk, (h.reshape(S // rows, rows, -1), gold.reshape(S // rows, rows)))
    return jnp.mean(nll.reshape(S)[: S - 1])


def batch_loss(m: Dict, mm: Callable, params: Dict, tokens: jax.Array) -> jax.Array:
    """Mean over the rows of a (B, S) batch, one row at a time."""
    per = jax.lax.map(jax.checkpoint(lambda t: sequence_loss(m, mm, params, t)), tokens)
    return jnp.mean(per)


# -------------------------------------------------------------- optimizer


def lr_at(opt: Dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_frac`` of ``lr``."""
    warm = min(1.0, step / max(1, opt["warmup_steps"]))
    prog = min(1.0, max(0.0, (step - opt["warmup_steps"])
                        / max(1, opt["total_steps"] - opt["warmup_steps"])))
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def _adamw(opt: Dict, params, m1, m2, grads, lr, bc1, bc2):
    """AdamW with global-norm clipping.  Weight decay applies to every
    per-layer leaf (norm scales included, as the traffic file states) and to
    the global matrices, not to the final norm's scale."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]

    def one(p, a, b, g, matrix):
        g = g * clip
        a = b1 * a + (1 - b1) * g
        b = b2 * b + (1 - b2) * g * g
        delta = (a / bc1) / (jnp.sqrt(b / bc2) + opt["eps"])
        if matrix:
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, a, b

    out = {}
    for k in params:
        if k == "layers":
            out[k] = {n: one(params[k][n], m1[k][n], m2[k][n], grads[k][n], True)
                      for n in params[k]}
        else:
            out[k] = one(params[k], m1[k], m2[k], grads[k], params[k].ndim >= 2)
    pick = lambda i: {k: ({n: t[i] for n, t in v.items()} if k == "layers" else v[i])
                      for k, v in out.items()}
    return pick(0), pick(1), pick(2), clip


def run(m: Dict, opt: Dict, seed_key, batches: Sequence[np.ndarray],
        precision: str = "float32", rows: slice = slice(None), against=None,
        keep_first: bool = False) -> Dict:
    """Three AdamW steps from the seeded weights on ``batches[:3]``.

    Returns the loss of each step, the norms of the first (clipped)
    gradient and of the unclipped one, and the norms of the weights' change
    after the three steps, each per leaf and layer.  ``rows`` keeps a part
    of every batch (the half-batch fault reads it).  ``against``, a first
    gradient in this layout (host arrays), adds the norms of its difference
    from this run's first clipped gradient (``diff_norms``);
    ``keep_first`` returns this run's first clipped gradient on the host
    (``first_grad``), for another run to be held against.
    """
    mm = MATMULS[precision]
    loss_fn = lambda p, t: batch_loss(m, mm, p, t)

    def step(params, m1, m2, tokens, lr, bc1, bc2, against):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        params, m1, m2, clip = _adamw(opt, params, m1, m2, grads, lr, bc1, bc2)
        raw = leaf_norms(grads)
        clipped = jax.tree_util.tree_map(lambda g: g * clip, grads)
        diff = None if against is None else leaf_norms(
            jax.tree_util.tree_map(lambda g, a: g - a, clipped, against))
        return (loss, params, m1, m2, {k: n * clip for k, n in raw.items()}, raw, diff,
                clipped if keep_first else None)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    if against is not None:
        against = jax.device_put(against)
    init = jax.jit(lambda k: reference_tree(k, m))
    params = init(seed_key)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    m1, m2 = zeros(params), zeros(params)
    losses = []
    out = {}
    for i in range(3):
        tokens = jnp.asarray(batches[i]["tokens"][rows])
        n = i + 1
        first = i == 0
        loss, params, m1, m2, gn, raw, diff, kept = step(
            params, m1, m2, tokens, jnp.float32(lr_at(opt, n)),
            jnp.float32(1 - opt["b1"] ** n), jnp.float32(1 - opt["b2"] ** n), against)
        losses.append(float(loss))
        if first:
            out.update(grad_norms=gn, raw_grad_norms=raw)
            if diff is not None:
                out["diff_norms"] = diff
            if kept is not None:
                out["first_grad"] = jax.device_get(kept)
            del kept
    del m1, m2
    out["change_norms"] = jax.jit(change_norms)(params, init(seed_key))
    to_np = lambda d: {k: np.asarray(v, np.float64) for k, v in d.items()}
    return {"losses": losses, **{k: v if k == "first_grad" else to_np(v) for k, v in out.items()}}
