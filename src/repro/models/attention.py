"""Attention: GQA for train/prefill (dense or chunked memory-efficient) and
single-step decode against a KV cache.

Long sequences never materialize the (S, S) score matrix: ``chunked_attention``
scans over KV blocks with an online softmax (the XLA twin of the Pallas
flash kernel in repro.kernels — the kernel is the TPU hot path, this is the
portable lowering the dry-run compiles).  This is itself an instance of the
paper's theme: the score matrix is *recomputed* blockwise in the backward
pass instead of being cached.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import resolve, shard
from .layers import _init_normal, apply_rope


def attention_init(
    rng,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    qkv_bias: bool = False,
):
    rq, rk, rv, ro = jax.random.split(rng, 4)
    scale = d_model**-0.5
    p = {
        "wq": _init_normal(rq, (d_model, n_heads * d_head), scale),
        "wk": _init_normal(rk, (d_model, n_kv_heads * d_head), scale),
        "wv": _init_normal(rv, (d_model, n_kv_heads * d_head), scale),
        "wo": _init_normal(ro, (n_heads * d_head, d_model), (n_heads * d_head) ** -0.5),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * d_head,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * d_head,), jnp.float32)
    return p


def qkv_proj(p, x, n_heads, n_kv_heads, d_head, positions, rope_theta):
    B, S, _ = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, n_heads, d_head)
    k = k.reshape(B, S, n_kv_heads, d_head)
    v = v.reshape(B, S, n_kv_heads, d_head)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, D) → (B, S, H, D) by repeating each kv head H/KV times."""
    B, S, KV, D = k.shape
    rep = n_heads // KV
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True
) -> jax.Array:
    """Reference O(S²)-memory attention. q (B,S,H,D), k/v (B,S,KV,D)."""
    B, S, H, D = q.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Memory-efficient attention: online softmax over KV chunks.

    Never materializes more than (B, H, q_chunk, kv_chunk) scores.  Wrapped in
    jax.checkpoint at the call site so the backward recomputes blocks — the
    flash-attention recipe expressed in XLA.
    """
    B, S, H, D = q.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    Sk = k.shape[1]
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, Sk)
    # pad to chunk multiples (e.g. VLM prefix makes S = 32768 + 576); padded
    # KV rows sit beyond every real query position, so the causal mask
    # excludes them; padded Q rows are sliced off at the end.
    orig_S = S
    pad_q = (-S) % q_chunk
    pad_k = (-Sk) % kv_chunk
    if pad_q or pad_k:
        assert causal, "chunk padding requires causal masking"
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        S += pad_q
        Sk += pad_k
    nq, nk = S // q_chunk, Sk // kv_chunk

    qs = q.reshape(B, nq, q_chunk, H, D).transpose(1, 0, 3, 2, 4)  # (nq,B,H,qc,D)
    ks = k.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / math.sqrt(D)

    def per_q_chunk(qi, q_blk):
        # online softmax state: (acc, row_max, row_sum)
        acc0 = jnp.zeros((B, H, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), -jnp.inf, jnp.float32)
        s0 = jnp.zeros((B, H, q_chunk), jnp.float32)

        def body(carry, inputs):
            acc, m, s = carry
            ki, (k_blk, v_blk) = inputs
            scores = (
                jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk).astype(jnp.float32)
                * scale
            )
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
                mask = qpos[:, None] >= kpos[None, :]
                scores = jnp.where(mask, scores, -jnp.inf)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(scores - m_safe[..., None])
            p = jnp.where(jnp.isfinite(scores), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            s = s * alpha + p.sum(axis=-1)
            return (acc, m_new, s), None

        (acc, m, s), _ = jax.lax.scan(
            body, (acc0, m0, s0), (jnp.arange(nk), (ks, vs))
        )
        out = acc / jnp.maximum(s[..., None], 1e-30)
        return out  # (B,H,qc,D)

    outs = jax.lax.map(lambda t: per_q_chunk(t[0], t[1]), (jnp.arange(nq), qs))
    # (nq,B,H,qc,D) → (B, S, H, D)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, D)
    return out[:, :orig_S].astype(q.dtype)


def kernel_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True
) -> jax.Array:
    """The Pallas flash kernel, run shard by shard under the ambient mesh.

    GSPMD cannot partition a Mosaic kernel, so under a mesh the call goes
    through ``shard_map`` with batch and heads split by the same rules as
    the surrounding constraints.  When q and kv heads would not split alike
    (GQA kv heads that do not divide the model axis) heads stay whole, so
    each q head still finds its kv head on the same device.
    """
    from repro.kernels.ops import flash_attention

    kernel = functools.partial(flash_attention, causal=causal)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return kernel(q, k, v)
    q_spec = resolve(("batch", None, "heads", None), shape=q.shape)
    kv_spec = resolve(("batch", None, "kv_heads", None), shape=k.shape)
    if q_spec[2] != kv_spec[2]:
        q_spec = P(q_spec[0], None, None, None)
        kv_spec = P(kv_spec[0], None, None, None)
    # check_vma=False: pallas_call out_shapes carry no varying-axes info
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k, v)


def attention(
    p,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    rope_theta: float,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    chunked_threshold: int = 8192,
    backend: str = "auto",
) -> jax.Array:
    """Self-attention over a full sequence (train / prefill).

    backend: "auto" → Pallas flash kernel on TPU, XLA path elsewhere;
             "kernel" / "xla" force one side (tests compare the two).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = qkv_proj(p, x, n_heads, n_kv_heads, d_head, positions, rope_theta)
    use_kernel = backend == "kernel" or (
        backend == "auto" and jax.default_backend() == "tpu" and S % 128 == 0
    )
    if use_kernel:
        ctx = kernel_attention(q, k, v, causal=causal)
    elif S > chunked_threshold:
        ctx = jax.checkpoint(
            lambda q_, k_, v_: chunked_attention(q_, k_, v_, causal=causal)
        )(q, k, v)
    else:
        ctx = dense_attention(q, k, v, causal=causal)
    ctx = shard(ctx, "batch", None, "heads", None)
    out = jnp.einsum(
        "bsz,zd->bsd", ctx.reshape(B, S, n_heads * d_head), p["wo"].astype(x.dtype)
    )
    return shard(out, "batch", None, "model")


def decode_attention(
    p,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    position: jax.Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    rope_theta: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step.  x (B,1,d); cache_k/v (B,S,KV,D); position (B,).

    Returns (out (B,1,d), new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    dt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, 1, n_heads, d_head)
    k = k.reshape(B, 1, n_kv_heads, d_head)
    v = v.reshape(B, 1, n_kv_heads, d_head)
    if rope_theta:
        q = apply_rope(q, position[:, None], rope_theta)
        k = apply_rope(k, position[:, None], rope_theta)

    # in-place cache update at `position`
    def upd(cache, new):
        return jax.vmap(
            lambda c, n, pos: jax.lax.dynamic_update_slice_in_dim(c, n, pos, axis=0)
        )(cache, new, position)

    cache_k = upd(cache_k, k)
    cache_v = upd(cache_v, v)
    cache_k = shard(cache_k, "batch", "seq_sp", "kv_heads", None)
    cache_v = shard(cache_v, "batch", "seq_sp", "kv_heads", None)

    S = cache_k.shape[1]
    kf = _expand_kv(cache_k, n_heads)
    vf = _expand_kv(cache_v, n_heads)
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
        / math.sqrt(d_head)
    )
    valid = (jnp.arange(S)[None, :] <= position[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    out = jnp.einsum(
        "bsz,zd->bsd", ctx.reshape(B, 1, n_heads * d_head), p["wo"].astype(dt)
    )
    return out, cache_k, cache_v
