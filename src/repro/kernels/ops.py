"""Jit-ready wrapper: ``flash_attention`` with a custom VJP whose backward
*recomputes* the attention probabilities (kernels/flash_attention.py).

Interface matches the model layout (B, S, H, D) / (B, S, KV, D); the kernel
layout transpose is fused by XLA.  ``interpret=None`` auto-selects: compiled
on TPU, interpret elsewhere (this container is CPU-only, so tests and
examples run the very same kernel body in interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import flash_attention as fa
from .ref import expand_kv


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = fa.flash_attention_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = fa.flash_attention_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    # residuals: q, k, v, out, lse — NOT the (Sq, Sk) probabilities
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    H, KV = q.shape[1], k.shape[1]
    kf = expand_kv(k, H)
    vf = expand_kv(v, H)
    dq, dk_full, dv_full = fa.flash_attention_bwd(
        q, kf, vf, out, lse, do, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    if KV != H:  # GQA: fold the head group back onto its kv head
        B, _, Sk, D = dk_full.shape
        dk = dk_full.reshape(B, KV, H // KV, Sk, D).sum(axis=2).astype(k.dtype)
        dv = dv_full.reshape(B, KV, H // KV, Sk, D).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_full.astype(k.dtype), dv_full.astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Differentiable flash attention in model layout (B, S, H, D).  Tiles
    left as None are chosen from the shape, per kernel."""
    if interpret is None:
        interpret = _auto_interpret()
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = _flash(qh, kh, vh, causal, block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)
