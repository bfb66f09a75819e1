"""Flash attention as Pallas TPU kernels (forward + recompute backward).

This is the kernel-level instance of the paper's idea: the (Sq, Sk) score
matrix is *never cached* — the forward keeps only the per-row logsumexp
(M_v of the boundary, in the paper's language), and the backward *recomputes*
the probabilities blockwise from q, k and that statistic.  Cache O(S) instead
of O(S²); recompute cost is one extra QKᵀ per backward block — exactly the
overhead-vs-memory trade the DP reasons about, hard-coded at the tile level.

TPU adaptation (DESIGN.md §3): tiles are BlockSpec-shaped for VMEM residency
with MXU-aligned (multiple-of-128) matmul dims; the kv loop is the innermost
*sequential* grid dimension carrying the online-softmax state in VMEM scratch
(TPU grids iterate sequentially per core, unlike CUDA thread blocks, so the
accumulator lives across grid steps instead of in shared memory).

Layouts: q (B, H, Sq, D);  k, v (B, KV, Sk, D) with KV | H (GQA: the kv-head
index map is h → h·KV/H).  Matmuls take the operands in their own dtype and
accumulate in f32.  Row statistics (lse, delta) cross HBM as (B, H, Sq, 1):
Mosaic wants the last two block dims divisible by (8, 128) or equal to the
array's, so a (1, 1, bq) block of a (B, H, Sq) array is refused, while a
(1, 1, bq, 1) block is accepted and reads back as a (bq, 1) column that
broadcasts across the score tile's lanes.  Inside the kernels every value
stays 2-D (Mosaic has no 1-D vector layout).

Validated in interpret mode against kernels.ref on CPU; compiled for v5e in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked exp() exact 0
                 # without nan from (-inf) - (-inf)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    o_ref,  # (1, 1, bq, D)
    lse_ref,  # (1, 1, bq, 1)
    acc_ref,  # scratch (bq, D) f32
    m_ref,  # scratch (bq, 1) f32
    l_ref,  # scratch (bq, 1) f32
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_k: int,
    seq_q: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal band
    # query rows of this block: [iq·bq, iq·bq + bq); keys: [ik·bk, ik·bk + bk)
    off = seq_k - seq_q  # decode-style alignment (query i sees keys ≤ i+off)
    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1 + off)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos + off >= kpos, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]  # (bq, 1)
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[...] + jnp.log(l_safe), NEG_INF)
        lse_ref[0, 0] = lse.astype(lse_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KV, Sk, D)
    v: jax.Array,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,H,Sq,D), lse (B,H,Sq))."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    sm_scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        seq_k=Sk,
        seq_q=Sq,
    )
    grid = (B, H, nq, nk)
    scratch = [
        _VMEM((block_q, D), jnp.float32),
        _VMEM((block_q, 1), jnp.float32),
        _VMEM((block_q, 1), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward — recompute probabilities blockwise from (q, k, lse)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, causal, sm_scale, block_q, block_k, seq_k, seq_q
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    off = seq_k - seq_q
    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1 + off)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]  # (bq, 1) rowsum(do * o)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos + off >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)  # recomputed probabilities
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, causal, sm_scale, block_q, block_k, seq_k, seq_q
):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    off = seq_k - seq_q
    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1 + off)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]  # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos + off >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk) recomputed
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ · do  (bk, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale  # (bq, bk)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ · q  (bk, D)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, H, Sk, D)  — pre-expanded to full heads
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,  # (B, H, Sq)
    do: jax.Array,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    assert k.shape[1] == H, "backward expects kv expanded to full heads"
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = Sq // block_q, Sk // block_k
    sm_scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # (B, H, Sq, 1)
    lse = lse[..., None]  # (B, H, Sq, 1): see the module note on row layouts

    kw = dict(
        causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        seq_k=Sk, seq_q=Sq,
    )

    q_spec_q = pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec_q = pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0))
    r_spec_q = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(B, H, nq, nk),
        in_specs=[q_spec_q, k_spec_q, k_spec_q, q_spec_q, r_spec_q, r_spec_q],
        out_specs=[q_spec_q],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype)],
        scratch_shapes=[_VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)[0]

    # dk/dv: kv block is the carried tile; q blocks iterate innermost
    q_spec_k = pl.BlockSpec((1, 1, block_q, D), lambda b, h, ik, iq: (b, h, iq, 0))
    k_spec_k = pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, iq: (b, h, ik, 0))
    r_spec_k = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(B, H, nk, nq),
        in_specs=[q_spec_k, k_spec_k, k_spec_k, q_spec_k, r_spec_k, r_spec_k],
        out_specs=[k_spec_k, k_spec_k],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            _VMEM((block_k, D), jnp.float32),
            _VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
