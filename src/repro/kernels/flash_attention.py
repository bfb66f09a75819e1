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
accumulator lives across grid steps instead of in shared memory).  A grid
step has a fixed cost (pipeline bookkeeping, DMA issue and wait) on top of
its MXU work, so the tiles are as large as the shape allows: ``tile_sizes``
takes, per kernel, the largest tile of ``TILES`` up to that kernel's cap
that divides the sequence and whose VMEM working set (``vmem_bytes``: the
double-buffered operand tiles, the f32 accumulators and the f32 score-sized
temporaries) fits ``VMEM_BUDGET``; ``vmem_limit_bytes`` is raised to that
working set where it passes the default scoped limit.  Under a causal mask
a block wholly above the diagonal does no work, and its index map is
clamped to the nearest block that does (forward and dq: the last kv block
a query block sees; dk/dv: the first query block that sees a kv block), so
the skipped step names the tile already resident and no DMA is issued.
Only blocks that straddle the diagonal build the mask.

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
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked exp() exact 0
                 # without nan from (-inf) - (-inf)

TILES = (1024, 512, 256, 128)
#: largest (block_q, block_k) of each kernel: the fastest tiles of a sweep
#: over TILES² on TPU v5e at (B, H, S, D) = (2, 32, 2048, 80) and at
#: (1, 40, 2048, 128) with 8 kv heads
TILE_CAPS = {"fwd": (1024, 1024), "dq": (1024, 1024), "dkv": (512, 1024)}
VMEM_BUDGET = 32 * 2**20
SCOPED_VMEM_DEFAULT = 16 * 2**20  # Mosaic's scoped VMEM limit unless raised


class Tiles(NamedTuple):
    block_q: int
    block_k: int
    vmem_bytes: int


def vmem_bytes(kind: str, block_q: int, block_k: int, head_dim: int,
               itemsize: int) -> int:
    """VMEM working set of one grid step of kernel ``kind`` ("fwd", "dq" or
    "dkv"): the operand and result tiles, double-buffered; the f32
    accumulators; and the f32 (block_q, block_k) score, probability, dp and
    ds tiles with the casts that feed the MXU."""
    dp = -(-head_dim // 128) * 128  # lanes: D pads to a multiple of 128
    col = 128 * 4  # one row of an f32 (b, 1) column, padded to 128 lanes
    score = block_q * block_k
    if kind == "fwd":  # q, k, v, out; lse | acc; m, l | s, p; p cast
        io = (2 * block_q + 2 * block_k) * dp * itemsize + block_q * col
        acc = block_q * dp * 4 + 2 * block_q * col
        temps = 2 * score * 4 + score * itemsize
    elif kind == "dq":  # q, do, k, v, dq; lse, delta | dq | s, p, dp, ds; ds cast
        io = (3 * block_q + 2 * block_k) * dp * itemsize + 2 * block_q * col
        acc = block_q * dp * 4
        temps = 4 * score * 4 + score * itemsize
    elif kind == "dkv":  # q, do, k, v, dk, dv; lse, delta | dk, dv | s, p, dp, ds; 2 casts
        io = (2 * block_q + 4 * block_k) * dp * itemsize + 2 * block_q * col
        acc = 2 * block_k * dp * 4
        temps = 4 * score * 4 + 2 * score * itemsize
    else:
        raise ValueError(kind)
    return 2 * io + acc + temps


def _largest_tile(seq: int, cap: int, fits) -> int:
    for t in TILES:
        if t <= cap and seq % t == 0 and fits(t):
            return t
    return TILES[-1] if seq % TILES[-1] == 0 else seq


def tile_sizes(kind: str, seq_q: int, seq_k: int, head_dim: int,
               itemsize: int) -> Tiles:
    """Tiles of kernel ``kind`` for this shape.  Each side takes the largest
    of ``TILES`` up to the kernel's cap that divides its sequence and keeps
    the working set within ``VMEM_BUDGET``; a sequence that no tile divides
    is one block (Mosaic accepts a block equal to the array's dim)."""
    cap_q, cap_k = TILE_CAPS[kind]
    ws = lambda bq, bk: vmem_bytes(kind, bq, bk, head_dim, itemsize)
    bq = _largest_tile(seq_q, cap_q, lambda t: ws(t, TILES[-1]) <= VMEM_BUDGET)
    bk = _largest_tile(seq_k, cap_k, lambda t: ws(bq, t) <= VMEM_BUDGET)
    return Tiles(bq, bk, ws(bq, bk))


def _blocks(kind: str, block_q: Optional[int], block_k: Optional[int],
            seq_q: int, seq_k: int, head_dim: int, itemsize: int) -> Tiles:
    """The tiles a call runs with: chosen from the shape where not given."""
    chosen = tile_sizes(kind, seq_q, seq_k, head_dim, itemsize)
    if block_q is None and block_k is None:
        return chosen
    bq = min(block_q or chosen.block_q, seq_q)
    bk = min(block_k or chosen.block_k, seq_k)
    assert seq_q % bq == 0 and seq_k % bk == 0, (seq_q, bq, seq_k, bk)
    return Tiles(bq, bk, vmem_bytes(kind, bq, bk, head_dim, itemsize))


def _compiler_params(tiles: Tiles):
    if tiles.vmem_bytes <= SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=tiles.vmem_bytes)


# ---------------------------------------------------------------------------
# Causal block structure
# ---------------------------------------------------------------------------
# Query row r sees keys ≤ r + off, off = Sk − Sq (decode-style alignment).


def _causal_block(iq, ik, block_q, block_k, off):
    """(run, whole) of block (iq, ik): some key of it is visible to some row,
    and every key to every row (no mask needed)."""
    run = ik * block_k <= iq * block_q + block_q - 1 + off
    whole = ik * block_k + block_k - 1 <= iq * block_q + off
    return run, whole


def _last_kv_block(iq, ik, block_q, block_k, off):
    """kv block of step (iq, ik), clamped to the last one query block iq
    needs: the steps past it re-name the resident tile."""
    last = jax.lax.div(jnp.maximum(iq * block_q + (block_q - 1 + off), 0), block_k)
    return jnp.minimum(ik, last)


def _first_q_block(ik, iq, block_q, block_k, off, nq):
    """Query block of step (ik, iq), clamped to the first one that sees kv
    block ik: the steps before it name the tile fetched for it."""
    first = jax.lax.div(jnp.maximum(ik * block_k - off, 0), block_q)
    return jnp.minimum(jnp.maximum(iq, first), nq - 1)


def _when_causal(causal, iq, ik, block_q, block_k, off, body):
    """Runs ``body(masked)`` on the blocks that hold visible keys: with the
    mask only where the block straddles the diagonal."""
    if not causal:
        body(False)
        return
    run, whole = _causal_block(iq, ik, block_q, block_k, off)
    pl.when(run & jnp.logical_not(whole))(lambda: body(True))
    pl.when(whole)(lambda: body(False))


def _scores(q, k, iq, ik, block_q, block_k, off, sm_scale, masked):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (bq, bk)
    if masked:
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos + off >= kpos, s, NEG_INF)
    return s


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
    off: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute(masked):
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], iq, ik, block_q, block_k, off,
                    sm_scale, masked)
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

    _when_causal(causal, iq, ik, block_q, block_k, off, _compute)

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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,H,Sq,D), lse (B,H,Sq)).  Tiles not given are chosen
    from the shape (``tile_sizes``)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    group = H // KV
    tiles = _blocks("fwd", block_q, block_k, Sq, Sk, D, q.dtype.itemsize)
    block_q, block_k = tiles.block_q, tiles.block_k
    nq, nk = Sq // block_q, Sk // block_k
    off = Sk - Sq

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        sm_scale=1.0 / math.sqrt(D),
        block_q=block_q,
        block_k=block_k,
        off=off,
    )

    def kv_map(b, h, iq, ik):
        if causal:
            ik = _last_kv_block(iq, ik, block_q, block_k, off)
        return b, h // group, ik, 0

    q_map = lambda b, h, iq, ik: (b, h, iq, 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_q, 1), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((block_q, D), jnp.float32),
            _VMEM((block_q, 1), jnp.float32),
            _VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(tiles),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward — recompute probabilities blockwise from (q, k, lse)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, causal, sm_scale, block_q, block_k, off
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _compute(masked):
        k = k_ref[0, 0]
        s = _scores(q_ref[0, 0], k, iq, ik, block_q, block_k, off, sm_scale,
                    masked)
        p = jnp.exp(s - lse_ref[0, 0])  # recomputed probabilities
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        ds = p * (dp - delta_ref[0, 0]) * sm_scale  # delta: rowsum(do * o)
        dq_acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_causal(causal, iq, ik, block_q, block_k, off, _compute)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, causal, sm_scale, block_q, block_k, off
):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _compute(masked):
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        s = _scores(q, k_ref[0, 0], iq, ik, block_q, block_k, off, sm_scale,
                    masked)
        p = jnp.exp(s - lse_ref[0, 0])  # (bq, bk) recomputed
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ · do  (bk, D)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0]) * sm_scale  # (bq, bk)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ · q  (bk, D)

    _when_causal(causal, iq, ik, block_q, block_k, off, _compute)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_dq(q, k, v, do, lse, delta, causal, tiles, interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q, block_k = tiles.block_q, tiles.block_k
    off = Sk - Sq

    def kv_map(b, h, iq, ik):
        if causal:
            ik = _last_kv_block(iq, ik, block_q, block_k, off)
        return b, h, ik, 0

    q_map = lambda b, h, iq, ik: (b, h, iq, 0)
    q_spec = pl.BlockSpec((1, 1, block_q, D), q_map)
    k_spec = pl.BlockSpec((1, 1, block_k, D), kv_map)
    r_spec = pl.BlockSpec((1, 1, block_q, 1), q_map)
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, sm_scale=1.0 / math.sqrt(D),
            block_q=block_q, block_k=block_k, off=off,
        ),
        grid=(B, H, Sq // block_q, Sk // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype)],
        scratch_shapes=[_VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(tiles),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)[0]


def _bwd_dkv(q, k, v, do, lse, delta, causal, tiles, interpret):
    """dk/dv: the kv block is the carried tile; q blocks iterate innermost."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q, block_k = tiles.block_q, tiles.block_k
    nq = Sq // block_q
    off = Sk - Sq

    def q_map(b, h, ik, iq):
        if causal:
            iq = _first_q_block(ik, iq, block_q, block_k, off, nq)
        return b, h, iq, 0

    k_map = lambda b, h, ik, iq: (b, h, ik, 0)
    q_spec = pl.BlockSpec((1, 1, block_q, D), q_map)
    k_spec = pl.BlockSpec((1, 1, block_k, D), k_map)
    r_spec = pl.BlockSpec((1, 1, block_q, 1), q_map)
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, sm_scale=1.0 / math.sqrt(D),
            block_q=block_q, block_k=block_k, off=off,
        ),
        grid=(B, H, Sk // block_k, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            _VMEM((block_k, D), jnp.float32),
            _VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_compiler_params(tiles),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)


def flash_attention_bwd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, H, Sk, D)  — pre-expanded to full heads
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,  # (B, H, Sq)
    do: jax.Array,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(dq, dk, dv).  Tiles not given are chosen per kernel from the shape."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    assert k.shape[1] == H, "backward expects kv expanded to full heads"
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # (B, H, Sq, 1)
    lse = lse[..., None]  # (B, H, Sq, 1): see the module note on row layouts
    blocks = functools.partial(
        _blocks, block_q=block_q, block_k=block_k, seq_q=Sq, seq_k=Sk,
        head_dim=D, itemsize=q.dtype.itemsize,
    )
    dq = _bwd_dq(q, k, v, do, lse, delta, causal, blocks("dq"), interpret)
    dk, dv = _bwd_dkv(q, k, v, do, lse, delta, causal, blocks("dkv"), interpret)
    return dq, dk, dv
