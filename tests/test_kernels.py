"""Pallas flash-attention kernel vs the pure-jnp oracle (interpret mode).

Sweeps shapes, dtypes, causality, GQA ratios and block sizes; checks both
the forward and the recompute backward.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ops import flash_attention
from repro.kernels.ref import attention_ref, attention_with_lse_ref

# the module, which the package's ``flash_attention`` function shadows
fa_op = importlib.import_module("repro.kernels.flash_attention")


def _mk(B, H, KV, Sq, Sk, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, KV, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, KV, Sk, D), dtype)
    return q, k, v


SHAPES = [
    # B, H, KV, Sq,  Sk,  D, block (None: tiles chosen from the shape)
    (1, 2, 2, 128, 128, 64, 64),
    (2, 4, 2, 256, 256, 64, 64),   # GQA 2:1
    (1, 8, 1, 128, 128, 32, 64),   # MQA
    (1, 2, 2, 128, 256, 64, 64),   # decode-style Sk > Sq
    (2, 2, 2, 64, 64, 128, 64),
    (1, 2, 2, 384, 384, 64, None),  # only 128 divides: 3 x 3 blocks
    (1, 2, 2, 640, 640, 64, None),  # 5 x 5 blocks of 128
    (1, 2, 2, 1024, 1024, 64, None),  # 512 tiles, 2 x 2 blocks
    (1, 4, 1, 1536, 1536, 80, None),  # GQA 4:1 at the cells' D, 512 tiles
    (1, 2, 2, 512, 1536, 64, None),  # Sq < Sk: offset 1024, 1 x 3 blocks
    (1, 4, 2, 256, 640, 64, None),  # Sq < Sk: offset 384, bq 256, bk 128
    (1, 2, 2, 384, 640, 64, 128),  # offset 256 across 3 x 5 blocks
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_oracle(shape, causal):
    B, H, KV, Sq, Sk, D, block = shape
    q, k, v = _mk(B, H, KV, Sq, Sk, D, jnp.float32)
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    oref, lref = attention_with_lse_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, oref, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(lse, lref, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    q, k, v = _mk(1, 4, 4, 128, 128, 64, dtype)
    out, _ = flash_attention_fwd(q, k, v, interpret=True)
    oref = attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        out.astype(jnp.float32), oref.astype(jnp.float32), rtol=tol, atol=tol
    )
    assert out.dtype == dtype


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64)])
def test_block_shape_invariance(bq, bk):
    """Output must not depend on the BlockSpec tiling."""
    q, k, v = _mk(1, 2, 2, 128, 128, 64, jnp.float32)
    out, lse = flash_attention_fwd(
        q, k, v, block_q=bq, block_k=bk, interpret=True
    )
    ref, lref = attention_with_lse_ref(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(lse, lref, rtol=1e-5, atol=2e-5)


BWD_CASES = [
    # KV, Sq, Sk, D, block (None: tiles chosen per kernel from the shape)
    pytest.param(1, 128, 128, 32, 64, id="1"),
    pytest.param(2, 128, 128, 32, 64, id="2"),
    pytest.param(4, 128, 128, 32, 64, id="4"),
    pytest.param(4, 384, 384, 32, None, id="4-s384-tile128"),
    pytest.param(1, 1024, 1024, 32, None, id="1-s1024-tile512"),
    pytest.param(1, 640, 640, 80, None, id="1-s640-d80"),
    pytest.param(2, 256, 640, 32, None, id="2-sq256-sk640"),
    pytest.param(4, 512, 1536, 32, None, id="4-sq512-sk1536"),
]


@pytest.mark.parametrize("KV,Sq,Sk,D,block", BWD_CASES)
def test_backward_recompute_matches_autodiff(KV, Sq, Sk, D, block):
    B, H = 1, 4
    q, k, v = _mk(B, H, KV, Sq, Sk, D, jnp.float32, seed=3)
    do = jax.random.normal(jax.random.PRNGKey(9), (B, Sq, H, D))

    def loss_kernel(q_, k_, v_):
        out = flash_attention(
            q_.transpose(0, 2, 1, 3),
            k_.transpose(0, 2, 1, 3),
            v_.transpose(0, 2, 1, 3),
            interpret=True,
            block_q=block,
            block_k=block,
        )
        return jnp.sum(out.transpose(0, 2, 1, 3) * do.transpose(0, 2, 1, 3))

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_ref(q_, k_, v_) * do.transpose(0, 2, 1, 3))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def test_no_score_matrix_in_residuals():
    """The whole point: residuals must be O(S), not O(S²) — inspect the VJP
    jaxpr for any (Sq, Sk) f32 intermediate crossing the fwd/bwd boundary."""
    S = 256
    q, k, v = _mk(1, 2, 2, S, S, 32, jnp.float32)

    def f(q_, k_, v_):
        return jnp.sum(
            flash_attention(
                q_.transpose(0, 2, 1, 3),
                k_.transpose(0, 2, 1, 3),
                v_.transpose(0, 2, 1, 3),
                interpret=True,
            )
        )

    # residuals of the custom_vjp: q, k, v, out, lse — all O(S·D) or O(S)
    out, vjp = jax.vjp(f, q, k, v)
    # vjp closure leaves: no (S, S)-shaped arrays
    leaves = jax.tree_util.tree_leaves(vjp)
    for leaf in leaves:
        if hasattr(leaf, "shape") and len(leaf.shape) >= 2:
            assert not (
                leaf.shape[-1] == S and leaf.shape[-2] == S
            ), f"O(S²) residual cached: {leaf.shape}"


def test_fully_masked_rows_are_zero():
    """Non-square causal with Sq > Sk never occurs, but padded/masked rows
    (first rows with off<0 alignment) must not produce NaNs."""
    q, k, v = _mk(1, 2, 2, 128, 128, 64, jnp.float32)
    out, _ = flash_attention_fwd(q, k, v, causal=True, interpret=True)
    assert not bool(jnp.any(jnp.isnan(out)))


def test_kernel_attention_under_mesh_matches_dense():
    """Under a mesh the kernel runs per shard (shard_map): GSPMD cannot
    partition a Mosaic kernel.  Same result as the XLA attention."""
    from repro.launch.mesh import auto_mesh
    from repro.models.attention import dense_attention, kernel_attention

    B, S, H, KV, D = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    with jax.sharding.set_mesh(auto_mesh((1, 1), ("data", "model"))):
        got = jax.jit(kernel_attention)(q, k, v)
    np.testing.assert_allclose(got, dense_attention(q, k, v), rtol=1e-5,
                               atol=2e-5)


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
def test_tile_rule_divides_and_fits(kind):
    """Every tile the rule picks divides its sequence; where 128 divides
    the sequence the tile is one of ``TILES`` and its working set is within
    the budget the rule reports against."""
    for S in (64, 128, 200, 384, 640, 1024, 1536, 2048, 4096, 32768):
        for Sk_extra in (0, 128, 1024):
            Sk = S + Sk_extra
            for D in (32, 64, 80, 128, 256):
                for itemsize in (2, 4):
                    t = fa_op.tile_sizes(kind, S, Sk, D, itemsize)
                    assert S % t.block_q == 0 and Sk % t.block_k == 0, (S, Sk, t)
                    assert t.vmem_bytes == fa_op.vmem_bytes(
                        kind, t.block_q, t.block_k, D, itemsize)
                    if S % 128 == 0 and Sk % 128 == 0:
                        assert t.block_q in fa_op.TILES and t.block_k in fa_op.TILES
                        assert t.vmem_bytes <= fa_op.VMEM_BUDGET, (S, Sk, D, t)


def _computing_share(Sq, Sk, bq, bk):
    off = Sk - Sq
    runs = [fa_op._causal_block(iq, ik, bq, bk, off)[0]
            for iq in range(Sq // bq) for ik in range(Sk // bk)]
    return sum(runs), len(runs)


def test_tiles_at_the_benchmark_shape():
    """(S=2048, D=80) in bfloat16: 1024 x 1024 tiles for the forward and dq
    (3 of 4 grid steps per (b, h) compute), 512 x 1024 for dk/dv (6 of 8);
    with 128 x 128 tiles 136 of 256 computed."""
    tiles = {kind: fa_op.tile_sizes(kind, 2048, 2048, 80, 2)[:2]
             for kind in ("fwd", "dq", "dkv")}
    assert tiles == {"fwd": (1024, 1024), "dq": (1024, 1024), "dkv": (512, 1024)}
    assert _computing_share(2048, 2048, 1024, 1024) == (3, 4)
    assert _computing_share(2048, 2048, 512, 1024) == (6, 8)
    assert _computing_share(2048, 2048, 128, 128) == (136, 256)


@pytest.mark.parametrize("Sq,Sk,bq,bk", [
    (512, 512, 128, 128), (512, 512, 128, 256), (512, 512, 256, 128),
    (256, 640, 128, 128), (384, 640, 128, 64), (128, 512, 64, 128),
])
def test_clamped_index_maps_name_the_computed_blocks(Sq, Sk, bq, bk):
    """Every step that computes fetches its own block; every step that is
    skipped names the block of the step next to it, so no DMA is issued."""
    off, nq, nk = Sk - Sq, Sq // bq, Sk // bk
    for iq in range(nq):
        kv = [int(fa_op._last_kv_block(iq, ik, bq, bk, off)) for ik in range(nk)]
        for ik in range(nk):
            run, whole = fa_op._causal_block(iq, ik, bq, bk, off)
            assert not whole or run
            if run:
                assert kv[ik] == ik
            elif ik > 0:
                assert kv[ik] == kv[ik - 1]
    for ik in range(nk):
        qs = [int(fa_op._first_q_block(ik, iq, bq, bk, off, nq)) for iq in range(nq)]
        for iq in range(nq):
            if fa_op._causal_block(iq, ik, bq, bk, off)[0]:
                assert qs[iq] == iq
            elif iq + 1 < nq:
                assert qs[iq] == qs[iq + 1]


@pytest.mark.parametrize("Sq,Sk,block", [(512, 512, 128), (256, 640, 128)])
def test_clamped_index_maps_match_unclamped(monkeypatch, Sq, Sk, block):
    """Clamping the causal index maps changes which tiles a skipped step
    names, never a result: forward and both backward kernels agree exactly
    with unclamped maps at multi-block sizes."""
    q, k, v = _mk(1, 4, 2, Sq, Sk, 64, jnp.float32, seed=4)
    do = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def run():
        out, lse = flash_attention_fwd(q, k, v, block_q=block, block_k=block,
                                       interpret=True)
        grads = fa_op.flash_attention_bwd(
            q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), out, lse,
            do, block_q=block, block_k=block, interpret=True)
        return (out, lse) + grads

    clamped = run()
    monkeypatch.setattr(fa_op, "_last_kv_block", lambda iq, ik, *a: ik)
    monkeypatch.setattr(fa_op, "_first_q_block", lambda ik, iq, *a: iq)
    for a, b in zip(clamped, run()):
        np.testing.assert_array_equal(a, b)
