"""Pallas flash-attention kernel vs the pure-jnp oracle (interpret mode).

Sweeps shapes, dtypes, causality, GQA ratios and block sizes; checks both
the forward and the recompute backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa_op
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ops import flash_attention
from repro.kernels.ref import attention_ref, attention_with_lse_ref


def _mk(B, H, KV, Sq, Sk, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, KV, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, KV, Sk, D), dtype)
    return q, k, v


SHAPES = [
    # B, H, KV, Sq,  Sk,  D
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 256, 256, 64),   # GQA 2:1
    (1, 8, 1, 128, 128, 32),   # MQA
    (1, 2, 2, 128, 256, 64),   # decode-style Sk > Sq
    (2, 2, 2, 64, 64, 128),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_oracle(shape, causal):
    B, H, KV, Sq, Sk, D = shape
    q, k, v = _mk(B, H, KV, Sq, Sk, D, jnp.float32)
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
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


@pytest.mark.parametrize("KV", [1, 2, 4])
def test_backward_recompute_matches_autodiff(KV):
    B, H, Sq, D = 1, 4, 128, 32
    q, k, v = _mk(B, H, KV, Sq, Sq, D, jnp.float32, seed=3)
    do = jax.random.normal(jax.random.PRNGKey(9), (B, Sq, H, D))

    def loss_kernel(q_, k_, v_):
        out = flash_attention(
            q_.transpose(0, 2, 1, 3),
            k_.transpose(0, 2, 1, 3),
            v_.transpose(0, 2, 1, 3),
            interpret=True,
            block_q=64,
            block_k=64,
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
