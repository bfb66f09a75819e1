"""The reference against the program's model (``repro.models``) on the CPU
at tiny widths, both in float32: the same weights give the same loss and
the same gradients, tied and untied, and the weights' two layouts hold the
same numbers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import traffic
import weights
from conftest import TINY


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_the_program_in_float32(tied):
    from repro.models import build_model

    m = dict(TINY, tie_word_embeddings=tied)
    cfg = dataclasses.replace(harness.model_config(m), dtype="float32")
    model = build_model(cfg)
    key = weights.seed_key(2 ** 31 + 12345)
    params = weights.program_params_fn(m)(key)
    t = {"batch": 2, "seq_len": 64, "batches": 1,
         "generator": {"zipf_a": 1.2, "motif_len": 16, "num_motifs": 64, "motif_prob": 0.5}}
    batch = {k: jnp.asarray(v) for k, v in traffic.batches(t, m["vocab_size"], 7)[0].items()}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss)(params, batch)
        lr, gr = jax.value_and_grad(
            lambda p: reference.batch_loss(m, reference.f32_matmul, p, batch["tokens"]))(
            weights.reference_tree(key, m))
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    np_, nr = weights.leaf_norms(weights.from_program(gp)), weights.leaf_norms(gr)
    assert set(np_) == set(nr)
    for k in nr:
        np.testing.assert_allclose(np.asarray(np_[k]), np.asarray(nr[k]), rtol=1e-4, err_msg=k)


def test_layouts_hold_the_same_numbers():
    key = weights.seed_key(99)
    prog = weights.from_program(weights.program_params_fn(TINY)(key))
    ref = weights.reference_tree(key, TINY)
    for a, b in zip(jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    one = weights.layer_leaf(key, TINY, "wq", 2)
    np.testing.assert_array_equal(np.asarray(ref["layers"]["wq"][2]), np.asarray(one))


def test_seed_key_takes_seeds_beyond_32_bits():
    a, b = weights.seed_key(2 ** 31 + 5), weights.seed_key(2 ** 33 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        weights.seed_key(-1)


def test_traffic_is_fixed_by_the_seed():
    t = {"batch": 2, "seq_len": 64, "batches": 3,
         "generator": {"zipf_a": 1.2, "motif_len": 16, "num_motifs": 64, "motif_prob": 0.5}}
    a, b = traffic.batches(t, 256, 2 ** 32 + 1), traffic.batches(t, 256, 2 ** 32 + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    assert max(int(x["tokens"].max()) for x in a) < 256
