"""Launch-layer planning: the DP plan on the unit chain, its lowering to
scan segments, and the invariance of the loss/grads under any plan."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, reduced
from repro.configs.base import ShapeConfig
from repro.launch.plan import (
    SegmentPlan,
    chain_graph,
    plan_inputs,
    plan_unit_segments,
    plan_with_microbatching,
    segments_from_result,
)
from repro.models import build_model

RNG = jax.random.PRNGKey(0)


def test_plan_covers_all_units():
    for arch in ("stablelm-3b", "mistral-large-123b", "zamba2-2.7b", "xlstm-1.3b"):
        cfg = get_config(arch)
        sp, res = plan_with_microbatching(cfg, SHAPES["train_4k"], 16,
                                          model_shards=16)
        from repro.models.transformer import unit_pattern

        _, n_units = unit_pattern(cfg)
        assert sum(sp.sizes) == n_units
        assert len(sp.sizes) == len(sp.remat)
        assert res.feasible


def test_budget_monotone_in_microbatches():
    """More microbatches → smaller per-microbatch working set → feasibility."""
    cfg = get_config("mistral-large-123b")
    sp, res = plan_with_microbatching(cfg, SHAPES["train_4k"], 16, model_shards=16)
    assert res.feasible
    assert sp.n_micro >= 1


def test_ample_budget_means_no_remat():
    """With a huge budget the time-centric plan caches everything; only the
    chain's sink boundary node (never in any ∂(L), eq. 1) is recomputed."""
    cfg = get_config("stablelm-3b")
    sp, res = plan_unit_segments(
        cfg, SHAPES["train_4k"], 16, model_shards=16, budget=1e18
    )
    assert res.feasible and res.overhead <= 1.0  # ≤ one boundary T
    assert not any(sp.remat)


def test_tight_budget_means_remat():
    cfg = get_config("stablelm-3b")
    pi = plan_inputs(cfg, SHAPES["train_4k"], 16, model_shards=16)
    sp, res = plan_unit_segments(
        cfg, SHAPES["train_4k"], 16, model_shards=16,
        budget=pi.bytes_interior * 3.0,
    )
    if res.feasible:
        assert any(sp.remat)


def test_segments_from_result_roundtrip():
    """Sequence → (sizes, remat) is consistent with the chain structure."""
    cfg = get_config("phi4-mini-3.8b")
    pi = plan_inputs(cfg, SHAPES["train_4k"], 16, model_shards=16)
    g = chain_graph(pi)
    from repro.core import exact_dp, min_feasible_budget
    from repro.core.dp import quantize_times

    q = quantize_times(g, 32)
    B = min_feasible_budget(q, "exact_dp") * 1.5
    res = exact_dp(q, B)
    sizes, remat = segments_from_result(res, pi.n_units)
    assert sum(sizes) == pi.n_units
    assert all(s >= 1 for s in sizes)


@pytest.mark.parametrize(
    "plans",
    [
        [(None, None)],  # default √n
        [((2, 2, 2, 2), (True, True, True, True)),
         ((4, 4), (True, False)),
         ((1,) * 8, (False,) * 8),
         ((8,), (False,)),
         ((3, 3, 2), (True, False, True))],
    ],
)
def test_loss_invariant_under_any_plan(plans):
    """The paper's guarantee, end to end on the production model: every
    canonical strategy computes the SAME loss and gradients."""
    cfg = reduced(get_config("stablelm-3b"), n_layers=8)
    model = build_model(cfg)
    params = model.init(RNG)
    batch = {
        "tokens": jax.random.randint(RNG, (2, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(RNG, (2, 16), 0, cfg.vocab_size),
    }
    ref = None
    for sizes, remat in plans:
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, segment_sizes=sizes,
                                 segment_remat=remat)
        )(params)
        flat = jnp.concatenate(
            [g.astype(jnp.float32).ravel() for g in jax.tree_util.tree_leaves(grads)]
        )
        if ref is None:
            ref = (loss, flat)
        else:
            np.testing.assert_allclose(loss, ref[0], rtol=1e-5)
            np.testing.assert_allclose(flat, ref[1], rtol=1e-4, atol=1e-6)


def test_long_context_uses_seq_shards():
    cfg = get_config("zamba2-2.7b")
    pi_local = plan_inputs(cfg, SHAPES["long_500k"], dp_shards=1, seq_shards=16,
                           model_shards=16)
    pi_full = plan_inputs(cfg, SHAPES["long_500k"], dp_shards=1, seq_shards=1,
                          model_shards=16)
    assert pi_local.bytes_boundary * 15 < pi_full.bytes_boundary


# ---------------------------------------------------------------------------
# The training launcher and its compile cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/cache/from/env"}, "/cache/from/env"),
    ({}, None),
], ids=["env_set", "env_unset"])
def test_compile_cache_dir_rule(env, expected):
    from repro.launch.compile_cache import REPO_CACHE_DIR, compile_cache_dir

    got = compile_cache_dir(env)
    assert got == (expected or str(REPO_CACHE_DIR))
    assert REPO_CACHE_DIR.name == ".jax_cache"
    assert (REPO_CACHE_DIR.parent / "chip_smoke.py").exists()  # the repo root


def test_enable_compile_cache_sets_no_dir_when_env_given(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.enable_compile_cache() == "/cache/from/env"
    assert updates == []


def _train_main(monkeypatch, argv):
    from repro.launch import train

    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    return train.main(argv)


def test_train_layers_cut_keeps_widths(monkeypatch):
    out = _train_main(monkeypatch, "--reduced --layers 3 --steps 2 --batch 2 "
                                   "--seq 16".split())
    cut, full = out["config"], reduced(get_config("stablelm-3b"))
    assert cut.n_layers == 3
    assert cut == dataclasses.replace(full, n_layers=3)
    assert out["plan"]["n_micro"] == 1 and sum(out["plan"]["segments"]) == 3
    assert len(out["losses"]) == len(out["step_seconds"]) == 2
    assert all(np.isfinite(out["losses"]))


def test_train_refuses_a_plan_that_needs_microbatches(monkeypatch):
    """The Trainer has no gradient accumulation: a plan with n_micro > 1
    must stop the launcher, not run the whole batch over budget."""
    with pytest.raises(ValueError, match="n_micro"):
        _train_main(monkeypatch, "--batch 256 --seq 4096 --steps 1".split())
