"""Fault-tolerance behaviours of the training loop."""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import phase_reader
from repro.configs import get_config, reduced
from repro.data import DataConfig, SyntheticLM
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.launch.mesh import auto_mesh
from repro.train import TrainConfig, Trainer


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("stablelm-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticLM(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    )
    return cfg, model, params, data


def _tc(**kw):
    base = dict(
        total_steps=8,
        log_every=0,
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=8),
    )
    base.update(kw)
    return TrainConfig(**base)


def test_loss_decreases(small_model):
    cfg, model, params, data = small_model
    tr = Trainer(model.loss, params, _tc(total_steps=30,
                 optimizer=AdamWConfig(lr=2e-3, warmup_steps=3, total_steps=30)))
    out = tr.run(iter(data))
    first5 = np.mean(out["losses"][:5])
    last5 = np.mean(out["losses"][-5:])
    assert last5 < first5, (first5, last5)


def test_checkpoint_restart_resumes_exactly(small_model):
    cfg, model, params, data = small_model
    with tempfile.TemporaryDirectory() as d:
        tc = _tc(ckpt_dir=d, ckpt_every=4)
        tr = Trainer(model.loss, params, tc)
        tr.run(iter(data))
        tr.close()
        p_end = tr.params

        tr2 = Trainer(model.loss, params, tc)
        assert tr2.maybe_restore()
        assert tr2.step == 8
        # restored params equal the final saved ones
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            tr2.params,
            p_end,
        )
        tr2.close()


def test_nan_guard_skips_update(small_model):
    cfg, model, params, data = small_model

    def poisoned_loss(p, batch):
        loss = model.loss(p, batch)
        # poison every second step via the batch content hash
        bad = (batch["tokens"][0, 0] % 2 == 0).astype(jnp.float32)
        return loss + bad * jnp.float32(jnp.nan)

    tr = Trainer(poisoned_loss, params, _tc(total_steps=6))
    p0 = jax.tree_util.tree_leaves(tr.params)[0].copy()
    out = tr.run(iter(data))
    assert out["skipped"] >= 1
    # params are still finite (never poisoned)
    for leaf in jax.tree_util.tree_leaves(tr.params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


def test_straggler_detection(small_model):
    cfg, model, params, data = small_model
    tr = Trainer(model.loss, params, _tc(total_steps=4, straggler_factor=1.5))
    seen = []
    tr.on_straggler = lambda step, dt, ewma: seen.append((step, dt, ewma))
    # simulate timing directly
    tr._track_time(1.0)
    tr._track_time(1.0)
    tr._track_time(5.0)  # 5x the EWMA → straggler
    assert tr.straggler_steps == 1
    assert seen and seen[0][1] == 5.0


def test_gradient_compression_error_feedback_converges(small_model):
    """int8 round-trip with error feedback should track the uncompressed
    trajectory closely (beyond-paper distributed trick)."""
    cfg, model, params, data = small_model
    tc_plain = _tc(total_steps=10, optimizer=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10))
    tc_comp = _tc(total_steps=10, compress_grads=True,
                  optimizer=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10))
    out_p = Trainer(model.loss, params, tc_plain).run(iter(data))
    out_c = Trainer(model.loss, params, tc_comp).run(iter(data))
    assert abs(out_p["final_loss"] - out_c["final_loss"]) < 0.1


def test_remesh_rejits(small_model):
    cfg, model, params, data = small_model
    tr = Trainer(model.loss, params, _tc(total_steps=2))
    tr.run(iter(data))
    mesh = auto_mesh((1, 1), ("data", "model"))
    tr.remesh(mesh)
    out = tr.run(iter(data))
    assert out["step"] == 2  # already at total; re-jit path exercised


def test_planned_step_matches_vanilla():
    """cfg.plan_budget routes the step through plan_function: same losses
    and parameters as the vanilla value_and_grad step, bit for bit, while
    actually planning under a halved activation budget."""
    from jax import lax

    from repro.core.jaxpr_graph import trace
    from repro.core.liveness import vanilla_peak

    dn = (((1,), (0,)), ((), ()))

    def loss_fn(params, batch):
        h = batch["x"]
        for w in params:
            h = lax.tanh(lax.dot_general(h, w, dn))
        return jnp.sum(h * h)

    key = jax.random.PRNGKey(0)
    params = [
        jax.random.normal(jax.random.fold_in(key, i), (16, 16)) * 0.3
        for i in range(6)
    ]
    batch = {"x": np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8, 16)))}
    budget = vanilla_peak(
        trace(loss_fn, params, batch).graph, liveness=False
    ) / 2

    def run(tc):
        tr = Trainer(loss_fn, params, tc)
        out = tr.run(iter([batch] * 4))
        return out, tr.params

    out_vanilla, p_vanilla = run(_tc(total_steps=4))
    out_planned, p_planned = run(_tc(total_steps=4, plan_budget=budget))
    assert out_vanilla["losses"] == out_planned["losses"]
    for a, b in zip(p_vanilla, p_planned):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_each_step_records_its_time_and_input_time(small_model):
    cfg, model, params, data = small_model
    out = Trainer(model.loss, params, _tc(total_steps=3)).run(iter(data))
    assert len(out["step_seconds"]) == len(out["input_seconds"]) == 3
    for step, inp in zip(out["step_seconds"], out["input_seconds"]):
        assert 0 < inp <= step


def test_compiles_counts_new_step_executables_only(small_model):
    cfg, model, params, data = small_model
    tr = Trainer(model.loss, params, _tc(total_steps=1))
    assert tr.run(iter(data))["compiles"] >= 1
    tr.cfg = dataclasses.replace(tr.cfg, total_steps=3)
    assert tr.run(iter(data))["compiles"] == 0
    shorter = ({k: v[:, :16] for k, v in b.items()} for b in data)
    tr.cfg = dataclasses.replace(tr.cfg, total_steps=4)
    assert tr.run(shorter)["compiles"] >= 1


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "kept"])
def test_compiled_step_names_its_phases(remat):
    """The step's named scopes reach the compiled instructions: forward,
    backward and optimizer ops in any plan, recomputed ops only where the
    sqrt(n) segments are rematerialised."""
    cfg = dataclasses.replace(reduced(get_config("stablelm-3b")), n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    loss = lambda p, b: model.loss(p, b, segment_sizes=(2, 2),
                                   segment_remat=(remat, remat))
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    text = Trainer(loss, params, _tc()).lower(batch).compile().as_text()
    found = set(phase_reader().op_phases(text).values())
    assert {"forward", "backward", "optimizer"} <= found
    assert ("recompute" in found) == remat
