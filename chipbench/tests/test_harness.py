"""A whole run of a tiny cell on the CPU, the chip check skipped: sound, it
comes out correct; with the timed path broken underneath, it does not.
Also the float8 control at this size, and the refusal without a chip."""

import json

import jax.numpy as jnp
import pytest

import run as entry
from conftest import ROOT

#: generous for the tiny cell, which reads about 3e-4, 4e-3, 2e-2 and 1e-3
LIMITS = {"loss_gap": 0.01, "grad_gap": 0.05, "grad_error": 0.1, "change_gap": 0.05}


def one_run(root, workload="tiny.sqrtn", trace=0):
    return entry.main(["--workload", workload, "--seed", str(2 ** 31 + 77),
                       "--seconds", "1", "--trace", str(trace)],
                      root=root)


def test_sound_run_is_correct_and_prints_its_checks_last(tiny_root, capsys):
    res = one_run(tiny_root(LIMITS), "tiny.time_centric")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == res
    assert err.strip().splitlines()[-1].startswith("check change_gap = ")


def test_traced_run_reports_per_layer_metrics(tiny_root):
    res = one_run(tiny_root(LIMITS), "tiny.time_centric", trace=1)
    assert res["correct"]
    # no TPU plane on the CPU: the readers of the trace find nothing
    assert {"plan_s", "plan_mem_ratio", "hbm_peak_gib", "compile_s", "mfu"} <= set(res["metrics"])
    assert "flash_fwd_roofline" not in res["metrics"]
    assert "busy_s" in res["device"] and "window_s" in res["device"]


def test_state_left_unchanged_is_not_correct(tiny_root, monkeypatch):
    from repro.optim import adamw

    def unchanged(cfg, grads, state, params):
        return params, state, {"grad_norm": adamw.global_norm(grads), "lr": jnp.float32(0)}

    monkeypatch.setattr(adamw, "update", unchanged)
    res = one_run(tiny_root(LIMITS))
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(tiny_root, monkeypatch):
    from repro.models.transformer import LM

    whole = LM.loss

    def half(self, params, batch, **kw):
        return whole(self, params, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, **kw)

    monkeypatch.setattr(LM, "loss", half)
    res = one_run(tiny_root(LIMITS))
    assert not res["correct"]


def test_loss_altered_where_it_is_produced_is_not_correct(tiny_root, monkeypatch):
    from repro.models.transformer import LM

    whole = LM.loss

    def off(self, params, batch, **kw):
        return 1.01 * whole(self, params, batch, **kw)

    monkeypatch.setattr(LM, "loss", off)
    res = one_run(tiny_root(LIMITS))
    assert not res["correct"]
    assert res["checks"]["loss_gap"]["value"] == pytest.approx(0.01, rel=1e-2)


#: this tiny cell's own limits, set on the CPU from a dozen seeds of the
#: program (at most 7.5e-4, 6.2e-3, 2.4e-2, 2.5e-3) and three of the float8
#: control (at least 8.7e-4, 3.2e-2, 0.27, 1.0e-2)
TINY_LIMITS = {"loss_gap": 0.003, "grad_gap": 0.015, "grad_error": 0.08, "change_gap": 0.005}


def test_float8_control_and_half_batch_are_judged_not_correct(tiny_root):
    import calibrate

    rows = calibrate.main(["--workload", "tiny.sqrtn", "--seeds", "11", "--controls", "1"],
                          root=tiny_root(TINY_LIMITS))
    kinds = {r["kind"]: r for r in rows}
    assert kinds["program"]["correct"]
    assert not kinds["control_fp8"]["correct"]
    assert not kinds["fault_half_batch"]["correct"]
    assert kinds["control_fp8"]["grad_gap"] > 5 * kinds["program"]["grad_gap"]
    assert kinds["control_fp8"]["grad_error"] > 5 * kinds["program"]["grad_error"]


def test_no_tpu_exits_without_a_result(capsys, monkeypatch, off_chip):
    import harness

    monkeypatch.setattr(harness, "chips", off_chip)
    with pytest.raises(SystemExit) as e:
        entry.main(["--workload", "stablelm-3b.l4.b2s2048", "--seed", "1", "--seconds", "1"],
                   root=ROOT)
    assert e.value.code == 2
    assert capsys.readouterr().out == ""
