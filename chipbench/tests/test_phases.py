"""The step's phases read from op_name metadata, and the trace readers on a
trace that also holds the program's own ``repro.train.*`` spans."""

import importlib.util
from types import SimpleNamespace

import pytest

import devtrace as T
import phases as P
from conftest import ROOT

META = ' metadata={{op_name="{}" source_file="loop.py" source_line=1}}'
HLO = "\n".join([
    "ENTRY %main (p: f32[4]) -> f32[4] {",
    "  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%f.1"
    + META.format("jit(step_fn)/jvp(model)/dot_general"),
    "  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%f.2"
    + META.format("jit(step_fn)/transpose(jvp(model))/while/body/closed_call/checkpoint/"
                  "rematted_computation/while/body/dot_general"),
    "  %fusion.3 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%f.3"
    + META.format("jit(step_fn)/transpose(jvp(model))/while/body/closed_call/checkpoint/"
                  "transpose/dot_general"),
    "  %flash_dq.1 = bf16[2,32,2048,80]{3,2,1,0} custom-call(%fusion.3), "
    'custom_call_target="tpu_custom_call"'
    + META.format("jit(step_fn)/transpose(jvp(model))/flash_dq/pallas_call"),
    "  %fusion.4 = f32[4]{0} fusion(%fusion.3), kind=kLoop, calls=%f.4"
    + META.format("jit(step_fn)/optimizer/jit(_where)/select_n"),
    "  %copy.5 = f32[4]{0} copy(%fusion.4)" + META.format("reduce_sum"),
    "  %while.6 = (f32[4]) while(%copy.5), condition=%c, body=%b"
    + META.format("jit(step_fn)/jvp(model)/while"),
    "  ROOT %tuple.7 = (f32[4]) tuple(%copy.5)",
    "}",
])
WANT = {"fusion.1": "forward", "fusion.2": "recompute", "fusion.3": "backward",
        "flash_dq.1": "backward", "fusion.4": "optimizer", "copy.5": "other",
        "while.6": "forward"}


def ev(name, start, dur):
    return (name, float(start), float(dur))


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step_fn)/jvp(model)/while/body/closed_call/dot_general", "forward"),
    ("jit(step_fn)/transpose(jvp(model))/jvp(model)/checkpoint/rematted_computation/mul",
     "recompute"),
    ("jit(step_fn)/transpose(jvp(model))/while/body/mul", "backward"),
    ("jit(step_fn)/optimizer/mul", "optimizer"),
    ("jit(step_fn)/grad_compression/round", "other"),
    ("jit(step_fn)/jvp(modelling)/mul", "other"),
    ("jit(step_fn)/my_optimizer_state/add", "other"),
    ("reduce_sum", "other"),
])
def test_phase_of_reads_whole_components(op_name, phase):
    assert P.phase_of(op_name) == phase


def test_op_phases_reads_each_marker():
    assert P.op_phases(HLO) == WANT


def test_phase_time_adds_up_to_the_ops_total():
    events = [ev("fusion.1", 0, 10), ev("while.6", 10, 40), ev("fusion.2", 12, 7),
              ev("fusion.3", 20, 5), ev("flash_dq.1", 25, 6), ev("fusion.4", 31, 4),
              ev("copy.5", 35, 2), ev("fusion.9", 37, 3), ev("fusion.1", 120, 9)]
    t = P.phase_time(events, P.op_phases(HLO), 0, 100)
    assert t == {"forward": 10.0, "backward": 11.0, "recompute": 7.0, "optimizer": 4.0,
                 "other": 5.0}
    total = sum(v for _, v in T.top_ops(events, 0, 100, {}, n=100))
    assert sum(t.values()) * 1e-9 == pytest.approx(total)


FWD = ("%flash_fwd.1 = (bf16[2,32,2048,80]{3,2,1,0}, f32[2,32,2048,1]{3,2,1,0}) "
       'custom-call(%a), custom_call_target="tpu_custom_call"')
DQ = ('%flash_dq.1 = bf16[2,32,2048,80]{3,2,1,0} custom-call(%a), '
      'custom_call_target="tpu_custom_call"')
DKV = ("%flash_dkv.1 = (bf16[2,32,2048,80]{3,2,1,0}, bf16[2,32,2048,80]{3,2,1,0}) "
       'custom-call(%a), custom_call_target="tpu_custom_call"')
OPS = [ev("fusion.1", 0, 10), ev(FWD, 10, 6), ev(DQ, 20, 3), ev(DKV, 23, 5),
       ev("fusion.4", 28, 4), ev("fusion.1", 40, 10), ev(FWD, 50, 6), ev(DQ, 60, 3),
       ev(DKV, 63, 5), ev("fusion.4", 68, 4)]
BENCH_SPANS = [("chipbench.window", 0.0, 80.0), ("chipbench.trainer_run", 0.0, 34.0),
               ("chipbench.trainer_run", 34.0, 45.0), ("chipbench.next_batch", 35.2, 0.2)]
PROGRAM_SPANS = [("repro.train.step", 0.0, 33.5), ("repro.train.dispatch", 0.2, 0.5),
                 ("repro.train.sync", 0.7, 32.3), ("repro.train.step", 34.5, 44.0),
                 ("repro.train.next_batch", 35.0, 3.0), ("repro.train.put_batch", 38.0, 1.5),
                 ("repro.train.dispatch", 39.5, 0.4), ("repro.train.sync", 39.9, 37.0)]


def test_idle_gaps_are_named_by_the_innermost_program_span():
    spans = BENCH_SPANS + PROGRAM_SPANS
    gaps = T.idle_gaps(OPS, spans, 0, 80)
    # 32..40 is idle, its middle in the second step's fetch; the benchmark's
    # own spans put it down to the whole Trainer.run
    assert gaps[0] == ["repro.train.next_batch", pytest.approx(8e-9)]
    assert T.idle_gaps(OPS, BENCH_SPANS, 0, 80)[0] == ["chipbench.trainer_run",
                                                        pytest.approx(8e-9)]


def test_idle_split_puts_each_gap_down_to_the_program_spans():
    spans = BENCH_SPANS + PROGRAM_SPANS
    split = P.idle_split(OPS, spans, 0, 72, min_ns=5)
    # the one gap of 5 ns or more, 32..40: step 1's sync to 33, the fetch
    # 35..38, the copy 38..39.5, the dispatch 39.5..39.9, step 2's sync from
    # 39.9; 33..35 in no program span
    assert split == pytest.approx({"gaps": 1, "repro.train.sync": 1.1,
                                   "repro.train.next_batch": 3.0,
                                   "repro.train.put_batch": 1.5,
                                   "repro.train.dispatch": 0.4, "outside": 2.0})
    assert P.idle_split(OPS, spans, 0, 72, min_ns=100) == {"gaps": 0, "outside": 0.0}


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(name, ROOT / "chipbench" / "metrics"
                                                  / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@pytest.mark.parametrize("name", ["device_idle_share", "flash_fwd_roofline",
                                  "flash_bwd_roofline"])
def test_existing_readers_ignore_program_spans(name):
    def ctx(spans):
        trace = SimpleNamespace(devices={"/device:TPU:0": OPS}, spans=spans, lo=0.0,
                                hi=80.0, kinds=T.kernel_kinds("\n".join([FWD, DQ, DKV])))
        return SimpleNamespace(
            m={"num_attention_heads": 32, "num_key_value_heads": 32, "head_dim": 80},
            traffic={"batch": 2, "seq_len": 2048}, mesh=[1, 1], trace=trace,
            peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})

    without = read(name, ctx(BENCH_SPANS))
    assert without is not None and without > 0
    assert read(name, ctx(BENCH_SPANS + PROGRAM_SPANS)) == without


def test_steptrace_reads_the_trainers_counters(tiny_root, monkeypatch, capsys):
    """On the CPU (no device plane) the tool still reports the Trainer's
    own counters and the program's spans in the trace."""
    import json

    import steptrace

    monkeypatch.setattr(T, "SPAN_PREFIX", T.SPAN_PREFIX)
    res = steptrace.main(["--workload", "tiny.sqrtn", "--seed", str(2 ** 31 + 5),
                          "--seconds", "1"], root=tiny_root())
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == res
    assert res["steps"] >= 1 and res["compiles"] == 0
    assert res["trace_steps"] == 2 and res["traced_input_wait_ms"] > 0
    assert 0 < res["input_wait_ms"] < 1e3 * 2 * 128 / res["tokens_per_s"]
    assert "phase_ms" not in res
