"""The trace reduction on small event lists: busy union, kernel sums and
kinds, exposed collective time, the breakdown."""

import pytest

import devtrace as T

FWD = "%jvp = (bf16[2,32,2048,80]{3,2,1,0}, f32[2,32,2048,1]{3,2,1,0}) custom-call(%a), custom_call_target=\"tpu_custom_call\""
DQ = "%t.2 = bf16[2,32,2048,80]{3,2,1,0} custom-call(%a), custom_call_target=\"tpu_custom_call\""
DKV = "%t.3 = (bf16[2,32,2048,80]{3,2,1,0}, bf16[2,32,2048,80]{3,2,1,0}) custom-call(%a), custom_call_target=\"tpu_custom_call\""


def ev(name, start, dur):
    return (name, float(start), float(dur))


def test_busy_is_the_union_clipped_to_the_window():
    events = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 10), ev("d", 95, 20)]
    assert T.busy_ns(events, 0, 100) == 15 + 10 + 5
    assert T.busy_ns(events, 8, 35) == 7 + 5


def test_flash_kinds_and_kernel_time():
    events = [ev(FWD, 0, 4), ev(DQ, 10, 3), ev(DKV, 20, 5), ev(FWD, 30, 4), ev("fusion.1", 40, 9)]
    assert [T.flash_kind(e, {}) for e in events] == ["fwd", "dq", "dkv", "fwd", None]
    assert T.kernel_time(events, ["fwd"], 0, 100, {}) == (2, 8.0)
    assert T.kernel_time(events, ["dq", "dkv"], 0, 100, {}) == (2, 8.0)
    assert T.kernel_time(events, ["fwd"], 25, 100, {}) == (1, 4.0)


def test_kinds_from_the_compiled_text_name_bare_events():
    hlo = "\n".join(["  " + FWD, "  ROOT " + DQ, "  " + DKV, "  %fusion.1 = f32[4] add(%a, %b)"])
    kinds = T.kernel_kinds(hlo)
    assert kinds == {"jvp": "fwd", "t.2": "dq", "t.3": "dkv"}
    bare = [ev("jvp", 0, 4), ev("t.2", 10, 3), ev("fusion.1", 20, 5)]
    assert [T.flash_kind(e, kinds) for e in bare] == ["fwd", "dq", None]


def test_events_named_by_their_hlo_text():
    # the chip's trace names each op by its instruction's HLO text
    fusion = "%fusion.198 = f32[4]{0:T(128)} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused_computation.198"
    events = [ev(FWD, 0, 4), ev(DQ, 10, 3), ev(fusion, 20, 5), ev(fusion, 30, 2)]
    assert [T.flash_kind(e, {}) for e in events] == ["fwd", "dq", None, None]
    assert T.kernel_time(events, ["fwd"], 0, 100, {}) == (1, 4.0)
    assert dict(T.top_ops(events, 0, 100, {})) == pytest.approx(
        {"flash_fwd": 4e-9, "flash_dq": 3e-9, "fusion": 7e-9})
    bare_text = ["%jvp = (bf16[2,4,256,64]{3,2,1,0}, f32[2,4,256,1]{3,2,1,0}) custom-call(%a)",
                 "%t.2 = bf16[2,4,256,64]{3,2,1,0} custom-call(%a)"]
    kinds = {"jvp": "fwd", "t.2": "dq"}
    assert [T.flash_kind(ev(n, 0, 1), kinds) for n in bare_text] == ["fwd", "dq"]


def test_collective_exposure_counts_only_uncovered_time():
    events = [ev("fusion.1", 0, 10), ev("all-reduce.1", 5, 10), ev("fusion.2", 12, 2),
              ev("all-gather.3", 20, 5)]
    # all-reduce 5..15: covered 5..10 and 12..14, so 3 exposed; all-gather 5
    assert T.collective_exposed_ns(events, 0, 100) == 3 + 5


def test_breakdown_names_ops_and_gaps():
    events = [ev("fusion.1", 0, 10), ev("while.3", 20, 30), ev("fusion.7", 20, 30),
              ev(FWD, 60, 5)]
    spans = [("chipbench.window", 0.0, 100.0), ("chipbench.trainer_run", 15.0, 50.0)]
    ops = dict(T.top_ops(events, 0, 100, {}))
    assert ops == {"fusion": 40e-9, "flash_fwd": 5e-9}
    gaps = T.idle_gaps(events, spans, 0, 100)
    assert gaps[0] == ["chipbench.window", 35e-9]
    assert gaps[1] == ["chipbench.trainer_run", 10e-9]
    assert gaps[2] == ["chipbench.trainer_run", 10e-9]
