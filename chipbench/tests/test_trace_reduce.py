"""The reduction from a trace to busy time, program times and the
breakdown: on a hand-made trace with known answers, and on a small cut of
a real v5e trace of `local-4n-f1.steady` (``data/trace_cut.json.gz``: the
first three executions of the verify program of a traced chip run of PR
25, with the first 1,500 operations of each, names cut to 60 characters;
made by loading the run's ``.xplane.pb`` through ``load_planes``)."""

import gzip
import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CUT = os.path.join(HERE, "data", "trace_cut.json.gz")


def test_union_counts_overlap_once_and_lists_gaps():
    busy, gaps = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert busy == 30 and gaps == [(20, 30)]


def test_hand_made_trace():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [
                ("jit__verify_kernel(1)", 0.0, 10e6),
                ("jit__verify_kernel(2)", 40e6, 20e6),
                ("jit_other(3)", 90e6, 1e6),
            ],
            "XLA Ops": [
                ("%fusion.1 = s32[] fusion()", 0.0, 4e6),
                ("%fusion.2 = s32[] fusion()", 4e6, 6e6),
                ("%copy.7 = s32[] copy()", 40e6, 20e6),
                ("%fusion.9 = s32[] fusion()", 90e6, 1e6),
            ],
        },
        "/host:CPU": {"python3": [("PjitFunction(_verify_kernel)", 0.0, 99e6)]},
    }
    out = trace_reduce.reduce_planes(planes, window_s=0.1)
    assert out["busy_s"] == pytest.approx(0.031)
    assert out["window_s"] == 0.1
    assert out["programs"]["_verify_kernel"] == pytest.approx([0.010, 0.020])
    assert out["programs"]["other"] == pytest.approx([0.001])
    assert [c[0] for c in out["calls"]] == ["_verify_kernel", "_verify_kernel", "other"]
    assert out["calls"][1][1:] == pytest.approx([0.040, 0.020])
    assert out["device_ops"][0] == ["copy", pytest.approx(0.020)]
    assert out["device_ops"][1] == ["fusion", pytest.approx(0.011)]
    assert out["idle_gaps"][0] == ["unattributed", pytest.approx(0.030)]
    assert out["idle_gaps"][1] == ["unattributed", pytest.approx(0.030)]


def test_idle_share_of_the_window_as_stamped():
    """Four calls of 16.17 ms in the 0.1005 s between start_trace's return
    and stop_trace (the chip run of PR 25 whose calls the log lists):
    35.6% idle.  With the stamp taken before start_trace (0.0567 s
    earlier) the same trace read 58.8%."""
    from readers import trace_idle

    modules = [("jit__verify_kernel(1)", s * 1e9, 16.17e6)
               for s in (0.0623, 0.0857, 0.1090, 0.1309)]
    planes = {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": [
        ("%fusion.1 = s32[] fusion()", s, d) for _, s, d in modules]}}
    run = {"trace": trace_reduce.reduce_planes(planes, window_s=0.1005)}
    assert trace_idle.read({}, run) == pytest.approx(35.64, abs=0.01)
    run = {"trace": trace_reduce.reduce_planes(planes, window_s=0.1005 + 0.0567)}
    assert trace_idle.read({}, run) == pytest.approx(58.85, abs=0.01)
    assert [c[1] for c in run["trace"]["calls"]] == pytest.approx(
        [0.0623, 0.0857, 0.1090, 0.1309])


def test_no_device_operation_is_nothing_to_read():
    assert trace_reduce.reduce_planes({}, 1.0) is None
    assert trace_reduce.reduce_planes({"/device:TPU:0": {"XLA Ops": []}}, 1.0) is None


def test_names():
    assert trace_reduce.program_name("jit__verify_kernel(16395490316122744020)") == "_verify_kernel"
    assert trace_reduce.op_kind("%multiply_add_fusion.140 = s32[128,63]{0,1} fusion(") == "multiply_add_fusion"
    assert trace_reduce.op_kind("%copy-start = (s32[32]) copy-start(") == "copy-start"


@pytest.fixture(scope="module")
def cut():
    with gzip.open(CUT, "rt") as f:
        raw = json.load(f)
    return {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in raw.items()
    }


def test_real_cut_program_found_by_name(cut):
    modules = cut["/device:TPU:0"]["XLA Modules"]
    span_s = (modules[-1][1] + modules[-1][2] - modules[0][1]) / 1e9
    out = trace_reduce.reduce_planes(cut, window_s=span_s)
    calls = out["programs"]["_verify_kernel"]
    assert len(calls) == 3
    # The verify program at rung 128 on a v5e: 16.2 ms a call (PERF.md).
    assert all(0.0155 < c < 0.0175 for c in calls)


def test_real_cut_busy_share(cut):
    lines = cut["/device:TPU:0"]
    ops = lines["XLA Ops"]
    out = trace_reduce.reduce_planes(cut, window_s=1.0)
    # Independent of union_ns: operations of one core run one after the
    # other, so the union is the sum of the durations less what overlaps,
    # counted here nanosecond by nanosecond over the covered stretches.
    covered = set()
    for _, start, dur in ops:
        covered.update(range(int(start), int(start + dur)))
    assert out["busy_s"] == pytest.approx(len(covered) / 1e9, rel=1e-3)
    assert 0 < out["busy_s"] <= sum(d for _, _, d in ops) / 1e9 + 1e-9
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
