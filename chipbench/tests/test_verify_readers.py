"""The readers of PR 26 on hand-made ``run`` dicts: legs and busy share
of the verify-stage trace (window edges, empty table), the histogram sum
(a clean run reads 0.0, a node without the series None), and the overlay
of the device trace on the table, laid over the calls of a cut of a real
v5e trace (``data/trace_cut.json.gz``)."""

import gzip
import json
import os

import pytest

import trace_reduce
from readers import (
    snapshot_hist_mean,
    snapshot_hist_sum,
    trace_gap_cause,
    verify_busy,
    verify_leg,
)

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
T0, SECONDS = 1000.0, 10.0


def spec(metric):
    with open(os.path.join(CHIPBENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def burst(collected, prepare=None, device_s=0.016, replay_s=0.002, **extra):
    """One entry of the table: 1 ms to submit, 1 ms of queueing for the
    dispatch thread, 4 ms of preparation, the device, 0.5 ms back to the
    loop, the replay."""
    e = {"collected": collected, "items": 3, "round": 7}
    if prepare is None:
        prepare = collected + 0.002
    e.update(
        submitted=collected + 0.001, claims=4, prepare=prepare,
        enqueued=prepare + 0.004, pad=128, chunks=1, cpu_s=0.003,
    )
    e["fetched"] = e["enqueued"] + device_s
    e["resumed"] = e["fetched"] + 0.0005
    e["replayed"] = e["resumed"] + replay_s
    e.update(extra)
    return e


def run_with(table, **more):
    run = {
        "t0": T0, "seconds": SECONDS,
        "snapshots": {"primary-0": {"verify_trace": table, "gauges": {}}},
        "scrape0": {"primary-0": {"histograms": {}}},
        "scrape1": {"primary-0": {"histograms": {}}},
    }
    run.update(more)
    return run


# ---------------------------------------------------------------- verify_leg


def test_legs_are_medians_over_the_bursts_that_start_in_the_window():
    table = {
        "1": burst(T0 - 0.010),                    # prepare before the window
        "2": burst(T0 - 0.002),                    # prepare AT t0: counted
        "3": burst(T0 + 1.0, device_s=0.020),
        "4": burst(T0 + 2.0, device_s=0.030),
        "5": burst(T0 + SECONDS - 0.002),          # prepare AT t1: not counted
        "6": {"collected": T0 + 3.0, "replayed": T0 + 3.001},  # no dispatch
    }
    table["2"]["prepare"] = T0
    table["5"]["prepare"] = T0 + SECONDS
    run = run_with(table)
    assert verify_leg.read(spec("verify.prepare_ms"), run) == pytest.approx(4.0)
    # enqueued -> fetched: 1's and 5's ``enqueued`` fall outside the
    # window (each leg is cut by its OWN from-stamp), so 16, 20, 30 ms.
    assert verify_leg.read(spec("verify.device_wait_ms"), run) == pytest.approx(20.0)
    assert verify_leg.read(spec("verify.replay_ms"), run) == pytest.approx(2.5)
    # The longest time on the dispatch thread among them (``prepare`` ->
    # ``fetched``: a freeze shows whether it hits the launch or the fetch).
    assert verify_leg.read(spec("verify.dispatch_max_ms"), run) == pytest.approx(34.0)
    table["3"]["fetched"] += 3.4
    assert verify_leg.read(spec("verify.dispatch_max_ms"), run) == pytest.approx(3424.0)


@pytest.mark.parametrize(
    "metric", ["verify.prepare_ms", "verify.device_wait_ms", "verify.replay_ms",
               "verify.dispatch_max_ms"]
)
def test_leg_of_an_empty_or_missing_table_is_none(metric):
    assert verify_leg.read(spec(metric), run_with({})) is None
    parent = run_with({})
    del parent["snapshots"]["primary-0"]["verify_trace"]  # before PR 26
    assert verify_leg.read(spec(metric), parent) is None
    assert verify_leg.read(spec(metric), dict(parent, snapshots={})) is None
    loop_only = {"1": {"collected": T0 + 1, "submitted": T0 + 1.001,
                       "resumed": T0 + 1.01, "replayed": T0 + 1.02}}
    assert verify_leg.read(spec(metric), run_with(loop_only)) is None


# --------------------------------------------------------------- verify_busy


def test_busy_share_is_the_union_cut_to_the_window():
    table = {
        "1": {"collected": T0 - 1.0, "replayed": T0 + 1.0},    # 1 s inside
        "2": {"collected": T0 + 2.0, "replayed": T0 + 3.0},    # 1 s
        "3": {"collected": T0 + 2.5, "replayed": T0 + 3.5},    # +0.5 s (overlap)
        "4": {"collected": T0 + 9.5, "replayed": T0 + 12.0},   # 0.5 s inside
        "5": {"collected": T0 + 5.0},                          # never replayed
        "6": {"collected": T0 - 5.0, "replayed": T0 - 4.0},    # outside
    }
    share = verify_busy.read(spec("verify.stage_busy_share"), run_with(table))
    assert share == pytest.approx(100.0 * 3.0 / SECONDS)


def test_busy_share_of_no_table_is_none_and_of_an_idle_stage_is_zero():
    busy = spec("verify.stage_busy_share")
    assert verify_busy.read(busy, run_with({})) is None
    before = {"1": {"collected": T0 - 5.0, "replayed": T0 - 4.0}}
    assert verify_busy.read(busy, run_with(before)) == 0.0


def test_busy_share_refuses_a_table_that_lost_the_window_s_start():
    table = {"9": {"collected": T0 + 4.0, "replayed": T0 + 5.0}}
    run = run_with(table)
    run["snapshots"]["primary-0"]["gauges"]["metrics.verify_trace_evictions"] = 3
    assert verify_busy.read(spec("verify.stage_busy_share"), run) is None
    table["1"] = {"collected": T0 - 1.0, "replayed": T0 - 0.5}
    assert verify_busy.read(spec("verify.stage_busy_share"), run) == pytest.approx(10.0)


# --------------------------------------------------------- snapshot_hist_sum


def hist(count, total):
    return {"count": count, "sum": total}


def test_loop_stall_ms_is_zero_in_a_clean_run_and_none_without_the_series():
    stall = spec("primary.loop_stall_ms")
    series = stall["series"]
    run = run_with({})
    assert snapshot_hist_sum.read(stall, run) is None  # watchdog off
    run["scrape1"]["primary-0"]["histograms"][series] = hist(0, 0.0)
    assert snapshot_hist_sum.read(stall, run) == 0.0  # a reading, not None
    run["scrape0"]["primary-0"]["histograms"][series] = hist(2, 0.5)
    run["scrape1"]["primary-0"]["histograms"][series] = hist(4, 2.25)
    assert snapshot_hist_sum.read(stall, run) == pytest.approx(1750.0)
    run["scrape1"]["primary-0"]["histograms"][series] = hist(2, 0.5)
    assert snapshot_hist_sum.read(stall, run) == 0.0  # stalls before t0 only


def test_queue_wait_ms_reads_the_verify_queue_s_residence():
    wait = spec("verify.queue_wait_ms")
    assert wait["series"] == "queue.primary.verify_window.residence_seconds"
    run = run_with({})
    assert snapshot_hist_mean.read(wait, run) is None
    run["scrape0"]["primary-0"]["histograms"][wait["series"]] = hist(100, 1.0)
    run["scrape1"]["primary-0"]["histograms"][wait["series"]] = hist(300, 2.0)
    assert snapshot_hist_mean.read(wait, run) == pytest.approx(5.0)


# ----------------------------------------------------------- trace_gap_cause

OFFSET = 1009.8  # wall clock of the cut's trace time 0 (the window's end - 0.2)


@pytest.fixture(scope="module")
def cut():
    with gzip.open(os.path.join(HERE, "data", "trace_cut.json.gz"), "rt") as f:
        planes = {
            plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
            for plane, lines in json.load(f).items()
        }
    return trace_reduce.reduce_planes(planes, window_s=0.1)


def table_over(calls, replay_s=0.0015, shift=None):
    """A table whose dispatches 4, 5, 6 are the cut's three calls: each
    is launched 0.3 ms before its call starts and fetched 0.2 ms after it
    ends, prepared for 4 ms, collected 1 ms before that, back on the loop
    0.3 ms after the fetch and replayed ``replay_s`` later.  Three
    dispatches before and two after, at spacings no run of three shares."""
    starts = [s for _, s, _ in calls]
    durs = [d for _, _, d in calls]
    before = [starts[0] - 0.0905, starts[0] - 0.0610, starts[0] - 0.0290]
    after = [starts[-1] + 0.0335, starts[-1] + 0.0590]
    table = {}
    for seq, start in enumerate(before + starts + after, 1):
        dur = durs[seq - 4] if 4 <= seq <= 6 else 0.01617
        enqueued = OFFSET + start - 0.0003
        e = {
            "collected": enqueued - 0.005, "submitted": enqueued - 0.0045,
            "prepare": enqueued - 0.004, "enqueued": enqueued,
            "fetched": OFFSET + start + dur + 0.0002, "chunks": 1, "pad": 128,
        }
        e["resumed"] = e["fetched"] + 0.0003
        e["replayed"] = e["resumed"] + replay_s
        if shift and str(seq) in shift:
            for k in ("prepare", "enqueued", "fetched", "resumed", "replayed"):
                e[k] += shift[str(seq)]
        table[str(seq)] = e
    return table


def gap_run(cut, table):
    return run_with(table, trace=cut)


def test_gaps_split_between_waiting_for_work_and_the_host(cut):
    calls = cut["calls"]
    assert [c[0] for c in calls] == ["_verify_kernel"] * 3
    gaps = [
        calls[1][1] - (calls[0][1] + calls[0][2]),
        calls[2][1] - (calls[1][1] + calls[1][2]),
    ]
    assert gaps == pytest.approx([0.006009, 0.009117], abs=2e-6)
    idle = spec("device.idle_for_work_share")
    share = trace_gap_cause.read(idle, gap_run(cut, table_over(calls)))
    # In each gap the host holds the stage for: the rest of the finished
    # dispatch (fetch lag 0.2 + hop 0.3 + replay 1.5 ms) and the next
    # one's collection, preparation and launch (5 + 0.3 ms) = 7.3 ms.
    # The first gap (6.009 ms) is all host; the second (9.117) waits
    # 1.817 ms for work.
    waited = gaps[1] - 0.0073
    assert share == pytest.approx(100.0 * waited / sum(gaps), abs=0.05)
    # A longer replay eats the wait: nothing of either gap is for work.
    busy = gap_run(cut, table_over(calls, replay_s=0.0040))
    assert trace_gap_cause.read(idle, busy) == pytest.approx(0.0, abs=1e-6)


ANYWHERE = (float("-inf"), float("inf"))


def test_overlay_finds_the_dispatches_by_spacing_alone(cut):
    calls = [(s, d) for _, s, d in cut["calls"]]
    table = table_over(cut["calls"])
    dispatches = sorted((e["enqueued"], e["fetched"]) for e in table.values())
    offset = trace_gap_cause.overlay(calls, dispatches, 0.002, ANYWHERE)
    assert offset == pytest.approx(OFFSET + 0.0002, abs=1e-6)


def test_a_table_that_does_not_fit_gives_none(cut):
    idle = spec("device.idle_for_work_share")
    # Dispatch 5 stamped 5 ms late: its call would start before it was
    # launched, whichever run of three is tried.
    late = table_over(cut["calls"], shift={"5": 0.005})
    assert trace_gap_cause.read(idle, gap_run(cut, late)) is None
    # Too few dispatches near the window's end, no table, no trace.
    few = {k: v for k, v in table_over(cut["calls"]).items() if k in ("4", "5")}
    assert trace_gap_cause.read(idle, gap_run(cut, few)) is None
    assert trace_gap_cause.read(idle, gap_run(cut, {})) is None
    assert trace_gap_cause.read(idle, run_with(table_over(cut["calls"]))) is None
    # A multi-chunk dispatch is several calls: not matched one to one.
    chunked = table_over(cut["calls"])
    chunked["5"]["chunks"] = 2
    assert trace_gap_cause.read(idle, gap_run(cut, chunked)) is None


def test_two_runs_that_fit_alike_are_told_apart_by_when_the_trace_was_asked_for():
    """Evenly spaced calls over evenly spaced dispatches (three calls in
    a tenth of a second at ~23 ms, as on the chip): every run of three
    fits and the spacing cannot choose, but the profiler's clock starts
    when the harness asks for the trace, 0.2 s before the window's end,
    give or take the node's 0.05 s poll."""
    calls = [(0.010 + 0.025 * i, 0.01617) for i in range(3)]
    dispatches = [
        (5.0 + 0.025 * i, 5.0 + 0.025 * i + 0.0167) for i in range(6)
    ]
    assert trace_gap_cause.overlay(calls, dispatches, 0.002, ANYWHERE) is None
    first = pytest.approx(4.99 + 0.0167 - 0.01617)
    assert trace_gap_cause.overlay(calls, dispatches[:3], 0.002, ANYWHERE) == first
    asked = 4.985
    slack = trace_gap_cause.OFFSET_SLACK_S
    assert trace_gap_cause.overlay(
        calls, dispatches, 0.002, (asked + slack[0], asked + slack[1])
    ) is None  # runs 1, 2 and 3 lie within 0.07 s of it
    assert trace_gap_cause.overlay(
        calls, dispatches, 0.002, (asked - 0.01, asked + 0.02)
    ) == first
    # ... and a run that fits but lies outside the range is not taken.
    assert trace_gap_cause.overlay(
        calls, dispatches[:3], 0.002, (asked + 0.03, asked + 0.07)
    ) is None


def test_the_range_comes_from_the_window_s_end_and_harness_json(cut):
    """The same table 0.1 s further from the window's end than the
    harness could have asked for the trace: no overlay."""
    idle = spec("device.idle_for_work_share")
    run = gap_run(cut, table_over(cut["calls"]))
    assert trace_gap_cause.read(idle, run) is not None
    assert trace_gap_cause.read(idle, dict(run, t0=T0 + 0.1)) is None
    assert trace_gap_cause.read(idle, dict(run, t0=T0 - 0.1)) is None
