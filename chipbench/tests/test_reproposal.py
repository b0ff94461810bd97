"""What PR 29 adds to the benchmark: the plain rule for own headers that
can never commit (``reference/orphans.py``), the two per-layer metrics
that read the proposer's new series, and the reader that sums a
histogram over every live primary.  On hand-made inputs; a program that
keeps no such series (the parent) reads None and nothing raises."""

import json
import os

import pytest

from readers import snapshot_hist_mean, snapshot_hist_sum_primaries
from reference.orphans import orphaned

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
REPO = os.path.dirname(CHIPBENCH)
CELLS = ["local-4n-f1.steady", "local-4n.steady"]


def spec(metric):
    with open(os.path.join(CHIPBENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def hist(count, total):
    return {"count": count, "sum": total}


def run_of(nodes):
    return {
        "scrape0": {n: {"histograms": {}} for n in nodes},
        "scrape1": {n: {"histograms": {}} for n in nodes},
    }


# ------------------------------------------------------------- the plain rule


def test_a_later_own_commit_orphans_the_own_rounds_it_skipped():
    committed = [("b", 1), ("a", 1), ("b", 2), ("a", 4), ("c", 3), ("a", 5)]
    assert orphaned(committed, "a", [1, 2, 3, 4, 5, 6], 50) == {2: 3, 3: 3}
    assert orphaned(committed, "c", [1, 2, 3, 4], 50) == {1: 4, 2: 4}


def test_the_garbage_horizon_orphans_without_an_own_commit():
    committed = [("b", 4), ("c", 9), ("b", 10), ("c", 11)]
    # round + gc_depth < highest committed round, and not before.
    assert orphaned(committed, "a", [3, 4, 5, 6], 5) == {3: 1, 4: 2, 5: 3}
    assert orphaned(committed, "a", [3, 4, 5, 6], 50) == {}


def test_what_commits_or_may_still_commit_is_not_orphaned():
    committed = [("a", 2), ("b", 2), ("a", 3)]
    assert orphaned(committed, "a", [2, 3, 4], 50) == {}
    assert orphaned([], "a", [1, 2], 50) == {}


@pytest.mark.parametrize("committed", [
    [("a", 3), ("a", 2)],
    [("a", 3), ("a", 3)],
    [("b", 9), ("a", 2)],
])
def test_a_sequence_no_tusk_emits_is_refused(committed):
    with pytest.raises(ValueError):
        orphaned(committed, "a", [2, 3], 5)


def test_the_rule_imports_nothing_of_the_program():
    with open(os.path.join(CHIPBENCH, "reference", "orphans.py")) as f:
        source = f.read()
    assert "narwhal_tpu" not in source.replace(
        "narwhal_tpu/primary/proposer.py", "")
    assert "\nimport " not in source.replace("from __future__ import", "")


# ---------------------------------------------------------- the two metrics


def test_parents_per_header_is_the_window_s_mean_at_primary_0():
    m = spec("primary.parents_per_header")
    assert m["series"] == "primary.header_parents" and m["node"] == "primary-0"
    run = run_of(["primary-0", "primary-1"])
    assert snapshot_hist_mean.read(m, run) is None  # the parent keeps none
    run["scrape0"]["primary-0"]["histograms"][m["series"]] = hist(300, 1200.0)
    run["scrape1"]["primary-0"]["histograms"][m["series"]] = hist(800, 3150.0)
    assert snapshot_hist_mean.read(m, run) == pytest.approx(3.9)
    run["scrape1"]["primary-0"]["histograms"][m["series"]] = hist(800, 2700.0)
    assert snapshot_hist_mean.read(m, run) == pytest.approx(3.0)


def test_reproposed_digests_are_summed_over_every_live_primary():
    m = spec("primary.payload_reproposed_digests")
    assert m["series"] == "primary.payload_reproposed"
    read = snapshot_hist_sum_primaries.read
    nodes = ["primary-0", "worker-0-0", "primary-1", "worker-1-0", "primary-2"]
    run = run_of(nodes)
    assert read(m, run) is None  # no primary keeps the series
    for n in nodes:
        run["scrape1"][n]["histograms"][m["series"]] = hist(0, 0.0)
    assert read(m, run) == 0.0  # a clean run is a reading
    run["scrape0"]["primary-1"]["histograms"][m["series"]] = hist(1, 2.0)
    run["scrape1"]["primary-1"]["histograms"][m["series"]] = hist(3, 7.0)
    run["scrape1"]["primary-2"]["histograms"][m["series"]] = hist(1, 1.0)
    run["scrape1"]["worker-0-0"]["histograms"][m["series"]] = hist(9, 99.0)
    assert read(m, run) == pytest.approx(6.0)  # 5 + 1; a worker's is not read
    # Before the window only: nothing inside it.
    run["scrape0"]["primary-2"]["histograms"][m["series"]] = hist(1, 1.0)
    run["scrape1"]["primary-1"]["histograms"][m["series"]] = hist(1, 2.0)
    assert read(m, run) == 0.0
    # One primary without the series does not hide the others.
    del run["scrape1"]["primary-0"]["histograms"][m["series"]]
    assert read(m, run) == 0.0


def test_both_metrics_are_reported_in_both_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, moves, better in (
        ("primary.parents_per_header", "commit_latency_p50_ms", "higher"),
        ("primary.payload_reproposed_digests", "commit_latency_p95_ms", "lower"),
    ):
        m = by_name[name]
        assert m["workloads"] == CELLS and m["layer"] == "primary"
        assert m["source"] == "program_counter"
        assert m["moves"] == moves and m["better"] == better
    # The four-chip cell reports every per-layer metric the benchmark has.
    assert all(m["workloads"] == CELLS for m in bench["per_layer"])
    assert [w["name"] for w in bench["workloads"]] == CELLS
    assert [w["chips"] for w in bench["workloads"]] == [1, 4]
