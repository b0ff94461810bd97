"""``primary.loop_stall_unexplained_ms`` on hand-made scrapes: the part
of the loop's stalls that neither the process nor a host counter
explains, over the window; nothing on a program that does not split
its stalls."""

import json
import os

import pytest

from readers import snapshot_hist_sum

CHIPBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERIES = "runtime.loop_stall_unexplained_seconds"


def scrapes(h0, h1):
    def one(h):
        return {"primary-0": {"gauges": {}, "histograms": h}}

    return {"scrape0": one(h0), "scrape1": one(h1)}


def spec():
    with open(os.path.join(CHIPBENCH, "layer_metrics",
                           "primary.loop_stall_unexplained_ms.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("h0, h1, expected", [
    ({SERIES: {"count": 1, "sum": 0.12}},
     {SERIES: {"count": 3, "sum": 0.42}}, 300.0),
    ({SERIES: {"count": 2, "sum": 0.3}},
     {SERIES: {"count": 2, "sum": 0.3}}, 0.0),  # a clean window reads 0.0
    ({}, {}, None),  # a program without the partition reports nothing
])
def test_unexplained_stall_ms_reads_the_partition_histogram(h0, h1, expected):
    value = snapshot_hist_sum.read(spec(), scrapes(h0, h1))
    assert value == (pytest.approx(expected) if expected is not None else None)
