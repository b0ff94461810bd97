"""`verifier.program_build_s` (PR 27) on hand-made ``run`` dicts: the
three sums of primary 0's compile ledger from its final snapshot, and
nothing where a program keeps none of it."""

import json
import os

import pytest

from readers import snapshot_detail_sum

CHIPBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(CHIPBENCH, "layer_metrics", "verifier.program_build_s.json")) as f:
    SPEC = json.load(f)

LEDGER = {"programs_built": 2, "trace_seconds": 49.4, "lower_seconds": 21.1,
          "build_seconds": 16.3, "cache_hits": 2, "cache_misses": 0}


def run_with(primary0):
    return {"snapshots": {"primary-0": primary0, "primary-1": {"detail": {}}}}


def test_sums_the_ledger_of_the_final_snapshot():
    run = run_with({"detail": {"crypto.verify.device": dict(LEDGER, platform="tpu")}})
    assert snapshot_detail_sum.read(SPEC, run) == pytest.approx(86.8)
    assert snapshot_detail_sum.read(dict(SPEC, scale=1000.0), run) == pytest.approx(86800.0)


@pytest.mark.parametrize("primary0", [
    {},                                              # no detail at all
    {"detail": {}},                                  # an OpenSSL primary
    {"detail": {"crypto.verify.device": None}},
    {"detail": {"crypto.verify.device": {"trace_seconds": 1.0}}},  # a partial ledger
], ids=["no-detail", "no-device", "null-device", "partial"])
def test_reports_nothing_where_the_ledger_is_not_kept(primary0):
    assert snapshot_detail_sum.read(SPEC, run_with(primary0)) is None
    assert snapshot_detail_sum.read(SPEC, {"snapshots": {}}) is None
