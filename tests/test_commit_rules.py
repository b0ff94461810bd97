"""The benchmark's commit-rule cases in tier-1, and the counters that say
which road each leader took to its commit (ISSUE 33).

The 83 cases are chipbench/tests/test_commit_rules.py's own (the plain
direct rule against ``LowDepthTusk`` and ``GoldenLowDepthTusk``, the plain
classic rule against ``Tusk`` and ``GoldenTusk``, arrival by arrival on 30
seeded DAGs): that module is loaded by path and its tests and fixture are
collected here under their own names, not copied.  The product's default
rule (the direct one) is thereby held to the reference ``correct`` judges
a run by, in the suite the driver runs.
"""

import asyncio
import importlib.util
import os
import sys

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.consensus import Consensus
from tests.common import committee
from tests.test_consensus import (
    genesis_digests,
    make_certificates,
    mock_certificate,
    sorted_names,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(REPO, "chipbench")


def load_benchmark_cases():
    """chipbench/ is no package: its test module finds ``committee`` and
    ``reference`` beside it through sys.path (chipbench/tests/conftest.py
    puts them there).  Do the same for the length of the import only."""
    path = os.path.join(CHIPBENCH, "tests", "test_commit_rules.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_test_commit_rules", path
    )
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, CHIPBENCH)
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(CHIPBENCH)
    return module


_cases = load_benchmark_cases()
globals().update(
    (name, obj)
    for name, obj in vars(_cases).items()
    if name.startswith("test_") or name == "dag"
)


# -- consensus.leaders_direct / _indirect / _skipped ---------------------------

ROADS = ("direct", "indirect", "skipped")


def all_direct(c, names):
    """Every validator cites every certificate below: each leader is
    decided by the arrival that completes its 2f+1 citations."""
    certs, _ = make_certificates(1, 9, genesis_digests(c), names)
    return certs


def thin_leader(c, names):
    """The round-2 leader is cited by exactly 2f round-3 certificates:
    short of the direct rule's gate, over the classic rule's, so the
    direct rule reaches it only by the chain walk from the round-4 leader
    (indirect 1) where the classic rule decides it by itself."""
    certs, _ = make_certificates(1, 2, genesis_digests(c), names)
    below = {x.origin: x.digest() for x in certs if x.round == 2}
    leader = sorted(names)[2 % len(names)]
    others = {d for o, d in below.items() if o != leader}
    parents = set()
    for k, name in enumerate(names):
        cites = set(below.values()) if k < 2 else others
        digest, cert = mock_certificate(name, 3, cites)
        certs.append(cert)
        parents.add(digest)
    rest, _ = make_certificates(4, 7, parents, names)
    return certs + rest


def dead_leader(c, names):
    """The round-2 leader's validator is silent for rounds 1 and 2: the
    decision of the round-4 leader passes over round 2 (skipped 1)."""
    leader = sorted(names)[2 % len(names)]
    certs, parents = make_certificates(
        1, 2, genesis_digests(c), [n for n in names if n != leader]
    )
    rest, _ = make_certificates(3, 7, parents, names)
    return certs + rest


@pytest.mark.parametrize("shape, rule, want", [
    (all_direct, None, (4, 0, 0)),
    (thin_leader, None, (2, 1, 0)),
    (dead_leader, None, (2, 0, 1)),
    # Under classic "direct" is its own f+1 trigger: the thin leader
    # clears it, decided by the first certificate three rounds above.
    (thin_leader, "classic", (2, 0, 0)),
], ids=["all-direct", "thin-leader-indirect", "dead-leader-skipped",
        "classic-thin-leader-direct"])
def test_leader_counters_say_which_road_each_leader_took(
    monkeypatch, shape, rule, want
):
    monkeypatch.delenv("NARWHAL_COMMIT_RULE", raising=False)
    c = committee()
    counters = [
        metrics.counter(f"consensus.leaders_{road}") for road in ROADS
    ]
    before = [m.value for m in counters]
    consensus = Consensus(
        c, 50, asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
        commit_rule=rule,
    )
    assert consensus.commit_rule == (rule or "lowdepth")
    undecided = 0
    for cert in shape(c, sorted_names()):
        now = [m.value for m in counters]
        if not consensus.tusk.process_certificate(cert):
            # Nothing on the path of a certificate that decides nothing.
            assert [m.value for m in counters] == now
            undecided += 1
    assert undecided
    got = tuple(m.value - b for m, b in zip(counters, before))
    assert got == want
    # Every even round below the frontier took exactly one of the roads.
    assert sum(got) == consensus.tusk.state.last_committed_round // 2
    # In every primary's snapshot, decided or not.
    snapshot = metrics.registry().snapshot()["counters"]
    assert all(f"consensus.leaders_{road}" in snapshot for road in ROADS)
