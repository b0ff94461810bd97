"""Parents in hand at the mint, and re-proposal of what can no longer
commit (ISSUE 29): the proposer's settle / re-propose decisions against
the benchmark's plain rule (chipbench/reference/orphans.py) on seeded
commit sequences, the Core's offer of post-quorum certificates, the
aggregator's once-only quorum, and a simulated four-up committee at the
source's header_linger 0 in which every digest handed to a proposer
commits exactly once on every replica."""

import asyncio
import importlib.util
import logging
import os
import random

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.consensus.replay import (
    TAG_COMMIT,
    TAG_INSERT,
    _CertDecoder,
    read_audit,
)
from narwhal_tpu.crypto import SignatureService, digest32
from narwhal_tpu.faults.spec import parse_scenario
from narwhal_tpu.primary.aggregators import CertificatesAggregator
from narwhal_tpu.primary.proposer import Proposer
from narwhal_tpu.sim import run_sim_scenario
from tests.common import committee, keys, make_certificate, make_header
from tests.test_core import make_core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    """chipbench/ is no package and imports nothing of the program: load
    the one file by path, leaving sys.path alone."""
    path = os.path.join(REPO, "chipbench", "reference", "orphans.py")
    spec = importlib.util.spec_from_file_location("reference_orphans", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.orphaned


orphaned = load_reference()


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


# -- the plain rule itself -----------------------------------------------------


def test_reference_own_round_skipped_then_later_own_round_committed():
    committed = [("b", 1), ("a", 1), ("b", 2), ("a", 4), ("c", 3)]
    # 2 and 3 fell under own round 4 (index 3); 5 may still commit.
    assert orphaned(committed, "a", [1, 2, 3, 4, 5], gc_depth=50) == {
        2: 3, 3: 3,
    }


def test_reference_garbage_horizon_passes_with_no_own_commit():
    committed = [("b", 4), ("c", 9), ("b", 10), ("c", 11)]
    # 3 + 5 < 9 at index 1; 4 + 5 < 10 at index 2; 5 + 5 < 11 at index 3;
    # 6 + 5 >= 11: may still commit.
    assert orphaned(committed, "a", [3, 4, 5, 6], gc_depth=5) == {
        3: 1, 4: 2, 5: 3,
    }


@pytest.mark.parametrize(
    "committed",
    [
        [("a", 3), ("a", 2)],  # an origin's rounds must rise
        [("a", 3), ("b", 3), ("a", 3)],
        [("b", 9), ("a", 2)],  # commits from under the horizon (depth 5)
    ],
)
def test_reference_refuses_a_sequence_no_tusk_emits(committed):
    with pytest.raises(ValueError):
        orphaned(committed, "a", [2, 3], gc_depth=5)


# -- the proposer against the plain rule ---------------------------------------

ORIGINS = ("me", "p1", "p2", "p3")


def seeded_schedule(seed, steps=120, gc_depth=6):
    """A seeded interleaving of own proposals and commits that a Tusk
    could emit: each walk commits, sorted by round, rounds above each
    origin's last committed one and not under the garbage horizon; own
    rounds are left out at random (orphans), for stretches long enough
    that the horizon passes some, and some never commit at all (a header
    that never got its certificate looks the same from here)."""
    rng = random.Random(seed)
    events = []
    own_round = 0
    last = {o: 0 for o in ORIGINS}
    last_round = 0
    drought = 0  # steps during which no own round commits
    for _ in range(steps):
        own_round += 1
        events.append(("propose", own_round, rng.randrange(3)))
        if drought:
            drought -= 1
        elif rng.random() < 0.08:
            drought = rng.randrange(gc_depth, 3 * gc_depth)
        if rng.random() < 0.45 or own_round < 3:
            continue
        leader_round = rng.randrange(max(1, last_round), own_round)
        walk = []
        for origin in ORIGINS:
            floor = max(last[origin], last_round - gc_depth - 1)
            for r in range(floor + 1, leader_round + 1):
                if origin == "me" and (drought or rng.random() < 0.3):
                    continue
                if origin != "me" and rng.random() < 0.1:
                    continue
                walk.append((origin, r))
        walk.sort(key=lambda x: x[1])
        for origin, r in walk:
            last[origin] = r
            events.append(("commit", origin, r))
        if walk:
            last_round = max(last_round, walk[-1][1])
    return events


@pytest.mark.parametrize("seed", range(12))
def test_proposer_decisions_equal_the_plain_rule(seed):
    """Drive a Proposer through a seeded schedule: what it finds
    orphaned, and at which commit, equals the plain rule's answer over
    the whole sequence; nothing is lost and nothing that commits was
    re-proposed (so no digest can commit twice)."""
    gc_depth = 6

    async def go():
        c = committee()
        kp = keys()[0]
        tx_core = asyncio.Queue()
        p = Proposer(
            kp.name, c, SignatureService(kp), 1_000, 100,
            None, asyncio.Queue(), tx_core, gc_depth=gc_depth,
        )
        headers, committed, found, handed = {}, [], {}, []
        fresh = 0
        for event in seeded_schedule(seed, gc_depth=gc_depth):
            if event[0] == "propose":
                _, round_, n = event
                for _ in range(n):
                    fresh += 1
                    digest = digest32(b"%d:%d" % (seed, fresh))
                    handed.append(digest)
                    p.digests.append((digest, 0))
                    p.payload_size += len(digest)
                p.round = round_
                p.last_parents = [digest32(b"parent")]
                await p._make_header()
                headers[round_] = tx_core.get_nowait()
                continue
            _, origin, round_ = event
            before = set(p._unsettled)
            p.deliver_commit(round_, origin == "me")
            committed.append((origin, round_))
            for r in before - set(p._unsettled) - {round_}:
                found[r] = len(committed) - 1
        assert found == orphaned(committed, "me", headers, gc_depth)
        assert found  # the schedule does orphan some
        own_committed = [r for o, r in committed if o == "me"]
        out = [d for r in own_committed for d in headers[r].payload]
        assert len(out) == len(set(out))  # none commits twice
        pending = [d for r in p._unsettled for d, _ in p._unsettled[r]]
        pending += [d for d, _ in p.digests]
        assert sorted(out + pending) == sorted(handed)  # none lost
        # A re-proposed digest rides a LATER header than its first.
        rode = {}
        for r in sorted(headers):
            for d in headers[r].payload:
                rode.setdefault(d, []).append(r)
        again = [rs for rs in rode.values() if len(rs) > 1]
        assert again and all(
            rs[i] in found for rs in again for i in range(len(rs) - 1)
        )

    run(go())


# -- the Core's offer and the aggregator's rule --------------------------------


def test_aggregator_emits_once_at_the_first_quorum():
    """The round-advance rule is upstream's: the first 2f+1 certificates,
    once; the fourth certificate emits nothing."""
    c = committee()
    aggregator = CertificatesAggregator()
    certs = [make_certificate(make_header(kp, c=c)) for kp in keys()]
    out = [aggregator.append(cert, c) for cert in certs]
    assert out[:2] == [None, None] and out[3] is None
    assert out[2] == [x.digest() for x in certs[:3]]
    assert aggregator.append(certs[0], c) is None  # authority reuse


def test_core_offers_every_fresh_post_quorum_certificate():
    """The fourth certificate of a round whose parent quorum went out is
    offered to the proposer, once; its re-delivery is not fresh and is
    not offered again."""

    async def go():
        c = committee()
        core, _, qs = make_core(c, keys()[0])
        quorums, late = [], []
        core.parents_cb = lambda parents, round: quorums.append(round)
        core.late_parents_cb = lambda digest, round: late.append(
            (digest, round)
        )
        task = asyncio.ensure_future(core.run())
        certs = [make_certificate(make_header(kp, c=c)) for kp in keys()]
        for cert in certs + [certs[3]]:
            await qs["primaries"].put(("certificate", cert))
        for _ in range(4):
            await asyncio.wait_for(qs["consensus"].get(), 5)
        await asyncio.sleep(0.05)
        assert quorums == [1]
        assert late == [(certs[3].digest(), 1)]
        task.cancel()
        core.network.close()

    run(go())


# -- a simulated committee, four up, header_linger 0 ---------------------------


def committed_payload(committee_, segments):
    """One replica's committed batch digests, in commit order, from its
    audit segments (every certificate that entered Tusk, every commit)."""
    decode = _CertDecoder()
    by_digest, out = {}, []
    for path in segments:
        for tag, payload in read_audit(path):
            if tag == TAG_INSERT:
                cert = decode(payload)
                by_digest[bytes(cert.digest())] = cert
            elif tag == TAG_COMMIT:
                out += [bytes(d) for d in by_digest[payload].header.payload]
    return out


@pytest.mark.parametrize("run_seed", [31, 32])
@pytest.mark.parametrize("cut_off", [False, True])
def test_sim_committee_commits_every_digest_exactly_once(
    tmp_path, run_seed, cut_off
):
    """Four validators up on a seeded schedule, the source's parameters
    (header_linger 0): every batch digest a proposer was handed, but for
    the run's last seconds, is committed on every replica, and none
    twice.  Validator 3 is 30 ms from its peers, so its certificate is
    the fourth at each of them, round after round: under "the first 2f+1
    only" no peer ever cites it and its batches never commit; in hand at
    every peer's mint, it is cited by every header.  Cut off for three
    seconds, it comes back with headers that were never certified or are
    cited by nobody, and their payload rides again."""
    logging.disable(logging.WARNING)
    wan = {"pairs": [
        {"src": 3, "dst": dst, "latency_ms": 30} for dst in range(3)
    ]}
    if cut_off:
        wan["partitions"] = [{"group": [3], "from_s": 6, "until_s": 9}]
    scenario = parse_scenario({
        "name": "sim_t_reproposal", "nodes": 4, "workers": 1, "rate": 400,
        "tx_size": 256, "duration": 25, "seed": 7, "wan": wan,
    })
    workdir = str(tmp_path / "sim")
    art = run_sim_scenario(scenario, run_seed, workdir)
    assert art["ok"], art["verdicts"]
    reg = metrics.registry()
    parents = reg.histograms["primary.header_parents"]
    reproposed = reg.histograms["primary.payload_reproposed"]
    assert reg.counters["primary.late_parents_cited"].value > 0
    if cut_off:
        assert reproposed.sum > 0
    else:
        assert parents.sum / parents.count > 3.9 and reproposed.sum == 0
    handed = {
        d: e["digest_at_primary"]
        for d, e in reg.trace.entries.items()
        if "digest_at_primary" in e
    }
    # What was handed over in the last seconds is still on its way when
    # the nodes are stopped: a dozen rounds and one re-proposal cycle.
    cutoff = max(handed.values()) - 3.0
    due = {bytes.fromhex(d) for d, t in handed.items() if t <= cutoff}
    assert len(due) > 20
    from narwhal_tpu.sim.committee import build_sim_committee, sim_keypairs

    committee_ = build_sim_committee(sim_keypairs(scenario), scenario.workers)
    for i in range(4):
        segments = [
            os.path.join(workdir, name)
            for name in sorted(os.listdir(workdir))
            if name.startswith(f"audit-primary-{i}.")
        ]
        out = committed_payload(committee_, segments)
        assert len(out) == len(set(out)), f"primary-{i} committed one twice"
        assert due <= set(out), (
            f"primary-{i}: {len(due - set(out))} of {len(due)} never committed"
        )
