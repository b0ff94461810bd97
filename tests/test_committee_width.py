"""A committee of ten with three down, at its own shape, small, on the CPU
(ISSUE 35; ROADMAP R5a): the paper's fault experiment, `bench-10n-f3`, ten
validators of which the three of sorted-key ranks 4, 7 and 9 never take
part, so that the seven live ones are exactly the quorum of seven.  The
benchmark's cell `bench-10n-f3.steady` runs that deployment on the chip
(PERF.md, sections 4 and 7); what the width means for the program is held
here, small, against the benchmark's plain reference.

- a simulated committee at the source's 200 ms timers: every replica's
  audit segment declares `lowdepth` and replays through the benchmark's
  plain reference (``chipbench/reference/``, loaded by path) to one
  sequence, every certificate carries exactly seven distinct votes (the
  live validators'), and the leader counters account for every even round
  below the frontier, the dead leader's among the skipped;
- one verify burst shaped as a round of that committee at primary 0 (six
  headers, six votes, six certificates of eight claims, one forged vote
  inside one certificate) through the batched verifier on the chip's
  bottom rung against OpenSSL claim by claim.  (How a larger burst of
  this committee splits over the ladder is `chunk_plan`'s, held in
  tests/test_ed25519.py at 60, 150 and 1,024 claims without building the
  top rung, which no run on the chip dispatched.)
"""

import importlib
import logging
import os
import sys

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.faults.spec import parse_scenario
from narwhal_tpu.sim import run_sim_scenario
from narwhal_tpu.sim.committee import sim_keypairs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(REPO, "chipbench")


def load_reference():
    """chipbench/ is no package and imports nothing of the program: its
    ``reference`` is found through sys.path for the length of the import
    only (as tests/test_commit_rules.py loads the benchmark's cases)."""
    sys.path.insert(0, CHIPBENCH)
    try:
        return (importlib.import_module("reference.check"),
                importlib.import_module("reference.wire"))
    finally:
        sys.path.remove(CHIPBENCH)


check, wire = load_reference()

N, DEAD_RANKS = 10, [4, 7, 9]
QUORUM = 2 * N // 3 + 1
# The source's timers and GC depth (BASELINE.md:12-14).
PARAMETERS = {"max_header_delay": 200, "max_batch_delay": 200, "gc_depth": 50}


def test_the_committee_is_a_quorum_of_all_who_live():
    assert QUORUM == 7 == N - len(DEAD_RANKS)
    # With n even only even ranks lead: one leader in five is dead.
    assert [r for r in DEAD_RANKS if r % 2 == 0] == [4]


# -- (a) the committee, simulated ---------------------------------------------


@pytest.fixture(scope="module")
def simulated(tmp_path_factory):
    """Ten validators on a seeded schedule at the source's timers;
    those of the dead ranks are stopped at time 0 and never return."""
    logging.disable(logging.WARNING)
    obj = {
        "name": "sim_t_bench_10n_f3", "nodes": N, "workers": 1, "rate": 400,
        "tx_size": 256, "duration": 14, "seed": 35,
        "parameters": dict(PARAMETERS),
    }
    names = [kp.name for kp in sim_keypairs(parse_scenario(obj, env={}))]
    ranked = sorted(names)
    dead = sorted(names.index(ranked[r]) for r in DEAD_RANKS)
    obj["crash"] = [{"node": i, "at_s": 0} for i in dead]
    workdir = str(tmp_path_factory.mktemp("sim"))
    try:
        art = run_sim_scenario(parse_scenario(obj, env={}), 35, workdir)
    finally:
        logging.disable(logging.NOTSET)
    counters = {
        road: metrics.registry().counters[f"consensus.leaders_{road}"].value
        for road in ("direct", "indirect", "skipped")
    }
    live = [i for i in range(N) if i not in dead]
    audits = [
        wire.read_audit(os.path.join(workdir, f"audit-primary-{i}.seg0.bin"))
        for i in live
    ]
    return {"art": art, "ranked": ranked, "audits": audits,
            "live_keys": {names[i] for i in live}, "counters": counters}


def plain_artifacts(sim) -> "check.Artifacts":
    return check.Artifacts(
        sorted_keys=sim["ranked"], gc_depth=PARAMETERS["gc_depth"],
        tx_size=256, audits=sim["audits"], stores=[], due=[], sample_worker={},
        batch_of={}, forged_sent=[], invalid_signatures=[], device=None,
        window_dispatches=None,
    )


def test_sim_committee_is_safe_and_live_with_three_down(simulated):
    verdicts = simulated["art"]["verdicts"]
    assert verdicts["safety"]["ok"], verdicts["safety"]
    assert verdicts["liveness"]["ok"], verdicts["liveness"]
    assert len(verdicts["liveness"]["nodes"]) == QUORUM


def test_every_replica_declares_lowdepth_and_replays_to_one_sequence(simulated):
    """The comparison a run's `correct` rests on, at this committee's
    size: each live replica's segment through ``PlainTusk`` under the
    rule it declares.  (A simulation signs with MACs, sim/committee.py's
    fidelity notes, so OpenSSL refuses every certificate here: real
    signatures at this shape are the burst tests' below.)"""
    art = plain_artifacts(simulated)
    assert check.commit_rule(art) == "lowdepth"
    mismatches, _, sequences, _ = check.replay(art)
    assert mismatches == 0
    assert len(sequences) == QUORUM and min(map(len, sequences)) > 100
    longest = max(sequences, key=len)
    assert all(s == longest[:len(s)] for s in sequences)


def test_every_certificate_holds_exactly_seven_distinct_live_votes(simulated):
    seen = 0
    for tag, payload in simulated["audits"][0]:
        if tag != b"I":
            continue
        cert = wire.decode_certificate(payload, simulated["ranked"])
        voters = {name for name, _ in cert.votes}
        assert len(cert.votes) == len(voters) == QUORUM
        assert voters == simulated["live_keys"]
        assert len(cert.header.parents) == QUORUM or cert.round == 1
        seen += 1
    assert seen > 200


def test_leader_counters_account_for_every_even_round_below_the_frontier(
    simulated,
):
    """The registry is the committee's in a simulation: the three roads
    sum to the live replicas' frontiers, as the plain rule reads them
    from each audit segment.  One leader in five is dead, so a fifth of
    the even rounds are passed over and none needs the chain walk."""
    frontiers = []
    for records in simulated["audits"]:
        tusk = check.PlainTusk(
            simulated["ranked"], PARAMETERS["gc_depth"], "lowdepth"
        )
        for tag, payload in records[2:]:
            if tag == b"I":
                tusk.process_certificate(
                    wire.decode_certificate(payload, simulated["ranked"])
                )
        frontiers.append(tusk.last_committed_round // 2)
    got = simulated["counters"]
    assert sum(got.values()) == sum(frontiers)
    assert got["skipped"] > 0 and got["direct"] > 3 * got["skipped"]
    assert got["indirect"] <= QUORUM


# -- (b) the round's verify burst on the chip's bottom rung -------------------


@pytest.fixture(scope="module")
def chip_ladder(tmp_path_factory):
    """The chip's bottom rung built for jax-cpu (~2 min, once, into a
    directory of this file's own): the shape a device-backed primary
    dispatches, which the one-rung CPU ladder never reaches."""
    pytest.importorskip("jax")
    from narwhal_tpu.ops import ed25519, programs

    directory = tmp_path_factory.mktemp("programs")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(programs, "program_dir", lambda: str(directory))
        yield ed25519


def round_burst(forge_in=3):
    """(messages, keys, signatures, the claim indices of each message)
    of one round at primary 0: six peers' headers, six votes on its own
    header, six peers' certificates of 2f+2 claims each (the header's
    signature and its seven votes).  Peer ``forge_in``'s certificate
    carries one vote signed over another digest (None: no forgery)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    sks = [Ed25519PrivateKey.from_private_bytes(bytes([i + 1]) * 32)
           for i in range(QUORUM)]
    pks = [sk.public_key().public_bytes_raw() for sk in sks]
    claims, groups = [], []

    def claim(signer, message, sign_over=None):
        claims.append((message, pks[signer],
                       sks[signer].sign(sign_over or message)))
        return len(claims) - 1

    def digest(kind, author):
        return bytes([kind, author]) * 16

    for peer in range(1, QUORUM):
        groups.append(("header", [claim(peer, digest(1, peer))]))
    for peer in range(1, QUORUM):
        groups.append(("vote", [claim(peer, digest(2, 0))]))
    for peer in range(1, QUORUM):
        members = [claim(peer, digest(3, peer))]
        for voter in range(QUORUM):
            forged = peer == forge_in and voter == 5
            members.append(claim(
                voter, digest(4, peer), digest(5, peer) if forged else None
            ))
        groups.append(("certificate", members))
    messages, keys, sigs = map(list, zip(*claims))
    return messages, keys, sigs, groups


def openssl_mask(messages, keys, sigs):
    return [check.openssl_verify(m, k, s)
            for m, k, s in zip(messages, keys, sigs)]


def test_a_round_of_the_committee_is_one_dispatch_at_the_bottom_rung(chip_ladder):
    E = chip_ladder
    messages, keys, sigs, groups = round_burst()
    assert len(messages) == 6 + 6 + 6 * (QUORUM + 1) == 60
    dispatched = {}
    mask = E.verify_batch_arrays(
        messages, keys, sigs, dispatched, E.CHIP_RUNGS
    ).tolist()
    assert dispatched == {E.CHIP_RUNGS[0]: 1}
    assert mask == openssl_mask(messages, keys, sigs)
    refused = [k for k, (_, members) in enumerate(groups)
               if not all(mask[i] for i in members)]
    # The certificate with the forged vote, and nothing else.
    assert refused == [6 + 6 + (3 - 1)]
    assert groups[refused[0]][0] == "certificate"
    assert [mask[i] for i in groups[refused[0]][1]].count(False) == 1
