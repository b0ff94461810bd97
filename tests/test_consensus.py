"""Tusk golden tests (analog of reference consensus_tests.rs): synthetic
certificate DAGs with no signatures and no network, leader coin pinned to
authority 0, exact commit sequences asserted."""

import asyncio

import pytest

from narwhal_tpu.crypto import Digest
from narwhal_tpu.primary.messages import Certificate, Header, genesis
from narwhal_tpu.consensus import Consensus, Tusk
from narwhal_tpu.consensus.tusk import (
    RULE_MAGICS,
    CheckpointRuleMismatch,
    resolve_commit_rule,
)
from tests.common import committee, keys


def mock_certificate(origin, round_, parents):
    cert = Certificate(
        header=Header(
            author=origin, round=round_, payload={}, parents=set(parents)
        )
    )
    return cert.digest(), cert


def make_certificates(start, stop, initial_parents, names):
    """One certificate per authority for rounds [start, stop]; returns the
    certificates and the digests to use as next parents."""
    certificates = []
    parents = set(initial_parents)
    next_parents = set()
    for round_ in range(start, stop + 1):
        next_parents = set()
        for name in names:
            digest, cert = mock_certificate(name, round_, parents)
            certificates.append(cert)
            next_parents.add(digest)
        parents = set(next_parents)
    return certificates, next_parents


def sorted_names():
    return sorted(kp.name for kp in keys())


def genesis_digests(c):
    return {x.digest() for x in genesis(c)}


def feed(tusk, certificates):
    committed = []
    for cert in certificates:
        committed.extend(tusk.process_certificate(cert))
    return committed


def test_commit_one():
    """4 ideal rounds: the leader of round 2 commits with its round-1
    parents (reference consensus_tests.rs commit_one)."""
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)
    certs.append(trigger)

    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    committed = feed(tusk, certs)
    assert [x.round for x in committed] == [1, 1, 1, 1, 2]


def test_dead_node():
    """One dead (non-leader) node across 9 rounds: leaders of rounds 2, 4, 6
    commit; sequence interleaves whole rounds of 3."""
    c = committee()
    names = sorted_names()[:3]  # drop the last authority
    certs, _ = make_certificates(1, 9, genesis_digests(c), names)

    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    committed = feed(tusk, certs)
    rounds = [x.round for x in committed]
    expected = [(i - 1) // 3 + 1 for i in range(1, 16)] + [6]
    assert rounds[:16] == expected


def test_not_enough_support():
    """The leader of round 2 lacks f+1 support at first; it commits later,
    before the leader of round 4 (reference not_enough_support)."""
    c = committee()
    names = sorted_names()
    certs = []

    # Round 1: fully connected among the first 3 nodes.
    out, parents = make_certificates(1, 1, genesis_digests(c), names[:3])
    certs.extend(out)

    # Round 2: the only round with 4 certificates; remember the leader's.
    leader_2_digest, cert = mock_certificate(names[0], 2, parents)
    certs.append(cert)
    out, parents = make_certificates(2, 2, parents, names[1:])
    certs.extend(out)

    # Round 3: only node 0 links to the round-2 leader.
    next_parents = set()
    d, cert = mock_certificate(names[1], 3, parents)
    certs.append(cert)
    next_parents.add(d)
    d, cert = mock_certificate(names[2], 3, parents)
    certs.append(cert)
    next_parents.add(d)
    d, cert = mock_certificate(names[0], 3, parents | {leader_2_digest})
    certs.append(cert)
    next_parents.add(d)
    parents = next_parents

    # Rounds 4-6: fully connected among the first 3 nodes.
    out, parents = make_certificates(4, 6, parents, names[:3])
    certs.extend(out)

    # Round 7 triggers the commits.
    _, trigger = mock_certificate(names[0], 7, parents)
    certs.append(trigger)

    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    committed = feed(tusk, certs)
    rounds = [x.round for x in committed]
    assert rounds[:11] == [1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 4]


def test_missing_leader():
    """Node 0 (the leader) is absent in rounds 1-2 and reappears from round
    3: nothing commits until the leader of round 4 (reference
    missing_leader)."""
    c = committee()
    names = sorted_names()
    certs = []
    out, parents = make_certificates(1, 2, genesis_digests(c), names[1:])
    certs.extend(out)
    out, parents = make_certificates(3, 6, parents, names)
    certs.extend(out)
    _, trigger = mock_certificate(names[0], 7, parents)
    certs.append(trigger)

    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    committed = feed(tusk, certs)
    rounds = [x.round for x in committed]
    assert rounds[:11] == [1, 1, 1, 2, 2, 2, 3, 3, 3, 3, 4]


def test_idempotent_no_double_commit():
    """Feeding the same certificates again commits nothing new."""
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)

    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    committed = feed(tusk, certs + [trigger])
    assert len(committed) == 5
    committed_again = feed(tusk, certs + [trigger])
    assert committed_again == []


def test_async_consensus_runner():
    """The async wrapper forwards commits to both outputs in order."""

    async def go():
        c = committee()
        names = sorted_names()
        certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
        _, trigger = mock_certificate(names[0], 5, next_parents)
        certs.append(trigger)

        rx, tx_primary, tx_output = (
            asyncio.Queue(),
            asyncio.Queue(),
            asyncio.Queue(),
        )
        consensus = Consensus(c, 50, rx, tx_primary, tx_output, fixed_coin=True)
        task = asyncio.ensure_future(consensus.run())
        for cert in certs:
            await rx.put(cert)
        out = [await asyncio.wait_for(tx_output.get(), 5) for _ in range(5)]
        fb = [await asyncio.wait_for(tx_primary.get(), 5) for _ in range(5)]
        assert [x.round for x in out] == [1, 1, 1, 1, 2]
        assert [x.digest() for x in fb] == [x.digest() for x in out]
        task.cancel()

    asyncio.run(asyncio.wait_for(go(), 15))


def test_restore_torn_blob_raises_without_mutation():
    """A truncated/corrupt checkpoint must raise BEFORE any state mutates:
    the caller's fallback is the fresh frontier, which must be intact
    (ADVICE.md r05 — the old code assigned last_committed_round before
    validating the length)."""

    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)
    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    assert feed(tusk, certs + [trigger])
    blob = tusk.state.snapshot_bytes()

    fresh = Tusk(c, gc_depth=50, fixed_coin=True)
    before_round = fresh.state.last_committed_round
    before_map = dict(fresh.state.last_committed)
    for bad in (blob[: len(blob) // 2], b"", b"JUNK!!" + blob[6:], blob[:17]):
        with pytest.raises(ValueError):
            fresh.state.restore(bad)
        assert fresh.state.last_committed_round == before_round
        assert fresh.state.last_committed == before_map


def test_corrupt_checkpoint_boots_fresh_and_commits(tmp_path):
    """A torn checkpoint file on disk must not crash-loop the node: the
    Consensus boot logs loudly, ignores it, and commits from a fresh
    frontier (the reference's behavior — it has no checkpoint at all)."""

    async def go():
        ckpt = str(tmp_path / "consensus.ckpt")
        with open(ckpt, "wb") as f:
            # Torn mid-write, under the magic of the rule that runs when
            # nobody names one (another rule's magic is refused, below).
            f.write(RULE_MAGICS[resolve_commit_rule()] + b"\x00\x01")

        c = committee()
        names = sorted_names()
        certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
        _, trigger = mock_certificate(names[0], 5, next_parents)
        certs.append(trigger)

        rx, tx_primary, tx_output = (
            asyncio.Queue(),
            asyncio.Queue(),
            asyncio.Queue(),
        )
        consensus = Consensus(
            c, 50, rx, tx_primary, tx_output,
            fixed_coin=True, checkpoint_path=ckpt,
        )
        assert consensus.tusk.state.last_committed_round == 0  # fresh
        task = asyncio.ensure_future(consensus.run())
        for cert in certs:
            await rx.put(cert)
        out = [await asyncio.wait_for(tx_output.get(), 5) for _ in range(5)]
        assert [x.round for x in out] == [1, 1, 1, 1, 2]
        # The commit rewrote the checkpoint: a restart now restores
        # cleanly.  The rewrite runs in the executor (off the event
        # loop, PR 4), so poll for the write to land BEFORE cancelling
        # the runner — cancelling first could cancel a not-yet-started
        # executor job and the file would never appear.
        state = type(consensus.tusk)(c, gc_depth=50, fixed_coin=True).state
        for _ in range(100):
            with open(ckpt, "rb") as f:
                blob = f.read()
            try:
                state.restore(blob)
                break
            except ValueError:
                await asyncio.sleep(0.05)
        task.cancel()
        assert state.last_committed_round == 2

    asyncio.run(asyncio.wait_for(go(), 15))


@pytest.mark.parametrize("torn", [True, False], ids=["torn", "whole"])
def test_classic_checkpoint_refused_on_the_default_rule(
    tmp_path, monkeypatch, torn
):
    """A validator that restarts on this version over a checkpoint the
    classic rule wrote (upstream's rule, the default until PR 33) is
    refused at boot, torn file or whole: never the fresh-frontier
    fallback, never one rule's frontier read under the other.  The
    message tells the upgrading operator both rules and the way out."""
    monkeypatch.delenv("NARWHAL_COMMIT_RULE", raising=False)
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)
    classic = Tusk(c, gc_depth=50, fixed_coin=True)
    assert feed(classic, certs + [trigger])
    blob = classic.state.snapshot_bytes()
    ckpt = str(tmp_path / "consensus.ckpt")
    with open(ckpt, "wb") as f:
        f.write(blob[:8] if torn else blob)

    def boot(**kwargs):
        return Consensus(
            c, 50, asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
            fixed_coin=True, checkpoint_path=ckpt, **kwargs,
        )

    with pytest.raises(CheckpointRuleMismatch) as refused:
        boot()
    message = str(refused.value)
    assert "'classic'" in message and "'lowdepth'" in message
    assert "--commit-rule classic" in message
    assert "wipe the checkpoint" in message
    # The way out the message names works: the node stays on the old
    # rule (a whole checkpoint restores, a torn one boots fresh).
    stayed = boot(commit_rule="classic")
    assert stayed.tusk.state.last_committed_round == (0 if torn else 2)


def test_checkpoint_restore_resumes_without_redelivery():
    """Committed-frontier checkpointing (beyond reference parity —
    consensus/src/lib.rs:18-19 marks persisted consensus state as
    intended-but-unimplemented).  A restored Tusk fed the FULL certificate
    history again (the worst-case catch-up replay: e.g. a lagging peer
    rebroadcasting old rounds through the Core) must not re-deliver
    anything already committed, and must resume committing new rounds."""
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)

    first = Tusk(c, gc_depth=50, fixed_coin=True)
    committed = feed(first, certs + [trigger])
    assert committed, "fixture must commit something"
    blob = first.state.snapshot_bytes()

    # "Restart": fresh Tusk, restore the frontier, replay ALL certificates
    # (pre-crash history + the trigger) as a catch-up flood would.
    second = Tusk(c, gc_depth=50, fixed_coin=True)
    second.state.restore(blob)
    assert second.state.last_committed_round == first.state.last_committed_round
    replayed = feed(second, certs + [trigger])
    assert replayed == [], (
        "restored frontier must keep replayed history out of the sequence: "
        f"{[(x.origin, x.round) for x in replayed]}"
    )

    # New rounds after the replay commit exactly what the uninterrupted
    # instance commits for them.
    more, tail_parents = make_certificates(5, 8, next_parents, names)
    more = more[1:]  # round-5 leader already exists as `trigger`
    _, trigger2 = mock_certificate(names[0], 9, tail_parents)
    got = feed(second, more + [trigger2])
    want = feed(first, more + [trigger2])
    assert [x.digest() for x in got] == [x.digest() for x in want]
    assert got, "the resumed instance must keep committing"
