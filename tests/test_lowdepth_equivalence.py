"""LowDepthTusk vs its frozen oracle (consensus/golden_lowdepth.py).

The lower-depth commit rule CHANGES the commit sequence by design, so it
gets its own golden oracle and the full PR 4 replay/fuzz discipline:
reference scenarios, multi-leader bursts, gc-window wrap, checkpoint
restore, and randomized DAGs (in-order and out-of-order delivery) must
be byte-identical between the live indexed rule and the naive dict-walk
oracle — while classic-rule runs stay byte-identical to GoldenTusk
(pinned here too, so the flag can never leak across arms).  The flag
plumbing is covered alongside: constructor/env resolution, the classic
default, the kernel refusal, cross-rule checkpoint refusal, and the
audit rule marker judged per segment.
"""

import asyncio
import os
import random

import pytest

from narwhal_tpu.consensus import (
    CheckpointRuleMismatch,
    Consensus,
    LowDepthTusk,
    Tusk,
    resolve_commit_rule,
)
from narwhal_tpu.consensus.golden import GoldenTusk
from narwhal_tpu.consensus.golden_lowdepth import GoldenLowDepthTusk
from narwhal_tpu.consensus.replay import read_audit, replay_segments, TAG_RULE
from tests.common import committee
from tests.test_consensus import (
    feed,
    genesis_digests,
    make_certificates,
    mock_certificate,
    sorted_names,
)
from tests.test_tusk_equivalence import _random_dag_certs


def both_walks(certs, gc_depth=50):
    """Feed the identical delivery order through the frozen lowdepth
    oracle and the live indexed rule; assert byte-identical sequences."""
    c = committee()
    golden = feed(
        GoldenLowDepthTusk(c, gc_depth=gc_depth, fixed_coin=True), certs
    )
    live = feed(LowDepthTusk(c, gc_depth=gc_depth, fixed_coin=True), certs)
    assert [bytes(x.digest()) for x in live] == [
        bytes(x.digest()) for x in golden
    ]
    return golden


def test_reference_scenarios_equivalence():
    """The reference consensus_tests.rs stream shapes, lowdepth live vs
    lowdepth oracle — plus the depth claim itself: at equal stream depth
    the lowdepth rule commits leaders the classic rule still holds."""
    c = committee()
    names = sorted_names()

    # commit_one's stream: rounds 1..4 + the round-5 trigger.  A single
    # round-5 certificate satisfies the classic trigger (f+1 support for
    # leader 2 already sits at round 3) but NOT the lowdepth direct gate
    # for leader 4 (2f+1 support needs a quorum of round-5 children), so
    # both rules commit exactly the leader-2 cone — the lowdepth rule
    # just commits it EARLIER: at the third round-3 certificate, four
    # deliveries before classic's round-5 trigger.
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)
    committed = both_walks(certs + [trigger])
    classic = feed(Tusk(c, gc_depth=50, fixed_coin=True), certs + [trigger])
    assert [bytes(x.digest()) for x in committed] == [
        bytes(x.digest()) for x in classic
    ]
    early = LowDepthTusk(c, gc_depth=50, fixed_coin=True)
    first_commit_at = next(
        i
        for i, cert in enumerate(certs)
        if early.process_certificate(cert)
    )
    assert first_commit_at < len(certs) - 1, (
        "lowdepth must commit before the stream (let alone the round-5 "
        "trigger) ends"
    )

    # dead_node: one authority silent for the whole run.
    certs, _ = make_certificates(1, 9, genesis_digests(c), names[:3])
    assert both_walks(certs)

    # missing_leader: the leader authority idle for rounds 1-2.
    certs = []
    out, parents = make_certificates(1, 2, genesis_digests(c), names[1:])
    certs.extend(out)
    out, parents = make_certificates(3, 6, parents, names)
    certs.extend(out)
    _, trigger = mock_certificate(names[0], 7, parents)
    both_walks(certs + [trigger])


def test_multi_leader_burst_equivalence():
    """Odd rounds delivered before even rounds: direct support exists
    before any leader does, so each leader's own (late) arrival is the
    trigger — the seeding path — and each commit burst must match the
    oracle's."""
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, 16, genesis_digests(c), names)
    order = sorted(certs, key=lambda x: (x.round % 2 == 0, x.round))
    _, trigger = mock_certificate(names[0], 17, parents)
    got = both_walks(order + [trigger])
    # Several leader rounds committed (multi-leader coverage).
    assert len({x.round for x in got if x.round % 2 == 0}) >= 3


def test_gc_window_wrap_equivalence():
    """Continuous commits across several multiples of a small gc window:
    end-state parity, not just sequence parity."""
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 30, genesis_digests(c), names)
    golden = GoldenLowDepthTusk(c, gc_depth=6, fixed_coin=True)
    live = LowDepthTusk(c, gc_depth=6, fixed_coin=True)
    got_g = feed(golden, certs)
    got_l = feed(live, certs)
    assert [bytes(x.digest()) for x in got_l] == [
        bytes(x.digest()) for x in got_g
    ]
    assert got_g, "fixture must commit"
    assert live.state.last_committed == golden.state.last_committed
    assert live.state.last_committed_round == golden.state.last_committed_round
    assert {
        r: set(v) for r, v in live.state.dag.items()
    } == {r: set(v) for r, v in golden.state.dag.items()}


def test_checkpoint_restore_equivalence():
    """Both lowdepth walks restored from the same frontier blob ignore a
    full catch-up replay and then commit new rounds byte-identically."""
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)

    first = GoldenLowDepthTusk(c, gc_depth=50, fixed_coin=True)
    assert feed(first, certs + [trigger])
    blob = first.state.snapshot_bytes()
    assert blob[:6] == b"NCKLD1"

    golden = GoldenLowDepthTusk(c, gc_depth=50, fixed_coin=True)
    golden.state.restore(blob)
    live = LowDepthTusk(c, gc_depth=50, fixed_coin=True)
    live.state.restore(blob)
    assert feed(golden, certs + [trigger]) == []
    assert feed(live, certs + [trigger]) == []

    more, tail_parents = make_certificates(5, 8, next_parents, names)
    more = more[1:]  # round-5 leader already exists as `trigger`
    _, trigger2 = mock_certificate(names[0], 9, tail_parents)
    got = feed(live, more + [trigger2])
    want = feed(golden, more + [trigger2])
    assert [bytes(x.digest()) for x in got] == [
        bytes(x.digest()) for x in want
    ]
    assert got, "the restored instances must keep committing"


def test_fuzz_equivalence_in_and_out_of_order():
    rng = random.Random(0x10D)
    for trial in range(6):
        certs = _random_dag_certs(rng, rounds=rng.randint(6, 20))
        order = list(certs)
        order.sort(key=lambda x: (x.round, rng.random()))
        both_walks(order)
    for trial in range(4):
        certs = _random_dag_certs(rng, rounds=rng.randint(6, 16))
        order = list(certs)
        # Children ahead of their parents in delivery order.
        order.sort(key=lambda x: x.round + rng.uniform(-2.2, 0.0))
        both_walks(order)


def test_fuzz_small_gc_depth_equivalence():
    rng = random.Random(0x1DC)
    for _ in range(3):
        both_walks(_random_dag_certs(rng, rounds=14), gc_depth=4)


def test_lowdepth_commits_ahead_of_classic():
    """The latency mechanism, pinned structurally: on one round-ordered
    full stream the lowdepth frontier is NEVER behind classic, runs 2
    rounds ahead whenever its direct path has fired (depth 1 vs depth 3
    on the leader), every leader is committed at a strictly earlier
    delivery index, and the full sequences agree where both committed
    (the lowdepth sequence extends the classic one, never reorders
    it)."""
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 20, genesis_digests(c), names)
    classic = Tusk(c, gc_depth=50, fixed_coin=True)
    lowdepth = LowDepthTusk(c, gc_depth=50, fixed_coin=True)
    gaps = set()
    seq_classic, seq_lowdepth = [], []
    first_commit = {}  # leader round → (lowdepth index, classic index)
    for i, cert in enumerate(certs):
        seq_classic.extend(classic.process_certificate(cert))
        seq_lowdepth.extend(lowdepth.process_certificate(cert))
        for tusk, slot in ((lowdepth, 0), (classic, 1)):
            r = tusk.state.last_committed_round
            if r and r not in first_commit:
                first_commit.setdefault(r, [None, None])
            for rr in first_commit:
                if rr <= r and first_commit[rr][slot] is None:
                    first_commit[rr][slot] = i
        if classic.state.last_committed_round > 0:
            gaps.add(
                lowdepth.state.last_committed_round
                - classic.state.last_committed_round
            )
    assert gaps == {0, 2}, gaps
    assert min(gaps) >= 0, "lowdepth frontier must never trail classic"
    reached_by_both = [
        v for v in first_commit.values() if None not in v
    ]
    assert reached_by_both
    assert all(low < cl for low, cl in reached_by_both), first_commit
    # Sequence agreement: lowdepth extends, never reorders.
    a = [bytes(x.digest()) for x in seq_classic]
    b = [bytes(x.digest()) for x in seq_lowdepth]
    assert len(b) > len(a)
    assert b[: len(a)] == a


# -- flag plumbing -------------------------------------------------------------


def run_consensus(tmp_path, certs, want, name, **kwargs):
    """Drive a Consensus instance over `certs`; assert the output equals
    `want`; return the audit segment path."""
    audit = os.path.join(str(tmp_path), f"{name}.audit.bin")

    async def go():
        rx, tx_primary, tx_output = (
            asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
        )
        cons = Consensus(
            committee(), 50, rx, tx_primary, tx_output,
            fixed_coin=True, audit_path=audit, **kwargs,
        )
        for cert in certs:
            rx.put_nowait(cert)
        task = asyncio.ensure_future(cons.run())
        out = [
            await asyncio.wait_for(tx_output.get(), 5) for _ in range(len(want))
        ]
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        cons._audit.close()
        assert [bytes(x.digest()) for x in out] == [
            bytes(x.digest()) for x in want
        ]
        return cons

    cons = asyncio.run(asyncio.wait_for(go(), 15))
    return audit, cons


def _stream():
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 8, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 9, next_parents)
    return certs + [trigger]


def test_classic_default_and_env_selection(tmp_path, monkeypatch):
    """Unset flag → lowdepth (the product's default since PR 33),
    byte-identical to GoldenLowDepthTusk; the env knob selects classic;
    the constructor arg beats the env (CLI precedence — node/main.py
    passes --commit-rule through as the arg); garbage raises."""
    certs = _stream()
    c = committee()

    monkeypatch.delenv("NARWHAL_COMMIT_RULE", raising=False)
    assert resolve_commit_rule() == "lowdepth"
    want = feed(GoldenLowDepthTusk(c, 50, fixed_coin=True), certs)
    _, cons = run_consensus(tmp_path, certs, want, "default")
    assert isinstance(cons.tusk, LowDepthTusk)
    assert cons.commit_rule == "lowdepth"
    # An empty value is unset, not garbage.
    monkeypatch.setenv("NARWHAL_COMMIT_RULE", "")
    assert resolve_commit_rule() == "lowdepth"

    monkeypatch.setenv("NARWHAL_COMMIT_RULE", "classic")
    assert resolve_commit_rule() == "classic"
    want = feed(GoldenTusk(c, 50, fixed_coin=True), certs)
    _, cons = run_consensus(tmp_path, certs, want, "env")
    assert isinstance(cons.tusk, Tusk) and not isinstance(
        cons.tusk, LowDepthTusk
    )
    assert cons.commit_rule == "classic"

    # Explicit arg (the CLI path) wins over the env.
    want = feed(GoldenLowDepthTusk(c, 50, fixed_coin=True), certs)
    _, cons = run_consensus(
        tmp_path, certs, want, "arg-wins", commit_rule="lowdepth"
    )
    assert cons.commit_rule == "lowdepth"

    monkeypatch.setenv("NARWHAL_COMMIT_RULE", "sideways")
    with pytest.raises(ValueError, match="sideways"):
        resolve_commit_rule()
    assert resolve_commit_rule("classic") == "classic"


def test_checkpoint_refuses_cross_rule_restore(tmp_path):
    """A checkpoint written under one rule must refuse — loudly, at boot,
    NOT via the torn-file fresh-frontier fallback — to restore under the
    other (both directions)."""
    c = committee()
    for writer, reader_rule in (
        (Tusk(c, 50, fixed_coin=True), "lowdepth"),
        (LowDepthTusk(c, 50, fixed_coin=True), "classic"),
    ):
        feed(writer, _stream())
        assert writer.state.last_committed_round > 0
        path = os.path.join(
            str(tmp_path), f"ckpt-{writer.commit_rule}.consensus.ckpt"
        )
        with open(path, "wb") as f:
            f.write(writer.state.snapshot_bytes())
        with pytest.raises(CheckpointRuleMismatch):
            Consensus(
                c, 50,
                asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
                fixed_coin=True,
                checkpoint_path=path,
                commit_rule=reader_rule,
            )
        # Same rule restores fine.
        cons = Consensus(
            c, 50,
            asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
            fixed_coin=True,
            checkpoint_path=path,
            commit_rule=writer.commit_rule,
        )
        assert (
            cons.tusk.state.last_committed_round
            == writer.state.last_committed_round
        )


def test_audit_rule_marker_judged_per_segment(tmp_path):
    """Each audit segment records its commit rule and the replay judge
    picks the matching oracle per segment: a lowdepth recording passes
    under the lowdepth oracle, is NOT judged by GoldenTusk, and a
    classic segment alongside it still judges classic — while a
    lowdepth recording whose marker claims classic fails its replay."""
    c = committee()
    certs = _stream()

    want_ld = feed(GoldenLowDepthTusk(c, 50, fixed_coin=True), certs)
    audit_ld, _ = run_consensus(
        tmp_path, certs, want_ld, "seg-ld", commit_rule="lowdepth"
    )
    records = read_audit(audit_ld)
    assert records[1] == (TAG_RULE, b"lowdepth")

    want_cl = feed(GoldenTusk(c, 50, fixed_coin=True), certs)
    audit_cl, _ = run_consensus(
        tmp_path, certs, want_cl, "seg-cl", commit_rule="classic"
    )
    assert read_audit(audit_cl)[1] == (TAG_RULE, b"classic")

    # Each judged under its own oracle, in one replay call.
    verdict = replay_segments(c, 50, [audit_ld], fixed_coin=True)
    assert verdict["ok"], verdict["violations"]
    assert verdict["rules"] == ["lowdepth"]
    verdict = replay_segments(c, 50, [audit_cl], fixed_coin=True)
    assert verdict["ok"], verdict["violations"]
    assert verdict["rules"] == ["classic"]

    # A lying marker (lowdepth recording re-tagged classic) must FAIL.
    # The stream matters: on a trigger-terminated stream both rules
    # commit the identical sequence (lowdepth only commits EARLIER), so
    # use the trigger-less stream where the lowdepth recording commits
    # two leader rounds the classic oracle never reaches — the recorded
    # sequence is then longer than the lying oracle's and diverges.
    body = _stream()[:-1]
    want_tail = feed(GoldenLowDepthTusk(c, 50, fixed_coin=True), body)
    audit_tail, _ = run_consensus(
        tmp_path, body, want_tail, "seg-tail", commit_rule="lowdepth"
    )
    classic_replay = feed(GoldenTusk(c, 50, fixed_coin=True), body)
    assert len(want_tail) > len(classic_replay)
    lying = os.path.join(str(tmp_path), "seg-lying.audit.bin")
    with open(audit_tail, "rb") as f:
        blob = f.read()
    with open(lying, "wb") as f:
        f.write(blob.replace(b"M\x08\x00\x00\x00lowdepth", b"M\x07\x00\x00\x00classic", 1))
    verdict = replay_segments(c, 50, [lying], fixed_coin=True)
    assert not verdict["ok"]
    assert verdict["rules"] == ["classic"]


def test_markerless_segment_replays_classic(tmp_path):
    """Pre-marker segments (and harness-written fixtures) still judge:
    no TAG_RULE record means the classic oracle, which is what recorded
    them."""
    c = committee()
    certs = _stream()
    want = feed(GoldenTusk(c, 50, fixed_coin=True), certs)
    audit, _ = run_consensus(
        tmp_path, certs, want, "seg-old", commit_rule="classic"
    )
    with open(audit, "rb") as f:
        blob = f.read()
    stripped = os.path.join(str(tmp_path), "seg-stripped.audit.bin")
    with open(stripped, "wb") as f:
        f.write(blob.replace(b"M\x07\x00\x00\x00classic", b"", 1))
    verdict = replay_segments(c, 50, [stripped], fixed_coin=True)
    assert verdict["ok"], verdict["violations"]
    assert verdict["rules"] == ["classic"]
