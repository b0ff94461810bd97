"""MultiLeaderTusk vs its frozen oracle (consensus/golden_multileader.py).

The multileader rule CHANGES the commit sequence by design (K leader
slots per even round, slot-ordered anchor scan), so it gets its own
golden oracle and the full PR 4 replay/fuzz discipline: reference
scenarios, the quorum-starved burst shape, gc-window wrap, checkpoint
restore, and randomized DAGs (in-order and out-of-order delivery) must
be byte-identical between the live indexed rule and the naive dict-walk
oracle — under the pinned test coin AND under the real round-salted
schedule, which live rule and oracle each derive independently.

Alongside the equivalence suite this file pins the ISSUE 19 satellites:
slot-schedule determinism across processes (a subprocess with a
different PYTHONHASHSEED derives the identical schedule), slot-0
fairness (no authority out of slot 0 for more than committee_size
consecutive even rounds), the six-direction cross-rule checkpoint
refusal (classic/lowdepth/multileader, both ways each), flag plumbing,
the kernel refusal, and the per-segment audit rule marker with its
lying-marker counterpart.
"""

import asyncio
import os
import random
import subprocess
import sys

import pytest

from narwhal_tpu.consensus import (
    CheckpointRuleMismatch,
    Consensus,
    LowDepthTusk,
    MultiLeaderTusk,
    Tusk,
    leader_slots,
    resolve_commit_rule,
)
from narwhal_tpu.consensus.golden_multileader import GoldenMultiLeaderTusk
from narwhal_tpu.consensus.replay import read_audit, replay_segments, TAG_RULE
from narwhal_tpu.consensus.tusk import MULTILEADER_SLOTS
from tests.common import committee
from tests.test_consensus import (
    feed,
    genesis_digests,
    make_certificates,
    mock_certificate,
    sorted_names,
)
from tests.test_tusk_equivalence import _random_dag_certs


def both_walks(certs, gc_depth=50, fixed_coin=True):
    """Feed the identical delivery order through the frozen multileader
    oracle and the live indexed rule; assert byte-identical sequences."""
    c = committee()
    golden = feed(
        GoldenMultiLeaderTusk(c, gc_depth=gc_depth, fixed_coin=fixed_coin),
        certs,
    )
    live = feed(
        MultiLeaderTusk(c, gc_depth=gc_depth, fixed_coin=fixed_coin), certs
    )
    assert [bytes(x.digest()) for x in live] == [
        bytes(x.digest()) for x in golden
    ]
    return golden


def _ml_burst(rounds=12):
    """The multileader worst-case burst: rounds delivered ascending but
    every odd (support) round quorum-STARVED at 2f stake, so each even
    round's slots stay undecided (never dead — the non-supporting stake
    is withheld, not opposed) and nothing commits; the single withheld
    round-(rounds-1) support certificate is the trigger that flattens
    the whole chain in one process_certificate call."""
    c = committee()
    names = sorted_names()
    quorum = c.quorum_threshold()
    parents = genesis_digests(c)
    order, trigger = [], None
    for r in range(1, rounds + 1):
        nxt = set()
        stake = 0
        for name in names:
            digest, cert = mock_certificate(name, r, parents)
            nxt.add(digest)
            if r % 2 == 0:
                order.append(cert)
            elif stake + c.stake(name) < quorum:
                order.append(cert)
                stake += c.stake(name)
            elif trigger is None and r == rounds - 1:
                trigger = cert
        parents = nxt
    assert trigger is not None
    return order, trigger


def test_reference_scenarios_equivalence():
    """The reference consensus_tests.rs stream shapes, multileader live
    vs multileader oracle — plus the depth claim: the direct anchor
    fires at the round-3 support quorum, before classic's round-5
    trigger ever arrives."""
    c = committee()
    names = sorted_names()

    # commit_one's stream: rounds 1..4 + the round-5 trigger.
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)
    committed = both_walks(certs + [trigger])
    assert committed, "commit_one stream must commit under multileader"
    early = MultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
    first_commit_at = next(
        i for i, cert in enumerate(certs) if early.process_certificate(cert)
    )
    assert first_commit_at < len(certs) - 1, (
        "multileader must anchor before the stream (let alone the "
        "round-5 trigger) ends"
    )
    assert early.last_anchor == (2, 0)

    # dead_node: one authority silent for the whole run.
    certs, _ = make_certificates(1, 9, genesis_digests(c), names[:3])
    assert both_walks(certs)

    # missing_leader: the slot-0 authority idle for rounds 1-2.
    certs = []
    out, parents = make_certificates(1, 2, genesis_digests(c), names[1:])
    certs.extend(out)
    out, parents = make_certificates(3, 6, parents, names)
    certs.extend(out)
    _, trigger = mock_certificate(names[0], 7, parents)
    both_walks(certs + [trigger])


def test_backup_slot_rescues_dead_slot_zero():
    """The multileader mechanism itself: an even round whose slot-0
    leader never produced is provably DEAD (full child stake, zero
    support), so the scan anchors on slot 1 — a round classic (and
    lowdepth) can only reach indirectly, if at all."""
    c = committee()
    names = sorted_names()
    certs = []
    out, parents = make_certificates(1, 3, genesis_digests(c), names)
    certs.extend(out)
    # Round 4 without the fixed-coin slot-0 authority (names[0]).
    out, parents = make_certificates(4, 4, parents, names[1:])
    certs.extend(out)
    out, parents = make_certificates(5, 8, parents, names)
    certs.extend(out)
    got = both_walks(certs)
    live = MultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
    anchors = []
    for cert in certs:
        if live.process_certificate(cert):
            anchors.append(live.last_anchor)
    assert (4, 1) in anchors, anchors
    assert any(
        x.round == 4 and x.header.author == names[1] for x in got
    ), "the slot-1 leader of the dead-slot-0 round must be committed"


def test_quorum_starved_burst_equivalence():
    """Nothing commits while every support round sits at 2f stake; the
    single withheld support certificate then commits the entire chain —
    and the burst must match the oracle's byte-for-byte."""
    c = committee()
    order, trigger = _ml_burst(rounds=12)
    live = MultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
    for cert in order:
        assert live.process_certificate(cert) == [], (
            "quorum-starved stream must not commit before the trigger"
        )
    burst = live.process_certificate(trigger)
    assert len({x.round for x in burst if x.round % 2 == 0}) >= 4
    both_walks(order + [trigger])


def test_gc_window_wrap_equivalence():
    """Continuous commits across several multiples of a small gc window:
    end-state parity, not just sequence parity."""
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 30, genesis_digests(c), names)
    golden = GoldenMultiLeaderTusk(c, gc_depth=6, fixed_coin=True)
    live = MultiLeaderTusk(c, gc_depth=6, fixed_coin=True)
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
    """Both multileader walks restored from the same frontier blob ignore
    a full catch-up replay and then commit new rounds byte-identically."""
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)

    first = GoldenMultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
    assert feed(first, certs + [trigger])
    blob = first.state.snapshot_bytes()
    assert blob[:6] == b"NCKML1"

    golden = GoldenMultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
    golden.state.restore(blob)
    live = MultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
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
    rng = random.Random(0x311)
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
    rng = random.Random(0x31C)
    for _ in range(3):
        both_walks(_random_dag_certs(rng, rounds=14), gc_depth=4)


def test_real_salt_schedule_equivalence():
    """With the round-salted schedule live (fixed_coin=False) the oracle
    and the indexed rule derive the slot permutation INDEPENDENTLY (the
    oracle carries its own frozen copy of the schedule function) — they
    must still agree byte-for-byte on dense and fuzzed streams."""
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 20, genesis_digests(c), names)
    assert both_walks(certs, fixed_coin=False)
    rng = random.Random(0x5A1)
    for _ in range(4):
        order = _random_dag_certs(rng, rounds=rng.randint(8, 16))
        order.sort(key=lambda x: (x.round, rng.random()))
        both_walks(order, fixed_coin=False)


def test_prefix_consistency_across_delivery_orders():
    """Two nodes seeing the same DAG in different (causally valid)
    orders must never commit conflicting sequences: one's commit
    sequence is a prefix of the other's.  This is the safety property
    the undecided-slot scan stop exists for."""
    rng = random.Random(0xC04E)
    c = committee()
    for _ in range(5):
        certs = _random_dag_certs(rng, rounds=rng.randint(8, 18))
        a_order = sorted(certs, key=lambda x: (x.round, rng.random()))
        b_order = sorted(certs, key=lambda x: (x.round, rng.random()))
        a = feed(MultiLeaderTusk(c, gc_depth=50), a_order)
        b = feed(MultiLeaderTusk(c, gc_depth=50), b_order)
        a_d = [bytes(x.digest()) for x in a]
        b_d = [bytes(x.digest()) for x in b]
        n = min(len(a_d), len(b_d))
        assert a_d[:n] == b_d[:n], "commit sequences forked"


def test_multileader_commits_ahead_of_classic():
    """The latency mechanism, pinned structurally: on one round-ordered
    full stream the multileader frontier is NEVER behind classic (the
    slot-0 anchor fires at depth 1, on the support quorum), and the
    classic sequence is a strict prefix of the multileader one — the
    rule commits more, earlier, without reordering what classic
    commits."""
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 20, genesis_digests(c), names)
    classic = Tusk(c, gc_depth=50, fixed_coin=True)
    ml = MultiLeaderTusk(c, gc_depth=50, fixed_coin=True)
    seq_classic, seq_ml = [], []
    for cert in certs:
        seq_classic.extend(classic.process_certificate(cert))
        seq_ml.extend(ml.process_certificate(cert))
        assert (
            ml.state.last_committed_round
            >= classic.state.last_committed_round
        ), "multileader frontier must never trail classic"
    a = [bytes(x.digest()) for x in seq_classic]
    b = [bytes(x.digest()) for x in seq_ml]
    assert len(b) > len(a)
    assert b[: len(a)] == a


# -- slot schedule (ISSUE 19 satellite: determinism + fairness) ----------------


def test_slot_schedule_shape():
    """K slots, no duplicates, fixed_coin pins the first K sorted
    authorities — on every even round."""
    names = sorted_names()
    for r in range(0, 40, 2):
        slots = leader_slots(names, r)
        assert len(slots) == min(len(names), MULTILEADER_SLOTS)
        assert len(set(slots)) == len(slots)
        assert set(slots) <= set(names)
        assert leader_slots(names, r, fixed_coin=True) == names[
            :MULTILEADER_SLOTS
        ]


def test_slot_schedule_deterministic_across_processes():
    """Same committee + round ⇒ same slot permutation in a DIFFERENT
    process with a different PYTHONHASHSEED — the schedule must depend
    on nothing but (sorted keys, round), or two nodes (or one node
    across a restart) would anchor on different slots and fork."""
    names = sorted_names()
    local = "|".join(
        ",".join(str(x) for x in leader_slots(names, r))
        for r in range(0, 81, 2)
    )
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "from tests.common import keys\n"
        "from narwhal_tpu.consensus import leader_slots\n"
        "names = sorted(kp.name for kp in keys())\n"
        "print('|'.join(','.join(str(x) for x in leader_slots(names, r))\n"
        "      for r in range(0, 81, 2)))\n"
    ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for hashseed in ("0", "31337"):
        env = dict(os.environ)
        env.update({"PYTHONHASHSEED": hashseed, "JAX_PLATFORMS": "cpu"})
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip().splitlines()[-1] == local


def test_slot_zero_fairness():
    """No authority is absent from slot 0 for more than committee_size
    consecutive even rounds: slot 0 rotates, so over any n consecutive
    even rounds every authority holds it exactly once — the salt only
    shuffles the BACKUP slots."""
    names = sorted_names()
    n = len(names)
    last_seen = {name: None for name in names}
    worst = 0
    for i, r in enumerate(range(0, 2 * 25 * n, 2)):
        head = leader_slots(names, r)[0]
        if last_seen[head] is not None:
            worst = max(worst, i - last_seen[head])
        last_seen[head] = i
    assert set(last_seen.values()) != {None}
    assert all(v is not None for v in last_seen.values()), (
        "every authority must hold slot 0"
    )
    assert worst <= n, f"slot-0 starvation: {worst} even rounds between turns"


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


def test_env_and_arg_select_multileader(tmp_path, monkeypatch):
    """The env knob selects multileader; the constructor arg (the CLI
    path) beats a contradicting env."""
    certs = _stream()
    c = committee()

    monkeypatch.setenv("NARWHAL_COMMIT_RULE", "multileader")
    assert resolve_commit_rule() == "multileader"
    want = feed(GoldenMultiLeaderTusk(c, 50, fixed_coin=True), certs)
    _, cons = run_consensus(tmp_path, certs, want, "env")
    assert isinstance(cons.tusk, MultiLeaderTusk)
    assert cons.commit_rule == "multileader"

    monkeypatch.setenv("NARWHAL_COMMIT_RULE", "classic")
    want = feed(GoldenMultiLeaderTusk(c, 50, fixed_coin=True), certs)
    _, cons = run_consensus(
        tmp_path, certs, want, "arg-wins", commit_rule="multileader"
    )
    assert isinstance(cons.tusk, MultiLeaderTusk)
    assert resolve_commit_rule("multileader") == "multileader"


def test_checkpoint_refuses_cross_rule_restore_all_six(tmp_path):
    """A checkpoint written under any rule must refuse — loudly, naming
    BOTH rules, NOT via the torn-file fresh-frontier fallback — to
    restore under either other rule: classic↔lowdepth↔multileader, all
    six directions.  Same-rule restore stays fine."""
    c = committee()
    makers = {
        "classic": lambda: Tusk(c, 50, fixed_coin=True),
        "lowdepth": lambda: LowDepthTusk(c, 50, fixed_coin=True),
        "multileader": lambda: MultiLeaderTusk(c, 50, fixed_coin=True),
    }
    blobs = {}
    for rule, make in makers.items():
        writer = make()
        feed(writer, _stream())
        assert writer.state.last_committed_round > 0
        path = os.path.join(str(tmp_path), f"ckpt-{rule}.consensus.ckpt")
        with open(path, "wb") as f:
            f.write(writer.state.snapshot_bytes())
        blobs[rule] = (path, writer.state.last_committed_round)
    directions = 0
    for writer_rule, (path, frontier) in blobs.items():
        for reader_rule in makers:
            if reader_rule == writer_rule:
                cons = Consensus(
                    c, 50,
                    asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
                    fixed_coin=True,
                    checkpoint_path=path,
                    commit_rule=reader_rule,
                )
                assert cons.tusk.state.last_committed_round == frontier
                continue
            with pytest.raises(CheckpointRuleMismatch) as exc:
                Consensus(
                    c, 50,
                    asyncio.Queue(), asyncio.Queue(), asyncio.Queue(),
                    fixed_coin=True,
                    checkpoint_path=path,
                    commit_rule=reader_rule,
                )
            # The refusal must name both rules — the operator flipped
            # the flag on a live store and needs to know which way.
            assert repr(writer_rule) in str(exc.value)
            assert repr(reader_rule) in str(exc.value)
            directions += 1
    assert directions == 6


def test_audit_rule_marker_judged_per_segment(tmp_path):
    """A multileader audit segment records its rule and the replay judge
    picks the multileader oracle for it — while the same recording
    re-tagged classic fails its replay (the multileader recording
    commits a leader round the classic oracle never reaches on the
    trigger-less stream)."""
    c = committee()
    certs = _stream()

    want_ml = feed(GoldenMultiLeaderTusk(c, 50, fixed_coin=True), certs)
    audit_ml, _ = run_consensus(
        tmp_path, certs, want_ml, "seg-ml", commit_rule="multileader"
    )
    records = read_audit(audit_ml)
    assert records[1] == (TAG_RULE, b"multileader")
    verdict = replay_segments(c, 50, [audit_ml], fixed_coin=True)
    assert verdict["ok"], verdict["violations"]
    assert verdict["rules"] == ["multileader"]

    body = _stream()[:-1]
    want_tail = feed(GoldenMultiLeaderTusk(c, 50, fixed_coin=True), body)
    audit_tail, _ = run_consensus(
        tmp_path, body, want_tail, "seg-tail", commit_rule="multileader"
    )
    from narwhal_tpu.consensus.golden import GoldenTusk

    classic_replay = feed(GoldenTusk(c, 50, fixed_coin=True), body)
    assert len(want_tail) > len(classic_replay)
    lying = os.path.join(str(tmp_path), "seg-lying.audit.bin")
    with open(audit_tail, "rb") as f:
        blob = f.read()
    with open(lying, "wb") as f:
        f.write(
            blob.replace(
                b"M\x0b\x00\x00\x00multileader",
                b"M\x07\x00\x00\x00classic",
                1,
            )
        )
    verdict = replay_segments(c, 50, [lying], fixed_coin=True)
    assert not verdict["ok"]
    assert verdict["rules"] == ["classic"]
