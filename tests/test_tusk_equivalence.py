"""Indexed Tusk vs the frozen r06 dict-walk oracle (consensus/golden.py).

The PR 4 commit-path rebuild (digest→certificate index, incremental
leader-support counters, one GC sweep per commit burst) must be
certificate-for-certificate — byte-identical commit order — equivalent to
the golden walk on every recorded stream: the reference scenarios,
multi-leader commit bursts, gc-window wrap, checkpoint restore, and
randomized DAGs (in-order and out-of-order delivery).  The white-box
tests additionally pin the two new state structures to their invariants:
index membership == DAG membership, and the incremental support counter
== the golden from-scratch rescan at every query point.
"""

import asyncio
import random

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.consensus import Consensus, Tusk
from narwhal_tpu.consensus.golden import GoldenTusk
from narwhal_tpu.primary.messages import Certificate, Header, genesis
from tests.common import committee, keys
from tests.test_consensus import (
    feed,
    genesis_digests,
    make_certificates,
    mock_certificate,
    sorted_names,
)


def both_walks(certs, gc_depth=50):
    """Feed the identical delivery order through the golden dict walk and
    the indexed walk; assert byte-identical commit sequences."""
    c = committee()
    golden = feed(GoldenTusk(c, gc_depth=gc_depth, fixed_coin=True), certs)
    indexed = feed(Tusk(c, gc_depth=gc_depth, fixed_coin=True), certs)
    assert [bytes(x.digest()) for x in indexed] == [
        bytes(x.digest()) for x in golden
    ]
    return golden


def _random_dag_certs(rng, rounds):
    names = sorted_names()
    certs = []
    parents = sorted(genesis_digests(committee()))
    for r in range(1, rounds + 1):
        live = rng.sample(names, rng.randint(3, 4))
        next_parents = []
        for name in sorted(live):
            chosen = rng.sample(
                parents, min(len(parents), rng.randint(3, len(parents)))
            )
            digest, cert = mock_certificate(name, r, chosen)
            certs.append(cert)
            next_parents.append(digest)
        parents = sorted(next_parents)
    return certs


def _commit_one(c, names):
    certs, next_parents = make_certificates(1, 4, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 5, next_parents)
    return certs + [trigger], lambda out: [x.round for x in out] == [1, 1, 1, 1, 2]


def _dead_node(c, names):
    certs, _ = make_certificates(1, 9, genesis_digests(c), names[:3])
    return certs, lambda out: len(out) == 16


def _not_enough_support(c, names):
    """The round-2 leader has one supporter in round 3 (f+1 = 2 needed),
    so it commits only later, through the round-4 leader's chain."""
    certs, parents = make_certificates(1, 1, genesis_digests(c), names[:3])
    leader_2_digest, cert = mock_certificate(names[0], 2, parents)
    certs.append(cert)
    out, parents = make_certificates(2, 2, parents, names[1:])
    certs.extend(out)
    next_parents = set()
    for name, cited in (
        (names[1], parents),
        (names[2], parents),
        (names[0], parents | {leader_2_digest}),
    ):
        d, cert = mock_certificate(name, 3, cited)
        certs.append(cert)
        next_parents.add(d)
    out, parents = make_certificates(4, 6, next_parents, names[:3])
    certs.extend(out)
    _, trigger = mock_certificate(names[0], 7, parents)
    return certs + [trigger], lambda out: [
        x.round for x in out if x.origin == names[0] and x.round % 2 == 0
    ] == [2, 4]


def _missing_leader(c, names):
    certs, parents = make_certificates(1, 2, genesis_digests(c), names[1:])
    out, parents = make_certificates(3, 6, parents, names)
    _, trigger = mock_certificate(names[0], 7, parents)
    return certs + out + [trigger], lambda out: bool(out)


@pytest.mark.parametrize(
    "scenario",
    [_commit_one, _dead_node, _not_enough_support, _missing_leader],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_reference_scenarios_equivalence(scenario):
    """The four reference consensus_tests.rs scenarios, golden vs indexed."""
    certs, holds = scenario(committee(), sorted_names())
    assert holds(both_walks(certs))


def test_multi_leader_burst_equivalence():
    """Odd rounds delivered before even rounds: nothing commits until one
    trigger certificate, which then commits the ENTIRE chain of linked
    leaders in one process_certificate call — the worst case for the
    per-certificate golden GC sweep the indexed walk batches."""
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, 16, genesis_digests(c), names)
    order = sorted(certs, key=lambda x: (x.round % 2 == 0, x.round))
    _, trigger = mock_certificate(names[0], 17, parents)

    golden = GoldenTusk(c, gc_depth=50, fixed_coin=True)
    indexed = Tusk(c, gc_depth=50, fixed_coin=True)
    assert feed(golden, order) == []
    assert feed(indexed, order) == []
    got = indexed.process_certificate(trigger)
    want = golden.process_certificate(trigger)
    assert [bytes(x.digest()) for x in got] == [
        bytes(x.digest()) for x in want
    ]
    # The burst spans several leader rounds (multi-leader commit).
    assert len({x.round for x in got if x.round % 2 == 0}) >= 3


def test_gc_window_wrap_equivalence():
    """Continuous commits across several multiples of a small gc window:
    the batched sweep must leave the DAG (and therefore every later
    commit) exactly where the golden per-certificate sweep leaves it."""
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 30, genesis_digests(c), names)
    golden = GoldenTusk(c, gc_depth=6, fixed_coin=True)
    indexed = Tusk(c, gc_depth=6, fixed_coin=True)
    got_g = feed(golden, certs)
    got_i = feed(indexed, certs)
    assert [bytes(x.digest()) for x in got_i] == [
        bytes(x.digest()) for x in got_g
    ]
    assert got_g, "fixture must commit"
    # End-state parity, not just sequence parity: same frontier, same
    # surviving DAG window.
    assert indexed.state.last_committed == golden.state.last_committed
    assert indexed.state.last_committed_round == golden.state.last_committed_round
    assert {
        r: set(v) for r, v in indexed.state.dag.items()
    } == {r: set(v) for r, v in golden.state.dag.items()}


@pytest.mark.parametrize(
    "gc_depth, rounds",
    [(50, 4), (6, 20)],
    ids=["near_frontier", "far_frontier"],
)
def test_checkpoint_restore_equivalence(gc_depth, rounds):
    """Both walks restored from the same frontier blob must ignore a full
    catch-up replay of pre-crash history and then commit new rounds
    byte-identically.  `far_frontier`: the blob's frontier lies more than
    a whole gc window above the restored (empty) DAG."""
    c = committee()
    names = sorted_names()
    certs, next_parents = make_certificates(1, rounds, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], rounds + 1, next_parents)

    first = GoldenTusk(c, gc_depth=gc_depth, fixed_coin=True)
    assert feed(first, certs + [trigger])
    blob = first.state.snapshot_bytes()
    if rounds > gc_depth:
        assert first.state.last_committed_round > gc_depth + 1

    golden = GoldenTusk(c, gc_depth=gc_depth, fixed_coin=True)
    golden.state.restore(blob)
    indexed = Tusk(c, gc_depth=gc_depth, fixed_coin=True)
    indexed.state.restore(blob)
    assert feed(golden, certs + [trigger]) == []
    assert feed(indexed, certs + [trigger]) == []

    more, tail_parents = make_certificates(
        rounds + 1, rounds + 6, next_parents, names
    )
    more = more[1:]  # that round's leader already exists as `trigger`
    _, trigger2 = mock_certificate(names[0], rounds + 7, tail_parents)
    got = feed(indexed, more + [trigger2])
    want = feed(golden, more + [trigger2])
    assert [bytes(x.digest()) for x in got] == [
        bytes(x.digest()) for x in want
    ]
    assert got, "the restored instances must keep committing"


@pytest.mark.parametrize(
    "order, seed",
    [("in_order", 0x1D5 + i) for i in range(6)]
    + [("out_of_order", 0xBEEF + i) for i in range(4)],
)
def test_fuzz_equivalence_in_and_out_of_order(order, seed):
    """One random DAG a case, each from its own seed, so a failing DAG is
    named by its id."""
    rng = random.Random(seed)
    if order == "in_order":
        certs = _random_dag_certs(rng, rounds=rng.randint(6, 20))
        certs.sort(key=lambda x: (x.round, rng.random()))
    else:
        certs = _random_dag_certs(rng, rounds=rng.randint(6, 16))
        # Children ahead of their parents in delivery order.
        certs.sort(key=lambda x: x.round + rng.uniform(-2.2, 0.0))
        assert any(
            a.round > b.round for a, b in zip(certs, certs[1:])
        ), "fixture produced no out-of-order pair"
    both_walks(certs)


@pytest.mark.parametrize("seed", [0x6C, 0x6D, 0x6E])
def test_fuzz_small_gc_depth_equivalence(seed):
    both_walks(_random_dag_certs(random.Random(seed), rounds=14), gc_depth=4)


# -- white-box: the two new indexed structures --------------------------------


def _dag_index(state):
    return {
        d: cert
        for authorities in state.dag.values()
        for (d, cert) in authorities.values()
    }


def test_digest_index_is_exactly_dag_membership():
    """After arbitrary feeds (commits, GC, replays), digest_index holds
    exactly the certificates currently in the DAG — the invariant
    order_dag/linked rely on for O(1) parent resolution."""
    rng = random.Random(0xF00)
    for gc_depth in (50, 6):
        for _ in range(3):
            certs = _random_dag_certs(rng, rounds=rng.randint(8, 20))
            tusk = Tusk(committee(), gc_depth=gc_depth, fixed_coin=True)
            feed(tusk, certs)
            want = _dag_index(tusk.state)
            assert dict(tusk.state.digest_index) == want
            # Replay everything (catch-up flood): still exact.
            feed(tusk, certs)
            assert dict(tusk.state.digest_index) == _dag_index(tusk.state)


def _rescan_support(tusk, leader_round):
    got = tusk.leader(leader_round, tusk.state.dag)
    if got is None:
        return 0
    leader_digest = got[0]
    return sum(
        tusk.committee.stake(cert.origin)
        for _, cert in tusk.state.dag.get(leader_round + 1, {}).values()
        if leader_digest in cert.header.parents
    )


def test_incremental_support_matches_rescan():
    """At every point the commit rule can query it (even rounds above the
    committed frontier), the incremental counter equals the golden
    from-scratch rescan of the child round — including streams where the
    leader arrives AFTER its supporters (the seeding path)."""
    rng = random.Random(0x5AB)
    for trial in range(5):
        certs = _random_dag_certs(rng, rounds=rng.randint(6, 16))
        order = list(certs)
        if trial % 2:
            order.sort(key=lambda x: x.round + rng.uniform(-2.2, 0.0))
        tusk = Tusk(committee(), gc_depth=50, fixed_coin=True)
        for cert in order:
            tusk.process_certificate(cert)
            top = max(tusk.state.dag)
            for lr in range(
                tusk.state.last_committed_round + 2, top + 1, 2
            ):
                assert tusk._support.get(lr, 0) == _rescan_support(
                    tusk, lr
                ), (trial, lr)


def test_support_exact_after_equivocation_overwrite():
    """An equivocating certificate replacing a (round, origin) slot —
    either a supporter changing its parents or the leader itself changing
    digest — must leave the counter equal to the rescan (the recompute
    path)."""
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, 4, genesis_digests(c), names)
    tusk = Tusk(c, gc_depth=50, fixed_coin=True)
    feed(tusk, certs)

    def equivocate(author, round_, parents):
        # Mock certs leave header.id at zero (digest ignores parents);
        # an equivocating twin needs a genuinely different digest, so
        # compute the real header id.
        header = Header(
            author=author, round=round_, payload={}, parents=set(parents)
        )
        header.id = header.compute_digest()
        return Certificate(header=header)

    # Supporter overwrite: names[1]'s round-3 certificate re-issued with a
    # thinner parent set that drops the round-2 leader.
    leader_digest = tusk.leader(2, tusk.state.dag)[0]
    thin = {
        d for d, _ in tusk.state.dag[2].values() if d != leader_digest
    }
    twin = equivocate(names[1], 3, thin)
    assert twin.digest() != tusk.state.dag[3][names[1]][0]
    tusk.insert_certificate(twin)
    assert tusk._support.get(2, 0) == _rescan_support(tusk, 2)

    # Leader overwrite: the round-2 leader re-issued with different
    # parents → different digest; all round-3 support must be re-counted
    # against the NEW digest.
    old_leader = tusk.state.dag[2][names[0]][1]
    relead = equivocate(
        names[0], 2, set(list(old_leader.header.parents)[:3])
    )
    assert relead.digest() != old_leader.digest()
    tusk.insert_certificate(relead)
    assert tusk._support.get(2, 0) == _rescan_support(tusk, 2)


def test_runner_burst_drains_backlog():
    """A backlog queued before the runner wakes is processed in ONE drain
    (the drain histogram observes one large batch, not one-per-wakeup),
    and the delivered order matches the pure state machine."""
    reg = metrics.registry()
    reg.reset()

    async def go():
        c = committee()
        names = sorted_names()
        certs, next_parents = make_certificates(
            1, 8, genesis_digests(c), names
        )
        _, trigger = mock_certificate(names[0], 9, next_parents)
        certs.append(trigger)

        rx, tx_primary, tx_output = (
            asyncio.Queue(),
            asyncio.Queue(),
            asyncio.Queue(),
        )
        consensus = Consensus(
            c, 50, rx, tx_primary, tx_output, fixed_coin=True
        )
        for cert in certs:  # whole backlog queued BEFORE the runner starts
            rx.put_nowait(cert)
        task = asyncio.ensure_future(consensus.run())
        want = feed(Tusk(c, gc_depth=50, fixed_coin=True), certs)
        assert want
        out = [
            await asyncio.wait_for(tx_output.get(), 5)
            for _ in range(len(want))
        ]
        assert [bytes(x.digest()) for x in out] == [
            bytes(x.digest()) for x in want
        ]
        task.cancel()

        drain = reg.histograms["consensus.drain_batch_size"]
        assert drain.count >= 1
        assert drain.sum == len(certs), "every certificate drained exactly once"
        # The backlog collapsed into few wakeups, not one per certificate.
        assert drain.count < len(certs)

    asyncio.run(asyncio.wait_for(go(), 15))
