"""Proposer tests (analog of reference proposer_tests.rs:7-68): empty header
on timeout; payload header by size."""

import asyncio

import pytest

from narwhal_tpu.crypto import SignatureService, digest32
from narwhal_tpu.primary.messages import genesis
from narwhal_tpu.primary.proposer import Proposer
from tests.common import committee, keys


@pytest.fixture
def run():
    def _run(coro):
        return asyncio.run(asyncio.wait_for(coro, 15))

    return _run


def make_proposer(
    c, kp, header_size=1_000, delay_ms=50, min_delay_ms=0, linger_ms=0
):
    rx_core, rx_workers, tx_core = (
        asyncio.Queue(),
        asyncio.Queue(),
        asyncio.Queue(),
    )
    p = Proposer(
        kp.name,
        c,
        SignatureService(kp),
        header_size,
        delay_ms,
        rx_core,
        rx_workers,
        tx_core,
        min_header_delay_ms=min_delay_ms,
        header_linger_ms=linger_ms,
    )
    return p, rx_core, rx_workers, tx_core


def test_empty_header_on_timeout(run):
    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, tx_core = make_proposer(c, kp, header_size=1_000, delay_ms=50)
        task = asyncio.ensure_future(p.run())
        header = await asyncio.wait_for(tx_core.get(), 5)
        assert header.round == 1 and header.payload == {}
        assert header.parents == {x.digest() for x in genesis(c)}
        header.verify(c)
        task.cancel()

    run(go())


def test_payload_header_by_size(run):
    async def go():
        c = committee()
        kp = keys()[0]
        # Huge delay: sealing must be triggered by payload size alone.
        p, _, rx_workers, tx_core = make_proposer(
            c, kp, header_size=32, delay_ms=60_000
        )
        task = asyncio.ensure_future(p.run())
        digest = digest32(b"batch")
        await rx_workers.put((digest, 3))
        header = await asyncio.wait_for(tx_core.get(), 5)
        assert header.payload == {digest: 3} and header.round == 1
        header.verify(c)
        task.cancel()

    run(go())


def test_round_advance_requires_parents(run):
    async def go():
        c = committee()
        kp = keys()[0]
        p, rx_core, _, tx_core = make_proposer(c, kp, header_size=1_000, delay_ms=50)
        task = asyncio.ensure_future(p.run())
        first = await asyncio.wait_for(tx_core.get(), 5)
        assert first.round == 1
        # No parents delivered: proposer must NOT mint round-2 headers.
        await asyncio.sleep(0.3)
        assert tx_core.empty()
        # Parents for round 1 arrive: round advances and a header appears.
        parents = [digest32(bytes([i]) * 3) for i in range(3)]
        await rx_core.put((parents, 1))
        second = await asyncio.wait_for(tx_core.get(), 5)
        assert second.round == 2 and second.parents == set(parents)
        task.cancel()

    run(go())


# --- round-cadence edges (ISSUE r10) -----------------------------------------


def test_parents_after_expired_deadline_mint_immediately(run):
    """Parents arriving AFTER max_header_delay already expired must mint
    the next header right away, not re-arm a fresh full delay."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, rx_core, _, tx_core = make_proposer(
            c, kp, header_size=1_000, delay_ms=50
        )
        task = asyncio.ensure_future(p.run())
        first = await asyncio.wait_for(tx_core.get(), 5)
        assert first.round == 1
        # Let the deadline expire several times over with no parents.
        await asyncio.sleep(0.4)
        assert tx_core.empty()
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await rx_core.put(([digest32(bytes([i]) * 3) for i in range(3)], 1))
        second = await asyncio.wait_for(tx_core.get(), 5)
        # Immediate (empty-payload, expired timer): far less than a fresh
        # 50 ms delay, with slack for a loaded host.
        assert loop.time() - t0 < 2.0
        assert second.round == 2
        task.cancel()

    run(go())


def test_min_header_delay_proposes_partial_payload(run):
    """With the min-delay cadence on, a parent quorum plus ANY payload
    proposes after min_header_delay instead of riding max_header_delay
    (here: effectively never) waiting for header_size bytes."""

    async def go():
        c = committee()
        kp = keys()[0]
        # max delay far beyond the test timeout: only the min-delay path
        # can mint this header.
        p, _, rx_workers, tx_core = make_proposer(
            c, kp, header_size=1_000_000, delay_ms=60_000, min_delay_ms=10
        )
        task = asyncio.ensure_future(p.run())
        digest = digest32(b"one small batch")
        await rx_workers.put((digest, 0))
        header = await asyncio.wait_for(tx_core.get(), 5)
        assert header.round == 1 and header.payload == {digest: 0}
        task.cancel()

    run(go())


def test_min_header_delay_empty_rounds_still_wait_max(run):
    """Empty-payload rounds must NOT fire at the min cadence — an idle
    committee rides max_header_delay exactly as before the knob."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, tx_core = make_proposer(
            c, kp, header_size=1_000, delay_ms=400, min_delay_ms=10
        )
        task = asyncio.ensure_future(p.run())
        # Well past several min periods, still inside max: no header.
        await asyncio.sleep(0.15)
        assert tx_core.empty()
        header = await asyncio.wait_for(tx_core.get(), 5)
        assert header.round == 1 and header.payload == {}
        task.cancel()

    run(go())


def test_min_header_delay_rate_limits_full_payload(run):
    """min_header_delay is also the round-cadence floor: two consecutive
    size-triggered headers must be at least min_header_delay apart."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, rx_core, rx_workers, tx_core = make_proposer(
            c, kp, header_size=16, delay_ms=60_000, min_delay_ms=200
        )
        task = asyncio.ensure_future(p.run())
        loop = asyncio.get_running_loop()
        await rx_workers.put((digest32(b"a"), 0))
        first = await asyncio.wait_for(tx_core.get(), 5)
        t1 = loop.time()
        assert first.round == 1
        # Round 2 payload + parents are ready almost immediately...
        await rx_workers.put((digest32(b"b"), 0))
        await rx_core.put(([digest32(bytes([i]) * 3) for i in range(3)], 1))
        second = await asyncio.wait_for(tx_core.get(), 5)
        # ...but the mint waits out the min delay.
        assert loop.time() - t1 >= 0.15
        assert second.round == 2
        task.cancel()

    run(go())


def test_round_advance_observed_exactly_once_per_advance(run):
    """primary.round_advance_seconds gets exactly one observation per
    actual advance — duplicate or stale parent deliveries (queue path or
    the direct deliver_parents callback) observe nothing."""

    async def go():
        from narwhal_tpu import metrics

        c = committee()
        kp = keys()[0]
        p, rx_core, _, tx_core = make_proposer(c, kp, header_size=1_000, delay_ms=50)
        hist = metrics.histogram("primary.round_advance_seconds")
        base = hist.count
        task = asyncio.ensure_future(p.run())
        parents = [digest32(bytes([i]) * 3) for i in range(3)]

        # First advance (1 -> 2): arms _last_advance, no period yet.
        p.deliver_parents(parents, 1)
        assert p.round == 2 and hist.count == base
        # Second advance (2 -> 3): one observation.
        p.deliver_parents(parents, 2)
        assert p.round == 3 and hist.count == base + 1
        # Stale and duplicate deliveries: no advance, no observation.
        p.deliver_parents(parents, 2)
        p.deliver_parents(parents, 1)
        assert p.round == 3 and hist.count == base + 1
        # The queue path shares the same dedupe.
        await rx_core.put((parents, 2))
        await asyncio.sleep(0.1)
        assert p.round == 3 and hist.count == base + 1
        await rx_core.put((parents, 3))
        await asyncio.sleep(0.1)
        assert p.round == 4 and hist.count == base + 2
        task.cancel()

    run(go())


def test_deliver_parents_wakes_run_loop_and_stamps_round_trace(run):
    """The Core's direct callback must wake the proposer out of its queue
    wait (minting the next header without a queue round-trip) and stamp
    the round-cadence trace (header_proposed + round_advance)."""

    async def go():
        from narwhal_tpu import metrics

        c = committee()
        kp = keys()[0]
        p, _, _, tx_core = make_proposer(c, kp, header_size=1_000, delay_ms=50)
        task = asyncio.ensure_future(p.run())
        first = await asyncio.wait_for(tx_core.get(), 5)
        assert first.round == 1
        parents = [digest32(bytes([i]) * 3) for i in range(3)]
        p.deliver_parents(parents, 1)
        second = await asyncio.wait_for(tx_core.get(), 5)
        assert second.round == 2 and second.parents == set(parents)
        rt = metrics.round_trace().entries
        assert "header_proposed" in rt.get("1", {})
        assert "round_advance" in rt.get("1", {})
        assert "header_proposed" in rt.get("2", {})
        task.cancel()

    run(go())


def test_min_header_delay_clamped_to_max(run):
    """min_header_delay above max_header_delay is incoherent (payload
    rounds would cycle SLOWER than empty ones) — it clamps to the max."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, _ = make_proposer(
            c, kp, header_size=1_000, delay_ms=100, min_delay_ms=500
        )
        assert p.min_header_delay == p.max_header_delay == 0.1

    run(go())


def test_header_linger_holds_mint_and_cites_late_parent(run):
    """With header_linger on, a round advance arms a linger window: the
    fast (payload-ready) mint path holds until it passes, and a
    post-quorum certificate forwarded via deliver_late_parent inside
    the window lands in the minted header's parent set.  Round 1 (no
    advance yet) is unaffected."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, rx_workers, tx_core = make_proposer(
            c, kp, header_size=16, delay_ms=60_000, linger_ms=300
        )
        task = asyncio.ensure_future(p.run())
        loop = asyncio.get_running_loop()
        await rx_workers.put((digest32(b"a"), 0))
        first = await asyncio.wait_for(tx_core.get(), 5)
        assert first.round == 1  # no linger before the first advance
        parents = [digest32(bytes([i]) * 3) for i in range(3)]
        late = digest32(b"the straggler certificate")
        t0 = loop.time()
        p.deliver_parents(parents, 1)
        await rx_workers.put((digest32(b"b"), 0))
        # Payload + parents are ready, but the linger window holds...
        await asyncio.sleep(0.1)
        assert tx_core.empty()
        # ...long enough for a post-quorum certificate to be merged.
        p.deliver_late_parent(late, 1)
        second = await asyncio.wait_for(tx_core.get(), 5)
        assert loop.time() - t0 >= 0.25
        assert second.round == 2
        assert second.parents == set(parents) | {late}
        task.cancel()

    run(go())


def test_deliver_late_parent_drops_stale_duplicate_and_consumed(run):
    """The late-parent merge is citation-widening only: a stale round, a
    duplicate digest, or an already-consumed parent set are silently
    dropped."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, _ = make_proposer(c, kp, linger_ms=100)
        parents = [digest32(bytes([i]) * 3) for i in range(3)]
        p.deliver_parents(parents, 1)
        assert p.round == 2
        # Stale round (certificate of round 2 while proposing round 2 —
        # only parent-round certificates, round 1, merge).
        p.deliver_late_parent(digest32(b"x"), 2)
        assert len(p.last_parents) == 3
        # Duplicate digest: no-op.
        p.deliver_late_parent(parents[0], 1)
        assert len(p.last_parents) == 3
        # Fresh parent-round digest: merged.
        extra = digest32(b"y")
        p.deliver_late_parent(extra, 1)
        assert p.last_parents[-1] == extra and len(p.last_parents) == 4
        # Consumed parent set (post-mint): no resurrection.
        p.last_parents = []
        p.deliver_late_parent(digest32(b"z"), 1)
        assert p.last_parents == []

    run(go())


def test_header_linger_clamped_to_max(run):
    """A linger window the max deadline always truncates would silently
    never run full length — it clamps to the max, loudly."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, _ = make_proposer(
            c, kp, header_size=1_000, delay_ms=100, linger_ms=500
        )
        assert p.header_linger == p.max_header_delay == 0.1

    run(go())


def test_late_parent_cited_without_linger_and_mint_not_held(run):
    """header_linger 0 (the source's): a post-quorum certificate that is
    in hand when the header is minted is cited, and nothing is held for
    it — the timer-bound mint falls at the timer, and the payload-ready
    mint goes out at once and drops what comes after it."""

    async def go():
        c = committee()
        kp = keys()[0]
        loop = asyncio.get_running_loop()
        parents = [digest32(bytes([i]) * 3) for i in range(3)]
        late = digest32(b"the fourth certificate")

        # Timer-bound: empty payload, minted at max_header_delay.
        p, _, _, tx_core = make_proposer(c, kp, delay_ms=200)
        assert p.header_linger == 0
        task = asyncio.ensure_future(p.run())
        first = await asyncio.wait_for(tx_core.get(), 5)
        t0 = loop.time()
        p.deliver_parents(parents, first.round)
        p.deliver_late_parent(late, first.round)
        second = await asyncio.wait_for(tx_core.get(), 5)
        assert 0.15 <= loop.time() - t0 < 0.4  # the timer, no later
        assert second.parents == set(parents) | {late}
        task.cancel()

        # Payload-ready: minted as soon as parents and payload meet.
        p, _, rx_workers, tx_core = make_proposer(
            c, kp, header_size=16, delay_ms=60_000
        )
        task = asyncio.ensure_future(p.run())
        await rx_workers.put((digest32(b"a"), 0))
        first = await asyncio.wait_for(tx_core.get(), 5)
        await rx_workers.put((digest32(b"b"), 0))
        await asyncio.sleep(0.02)
        t0 = loop.time()
        p.deliver_parents(parents, first.round)
        second = await asyncio.wait_for(tx_core.get(), 5)
        assert loop.time() - t0 < 0.1  # not held
        assert second.parents == set(parents)
        p.deliver_late_parent(late, first.round)  # consumed set: dropped
        assert p.last_parents == []
        task.cancel()

    run(go())


def test_header_parents_histogram_and_late_counter_count_always(run):
    """primary.header_parents observes every minted header's parent
    count and primary.late_parents_cited counts with header_linger 0."""
    from narwhal_tpu import metrics

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, tx_core = make_proposer(c, kp, delay_ms=30)
        hist = metrics.registry().histograms["primary.header_parents"]
        cited = metrics.registry().counters["primary.late_parents_cited"]
        count0, sum0, cited0 = hist.count, hist.sum, cited.value
        task = asyncio.ensure_future(p.run())
        first = await asyncio.wait_for(tx_core.get(), 5)  # 4 genesis parents
        p.deliver_parents([digest32(bytes([i]) * 3) for i in range(3)], 1)
        p.deliver_late_parent(digest32(b"late"), 1)
        await asyncio.wait_for(tx_core.get(), 5)
        task.cancel()
        assert len(first.parents) == 4
        assert hist.count - count0 == 2 and hist.sum - sum0 == 8
        assert cited.value - cited0 == 1

    run(go())


async def mint(p, tx_core, round_, payload):
    """One own header of ``round_`` with ``payload``, minted directly."""
    p.round = round_
    p.last_parents = [digest32(b"parent")]
    p.digests = list(payload)
    p.payload_size = 32 * len(payload)
    await p._make_header()
    header = tx_core.get_nowait()
    assert header.round == round_ and header.payload == dict(payload)
    return header


def test_own_commit_settles_and_reproposes_what_it_skipped(run):
    """Own rounds 1-3 proposed; round 3 commits first: rounds 1 and 2 can
    never commit (Tusk skips at or under the origin's last committed
    round), so their digests go back to the FRONT in their old order,
    are counted, marked and logged to the flight ring, and ride the next
    header.  A second report changes nothing."""
    from narwhal_tpu import metrics

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, tx_core = make_proposer(c, kp)
        reg = metrics.registry()
        orphaned0 = reg.counters["primary.own_headers_orphaned"].value
        hist = reg.histograms["primary.payload_reproposed"]
        count0, sum0 = hist.count, hist.sum
        a, b, d, e, f = (digest32(bytes([i]) * 5) for i in range(5))
        await mint(p, tx_core, 1, [(a, 0), (b, 0)])
        await mint(p, tx_core, 2, [])  # an empty header: nothing to carry
        await mint(p, tx_core, 3, [(d, 0)])
        await mint(p, tx_core, 4, [(e, 1)])
        p.digests, p.payload_size = [(f, 0)], 32
        p.deliver_commit(2, False)  # a peer's certificate: settles nothing
        assert sorted(p._unsettled) == [1, 2, 3, 4]
        p.deliver_commit(3, True)
        assert sorted(p._unsettled) == [4]  # may still commit: kept
        assert p.digests == [(a, 0), (b, 0), (f, 0)]
        assert p.payload_size == 96
        assert reg.counters["primary.own_headers_orphaned"].value - orphaned0 == 2
        assert (hist.count - count0, hist.sum - sum0) == (1, 2)
        entry = reg.trace.entries[bytes(a).hex()]
        assert entry["header"] <= entry["reproposed"]
        assert "reproposed" not in reg.trace.entries[bytes(d).hex()]
        event = [
            ev for ev in reg.flight.events
            if ev["kind"] == "payload_reproposed"
        ][-1]
        assert event["rounds"] == [1, 2] and event["digests"] == 2
        p.deliver_commit(3, True)
        assert p.digests == [(a, 0), (b, 0), (f, 0)]
        header = await mint(p, tx_core, 5, p.digests)
        assert list(header.payload) == [a, b, f]
        # The second `header` stamp does not move the first.
        assert reg.trace.entries[bytes(a).hex()]["header"] == entry["header"]

    run(go())


def test_garbage_horizon_orphans_without_an_own_commit(run):
    """No own commit at all (a header that never got its certificate):
    the kept round is re-proposed once the committed round has passed it
    by more than gc_depth (State.gc's predicate), not before."""

    async def go():
        c = committee()
        kp = keys()[0]
        p, _, _, tx_core = make_proposer(c, kp)
        assert p.gc_depth == 50
        a = digest32(b"never certified")
        await mint(p, tx_core, 7, [(a, 0)])
        p.deliver_commit(57, False)  # 7 + 50 >= 57: may still commit
        assert sorted(p._unsettled) == [7] and p.digests == []
        p.deliver_commit(58, False)
        assert p._unsettled == {} and p.digests == [(a, 0)]

    run(go())
