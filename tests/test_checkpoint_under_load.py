"""Checkpoint restore under CONCURRENT inserts (ISSUE 6 satellite).

The existing torn-file tests are quiescent: they restore into an idle
instance.  Here a live Consensus runner is killed MID-STREAM while a
feeder keeps inserting certificates, then restarted over the same
checkpoint file and hit with the full catch-up flood (pre-crash history
replayed INTO consensus, like a lagging peer's sync storm).

Since ISSUE 10 each incarnation runs under a seeded
``ExploringEventLoop`` (narwhal_tpu/analysis/schedule.py): the
feeder/runner/drain interleaving — including where exactly the "crash"
lands relative to the stream — is pinned by the seed instead of
whatever the host scheduler felt like, and the waits are scheduling-tick
polls rather than wall-clock sleeps (the only residual real-time input
is the checkpoint fsync executor thread, whose completion timing cannot
be simulated; the wall deadlines below are deadlock guards, not pacing).
Asserted:

- the restart restores a non-zero frontier from the checkpoint;
- the frozen golden oracle, replayed over the two audit segments (with
  the restore marker applied at the segment boundary), reproduces each
  incarnation's recorded commit sequence byte-identically and passes the
  uniqueness/causal-history invariants (consensus/replay.py);
- the concatenated, re-delivery-deduplicated commit sequence across the
  crash equals the sequence an UNCRASHED golden walk produces over the
  same stream — a crash/restart must be invisible in the committed
  order.
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from narwhal_tpu.analysis.schedule import run_with_seed  # noqa: E402
import pytest  # noqa: E402

from narwhal_tpu.consensus import Consensus, Tusk  # noqa: E402
from narwhal_tpu.consensus.replay import (  # noqa: E402
    _oracle_for,
    replay_segments,
)
from narwhal_tpu.consensus.tusk import (  # noqa: E402
    RULE_MAGICS,
    CheckpointRuleMismatch,
    resolve_commit_rule,
)
from tests.common import committee  # noqa: E402
from tests.test_consensus import (  # noqa: E402
    feed,
    genesis_digests,
    make_certificates,
    mock_certificate,
    sorted_names,
)

GC_DEPTH = 50
# Interleaving pins: one seed per event-loop incarnation.  Change them
# and the tests still must pass — any seed is a legal schedule — but a
# FIXED seed makes a failure replayable byte-for-byte.
SEED_FIRST_RUN = 11
SEED_SECOND_RUN = 22
SEED_TORN_BOOT = 33


def default_oracle(*args, **kwargs):
    """The frozen oracle of the rule a Consensus runs when nobody names
    one (every Consensus below): the uncrashed reference walk."""
    return _oracle_for(resolve_commit_rule())(*args, **kwargs)


def _stream(rounds=24):
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, rounds, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], rounds + 1, parents)
    return c, certs + [trigger]


def test_restart_mid_burst_with_concurrent_inserts_agrees_with_oracle(
    tmp_path,
):
    c, stream = _stream()
    ckpt = str(tmp_path / "consensus.ckpt")
    seg0 = str(tmp_path / "audit.seg0.bin")
    seg1 = str(tmp_path / "audit.seg1.bin")

    # The uncrashed reference: one golden walk over the whole stream.
    full = [
        bytes(x.digest())
        for x in feed(default_oracle(c, GC_DEPTH, fixed_coin=True), list(stream))
    ]
    assert len(full) > 20, "fixture must commit substantially"

    # The first incarnation only ever sees a prefix of the stream (the
    # trigger certificate is withheld until the restart), so no matter
    # how the scheduler interleaves the feeder and the runner the crash
    # provably lands mid-sequence: first_commits <= len(prefix) < full.
    cut = (2 * len(stream)) // 3
    prefix = [
        bytes(x.digest())
        for x in feed(
            default_oracle(c, GC_DEPTH, fixed_coin=True), list(stream[:cut])
        )
    ]
    target = len(full) // 3
    assert target <= len(prefix) < len(full), "fixture prefix must straddle"

    async def first_run():
        rx, tx_p, tx_o = asyncio.Queue(), asyncio.Queue(), asyncio.Queue()
        cons = Consensus(
            c, GC_DEPTH, rx_primary=rx, tx_primary=tx_p, tx_output=tx_o,
            fixed_coin=True, checkpoint_path=ckpt, audit_path=seg0,
        )
        task = asyncio.get_running_loop().create_task(cons.run())
        committed = []

        async def drain():
            while True:
                committed.append(bytes((await tx_o.get()).digest()))
                await tx_p.get()  # keep the feedback queue drained too

        drain_task = asyncio.get_running_loop().create_task(drain())

        async def feeder():
            for cert in stream[:cut]:
                await rx.put(cert)
                await asyncio.sleep(0)  # interleave with the runner

        feeder_task = asyncio.get_running_loop().create_task(feeder())
        # Kill the consensus instance MID-BURST: after some commits have
        # landed but (deliberately) well before the stream is done.
        deadline = asyncio.get_running_loop().time() + 90
        while len(committed) < target:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0)
        task.cancel()
        feeder_task.cancel()
        drain_task.cancel()
        await asyncio.gather(
            task, feeder_task, drain_task, return_exceptions=True
        )
        # Drain what consensus already HANDED OFF before the kill: the
        # checkpoint's documented at-least-once boundary is the
        # tx_output put (it is rewritten only after a burst's puts), so
        # the observer must consume everything put before declaring the
        # incarnation dead — under a shuffled schedule the drain task
        # can lag the runner by a whole burst, and dropping those
        # handed-off commits would fake a hole the product never made
        # (the audit segment records them; only this test's view lost
        # them).
        while True:
            try:
                committed.append(bytes(tx_o.get_nowait().digest()))
            except asyncio.QueueEmpty:
                break
        # What a real SIGKILL preserves is everything flushed to the OS;
        # emulate the page-cache boundary by flushing the audit buffer.
        cons._audit.close()
        return committed

    first_commits, _ = run_with_seed(first_run, SEED_FIRST_RUN, timeout=180)
    assert 0 < len(first_commits) < len(full), "must stop mid-burst"
    assert os.path.exists(ckpt), "checkpoint must exist after commits"

    async def second_run():
        rx, tx_p, tx_o = asyncio.Queue(), asyncio.Queue(), asyncio.Queue()
        cons = Consensus(
            c, GC_DEPTH, rx_primary=rx, tx_primary=tx_p, tx_output=tx_o,
            fixed_coin=True, checkpoint_path=ckpt, audit_path=seg1,
        )
        # The checkpoint anchored the frontier: a restart is not round 0.
        assert cons.tusk.state.last_committed_round > 0
        task = asyncio.get_running_loop().create_task(cons.run())
        committed = []

        async def drain():
            while True:
                committed.append(bytes((await tx_o.get()).digest()))
                await tx_p.get()

        drain_task = asyncio.get_running_loop().create_task(drain())
        # Catch-up flood: the ENTIRE stream again, pre-crash history
        # included — exactly what a lagging-peer sync storm delivers.
        for cert in stream:
            await rx.put(cert)
            await asyncio.sleep(0)
        # Settle: wait until the union of both incarnations' commits
        # covers the uncrashed walk (the known completion target — a
        # no-growth heuristic here was load-sensitive: one checkpoint
        # fsync stalling past the stability window under full-suite disk
        # contention cancelled the runner mid-stream).  Tick-based poll
        # (sleep(0)), so the wait itself adds no wall-clock schedule
        # noise; on deadline fall through: the final equality assert
        # reports the actual hole.
        first_set = set(first_commits)
        deadline = asyncio.get_running_loop().time() + 90
        while len(first_set | set(committed)) < len(full):
            if asyncio.get_running_loop().time() >= deadline:
                break
            await asyncio.sleep(0)
        task.cancel()
        drain_task.cancel()
        await asyncio.gather(task, drain_task, return_exceptions=True)
        cons._audit.close()
        return committed

    second_commits, _ = run_with_seed(
        second_run, SEED_SECOND_RUN, timeout=180
    )
    assert second_commits, "restarted instance must keep committing"

    # Golden-oracle replay over both segments: byte-identical per
    # incarnation, uniqueness + causal history clean.
    verdict = replay_segments(
        c, GC_DEPTH, [seg0, seg1], fixed_coin=True
    )
    assert verdict["ok"], verdict["violations"]
    assert verdict["recorded_commits"] >= len(first_commits)

    # The crash is invisible in the committed order: concatenated (and
    # boundary-deduplicated — the checkpoint is at-least-once) sequence
    # equals the uncrashed golden walk.
    seen = set()
    combined = []
    for d in first_commits + second_commits:
        if d not in seen:
            seen.add(d)
            combined.append(d)
    assert combined == full


def test_restart_from_torn_checkpoint_falls_back_fresh_and_stays_safe(
    tmp_path,
):
    """Tear the checkpoint file, restart, and replay the flood: the node
    must boot from a fresh frontier (torn file ignored loudly), re-commit
    from genesis, and the golden replay of its audit segment must still
    agree — re-commits are the allowed at-least-once boundary, disorder
    is not."""
    c, stream = _stream(rounds=12)
    ckpt = str(tmp_path / "consensus.ckpt")
    seg = str(tmp_path / "audit.seg0.bin")
    with open(ckpt, "wb") as f:
        # Torn: the running rule's magic + a truncated body.
        f.write(RULE_MAGICS[resolve_commit_rule()] + b"\x03")
    # The fresh boot re-commits the full prefix, in the oracle's order.
    full = [
        bytes(x.digest())
        for x in feed(default_oracle(c, GC_DEPTH, fixed_coin=True), list(stream))
    ]
    full_count = len(full)

    async def go():
        rx, tx_p, tx_o = asyncio.Queue(), asyncio.Queue(), asyncio.Queue()
        cons = Consensus(
            c, GC_DEPTH, rx_primary=rx, tx_primary=tx_p, tx_output=tx_o,
            fixed_coin=True, checkpoint_path=ckpt, audit_path=seg,
        )
        assert cons.tusk.state.last_committed_round == 0  # fresh fallback
        task = asyncio.get_running_loop().create_task(cons.run())
        committed = []

        async def drain():
            while True:
                committed.append(bytes((await tx_o.get()).digest()))
                await tx_p.get()

        drain_task = asyncio.get_running_loop().create_task(drain())
        for cert in stream:
            await rx.put(cert)
        # Wait for the known target count (not a no-growth heuristic —
        # see the sibling test), on a tick-based poll; on deadline the
        # final equality assert reports the actual shortfall.
        deadline = asyncio.get_running_loop().time() + 90
        while len(committed) < full_count:
            if asyncio.get_running_loop().time() >= deadline:
                break
            await asyncio.sleep(0)
        task.cancel()
        drain_task.cancel()
        await asyncio.gather(task, drain_task, return_exceptions=True)
        cons._audit.close()
        return committed

    committed, _ = run_with_seed(go, SEED_TORN_BOOT, timeout=180)
    assert committed
    verdict = replay_segments(c, GC_DEPTH, [seg], fixed_coin=True)
    assert verdict["ok"], verdict["violations"]
    assert committed == full


def test_restart_on_the_default_over_a_classic_checkpoint_is_refused(
    tmp_path, monkeypatch
):
    """The upgrade across PR 33's change of default: a validator that
    committed under classic (upstream's rule) and restarts with no rule
    named must not boot — neither from the classic frontier nor fresh —
    and the refusal names both rules and `--commit-rule classic`; with
    that flag the same checkpoint restores and the node carries on."""
    monkeypatch.delenv("NARWHAL_COMMIT_RULE", raising=False)
    c, stream = _stream(rounds=12)
    ckpt = str(tmp_path / "consensus.ckpt")
    before = Tusk(c, GC_DEPTH, fixed_coin=True)
    assert feed(before, list(stream))
    with open(ckpt, "wb") as f:
        f.write(before.state.snapshot_bytes())

    def boot(**kwargs):
        return Consensus(
            c, GC_DEPTH, rx_primary=asyncio.Queue(),
            tx_primary=asyncio.Queue(), tx_output=asyncio.Queue(),
            fixed_coin=True, checkpoint_path=ckpt, **kwargs,
        )

    with pytest.raises(CheckpointRuleMismatch) as refused:
        boot()
    message = str(refused.value)
    assert "'classic'" in message and "'lowdepth'" in message
    assert "--commit-rule classic" in message
    stayed = boot(commit_rule="classic")
    assert (
        stayed.tusk.state.last_committed_round
        == before.state.last_committed_round
        > 0
    )


def test_consensus_survives_checkpoint_write_failure(tmp_path):
    """The race the narwhal-race harness caught (ISSUE 10): under the
    seeded loop, the crash/restart pair intermittently lost the SAME 40
    commits — the restarted incarnation's consensus task was DEAD.  Root
    cause pair: (a) ``_write_checkpoint`` used a fixed ``<path>.tmp``,
    so the pre-crash incarnation's still-in-flight executor write raced
    the restarted one's and the loser's ``os.replace`` raised
    FileNotFoundError; (b) Consensus.run let that exception kill the
    whole commit pipeline, permanently, while certificates kept
    queueing.  (b) is pinned here deterministically: a checkpoint path
    whose parent directory does not exist makes EVERY rewrite fail, and
    consensus must still commit the full stream — the checkpoint is an
    optimization, never a liveness dependency.  (a) is fixed by unique
    per-write tmp names (mkstemp), and the seeded-loop harness now joins
    the default executor at teardown so no incarnation's threads leak
    into the next."""
    c, stream = _stream(rounds=12)
    missing_dir = str(tmp_path / "gone" / "consensus.ckpt")
    seg = str(tmp_path / "audit.seg0.bin")
    full = [
        bytes(x.digest())
        for x in feed(default_oracle(c, GC_DEPTH, fixed_coin=True), list(stream))
    ]

    async def go():
        rx, tx_p, tx_o = asyncio.Queue(), asyncio.Queue(), asyncio.Queue()
        cons = Consensus(
            c, GC_DEPTH, rx_primary=rx, tx_primary=tx_p, tx_output=tx_o,
            fixed_coin=True, checkpoint_path=missing_dir, audit_path=seg,
        )
        task = asyncio.get_running_loop().create_task(cons.run())
        committed = []

        async def drain():
            while True:
                committed.append(bytes((await tx_o.get()).digest()))
                await tx_p.get()

        drain_task = asyncio.get_running_loop().create_task(drain())
        for cert in stream:
            await rx.put(cert)
            await asyncio.sleep(0)
        deadline = asyncio.get_running_loop().time() + 90
        while len(committed) < len(full):
            assert not task.done(), (
                "consensus task died on a checkpoint write failure: "
                f"{task.exception()!r}"
            )
            if asyncio.get_running_loop().time() >= deadline:
                break
            await asyncio.sleep(0)
        task.cancel()
        drain_task.cancel()
        await asyncio.gather(task, drain_task, return_exceptions=True)
        cons._audit.close()
        return committed

    committed, _ = run_with_seed(go, SEED_FIRST_RUN, timeout=180)
    assert committed == full, (
        f"checkpoint failures cost commits: {len(committed)}/{len(full)}"
    )
