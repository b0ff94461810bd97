"""The verify-stage trace and the loop-stall record (ISSUE 26): spans
INSIDE the verify path of a primary (``metrics.VERIFY_STAGES``, one
entry per burst, stamped where the work happens on the wall clock the
device trace is bounded with) and a cause beside each event-loop stall.

Pinned here: the seven stamps of a dispatch are monotone and telescope
to the wall time of the burst on the pipelined and the inline path, a
backend without a dispatch thread marks the loop stages alone, the
table rides the final snapshot flush and an explicit scrape but never a
periodic rewrite, a held loop leaves ONE stall that tells collector,
snapshot writer, CPU and in-flight burst apart, every collection lands
in ``runtime.gc_pause_seconds``, and the node's own profiler hook keeps
the ``verify.*`` annotations on the profiler's clock."""

import asyncio
import gc
import glob
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.analysis.watchdog import LoopWatchdog
from narwhal_tpu.crypto import backend as cb
from narwhal_tpu.utils import devtrace
from tests.common import committee, keys, make_certificate, make_header
from tests.test_core import make_core

pytestmark = pytest.mark.skipif(
    not metrics.registry().enabled, reason="metrics stubbed"
)

HOLD_S = 0.05  # the stub's "device" time


def run(coro, timeout=30):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class _ThreadedBackend:
    """Stands in for the batched device verifier: one dispatch thread
    that prepares, "launches", waits for its device and hands back its
    own stamps, as ``TpuBackend.averify_batch_mask_timed`` does."""

    name = "threaded-stub"

    def __init__(self, off_loop: bool) -> None:
        self.dispatches_off_loop = off_loop
        self._executor = ThreadPoolExecutor(max_workers=1)

    async def averify_batch_mask_timed(self, messages, keys_, sigs):
        def timed():
            stamps = {"prepare": time.time()}
            cpu0 = time.thread_time()
            mask = cb.CpuBackend().verify_batch_mask(messages, keys_, sigs)
            stamps["enqueued"] = time.time()
            time.sleep(HOLD_S)
            stamps["fetched"] = time.time()
            stamps.update(pad=16, chunks=1, cpu_s=time.thread_time() - cpu0)
            return mask, HOLD_S, stamps

        return await asyncio.get_running_loop().run_in_executor(
            self._executor, timed
        )


async def _one_burst(core, qs, pipelined):
    """Put one certificate through core.run() and return its entry in
    the verify-stage trace with the wall time the test saw around it."""
    table = metrics.verify_trace()
    # Every Core counts its bursts from 1 (one Core a process, but not
    # in this one): start from an empty table.
    table.entries.clear()
    cert = make_certificate(make_header(keys()[1], c=committee()))
    task = asyncio.get_running_loop().create_task(core.run())
    try:
        assert (core._verify_q is not None) == pipelined
        t0 = time.time()
        qs["primaries"].put_nowait(("certificate", cert))
        for _ in range(1000):
            fresh = list(table.entries)
            if fresh and "replayed" in table.entries[fresh[0]]:
                break
            await asyncio.sleep(0.005)
        t1 = time.time()
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        core.network.close()
    assert len(fresh) == 1, fresh
    return table.entries[fresh[0]], t0, t1


@pytest.mark.parametrize(
    "backend, pipelined, stages",
    [
        ("threaded", True, metrics.VERIFY_STAGES),
        ("threaded", False, metrics.VERIFY_STAGES),
        ("cpu", False, ("collected", "submitted", "resumed", "replayed")),
    ],
    ids=["pipelined", "inline", "inline-no-dispatch-thread"],
)
def test_stamps_of_a_dispatch_are_monotone_and_telescope(
    monkeypatch, backend, pipelined, stages
):
    async def go():
        live = (
            _ThreadedBackend(off_loop=pipelined)
            if backend == "threaded" else cb.CpuBackend()
        )
        monkeypatch.setattr(cb, "_backend", live)
        c = committee()
        core, _, qs = make_core(c, keys()[0])
        return await _one_burst(core, qs, pipelined)

    entry, t0, t1 = run(go())
    assert [s for s in metrics.VERIFY_STAGES if s in entry] == list(stages)
    stamps = [entry[s] for s in stages]
    assert stamps == sorted(stamps), entry
    # The legs telescope: their sum IS replayed - collected, which lies
    # inside the wall time the test saw around the burst...
    legs = [b - a for a, b in zip(stamps, stamps[1:])]
    assert sum(legs) == pytest.approx(entry["replayed"] - entry["collected"])
    assert t0 <= entry["collected"] and entry["replayed"] <= t1
    quorum = committee().quorum_threshold()
    assert entry["items"] == 1 and entry["claims"] == quorum + 1
    assert entry["round"] == 1
    if backend == "threaded":
        # ... and holds the stub's device time between launch and fetch.
        assert entry["fetched"] - entry["enqueued"] >= HOLD_S
        assert entry["pad"] == 16 and entry["chunks"] == 1
        assert 0 <= entry["cpu_s"] < entry["fetched"] - entry["prepare"]
    else:
        assert "pad" not in entry and "cpu_s" not in entry


def test_a_burst_with_no_claims_carries_the_loop_ends_alone(monkeypatch):
    """A re-delivered certificate hits the verified cache: no dispatch,
    but the stage was busy replaying it, and the entry says so."""

    async def go():
        monkeypatch.setattr(cb, "_backend", cb.CpuBackend())
        metrics.verify_trace().entries.clear()
        c = committee()
        core, _, _ = make_core(c, keys()[0])
        cert = make_certificate(make_header(keys()[1], c=c))
        try:
            first = await core._handle_primaries_burst([("certificate", cert)])
            again = await core._handle_primaries_burst([("certificate", cert)])
        finally:
            core.network.close()
        return first, again

    first, again = run(go())
    entries = metrics.verify_trace().entries
    assert int(again) == int(first) + 1
    assert "submitted" in entries[first] and entries[first]["claims"] > 0
    assert set(entries[again]) == {"collected", "items", "round"}


def test_table_is_bounded_fifo_and_counts_evictions():
    reg = metrics.Registry()
    reg.verify_trace.cap = 4
    for seq in range(6):
        reg.verify_trace.mark(str(seq), "collected", float(seq))
    assert list(reg.verify_trace.entries) == ["2", "3", "4", "5"]
    assert reg.snapshot()["gauges"]["metrics.verify_trace_evictions"] == 2
    with pytest.raises(ValueError):
        reg.verify_trace.mark("6", "header")  # a stage of another table


def test_periodic_snapshot_has_no_verify_trace_and_the_final_flush_has(
    tmp_path,
):
    reg = metrics.Registry()
    reg.verify_trace.mark("1", "collected", 1.0, items=3)
    reg.trace.mark("ab" * 32, "seal", 1.0)
    path = str(tmp_path / "metrics.json")

    async def go():
        # Every rewrite carries the stage trace here (trace_every=1): the
        # verify-stage table still stays out of all of them.
        writer = metrics.SnapshotWriter(
            reg, path, interval_s=0.02, trace_every=1
        )
        task = asyncio.get_running_loop().create_task(writer.run())
        periodic = None
        for _ in range(200):
            await asyncio.sleep(0.01)
            if os.path.exists(path):
                with open(path) as f:
                    periodic = json.load(f)
                break
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        return periodic

    periodic = run(go())
    assert periodic["trace"] and periodic["verify_trace"] == {}
    with open(path) as f:
        final = json.load(f)
    assert final["verify_trace"] == {"1": {"collected": 1.0, "items": 3}}
    # Each rewrite was timed, the final one too.
    written = final["histograms"]["runtime.snapshot_write_seconds"]
    assert written["count"] >= 1 and written["sum"] > 0


async def _get(port: int, target: str, timeout: float = 20.0):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {target} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(-1), timeout)
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), json.loads(body)


def test_scrape_carries_the_table_unless_it_says_trace_0():
    reg = metrics.Registry()
    reg.verify_trace.mark("7", "collected", 2.0)

    async def go():
        server = await metrics.MetricsServer.spawn(reg, 0)
        try:
            return (
                await _get(server.port, "/metrics.json"),
                await _get(server.port, "/metrics.json?trace=0"),
            )
        finally:
            await server.shutdown()

    (s_full, full), (s_lean, lean) = run(go())
    assert s_full == s_lean == 200
    assert full["verify_trace"] == {"7": {"collected": 2.0}}
    assert lean["verify_trace"] == {} and lean["trace"] == {}


# -- the stall record ---------------------------------------------------------


def test_a_held_loop_leaves_one_stall_with_its_cause(tmp_path):
    reg = metrics.registry()
    table = metrics.verify_trace()
    stalls0 = reg.counters["runtime.loop_stalls"].value if (
        "runtime.loop_stalls" in reg.counters
    ) else 0

    async def go():
        dog = LoopWatchdog(threshold_s=0.1, interval_s=0.025).start()
        writer = metrics.SnapshotWriter(reg, str(tmp_path / "m.json"))
        try:
            await asyncio.sleep(0.06)  # let the beat task stamp
            # A burst in flight on the "device" while the loop is held
            # by a snapshot write, a collection and a blocking call.
            table.mark("900001", "collected")
            table.mark("900001", "submitted")
            table.mark("900001", "prepare")
            writer.write_once(include_trace=False)
            gc.collect()
            time.sleep(0.3)
            await asyncio.sleep(0.08)  # the late beat, then a clean one
        finally:
            await dog.shutdown()
        return dict(dog._last_stall)

    last = run(go())
    table.entries.pop("900001", None)
    assert reg.counters["runtime.loop_stalls"].value == stalls0 + 1
    assert last["stall_s"] > 0.2 and last["ts"] > 0
    assert "time.sleep" in last["stack"] or "test_a_held_loop" in last["stack"]
    # Off the cores (asleep): CPU time far under the wall time of the
    # stall, for the process and for the loop thread.
    assert 0 <= last["loop_cpu_s"] <= last["cpu_s"] + 0.01
    assert last["loop_cpu_s"] < last["stall_s"]
    assert last["gc_s"] > 0 and last["gc_gen"] == 2
    assert last["snapshot_write"] is True and last["snapshot_write_s"] > 0
    assert last["dispatch"] == {"seq": "900001", "stage": "prepare"}
    # The same record is the flight ring's newest loop_stall event.
    events = [
        e for e in reg.flight.snapshot()["events"] if e["kind"] == "loop_stall"
    ]
    assert events and events[-1]["stall_s"] == last["stall_s"]
    assert events[-1]["dispatch"] == last["dispatch"]
    assert events[-1]["stack"] == last["stack"]
    # ... and is kept where the ring's turnover cannot reach it.
    assert reg.snapshot()["detail"]["runtime.loop_stall_log"][-1] == last


def test_a_clean_stall_names_no_collector_writer_or_dispatch():
    metrics.verify_trace().entries.clear()

    async def go():
        dog = LoopWatchdog(threshold_s=0.05, interval_s=0.0125).start()
        try:
            await asyncio.sleep(0.03)
            gc.disable()
            try:
                time.sleep(0.15)
            finally:
                gc.enable()
            await asyncio.sleep(0.05)
        finally:
            await dog.shutdown()
        return dict(dog._last_stall)

    last = run(go())
    assert last["stall_s"] > 0.1
    assert last["gc_s"] == 0 and last["gc_gen"] == -1
    assert last["snapshot_write"] is False and last["dispatch"] is None


def test_what_the_thread_saw_of_an_earlier_beat_is_not_taken_for_this_one():
    """The watcher thread can write its mid-stall capture after that
    stall's late beat has already run (seen on the chip: a stall's record
    carried the burst of a stall 7 s before it); a capture is only taken
    by the beat it was made in."""
    metrics.verify_trace().entries.clear()

    async def go():
        dog = LoopWatchdog(threshold_s=0.05, interval_s=0.0125).start()
        try:
            await asyncio.sleep(0.03)
            dog._stop.set()  # the thread is gone: nothing fresh comes
            dog._thread.join(2)
            dog._during = {
                "beat": -1.0, "stack": "stale",
                "dispatch": {"seq": "1070", "stage": "submitted"},
            }
            time.sleep(0.15)
            await asyncio.sleep(0.05)
        finally:
            await dog.shutdown()
        return dict(dog._last_stall)

    last = run(go())
    assert last["stall_s"] > 0.1
    assert last["stack"] == "" and last["dispatch"] is None


def test_forced_collection_lands_in_gc_pause_seconds():
    async def go():
        dog = LoopWatchdog(threshold_s=0.5).start()
        hist = metrics.registry().histograms["runtime.gc_pause_seconds"]
        count0, sum0 = hist.count, hist.sum
        try:
            gc.collect()
        finally:
            await dog.shutdown()
        hooked = dog._on_gc in gc.callbacks
        return hist.count - count0, hist.sum - sum0, hooked

    observed, seconds, still_hooked = run(go())
    assert observed >= 1 and seconds > 0
    assert not still_hooked, "shutdown must take the collector hook out"


# -- the node's own profiler hook ---------------------------------------------


def test_debug_profile_keeps_the_verify_annotations(tmp_path):
    """GET /debug/profile runs one profiler session beside the metrics
    path and replies with the stamps that bound it; a ``verify.*``
    annotation made during it is in the trace's host plane."""
    jax = pytest.importorskip("jax")
    profile_dir = str(tmp_path / "metrics.json.profile")

    async def go():
        server = await metrics.MetricsServer.spawn(
            metrics.Registry(), 0, profile_dir=profile_dir
        )

        async def work():
            for seq in range(40):
                with devtrace.annotate("verify.fetch", dispatch=seq):
                    await asyncio.sleep(0.005)

        worker = asyncio.get_running_loop().create_task(work())
        try:
            bad = await _get(server.port, "/debug/profile?seconds=99")
            ok = await _get(server.port, "/debug/profile?seconds=0.05", 120)
        finally:
            await worker
            await server.shutdown()
        return bad, ok

    (bad_status, _), (status, reply) = run(go(), timeout=180)
    assert bad_status == 400
    assert status == 200, reply
    assert reply["asked"] <= reply["start"] < reply["stop"] <= reply["written"]
    assert reply["stop"] - reply["start"] >= 0.05
    assert reply["trace_dir"] == profile_dir and reply["host_tracer_level"] == 1
    (path,) = glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    names = {
        event.name
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines
        for event in line.events
    }
    assert "verify.fetch" in names


def test_debug_profile_refuses_a_node_with_nowhere_to_write():
    async def go():
        server = await metrics.MetricsServer.spawn(metrics.Registry(), 0)
        try:
            return await _get(server.port, "/debug/profile?seconds=0.1")
        finally:
            await server.shutdown()

    status, reply = run(go())
    assert status == 409 and "error" in reply
