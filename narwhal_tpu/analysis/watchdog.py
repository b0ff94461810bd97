"""Event-loop stall watchdog — the runtime half of the invariant suite.

The static ``no-blocking-in-async`` rule bans the blocking-call shapes we
know about; this watchdog measures the ones we don't.  The paper's whole
latency story rides on the primary's single asyncio loop never stalling
(the round period is pure critical path — r10 attribution), so "the loop
never blocks" must be a MEASURED property, not an inferred one.

Mechanism (``NARWHAL_LOOP_WATCHDOG_MS``, default 100: one header timer, a
shorter hold cannot cost a round; ``0`` = off):

- a heartbeat task on the watched loop stamps a monotonic timestamp
  every ``interval`` seconds.  When a beat arrives LATE, the loop was
  held by something — the overshoot beyond the scheduled interval is the
  stall length, observed into the ``runtime.loop_stall_seconds``
  histogram (plus the ``runtime.loop_stalls`` counter);
- a daemon thread watches the same timestamp from outside.  The moment
  the gap crosses the threshold it captures the LOOP thread's current
  stack via ``sys._current_frames()`` — i.e. a stack excerpt from
  *inside* the stall, naming the blocking callee — logs it, and parks it
  in the ``runtime.loop_stall_last`` snapshot detail.  The loop itself
  cannot log while wedged; the thread can (same stance as the
  ``NARWHAL_FAULTHANDLER_S`` C-level dumper, but scoped, rate-limited
  and joined to the metrics plane);
- ``loop.slow_callback_duration`` is aligned to the threshold so asyncio
  debug mode (when enabled) agrees with the watchdog about what "slow"
  means;
- a CAUSE beside each stall, in its ``loop_stall`` flight event, in
  ``runtime.loop_stall_last`` and in ``runtime.loop_stall_log`` (the
  last 32, which outlive the flight ring's turnover), so that the
  suspects are told apart in the run's own final snapshot (PERF.md, PR
  26):

  ``cpu_s``           ``time.process_time()`` across the late beat: near
                      the wall time the process computed, far below it
                      the process was off the cores or blocked in a call
  ``loop_cpu_s``      the same for the loop thread alone
                      (``time.thread_time()``; 10 ms ticks on some
                      hosts): the process busy and the loop thread not
                      says another thread held the interpreter
  ``gc_s``, ``gc_gen``  collector time inside the beat and the oldest
                      generation collected, from one ``gc.callbacks``
                      hook that also fills ``runtime.gc_pause_seconds``
  ``snapshot_write``  a ``SnapshotWriter.write_once`` ran inside it
                      (``runtime.snapshot_write_seconds`` moved), with
                      ``snapshot_write_s``
  ``dispatch``        the verify-stage burst in flight, as
                      ``{"seq", "stage"}`` with the last stage it had
                      reached (metrics.VERIFY_STAGES), or None

  ``host``            what the kernel counted across it
                      (``utils/hoststat.py``): each host key's raw delta
                      from the newest sample at or before the stall's last
                      beat to the first sample after the late beat, a
                      window ``host_window_s`` long (``{}`` and None where
                      no sample brackets the stall)
  ``cause``           ``program`` where ``cpu_s``, ``gc_s`` or
                      ``snapshot_write_s`` covers half the stall; else the
                      host key of :data:`HOST_CAUSES` that moved most, if
                      it covers half (``cpu_throttled``, which the kernel
                      sums over CPUs, divided by the CPUs the process may
                      run on, and only where ``cpu_nr_throttled`` moved
                      too); else ``unexplained``.  Each stall is observed
                      into the one of ``runtime.loop_stall_{program,host,
                      unexplained}_seconds`` its cause names: the three sum
                      to ``runtime.loop_stall_seconds``.

  A stall of the dispatch thread or of the device with the loop alive
  shows instead in ``metrics.verify_trace()`` as one burst with a second
  between two stages.

The thread samples the host counters at most ten times a second, never
on the loop: every fourth poll at the default beat while the loop keeps
time, none while it is held, and one at the first poll after a late beat
(once four polls have passed since the last).  The late beat observes
the stall into ``runtime.loop_stalls`` and ``runtime.loop_stall_seconds``
at once; its record waits for that sample, then the loop observes the
cause's histogram and publishes the flight event and the log entry.
While the thread watches a stall it holds a ``runtime.loop_stall`` span
open (``devtrace.annotate``), on the device trace's clock in a
device-backed node.

Cost: one trivial task wakeup per interval on the loop (40 a second at
the default), one daemon thread, two clock reads per collection, and
the host files read ten times a second.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import logging
import math
import os
import sys
import threading
import time
import traceback
from typing import Optional

from .. import metrics
from ..utils import devtrace
from ..utils.env import env_int
from ..utils.hoststat import HostStat
from ..utils.tasks import spawn

log = logging.getLogger("narwhal.watchdog")

_STACK_LIMIT = 12  # frames kept in the excerpt
# Stall records kept for the snapshot.  The flight ring holds them too,
# but its 512 events turn over in under a minute of round advances, and a
# run has to keep every stall of its window until its final snapshot.
_STALL_LOG = 32
_SAMPLE_HZ = 10  # the most the thread samples the host counters
_SAMPLES = 8  # kept: a stall is resolved within two polls of its end
# Host keys that can name a stall, in the order that breaks a tie.
HOST_CAUSES = (
    "cpu_throttled", "cpu_steal", "memory_full", "io_full", "memory_some",
    "cpu_some",
)


def stall_cause(stall: dict, host: dict, cpus: int = 1) -> str:
    """``program``, the host key that covers half the stall, or
    ``unexplained`` (the rule in the module's docstring); ``cpus``: the
    CPUs the process may run on, over which the kernel sums throttled
    time."""
    half = stall["stall_s"] / 2
    if max(stall["cpu_s"], stall["gc_s"], stall["snapshot_write_s"]) >= half:
        return "program"
    moved = {k: host.get(k, 0.0) for k in HOST_CAUSES}
    moved["cpu_throttled"] /= cpus
    if host.get("cpu_nr_throttled", 0) <= 0:
        del moved["cpu_throttled"]
    key = max(moved, key=moved.get)
    return key if moved[key] >= half else "unexplained"


class LoopWatchdog:
    """Watch one event loop for callbacks that hold it past ``threshold_s``."""

    def __init__(
        self,
        threshold_s: float,
        interval_s: Optional[float] = None,
        host: Optional[HostStat] = None,
    ):
        self.threshold_s = threshold_s
        # Beat fast enough that the measured overshoot approximates the
        # true stall length, slow enough to stay off the hot path.
        self.interval_s = (
            interval_s if interval_s is not None else max(threshold_s / 4, 0.005)
        )
        self._last_beat = time.monotonic()
        self._stop = threading.Event()
        self._loop_thread_id: Optional[int] = None
        self._task: Optional[asyncio.Task] = None
        self._thread: Optional[threading.Thread] = None
        self._last_stall: dict = {}
        self._stalls: collections.deque = collections.deque(maxlen=_STALL_LOG)
        self._stack_captured = False
        # What the thread saw DURING a stall, with the beat it belongs
        # to: the late beat moves it into that stall's record, and what
        # the thread writes after its beat has ended is never taken for
        # the next stall's.
        self._during: dict = {}
        self._m_stalls = metrics.counter("runtime.loop_stalls")
        self._m_stall_s = metrics.histogram("runtime.loop_stall_seconds")
        self._m_gc_s = metrics.histogram("runtime.gc_pause_seconds")
        self._m_write_s = metrics.histogram("runtime.snapshot_write_seconds")
        self._m_cause_s = {
            "program": metrics.histogram("runtime.loop_stall_program_seconds"),
            "host": metrics.histogram("runtime.loop_stall_host_seconds"),
            "unexplained": metrics.histogram(
                "runtime.loop_stall_unexplained_seconds"
            ),
        }
        self._verify_trace = metrics.verify_trace()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # Host counters: (monotonic, sample), appended by the thread alone;
        # stalls wait in ``_pending`` as (beat, end, overshoot, record)
        # until a sample after their late beat is in.
        self._host = host if host is not None else HostStat()
        self._cpus = len(os.sched_getaffinity(0))
        self._sample_every = math.ceil(
            round(1 / (_SAMPLE_HZ * self.interval_s), 6)
        )
        self._samples: collections.deque = collections.deque(maxlen=_SAMPLES)
        self._pending: collections.deque = collections.deque()
        # Collector accounting (the gc hook; any thread may collect, and
        # a collection is never re-entered).
        self._gc_t0 = 0.0
        self._gc_total_s = 0.0
        self._gc_gen = -1
        metrics.detail_fn("runtime.loop_stall_last", lambda: self._last_stall)
        metrics.detail_fn("runtime.loop_stall_log", lambda: list(self._stalls))

    def start(self) -> "LoopWatchdog":
        loop = asyncio.get_running_loop()
        # Align asyncio's own slow-callback notion (used when loop debug
        # mode is on) with the watchdog threshold.
        loop.slow_callback_duration = self.threshold_s
        self._loop_thread_id = threading.get_ident()
        self._loop = loop
        self._last_beat = time.monotonic()
        gc.callbacks.append(self._on_gc)
        self._task = spawn(self._beat(), name="loop-watchdog-beat")
        self._thread = threading.Thread(
            target=self._watch, name="loop-watchdog", daemon=True
        )
        self._thread.start()
        log.info(
            "Loop-stall watchdog armed: threshold %.0f ms, beat %.0f ms",
            self.threshold_s * 1000, self.interval_s * 1000,
        )
        return self

    async def shutdown(self) -> None:
        self._stop.set()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval_s + 1)
        # What the thread handed over lands first; a stall it never saw
        # (it had died) is published without a host window.
        await asyncio.sleep(0)
        if self._thread is None or not self._thread.is_alive():
            self._resolve(self._record, final=True)

    # -- loop side: measure ---------------------------------------------------

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        pause = time.perf_counter() - self._gc_t0
        self._m_gc_s.observe(pause)
        self._gc_total_s += pause
        self._gc_gen = max(self._gc_gen, info.get("generation", -1))

    def _dispatch_in_flight(self) -> Optional[dict]:
        """The newest verify-stage burst, if the stage still holds it."""
        entries = self._verify_trace.entries
        try:
            seq = next(reversed(entries))
            entry = entries[seq]
            stage = [s for s in metrics.VERIFY_STAGES if s in entry][-1]
        except (StopIteration, KeyError, IndexError, RuntimeError):
            # empty, evicted or resized under us (thread side): nothing
            # to name
            return None
        return None if stage == "replayed" else {"seq": seq, "stage": stage}

    async def _beat(self) -> None:
        while True:
            self._last_beat = beat = time.monotonic()
            self._stack_captured = False
            cpu0, loop_cpu0 = time.process_time(), time.thread_time()
            gc0, self._gc_gen = self._gc_total_s, -1
            writes0, write_s0 = self._m_write_s.count, self._m_write_s.sum
            await asyncio.sleep(self.interval_s)
            # The sleep was scheduled for interval_s; anything beyond it
            # is time some callback (or a CPU-bound stretch of one) held
            # the loop.
            end = time.monotonic()
            overshoot = end - beat - self.interval_s
            if overshoot >= self.threshold_s:
                self._m_stalls.inc()
                self._m_stall_s.observe(overshoot)
                during = self._during
                if during.get("beat") != beat:
                    during = {}
                stall = {
                    "stall_s": round(overshoot, 4),
                    "ts": time.time(),
                    "cpu_s": round(time.process_time() - cpu0, 4),
                    "loop_cpu_s": round(time.thread_time() - loop_cpu0, 4),
                    "gc_s": round(self._gc_total_s - gc0, 4),
                    "gc_gen": self._gc_gen,
                    "snapshot_write": self._m_write_s.count > writes0,
                    "snapshot_write_s": round(
                        self._m_write_s.sum - write_s0, 4
                    ),
                    # As the thread saw it mid-stall; a hold too short
                    # for the thread to catch is described as it ends.
                    "dispatch": during.get(
                        "dispatch", self._dispatch_in_flight()
                    ),
                    "stack": during.get("stack", ""),
                }
                self._pending.append((beat, end, overshoot, stall))

    def _record(self, overshoot: float, stall: dict) -> None:
        """Publish one stall, its host window and cause in hand (on the
        loop)."""
        cause = stall["cause"]
        self._m_cause_s[
            cause if cause in ("program", "unexplained") else "host"
        ].observe(overshoot)
        # Stalls are flight-recorder landmarks: the ring shows what the
        # committee was doing around the freeze.
        metrics.flight_event("loop_stall", **stall)
        self._stalls.append(stall)
        self._last_stall.clear()
        self._last_stall.update(stall)

    # -- thread side: name the culprit ----------------------------------------

    def _sample(self) -> None:
        self._samples.append((time.monotonic(), self._host.sample()))

    def _hand_over(self, overshoot: float, stall: dict) -> None:
        try:
            self._loop.call_soon_threadsafe(self._record, overshoot, stall)
        except RuntimeError:  # the loop is closed: nobody reads it now
            pass

    def _resolve(self, publish, final: bool = False) -> None:
        """Give each stall that ended before the newest sample its
        ``host`` deltas and ``cause`` and ``publish`` it, in order;
        ``final``: every stall still waiting, without a window where no
        sample follows it."""
        samples = list(self._samples)
        while self._pending:
            beat, end, overshoot, stall = self._pending[0]
            after = samples[-1] if samples and samples[-1][0] > end else None
            if after is None and not final:
                return
            self._pending.popleft()
            before = next(
                (s for s in reversed(samples) if s[0] <= beat), None
            )
            host, window = {}, None
            if before is not None and after is not None:
                host = {
                    k: round(after[1][k] - v, 6)
                    for k, v in before[1].items() if k in after[1]
                }
                window = round(after[0] - before[0], 4)
            stall["host"] = host
            stall["host_window_s"] = window
            stall["cause"] = stall_cause(stall, host, self._cpus)
            publish(overshoot, stall)

    def _watch(self) -> None:
        since, span, span_beat = self._sample_every, None, None
        while not self._stop.wait(self.interval_s):
            since += 1
            beat = self._last_beat
            gap = time.monotonic() - beat
            # No sample while the loop is held: none could bracket a stall.
            if since >= self._sample_every and (
                self._pending or gap < 2 * self.interval_s
            ):
                self._sample()
                since = 0
                self._resolve(self._hand_over)
            if span is not None and beat != span_beat:
                span.__exit__(None, None, None)
                span = None
            if gap - self.interval_s < self.threshold_s or self._stack_captured:
                continue
            # The loop is stalled RIGHT NOW: its thread's stack names the
            # blocking callee. One capture per stall (flag reset by the
            # next beat), so a long wedge logs once, not per tick.  The
            # span lies on the device trace's clock until the beat resumes.
            self._stack_captured = True
            span_beat = beat
            span = devtrace.annotate(
                "runtime.loop_stall", beat=time.time() - gap
            )
            span.__enter__()
            frame = sys._current_frames().get(self._loop_thread_id)
            if frame is None:
                continue
            excerpt = "".join(
                traceback.format_stack(frame, limit=_STACK_LIMIT)
            )
            self._during = {
                "beat": beat,
                "stack": excerpt,
                "dispatch": self._dispatch_in_flight(),
            }
            log.warning(
                "Event loop stalled > %.0f ms; loop thread stack:\n%s",
                self.threshold_s * 1000, excerpt,
            )
        if span is not None:
            span.__exit__(None, None, None)
        # A stall that ended just now still gets the sample after it.
        if self._pending:
            self._sample()
            self._resolve(self._hand_over)


def install_from_env() -> Optional[LoopWatchdog]:
    """Arm the watchdog on the running loop unless
    ``NARWHAL_LOOP_WATCHDOG_MS`` is 0 (default 100; node/main.py calls
    this once per process); returns the armed instance or None."""
    ms = env_int("NARWHAL_LOOP_WATCHDOG_MS")
    if not ms or ms <= 0:
        return None
    return LoopWatchdog(ms / 1000.0).start()
