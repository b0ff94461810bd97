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

  A stall of the dispatch thread or of the device with the loop alive
  shows instead in ``metrics.verify_trace()`` as one burst with a second
  between two stages.

Cost: one trivial task wakeup per interval on the loop (40 a second at
the default), one daemon thread, and two clock reads per collection.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import logging
import sys
import threading
import time
import traceback
from typing import Optional

from .. import metrics
from ..utils.env import env_int
from ..utils.tasks import spawn

log = logging.getLogger("narwhal.watchdog")

_STACK_LIMIT = 12  # frames kept in the excerpt
# Stall records kept for the snapshot.  The flight ring holds them too,
# but its 512 events turn over in under a minute of round advances, and a
# run has to keep every stall of its window until its final snapshot.
_STALL_LOG = 32


class LoopWatchdog:
    """Watch one event loop for callbacks that hold it past ``threshold_s``."""

    def __init__(self, threshold_s: float, interval_s: Optional[float] = None):
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
        self._verify_trace = metrics.verify_trace()
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
            overshoot = time.monotonic() - self._last_beat - self.interval_s
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
                }
                # Stalls are flight-recorder landmarks: the ring shows
                # what the committee was doing around the freeze.
                stall["stack"] = during.get("stack", "")
                metrics.flight_event("loop_stall", **stall)
                self._stalls.append(stall)
                self._last_stall.clear()
                self._last_stall.update(stall)

    # -- thread side: name the culprit ----------------------------------------

    def _watch(self) -> None:
        while not self._stop.wait(self.interval_s):
            beat = self._last_beat
            gap = time.monotonic() - beat
            if gap - self.interval_s < self.threshold_s or self._stack_captured:
                continue
            # The loop is stalled RIGHT NOW: its thread's stack names the
            # blocking callee. One capture per stall (flag reset by the
            # next beat), so a long wedge logs once, not per tick.
            self._stack_captured = True
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


def install_from_env() -> Optional[LoopWatchdog]:
    """Arm the watchdog on the running loop unless
    ``NARWHAL_LOOP_WATCHDOG_MS`` is 0 (default 100; node/main.py calls
    this once per process); returns the armed instance or None."""
    ms = env_int("NARWHAL_LOOP_WATCHDOG_MS")
    if not ms or ms <= 0:
        return None
    return LoopWatchdog(ms / 1000.0).start()
