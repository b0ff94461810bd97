"""utils/tasks.spawn() + the loop-stall watchdog (ISSUE 9 runtime half)."""

import asyncio
import logging
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from narwhal_tpu import metrics  # noqa: E402
from narwhal_tpu.analysis.watchdog import (  # noqa: E402
    LoopWatchdog,
    install_from_env,
)
from narwhal_tpu.utils import tasks  # noqa: E402
from narwhal_tpu.utils.tasks import spawn  # noqa: E402


# -- spawn() ------------------------------------------------------------------

def test_spawn_retains_strong_ref_until_done():
    async def main():
        release = asyncio.Event()

        async def work():
            await release.wait()

        task = spawn(work(), name="retained")
        await asyncio.sleep(0)
        assert task in tasks._TASKS
        assert tasks.alive_count() >= 1
        release.set()
        await task
        # The done-callback runs after the await completes.
        await asyncio.sleep(0)
        assert task not in tasks._TASKS

    asyncio.run(main())


def test_spawn_logs_unhandled_exception(caplog):
    async def main():
        async def dies():
            raise RuntimeError("pipeline stage exploded")

        task = spawn(dies(), name="doomed-stage")
        await asyncio.gather(task, return_exceptions=True)
        await asyncio.sleep(0)

    with caplog.at_level(logging.ERROR, logger="narwhal.tasks"):
        asyncio.run(main())
    died = [r for r in caplog.records if "died of an unhandled" in r.message]
    assert len(died) == 1
    assert "doomed-stage" in died[0].getMessage()
    assert died[0].exc_info is not None


def test_spawn_cancellation_is_silent(caplog):
    async def main():
        async def forever():
            await asyncio.Event().wait()

        task = spawn(forever(), name="cancelled")
        await asyncio.sleep(0)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        await asyncio.sleep(0)
        assert task not in tasks._TASKS

    with caplog.at_level(logging.ERROR, logger="narwhal.tasks"):
        asyncio.run(main())
    assert not [r for r in caplog.records if "died" in r.message]


def test_asyncio_trap_catches_destroyed_pending_message():
    # The conftest escalation path for "Task was destroyed but it is
    # pending!" (emitted via the asyncio LOGGER, not as a warning —
    # filterwarnings cannot catch it).  Exercise the handler directly:
    # routing a real record through the live logger would rightly fail
    # THIS test's teardown.
    from tests.conftest import _AsyncioErrorTrap

    trap = _AsyncioErrorTrap()
    record = logging.LogRecord(
        "asyncio", logging.ERROR, __file__, 0,
        "Task was destroyed but it is pending!", None, None,
    )
    trap.emit(record)
    assert trap.messages == ["Task was destroyed but it is pending!"]
    trap.emit(logging.LogRecord(
        "asyncio", logging.ERROR, __file__, 0, "unrelated", None, None
    ))
    assert len(trap.messages) == 1


def test_background_tasks_gauge_registered():
    if metrics.registry().enabled:
        assert "runtime.background_tasks" in metrics.registry().gauge_fns


# -- loop-stall watchdog ------------------------------------------------------

def _stall_instruments():
    reg = metrics.registry()
    return (
        reg.histograms.get("runtime.loop_stall_seconds"),
        reg.counters.get("runtime.loop_stalls"),
    )


@pytest.mark.skipif(
    not metrics.registry().enabled, reason="metrics stubbed"
)
def test_watchdog_measures_a_real_stall_and_names_the_stack():
    async def main():
        dog = LoopWatchdog(threshold_s=0.05, interval_s=0.01).start()
        hist, ctr = _stall_instruments()
        count0, stalls0 = hist.count, ctr.value
        try:
            # Hold the loop well past the threshold (tests/ are outside
            # the linter's scope, and this blocking IS the fixture).
            await asyncio.sleep(0.03)  # let the beat task stamp once
            time.sleep(0.3)
            # Two beats after the stall: one measures the overshoot, the
            # next gives the watcher thread a tick to settle.
            await asyncio.sleep(0.05)
        finally:
            await dog.shutdown()
        assert hist.count > count0, "stall was not observed"
        assert hist.sum > 0.2  # the 0.3 s hold dominates the observation
        assert ctr.value > stalls0
        last = dog._last_stall
        assert last.get("stall_s", 0) > 0.2
        # The watcher thread captured the loop thread's stack mid-stall,
        # naming this very test as the culprit.
        assert "time.sleep" in last.get("stack", "") or "test_watchdog" in (
            last.get("stack", "")
        )

    asyncio.run(main())


def test_watchdog_quiet_loop_observes_nothing():
    async def main():
        dog = LoopWatchdog(threshold_s=0.2, interval_s=0.02).start()
        hist, _ = _stall_instruments()
        count0 = hist.count if hist else 0
        await asyncio.sleep(0.15)
        await dog.shutdown()
        assert (hist.count if hist else 0) == count0

    asyncio.run(main())


def test_install_from_env(monkeypatch):
    async def off():
        monkeypatch.setenv("NARWHAL_LOOP_WATCHDOG_MS", "0")
        assert install_from_env() is None

    async def unset():
        # On by default since PR 26: one header timer.
        monkeypatch.delenv("NARWHAL_LOOP_WATCHDOG_MS", raising=False)
        dog = install_from_env()
        assert dog is not None and dog.threshold_s == pytest.approx(0.1)
        assert dog.interval_s == pytest.approx(0.025)
        await dog.shutdown()

    async def armed():
        monkeypatch.setenv("NARWHAL_LOOP_WATCHDOG_MS", "50")
        dog = install_from_env()
        assert dog is not None and dog.threshold_s == pytest.approx(0.05)
        assert asyncio.get_running_loop().slow_callback_duration == (
            pytest.approx(0.05)
        )
        await dog.shutdown()

    asyncio.run(off())
    asyncio.run(unset())
    asyncio.run(armed())
