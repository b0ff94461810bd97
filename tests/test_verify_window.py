"""Verify-batch accumulation window (ISSUE r19, ROADMAP item 1): with
NARWHAL_VERIFY_BATCH_WINDOW_MS > 0 the Core routes drained peer bursts
through a pipelined verify stage that coalesces cross-message-type
signature claims from MULTIPLE drains into ONE backend dispatch — the
serial→batched conversion the crypto ledger must show as a batch-size
distribution shift.  These tests pin the coalescing (one batch_burst
call covering several puts), the replay semantics (every message still
processed, per-kind claim arithmetic intact), the batch-max bound, and
backend-selection ergonomics (strict boot failure vs explicit cpu
fallback, env/CLI precedence)."""

import asyncio
import sys

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.crypto import backend as cb
from tests.common import (
    committee,
    keys,
    make_certificate,
    make_header,
)
from tests.test_core import make_core


def run(coro, timeout=30):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def cnt(name: str) -> float:
    c = metrics.registry().counters.get(name)
    return c.value if c is not None else 0


def hist_count(name: str) -> int:
    h = metrics.registry().histograms.get(name)
    return h.count if h is not None else 0


def make_window_core(c, me, window_ms=200.0, batch_max=256):
    core, store, qs = make_core(c, me)
    # Reconfigure the window post-construction (make_core builds with
    # the env default, off): the queue exists iff the window is on.
    core.verify_window_s = window_ms / 1000.0
    core.verify_batch_max = batch_max
    core._verify_q = asyncio.Queue(maxsize=max(256, 2 * batch_max))
    return core, store, qs


async def drive(core, qs, items, done, deadline_s=15.0):
    """Run core.run() while feeding ``items`` into rx_primaries in two
    spaced puts (two separate drains that the window must coalesce),
    then poll until ``done()`` (a counter predicate) or the deadline."""
    task = asyncio.get_running_loop().create_task(core.run())
    try:
        half = max(1, len(items) // 2)
        for it in items[:half]:
            qs["primaries"].put_nowait(it)
        # Let run() drain the first chunk into the verify queue, then
        # land the second chunk inside the accumulation window.
        for _ in range(4):
            await asyncio.sleep(0)
        for it in items[half:]:
            qs["primaries"].put_nowait(it)
        loop = asyncio.get_running_loop()
        stop = loop.time() + deadline_s
        while not done() and loop.time() < stop:
            await asyncio.sleep(0.01)
        assert done(), "burst never replayed within the deadline"
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        core.network.close()


def test_window_coalesces_two_drains_into_one_dispatch():
    """Certificates landing in two separate drains within the window
    must verify in ONE batch_burst call whose op count is the sum of
    both drains' claims (quorum+1 each)."""

    async def go():
        c = committee()
        me = keys()[0]
        core, store, qs = make_window_core(c, me, window_ms=300.0)
        quorum = c.quorum_threshold()
        certs = [
            make_certificate(make_header(kp, c=c))
            for kp in keys()[1:4]
        ]
        calls0 = hist_count("crypto.verify.batch_size.batch_burst")
        ops0 = cnt("crypto.verify.ops.batch_burst")
        certs0 = cnt("primary.certificates_processed")
        await drive(
            core, qs, [("certificate", x) for x in certs],
            done=lambda: cnt("primary.certificates_processed") - certs0
            >= len(certs),
        )
        assert cnt("primary.certificates_processed") - certs0 == len(certs)
        assert (
            cnt("crypto.verify.ops.batch_burst") - ops0
            == len(certs) * (quorum + 1)
        )
        # The coalescing claim: ONE dispatch covered both drains.
        assert (
            hist_count("crypto.verify.batch_size.batch_burst") - calls0 == 1
        )

    run(go())


def test_window_off_keeps_inline_per_burst_dispatch():
    """window=0 (the default): the verify queue does not exist and each
    _handle_primaries_burst call dispatches inline — the pre-r19 path
    the serial A/B arm measures."""

    async def go():
        c = committee()
        me = keys()[0]
        core, store, qs = make_core(c, me)
        assert core._verify_q is None
        calls0 = hist_count("crypto.verify.batch_size.batch_burst")
        for kp in keys()[1:3]:
            cert = make_certificate(make_header(kp, c=c))
            await core._handle_primaries_burst([("certificate", cert)])
        assert (
            hist_count("crypto.verify.batch_size.batch_burst") - calls0 == 2
        )
        core.network.close()

    run(go())


def test_window_respects_batch_max():
    """More messages than verify_batch_max inside one window must split
    into at least two dispatches, none covering more than the cap."""

    async def go():
        c = committee()
        me = keys()[0]
        core, store, qs = make_window_core(c, me, window_ms=300.0,
                                           batch_max=2)
        certs = [
            make_certificate(make_header(kp, round_=r, c=c))
            for r in (1,)
            for kp in keys()[1:4]
        ]
        calls0 = hist_count("crypto.verify.batch_size.batch_burst")
        certs0 = cnt("primary.certificates_processed")
        await drive(
            core, qs, [("certificate", x) for x in certs],
            done=lambda: cnt("primary.certificates_processed") - certs0
            >= len(certs),
        )
        assert cnt("primary.certificates_processed") - certs0 == len(certs)
        assert (
            hist_count("crypto.verify.batch_size.batch_burst") - calls0 >= 2
        )

    run(go())


def test_window_replay_still_counts_per_kind_claims():
    """The burst-claims protocol arithmetic (one header claim per
    header, quorum+1 per certificate) survives the window path — the
    bench's protocol_check reads these."""

    async def go():
        c = committee()
        me = keys()[0]
        core, store, qs = make_window_core(c, me, window_ms=300.0)
        quorum = c.quorum_threshold()
        header = make_header(keys()[1], c=c)
        cert = make_certificate(make_header(keys()[2], c=c))
        h0 = cnt("crypto.burst_claims.header")
        c0 = cnt("crypto.burst_claims.certificate")
        hdr0 = cnt("primary.headers_processed")
        await drive(
            core, qs, [("header", header), ("certificate", cert)],
            done=lambda: (
                cnt("crypto.burst_claims.certificate") - c0 >= quorum + 1
                and cnt("primary.headers_processed") - hdr0 >= 2
            ),
        )
        assert cnt("crypto.burst_claims.header") - h0 == 1
        assert cnt("crypto.burst_claims.certificate") - c0 == quorum + 1

    run(go())


def test_env_window_constructs_verify_queue(monkeypatch):
    """NARWHAL_VERIFY_BATCH_WINDOW_MS > 0 in the environment arms the
    pipeline at Core construction (what `node run` children see when
    the bench passes --verify-window-ms)."""
    monkeypatch.setenv("NARWHAL_VERIFY_BATCH_WINDOW_MS", "15")
    monkeypatch.setenv("NARWHAL_VERIFY_BATCH_MAX", "64")

    async def go():
        c = committee()
        core, store, qs = make_core(c, keys()[0])
        assert core._verify_q is not None
        assert core.verify_window_s == pytest.approx(0.015)
        assert core.verify_batch_max == 64
        core.network.close()

    run(go())


def test_crashed_verify_loop_surfaces_instead_of_wedging():
    """A verify stage that dies must re-raise out of run() — even when
    run() is blocked forwarding into a FULL verify queue (the sole
    consumer is gone, so without the race the primary would silently
    stop processing peer messages forever)."""

    async def go():
        c = committee()
        me = keys()[0]
        core, store, qs = make_window_core(c, me, window_ms=50.0)
        core._verify_q = asyncio.Queue(maxsize=1)  # force the full path

        async def boom(items, collected=None):
            raise RuntimeError("verify stage boom")

        core._handle_primaries_burst = boom
        task = asyncio.get_running_loop().create_task(core.run())
        try:
            for kp in keys()[1:4]:
                qs["primaries"].put_nowait(
                    ("certificate",
                     make_certificate(make_header(kp, c=c)))
                )
            with pytest.raises(RuntimeError, match="boom"):
                await asyncio.wait_for(task, 10)
        finally:
            if not task.done():
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)
            core.network.close()

    run(go())


def test_crashed_verify_loop_wakes_idle_run():
    """The verify task rides in run()'s wait set: its death surfaces
    promptly even with NO further traffic arriving."""

    async def go():
        c = committee()
        me = keys()[0]
        core, store, qs = make_window_core(c, me, window_ms=10.0)

        async def boom(items, collected=None):
            raise RuntimeError("idle boom")

        core._handle_primaries_burst = boom
        task = asyncio.get_running_loop().create_task(core.run())
        try:
            qs["primaries"].put_nowait(
                ("certificate",
                 make_certificate(make_header(keys()[1], c=c)))
            )
            # One message, then silence: the crash must still re-raise.
            with pytest.raises(RuntimeError, match="idle boom"):
                await asyncio.wait_for(task, 10)
        finally:
            if not task.done():
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)
            core.network.close()

    run(go())


class _SlowOffLoopBackend:
    """Stands in for the batched device verifier: a dispatch is off the
    event loop and stays in flight until the test releases it."""

    name = "slow-off-loop"
    dispatches_off_loop = True

    def __init__(self):
        self.in_flight = asyncio.Event()
        self.release = asyncio.Event()
        self.batches = []

    async def averify_batch_mask_timed(self, messages, keys, sigs):
        self.batches.append(len(messages))
        self.in_flight.set()
        await self.release.wait()
        return cb.CpuBackend().verify_batch_mask(messages, keys, sigs), 0.0


def test_off_loop_backend_never_holds_the_own_header_behind_a_verify(
    monkeypatch,
):
    """A backend whose dispatch is off the event loop is driven through
    the pipelined stage at the DEFAULT window (0): while peers'
    certificates are in flight on the device the node's own header is
    still processed and broadcast (PR 22: awaited inline, it reached the
    peers 44 ms late on the chip), and what arrived during the dispatch
    shares the next one."""

    async def go():
        backend = _SlowOffLoopBackend()
        monkeypatch.setattr(cb, "_backend", backend)
        c = committee()
        me = keys()[0]
        core, store, qs = make_core(c, me)
        assert core._verify_q is not None and core.verify_window_s == 0.0
        sent = []
        monkeypatch.setattr(
            core, "_broadcast_own_header",
            lambda header: sent.append(header.id) or [],
        )
        quorum = c.quorum_threshold()
        certs = [
            make_certificate(make_header(kp, c=c)) for kp in keys()[1:4]
        ]
        certs0 = cnt("primary.certificates_processed")
        task = asyncio.get_running_loop().create_task(core.run())
        try:
            qs["primaries"].put_nowait(("certificate", certs[0]))
            await asyncio.wait_for(backend.in_flight.wait(), 5)
            # In flight, not answered: the own header must not wait.
            own = make_header(me, c=c)
            qs["proposer_in"].put_nowait(own)
            for kp_cert in certs[1:]:
                qs["primaries"].put_nowait(("certificate", kp_cert))
            for _ in range(200):
                if sent:
                    break
                await asyncio.sleep(0.01)
            assert sent == [own.id]
            assert cnt("primary.certificates_processed") == certs0
            backend.release.set()
            for _ in range(500):
                if cnt("primary.certificates_processed") - certs0 >= 3:
                    break
                await asyncio.sleep(0.01)
            assert cnt("primary.certificates_processed") - certs0 == 3
            # One dispatch for the first arrival, ONE for both that
            # queued behind it.
            assert backend.batches == [quorum + 1, 2 * (quorum + 1)]
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            core.network.close()

    run(go())


# -- backend selection ergonomics (ISSUE 14 satellite) ------------------------


def test_set_backend_strict_raises_at_boot_on_import_failure(monkeypatch):
    """A jax/tpu request whose import fails must raise AT SELECTION
    (node boot), with the import error in the message — not deep in the
    first verify burst."""
    monkeypatch.setitem(sys.modules, "narwhal_tpu.ops.ed25519", None)
    with pytest.raises(RuntimeError, match="failed to import"):
        cb.set_backend("jax", strict=True)
    # The live backend is untouched by the failed selection.
    assert cb.get_backend().name == "cpu"


def test_set_backend_fallback_only_when_explicitly_allowed(monkeypatch):
    """NARWHAL_CRYPTO_BACKEND_STRICT=0 downgrades the boot failure to a
    logged cpu fallback; the default (strict) raises."""
    monkeypatch.setitem(sys.modules, "narwhal_tpu.ops.ed25519", None)
    monkeypatch.setenv("NARWHAL_CRYPTO_BACKEND_STRICT", "0")
    cb.set_backend("jax")
    assert cb.get_backend().name == "cpu"
    monkeypatch.setenv("NARWHAL_CRYPTO_BACKEND_STRICT", "1")
    with pytest.raises(RuntimeError):
        cb.set_backend("jax")


def test_set_backend_from_env_precedence(monkeypatch):
    """CLI choice wins over NARWHAL_CRYPTO_BACKEND; the env knob wins
    over the cpu default; unknown names still fail loud."""
    monkeypatch.setenv("NARWHAL_CRYPTO_BACKEND", "cpu")
    assert cb.set_backend_from_env(None) == "cpu"
    assert cb.get_backend().name == "cpu"
    monkeypatch.setitem(sys.modules, "narwhal_tpu.ops.ed25519", None)
    monkeypatch.setenv("NARWHAL_CRYPTO_BACKEND", "jax")
    with pytest.raises(RuntimeError):
        cb.set_backend_from_env(None)
    assert cb.set_backend_from_env("cpu") == "cpu"
    monkeypatch.delenv("NARWHAL_CRYPTO_BACKEND")
    with pytest.raises(ValueError):
        cb.set_backend("never-a-backend")


def test_averify_records_device_seconds_split():
    """The async batched seam records BOTH wall (across the await) and
    backend compute seconds per site — wall >= compute, and the compute
    histogram gains exactly one observation per call."""

    async def go():
        me = keys()[0]
        from narwhal_tpu.crypto import digest32

        d = digest32(b"w" * 32)
        sig = me.sign(d)
        reg = metrics.registry()

        def h(name):
            return reg.histograms.get(name)

        calls0 = h("crypto.verify.seconds.other")
        calls0 = calls0.count if calls0 else 0
        dev0 = h("crypto.verify.device_seconds.other")
        dev0 = dev0.count if dev0 else 0
        ok = await cb.averify_batch_mask(
            [bytes(d)] * 3, [me.name] * 3, [sig] * 3
        )
        assert ok == [True, True, True]
        wall = h("crypto.verify.seconds.other")
        dev = h("crypto.verify.device_seconds.other")
        assert wall.count == calls0 + 1
        assert dev.count == dev0 + 1
        assert dev.sum <= wall.sum + 1e-9

    run(go())
