"""Pluggable verification backend: CPU reference vs. TPU batched kernel.

The reference's `Signature::verify_batch` (crypto/src/lib.rs:206-219) is the
per-round crypto hot spot — 2f+1 ed25519 verifications per certificate × N
certificates per round (SURVEY.md §3.3).  Here that call is a seam: the CPU
backend loops over OpenSSL verifies; the TPU backend ships the whole batch to
a vmapped JAX verifier (narwhal_tpu/ops/ed25519.py) in one dispatch.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

from .. import metrics
from ..utils.clock import wall_now
from ..utils.devtrace import current_burst
from ..utils.env import env_flag, env_str
from .aggregate import AggregateSignature, verify_halfagg
from .digest import Digest
from .keys import PublicKey, Signature, cpu_verify

log = logging.getLogger("narwhal.crypto")

# -- crypto-cost ledger -------------------------------------------------------
#
# Every module-level verify entry point below is labelled with its CALL
# SITE so the bench's `crypto` section can attribute where verification
# ops (and their wall time) come from:
#
#   header / vote / certificate  inline sanitization (Header.verify,
#                                Vote.verify, Certificate.verify — the
#                                serial path)
#   batch_burst                  Core's accumulate→averify→replay seam
#                                (the batched path the ROADMAP item-1 A/B
#                                must show absorbing the serial ops)
#   certificate_agg              ONE half-aggregated quorum check under
#                                --cert-sig-scheme halfagg (whether it
#                                arrives serially via Certificate.verify
#                                or inside a burst batch) — ops count 1
#                                per certificate, which is the ledger
#                                witness for the "2f+1 → 1 verify"
#                                claim of ROADMAP item 2
#
# Per site: `crypto.verify.ops.<site>` (signature checks performed),
# `crypto.verify.seconds.<site>` (wall time per CALL — for the async
# batched path this includes event-loop yields/device round-trip, which
# is exactly the latency the caller pays), and
# `crypto.verify.batch_size.<site>` (ops per call — the serial→batched
# conversion shows up as mass moving to higher buckets).  The async
# batched path additionally records
# `crypto.verify.device_seconds.<site>`: the backend's own compute time
# (host prep + device dispatch + result sync), EXCLUDING event-loop
# yield/executor-queue time — without the split, the single wall
# histogram conflates "crypto is slow" with "the loop was busy", which
# under-credits pipelining in the A/B.
# Instrumentation lives HERE, on the module seam, so both the CPU and
# TPU backends are covered and backend-internal chunking is not
# double-counted.

_verify_instruments_cache: Dict[str, Tuple] = {}


def _verify_instruments(site: str):
    inst = _verify_instruments_cache.get(site)
    if inst is None:
        inst = _verify_instruments_cache[site] = (
            metrics.counter(f"crypto.verify.ops.{site}"),
            metrics.histogram(f"crypto.verify.seconds.{site}"),
            metrics.histogram(
                f"crypto.verify.batch_size.{site}", metrics.COUNT_BUCKETS
            ),
            metrics.histogram(f"crypto.verify.device_seconds.{site}"),
        )
    return inst


class CpuBackend:
    name = "cpu"

    def verify(self, message: bytes, key: PublicKey, sig: Signature) -> bool:
        return cpu_verify(message, key, sig)

    def verify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        sigs: Sequence[Signature],
    ) -> List[bool]:
        return [cpu_verify(m, k, s) for m, k, s in zip(messages, keys, sigs)]

    # Inline chunk size: ~64 OpenSSL verifies ≈ 10 ms — the max the event
    # loop may stall between yields.  A thread handoff per burst was
    # measured strictly worse on core-starved hosts (GIL/scheduler
    # ping-pong, cf. store.py), so big bursts stay on-loop but cooperative.
    AVERIFY_CHUNK = 64

    async def averify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        sigs: Sequence[Signature],
    ) -> List[bool]:
        mask, _ = await self.averify_batch_mask_timed(messages, keys, sigs)
        return mask

    async def averify_batch_mask_timed(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        sigs: Sequence[Signature],
    ) -> Tuple[List[bool], float]:
        """(mask, compute_seconds): compute time sums the synchronous
        verify chunks only — the inter-chunk event-loop yields are wall
        time the CALLER'S latency pays, not crypto cost."""
        n = len(messages)
        t0 = time.perf_counter()
        if n <= self.AVERIFY_CHUNK:
            return (
                self.verify_batch_mask(messages, keys, sigs),
                time.perf_counter() - t0,
            )
        import asyncio

        out: List[bool] = []
        compute = 0.0
        for i in range(0, n, self.AVERIFY_CHUNK):
            j = i + self.AVERIFY_CHUNK
            t0 = time.perf_counter()
            out.extend(self.verify_batch_mask(messages[i:j], keys[i:j], sigs[i:j]))
            compute += time.perf_counter() - t0
            # Yield between chunks so network/timers keep running during a
            # committee-sized burst (tens of ms of crypto at N=20+).
            await asyncio.sleep(0)
        return out, compute


_backend = CpuBackend()

# Two names for the batched JAX verifier (ops/ed25519.py).  "tpu" means
# the chip: selecting it on a host whose JAX has no TPU is a boot error,
# never a silent jax-cpu run logged as "tpu".  "jax" runs on whatever
# platform JAX has — the CPU tests and the A/B arms say that.
_BATCHED_NAMES = ("tpu", "jax")


def set_backend(name: str, strict: Optional[bool] = None) -> None:
    """Select the verification backend: "cpu", "tpu" (the batched
    verifier, on a TPU or not at all) or "jax" (the batched verifier on
    any JAX platform).

    A jax/tpu request whose import fails is a BOOT error, not a
    first-burst error: with ``strict`` (default: the
    NARWHAL_CRYPTO_BACKEND_STRICT flag, on) the import failure raises
    here, at selection time; with strict off it logs the import error
    and falls back to the cpu backend — an explicit, logged downgrade.
    A "tpu" request that finds another platform always raises.

    Selecting jax/tpu initialises JAX's backend: the process holds the
    chip from here on, so a parent that spawns chip users never calls
    this with a batched name.
    """
    global _backend
    if name == "cpu":
        _backend = CpuBackend()
    elif name in _BATCHED_NAMES:
        try:
            # deferred: JAX import is heavy
            from ..ops.ed25519 import TpuBackend, device_identity
        except ImportError as e:
            if strict is None:
                strict = env_flag("NARWHAL_CRYPTO_BACKEND_STRICT")
            if strict:
                raise RuntimeError(
                    f"crypto backend {name!r} requested but the batched "
                    f"verifier failed to import: {e} — install jax/numpy "
                    "or set NARWHAL_CRYPTO_BACKEND_STRICT=0 to fall back "
                    "to the cpu backend"
                ) from e
            log.error(
                "crypto backend %r unavailable (%s); falling back to cpu "
                "(NARWHAL_CRYPTO_BACKEND_STRICT=0)", name, e,
            )
            _backend = CpuBackend()
            return
        if name == "tpu":
            found = device_identity()
            if found["platform"] != "tpu":
                raise RuntimeError(
                    "crypto backend 'tpu' requested but JAX's default "
                    f"device is on platform {found['platform']!r} "
                    f"({found['kind']} x{found['count']}) — no TPU here; "
                    "say 'jax' to run the batched verifier on whatever "
                    "platform JAX has"
                )
        _backend = TpuBackend(name)
    else:
        raise ValueError(f"unknown crypto backend {name!r}")


def describe_backend() -> str:
    """The live backend for the boot log: its name, and for the batched
    verifier the platform, device kind and device count it runs on."""
    if _backend.name not in _BATCHED_NAMES:
        return _backend.name
    from ..ops import device_identity

    found = device_identity()
    return "{} on platform {} ({} x{})".format(
        _backend.name, found["platform"], found["kind"], found["count"]
    )


def set_backend_from_env(cli_choice: Optional[str] = None) -> str:
    """Boot-time backend selection: the CLI flag wins, then the
    NARWHAL_CRYPTO_BACKEND env knob, then "cpu".  Returns the name that
    was requested (the live backend's name may differ only under the
    non-strict fallback)."""
    name = cli_choice or env_str("NARWHAL_CRYPTO_BACKEND") or "cpu"
    set_backend(name)
    return name


def get_backend():
    return _backend


def verify_aggregate(
    message: bytes,
    signers: Sequence[PublicKey],
    agg: AggregateSignature,
    site: str = "certificate_agg",
) -> bool:
    """One half-aggregated quorum check: the whole 2f+1 vote set of a
    certificate is ONE op in the crypto ledger (`crypto.verify.ops.
    certificate_agg`).  The multiexp equation runs on the CPU fallback
    for now — a batched device multiexp kernel is the natural follow-up
    once the scheme flips default — so both backends route here."""
    ops, secs, sizes, _dev = _verify_instruments(site)
    t0 = time.perf_counter()
    try:
        return verify_halfagg(bytes(message), signers, bytes(agg))
    finally:
        ops.inc()
        sizes.observe(1)
        secs.observe(time.perf_counter() - t0)


def _split_aggregate_claims(messages, keys, sigs):
    """Partition a mixed claim batch into plain (message, key, sig)
    triples and aggregate (message, signer-tuple, AggregateSignature)
    claims — the shape Certificate.signature_claims emits under
    ``halfagg``.  Returns (plain_positions, plain triples, agg_positions,
    agg claims); plain order is preserved so the backend sees the same
    batch it would without aggregates present."""
    plain_pos: List[int] = []
    pm: List[bytes] = []
    pk: List[PublicKey] = []
    ps: List[Signature] = []
    agg_pos: List[int] = []
    aggs: List[Tuple[bytes, Sequence[PublicKey], AggregateSignature]] = []
    for i, (m, k, s) in enumerate(zip(messages, keys, sigs)):
        if isinstance(s, AggregateSignature):
            agg_pos.append(i)
            aggs.append((m, k, s))
        else:
            plain_pos.append(i)
            pm.append(m)
            pk.append(k)
            ps.append(s)
    return plain_pos, pm, pk, ps, agg_pos, aggs


def verify(
    message: bytes, key: PublicKey, sig: Signature, site: str = "other"
) -> bool:
    ops, secs, sizes, _dev = _verify_instruments(site)
    t0 = time.perf_counter()
    try:
        return _backend.verify(message, key, sig)
    finally:
        ops.inc()
        sizes.observe(1)
        secs.observe(time.perf_counter() - t0)


def verify_batch_mask(
    messages: Sequence[bytes],
    keys: Sequence[PublicKey],
    sigs: Sequence[Signature],
    site: str = "other",
) -> List[bool]:
    """Per-item validity mask for a batch of (message, key, signature).
    Aggregate claims (an AggregateSignature in the sig slot) are split
    out and checked one equation each under the ``certificate_agg``
    site; the plain remainder rides the selected backend unchanged."""
    if not (len(messages) == len(keys) == len(sigs)):
        raise ValueError("verify_batch: length mismatch")
    if not messages:
        return []
    if any(isinstance(s, AggregateSignature) for s in sigs):
        plain_pos, pm, pk, ps, agg_pos, aggs = _split_aggregate_claims(
            messages, keys, sigs
        )
        mask: List[bool] = [False] * len(messages)
        for pos, ok in zip(
            plain_pos, verify_batch_mask(pm, pk, ps, site=site) if pm else []
        ):
            mask[pos] = ok
        for pos, (m, k, s) in zip(agg_pos, aggs):
            mask[pos] = verify_aggregate(m, k, s)
        return mask
    ops, secs, sizes, _dev = _verify_instruments(site)
    t0 = time.perf_counter()
    try:
        return list(_backend.verify_batch_mask(messages, keys, sigs))
    finally:
        ops.inc(len(messages))
        sizes.observe(len(messages))
        secs.observe(time.perf_counter() - t0)


async def averify_batch_mask(
    messages: Sequence[bytes],
    keys: Sequence[PublicKey],
    sigs: Sequence[Signature],
    site: str = "other",
) -> List[bool]:
    """Async verify_batch_mask: the TPU backend runs the device round trip
    in an executor thread so the node's event loop (networking, proposer
    timers, waiters) keeps running during the dispatch+sync — without this,
    every Core burst would stall the whole primary for the device latency.

    Inside ``devtrace.burst(key)`` the call stamps the caller's entry of
    the verify-stage trace (metrics.VERIFY_STAGES): ``submitted`` and
    ``resumed`` here, on the loop, and a backend with a dispatch thread
    hands back its own stamps (``prepare``, ``enqueued``, ``fetched`` and
    the extras) as a third element of its result, which are marked here
    too — the table is written from the loop only."""
    if not (len(messages) == len(keys) == len(sigs)):
        raise ValueError("verify_batch: length mismatch")
    if not messages:
        return []
    if any(isinstance(s, AggregateSignature) for s in sigs):
        # Mixed burst under halfagg: plain claims (header signatures,
        # votes) keep the async backend path; each aggregate claim is
        # one CPU multiexp with an event-loop yield between equations
        # (the AVERIFY_CHUNK discipline — ~10-30 ms per equation on the
        # pure-Python fallback must not starve timers at N=20 catch-up).
        import asyncio

        plain_pos, pm, pk, ps, agg_pos, aggs = _split_aggregate_claims(
            messages, keys, sigs
        )
        mask: List[bool] = [False] * len(messages)
        if pm:
            plain_mask = await averify_batch_mask(pm, pk, ps, site=site)
            for pos, ok in zip(plain_pos, plain_mask):
                mask[pos] = ok
        for pos, (m, k, s) in zip(agg_pos, aggs):
            mask[pos] = verify_aggregate(m, k, s)
            await asyncio.sleep(0)
        return mask
    ops, secs, sizes, dev = _verify_instruments(site)
    verify_trace = metrics.verify_trace()
    trace_key = current_burst()
    if trace_key is not None:
        verify_trace.mark(trace_key, "submitted", claims=len(messages))
    t0 = time.perf_counter()
    try:
        mask, compute_s, *thread = await _backend.averify_batch_mask_timed(
            messages, keys, sigs
        )
        if trace_key is not None:
            resumed = wall_now()
            stamps = dict(thread[0]) if thread else {}
            for stage in ("prepare", "enqueued", "fetched"):
                if stage in stamps:
                    verify_trace.mark(trace_key, stage, stamps.pop(stage))
            verify_trace.mark(trace_key, "resumed", resumed, **stamps)
        # Backend-side compute only (host prep + dispatch + result sync)
        # vs the wall observation below, which additionally carries the
        # event-loop yields / executor-queue wait across the await.
        dev.observe(compute_s)
        return list(mask)
    finally:
        # Wall time across the await: includes event-loop yields and the
        # device round trip — the latency the calling burst actually pays.
        ops.inc(len(messages))
        sizes.observe(len(messages))
        secs.observe(time.perf_counter() - t0)


def verify_batch(
    digest: Digest,
    keys: Sequence[PublicKey],
    sigs: Sequence[Signature],
    site: str = "other",
) -> bool:
    """All-or-nothing batch verification of many signatures over ONE digest —
    the certificate-quorum check (reference primary/src/messages.rs:189-215).
    An empty batch is invalid: a zero-signature certificate must never pass."""
    if not keys:
        return False
    msgs = [bytes(digest)] * len(keys)
    return all(verify_batch_mask(msgs, keys, sigs, site=site))
