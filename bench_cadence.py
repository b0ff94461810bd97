#!/usr/bin/env python3
"""Round-cadence microbenchmark: the Core's header→vote→cert round-trip.

The r09 cert→commit attribution showed 97-98% of commit latency is protocol
cadence — `primary.round_advance_seconds` × commit depth — so this bench
isolates ONE round of that cadence through a live Core event loop: own
header in → own vote → 2f peer votes → our certificate assembled → 2f
peer certificates → parent quorum out.  Two arms, interleaved A/B per
iteration (ISSUE r10):

- **fast** — the vote fast path (`Core(fast_path=True)`, the default):
  header store records buffered via ``Store.write_deferred`` and flushed
  ONCE per drained burst before the staged votes leave, per-burst GC,
  cached committee address lists.
- **legacy** — ``Core(fast_path=False)``: one writev per header on the
  processing path, votes sent per header (the pre-r10 behavior; GC and
  address caching stay, so the arms isolate the persist/vote coalescing).

Honesty notes: signature batch verification is STUBBED (always-true mask)
— this measures cadence machinery, not crypto (the ed25519 cost is
measured by bench_crypto.py and identical in both arms); the network is a
null sender (loopback TCP would time the kernel, not the Core); the store
log lives on tmpfs when available (same reasoning as local_bench).  What
remains is exactly the per-round critical path the round period is made
of: queue hops, sanitize/replay, store persists, aggregation.

    python bench_cadence.py --sizes 4 20 50 --rounds 40 --iters 5 \
        --artifact artifacts/cadence_bench.json

``--gate`` turns on the CI regression gate: the fast arm's median
seconds-per-round must not exceed the legacy arm's by more than
``--gate-max-slowdown`` (default 1.15) at any committee size.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from narwhal_tpu.config import (  # noqa: E402
    Authority,
    Committee,
    PrimaryAddresses,
    WorkerAddresses,
)
from narwhal_tpu.crypto import KeyPair, Signature, SignatureService  # noqa: E402
from narwhal_tpu.primary.core import AtomicRound, Core  # noqa: E402
from narwhal_tpu.primary.messages import (  # noqa: E402
    Certificate,
    Header,
    Vote,
    genesis,
)
from narwhal_tpu.primary.synchronizer import Synchronizer  # noqa: E402
from narwhal_tpu.store import Store  # noqa: E402


def make_committee(n: int):
    """(committee, keypairs): seeded stake-1 loopback committee."""
    kps = [
        KeyPair.generate(rng_seed=i.to_bytes(32, "little")) for i in range(n)
    ]
    auths = {
        kp.name: Authority(
            stake=1,
            primary=PrimaryAddresses("127.0.0.1:0", "127.0.0.1:0"),
            workers={0: WorkerAddresses("127.0.0.1:0", "127.0.0.1:0", "127.0.0.1:0")},
        )
        for kp in kps
    }
    return Committee(auths), kps


class NullSender:
    """Stands in for ReliableSender: the bench times the Core, not TCP.
    Returns never-completing futures so cancel_handlers bookkeeping (and
    its GC) costs exactly what it costs live."""

    def __init__(self) -> None:
        self.sent = 0

    def _fut(self):
        return asyncio.get_running_loop().create_future()

    def send(self, address, message):
        self.sent += 1
        return self._fut()

    def broadcast(self, addresses, message):
        self.sent += len(addresses)
        return [self._fut() for _ in addresses]

    def close(self) -> None:
        pass


def prebuild_rounds(committee, kps, me_kp, rounds: int):
    """Pre-create every message OUTSIDE the timed region (construction +
    hashing is identical for both arms; signatures are dummy bytes since
    the batch verify is stubbed).  Per round: (own header, peer votes for
    it, peer certificates of the same round)."""
    dummy = Signature(bytes(64))
    me = me_kp.name
    others = [kp.name for kp in kps if kp.name != me]
    quorum = committee.quorum_threshold()
    names = sorted(committee.authorities.keys())
    parents = {c.digest() for c in genesis(committee)}
    out = []
    for r in range(1, rounds + 1):
        header = Header(author=me, round=r, payload={}, parents=set(parents))
        header.id = header.compute_digest()
        header.signature = dummy
        # Own vote (cast inline by the Core) counts 1; top up to quorum.
        votes = [
            Vote(id=header.id, round=r, origin=me, author=name, signature=dummy)
            for name in others[: quorum - 1]
        ]
        my_cert_digest = Certificate(header=header).digest()
        peer_certs = []
        for name in others:
            oh = Header(author=name, round=r, payload={}, parents=set(parents))
            oh.id = oh.compute_digest()
            oh.signature = dummy
            cert_votes = [
                (v, dummy) for v in names if v != name
            ][: quorum]
            peer_certs.append(Certificate(header=oh, votes=cert_votes))
        parents = {my_cert_digest} | {c.digest() for c in peer_certs}
        out.append((header, votes, peer_certs))
    return out


async def run_arm(committee, kps, me_kp, prebuilt, fast_path: bool, store_path: str):
    """Drive the prebuilt rounds through a live Core.run() loop; returns
    wall seconds per round (header in → parent quorum out)."""
    from narwhal_tpu.crypto import backend as crypto_backend

    real = crypto_backend.averify_batch_mask

    async def stub(msgs, keys, sigs, site="other"):
        return [True] * len(msgs)

    crypto_backend.averify_batch_mask = stub
    store = Store(store_path)
    qs = {
        name: asyncio.Queue()
        for name in (
            "primaries", "header_sync", "cert_sync", "header_loop",
            "cert_loop", "proposer_in", "consensus", "proposer_out",
        )
    }
    synchronizer = Synchronizer(
        me_kp.name, committee, store, qs["header_sync"], qs["cert_sync"]
    )
    core = Core(
        me_kp.name,
        committee,
        store,
        synchronizer,
        SignatureService(me_kp),
        AtomicRound(),
        gc_depth=50,
        rx_primaries=qs["primaries"],
        rx_header_waiter=qs["header_loop"],
        rx_certificate_waiter=qs["cert_loop"],
        rx_proposer=qs["proposer_in"],
        tx_consensus=qs["consensus"],
        tx_proposer=qs["proposer_out"],
        fast_path=fast_path,
    )
    core.network = NullSender()
    task = asyncio.get_running_loop().create_task(core.run())
    try:
        t0 = time.perf_counter()
        for header, votes, peer_certs in prebuilt:
            await qs["proposer_in"].put(header)
            # The Core must adopt the header before its votes are valid.
            while core.current_header is not header:
                await asyncio.sleep(0)
            for v in votes:
                qs["primaries"].put_nowait(("vote", v))
            for c in peer_certs:
                qs["primaries"].put_nowait(("certificate", c))
            await qs["proposer_out"].get()  # parent quorum for this round
        dt = time.perf_counter() - t0
    finally:
        # Restore the backend FIRST: store.close() can raise (it flushes
        # deferred records), and a leaked always-true verify stub would
        # silently poison every later arm in this process.
        crypto_backend.averify_batch_mask = real
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        store.close()
    if os.path.exists(store_path):
        os.remove(store_path)
    return dt / len(prebuilt)


def bench_size(n: int, rounds: int, iters: int, storedir: str):
    committee, kps = make_committee(n)
    me_kp = kps[0]
    prebuilt = prebuild_rounds(committee, kps, me_kp, rounds)
    samples = {"fast": [], "legacy": []}
    # Interleaved A/B: one fast + one legacy run per iteration, so host
    # noise (thermal drift, background load) lands on both arms equally.
    for i in range(iters):
        for arm, fast in (("fast", True), ("legacy", False)):
            path = os.path.join(storedir, f"cadence-{n}-{arm}-{i}.log")
            s = asyncio.run(
                run_arm(committee, kps, me_kp, prebuilt, fast, path)
            )
            samples[arm].append(s)
    med = {arm: statistics.median(v) for arm, v in samples.items()}
    return {
        "committee": n,
        "rounds": rounds,
        "iters": iters,
        "seconds_per_round": {
            arm: {
                "median": med[arm],
                "min": min(v),
                "mean": statistics.fmean(v),
                "samples": v,
            }
            for arm, v in samples.items()
        },
        "fast_vs_legacy": (
            med["legacy"] / med["fast"] if med["fast"] > 0 else None
        ),
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes", type=int, nargs="+", default=[4, 20, 50])
    parser.add_argument("--rounds", type=int, default=40)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--artifact", default=None)
    parser.add_argument(
        "--gate",
        action="store_true",
        help="fail (exit 1) if the fast arm's median is more than "
        "--gate-max-slowdown × the legacy arm's at any size",
    )
    parser.add_argument("--gate-max-slowdown", type=float, default=1.15)
    args = parser.parse_args()

    # Same tmpfs preference as local_bench: the store log's writev costs
    # should reflect page-cache appends, not a CI runner's disk.
    storedir = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(prefix="cadence_bench_", dir=storedir)
    try:
        results = []
        for n in args.sizes:
            r = bench_size(n, args.rounds, args.iters, tmp)
            results.append(r)
            f, l = (
                r["seconds_per_round"]["fast"]["median"],
                r["seconds_per_round"]["legacy"]["median"],
            )
            print(
                f"N={n:3d}: fast {1e6 * f:8.1f} us/round, "
                f"legacy {1e6 * l:8.1f} us/round, "
                f"ratio legacy/fast {r['fast_vs_legacy']:.2f}x"
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    artifact = {
        "bench": "cadence",
        "note": (
            "header->vote->cert round-trip through Core.run; signature "
            "batch verify stubbed (always true), network nulled — "
            "cadence machinery only.  Arms interleaved per iteration."
        ),
        "results": results,
    }
    if args.artifact:
        os.makedirs(os.path.dirname(args.artifact) or ".", exist_ok=True)
        with open(args.artifact, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"artifact written to {args.artifact}")

    if args.gate:
        for r in results:
            f = r["seconds_per_round"]["fast"]["median"]
            l = r["seconds_per_round"]["legacy"]["median"]
            if f > l * args.gate_max_slowdown:
                print(
                    f"GATE FAILED at N={r['committee']}: fast median "
                    f"{1e6 * f:.1f} us/round exceeds legacy "
                    f"{1e6 * l:.1f} us/round by more than "
                    f"{args.gate_max_slowdown:.2f}x",
                    file=sys.stderr,
                )
                return 1
        print("gate passed: fast arm within "
              f"{args.gate_max_slowdown:.2f}x of legacy at every size")
    return 0


if __name__ == "__main__":
    sys.exit(main())
