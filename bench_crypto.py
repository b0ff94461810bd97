#!/usr/bin/env python3
"""North-star microbenchmark: ed25519 signature verifications/sec/chip.

BASELINE.json names `ed25519 verifies/sec/chip` as this build's own metric.
This benchmark measures the TPU batch verifier (narwhal_tpu/ops/ed25519.py,
the device analog of the reference's dalek `verify_batch`,
/root/reference/crypto/src/lib.rs:206-219) against the CPU/OpenSSL verifier
on the same host, at batch sizes spanning the protocol's realistic range
(a 4-node certificate carries 3 sigs; a 50-node round can burst ~8k sigs
through the Core's accumulate→batch-verify seam).

Methodology:
- steady state only: first call per shape compiles; timings start after
  a warmup call per shape.
- `device`: median-of-N wall time of dispatch→block on the result mask —
  the latency a Core burst actually pays.
- `pipelined`: K batches dispatched back-to-back before blocking — the
  sustained chip rate when host prep overlaps device compute (the async
  verify path in primary/core.py works this way).
- `prep`: host-side bytes→limbs/windows + SHA-512 hash-to-scalar cost.
- CPU baseline: single-core OpenSSL verify loop (this host has 1 core;
  multiply by core count for a multi-core host figure).

Output: one JSON line per configuration plus a `summary` line; pass
`--artifact PATH` to also write the full result set to a JSON file.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def make_batch(n: int, seed: int = 7):
    """n valid (message, key, signature) triples over 32-byte messages."""
    import hashlib

    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.crypto.keys import cpu_verify

    from narwhal_tpu.crypto.digest import Digest

    kp = KeyPair.generate(rng_seed=hashlib.sha256(b"bench%d" % seed).digest())
    msgs = [hashlib.sha256(i.to_bytes(8, "little")).digest() for i in range(n)]
    # KeyPair.sign signs a Digest (32 bytes) — exactly the protocol's usage.
    sigs = [kp.sign(Digest(m)) for m in msgs]
    assert cpu_verify(msgs[0], kp.name, sigs[0])
    return msgs, [kp.name] * n, sigs


def bench_cpu(msgs, keys, sigs, budget_s: float = 2.0) -> float:
    """Single-core OpenSSL verifies/sec."""
    from narwhal_tpu.crypto.keys import cpu_verify

    n, i, t0 = 0, 0, time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        assert cpu_verify(msgs[i], keys[i], sigs[i])
        i = (i + 1) % len(msgs)
        n += 1
    return n / (time.perf_counter() - t0)


def bench_tpu(msgs, keys, sigs, batch: int, iters: int, pipeline_depth: int = 4):
    import numpy as np

    import jax.numpy as jnp

    from narwhal_tpu.ops import ed25519 as E

    m, k, s = msgs[:batch], keys[:batch], sigs[:batch]

    # Host prep cost (amortized per signature).
    t0 = time.perf_counter()
    packed = E.prepare_batch(m, k, s, batch)
    prep_s = time.perf_counter() - t0
    jpacked = jnp.asarray(packed)

    # Warmup / compile (persistent cache makes this fast on reruns).
    t0 = time.perf_counter()
    mask = np.asarray(E._verify_kernel(jpacked))
    compile_s = time.perf_counter() - t0
    if not mask.all():
        raise AssertionError("kernel rejected valid signatures")

    # Blocking latency per batch.
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(E._verify_kernel(jpacked))
        lat.append(time.perf_counter() - t0)
    lat_s = statistics.median(lat)

    # Pipelined: dispatch K batches, block once at the end.
    t0 = time.perf_counter()
    outs = [E._verify_kernel(jpacked) for _ in range(pipeline_depth)]
    for o in outs:
        o.block_until_ready()
    pipe_s = (time.perf_counter() - t0) / pipeline_depth

    return {
        "batch": batch,
        "prep_us_per_sig": round(1e6 * prep_s / batch, 2),
        "compile_or_cache_load_s": round(compile_s, 2),
        "device_ms_per_batch": round(1e3 * lat_s, 2),
        "device_verifies_per_s": round(batch / lat_s, 1),
        "pipelined_verifies_per_s": round(batch / pipe_s, 1),
    }


def make_quorum(quorum: int, seed: int = 11):
    """quorum distinct keypairs all voting over ONE 32-byte digest —
    the exact shape certificate sanitization verifies."""
    import hashlib

    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.crypto.digest import Digest

    msg = hashlib.sha256(b"cert-agg-%d-%d" % (quorum, seed)).digest()
    kps = [
        KeyPair.generate(
            rng_seed=hashlib.sha256(b"agg%d:%d" % (seed, i)).digest()
        )
        for i in range(quorum)
    ]
    votes = [(kp.name, kp.sign(Digest(msg))) for kp in kps]
    return msg, votes


def bench_aggregate(quorum: int, iters: int, batched: bool = False) -> dict:
    """The certificate-sanitization cost ladder at one quorum size:
    2f+1 serial CPU verifies (the `individual` scheme) vs ONE half-agg
    multiexp equation (`halfagg`) vs the batched-window device kernel
    over the same 2f+1 claims.  Oracle-checked before timing: the valid
    aggregate must verify and a bit-flipped / truncated / wrong-subset
    aggregate must not — a benchmark that times a verifier that accepts
    garbage measures nothing."""
    import statistics as stats

    from narwhal_tpu.crypto.aggregate import (
        aggregate_votes,
        cert_sig_wire_bytes,
        verify_halfagg,
    )
    from narwhal_tpu.crypto.keys import cpu_verify

    msg, votes = make_quorum(quorum)
    signers, agg = aggregate_votes(msg, votes)
    publics = [bytes(s) for s in signers]

    # Oracle: accept the real thing, reject the mutations.
    assert verify_halfagg(msg, publics, agg), "valid aggregate rejected"
    flipped = bytearray(agg)
    flipped[0] ^= 1
    assert not verify_halfagg(msg, publics, bytes(flipped)), (
        "bit-flipped aggregate accepted"
    )
    assert not verify_halfagg(msg, publics, bytes(agg)[:-32]), (
        "truncated aggregate accepted"
    )
    assert not verify_halfagg(msg, publics[:-1], agg), (
        "wrong-subset aggregate accepted"
    )
    by_key = {bytes(name): (name, sig) for name, sig in votes}
    ordered = [by_key[p] for p in publics]
    ordered_keys = [name for name, _ in ordered]
    ordered_sigs = [sig for _, sig in ordered]
    assert all(
        cpu_verify(msg, name, sig) for name, sig in votes
    ), "valid vote rejected by serial verifier"

    serial = []
    for _ in range(iters):
        t0 = time.perf_counter()
        ok = all(
            cpu_verify(msg, k, s)
            for k, s in zip(ordered_keys, ordered_sigs)
        )
        serial.append(time.perf_counter() - t0)
        assert ok
    agg_lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        ok = verify_halfagg(msg, publics, agg)
        agg_lat.append(time.perf_counter() - t0)
        assert ok
    out = {
        "quorum": quorum,
        "committee": {3: 4, 14: 20, 34: 50}.get(quorum),
        "serial_2f1_ms": round(1e3 * stats.median(serial), 3),
        "halfagg_verify_ms": round(1e3 * stats.median(agg_lat), 3),
        "halfagg_vs_serial": round(
            stats.median(agg_lat) / stats.median(serial), 3
        ),
        "verify_ops_per_cert": {"individual": quorum, "halfagg": 1},
        "sig_wire_bytes_v2": {
            "individual": cert_sig_wire_bytes("individual", quorum),
            "halfagg": cert_sig_wire_bytes("halfagg", quorum),
        },
    }

    # Batched-window arm: the device kernel over the same 2f+1 claims
    # (the verify-window pipeline's dispatch shape).  Opt-in
    # (--agg-batched): the first kernel call per shape pays an XLA
    # compile (minutes on a cold CPU host), and the ladder's
    # serial/aggregate legs are pure-Python and must not require a jax
    # install — CI passes the flag where tier-1's test_ed25519 pass has
    # already warmed the in-job compile cache.
    if not batched:
        out["batched_window_ms"] = None
        out["batched_window_skipped"] = "pass --agg-batched to enable"
        return out
    try:
        import numpy as np

        import jax.numpy as jnp

        from narwhal_tpu.ops import ed25519 as E

        msgs = [msg] * quorum
        jpacked = jnp.asarray(
            E.prepare_batch(msgs, ordered_keys, ordered_sigs, quorum)
        )
        mask = np.asarray(E._verify_kernel(jpacked))  # warmup / compile
        assert mask.all(), "batched kernel rejected valid quorum"
        batched = []
        for _ in range(iters):
            t0 = time.perf_counter()
            np.asarray(E._verify_kernel(jpacked))
            batched.append(time.perf_counter() - t0)
        out["batched_window_ms"] = round(1e3 * stats.median(batched), 3)
    except Exception as e:  # no jax / no device — ladder stays 2-leg
        out["batched_window_ms"] = None
        out["batched_window_skipped"] = f"{type(e).__name__}: {e}"
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--batches", type=int, nargs="+", default=[128, 512, 2048, 8192]
    )
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cpu-budget", type=float, default=2.0)
    ap.add_argument("--artifact", type=str, default=None)
    ap.add_argument(
        "--agg-quorums",
        type=int,
        nargs="+",
        default=None,
        help="Also run the certificate-aggregate ladder (serial 2f+1 vs "
        "one half-agg equation vs batched window) at these quorum sizes "
        "(3/14/34 = committees of 4/20/50).",
    )
    ap.add_argument(
        "--agg-only",
        action="store_true",
        help="Run ONLY the aggregate ladder (no TPU batch sweep) — the "
        "CI shape; defaults --agg-quorums to 3 14 34.",
    )
    ap.add_argument(
        "--agg-batched",
        action="store_true",
        help="Include the batched-window device-kernel leg in the "
        "aggregate ladder (pays an XLA compile per quorum shape when "
        "the persistent cache is cold).",
    )
    args = ap.parse_args()
    if args.agg_only and args.agg_quorums is None:
        args.agg_quorums = [3, 14, 34]

    if args.agg_only:
        results = {
            "metric": "cert_aggregate_verify_ladder",
            "aggregate": [],
        }
        for q in args.agg_quorums:
            r = bench_aggregate(q, args.iters, batched=args.agg_batched)
            results["aggregate"].append(r)
            print(json.dumps(r))
        if args.artifact:
            with open(args.artifact, "w") as f:
                json.dump(results, f, indent=2)
        return

    msgs, keys, sigs = make_batch(max(args.batches))

    cpu_vps = bench_cpu(msgs, keys, sigs, args.cpu_budget)
    from narwhal_tpu.ops import field25519 as F

    results = {
        "metric": "ed25519_verifies_per_sec_chip",
        "lane_dtype": "float32" if F.FP else "int32",
        "cpu_openssl_verifies_per_s_core": round(cpu_vps, 1),
        "host_cores": os.cpu_count(),
        "tpu": [],
    }
    import jax

    results["device"] = str(jax.devices()[0])
    for b in args.batches:
        r = bench_tpu(msgs, keys, sigs, b, args.iters)
        results["tpu"].append(r)
        print(json.dumps(r))

    if args.agg_quorums:
        results["aggregate"] = []
        for q in args.agg_quorums:
            r = bench_aggregate(q, args.iters, batched=args.agg_batched)
            results["aggregate"].append(r)
            print(json.dumps(r))

    best = max(results["tpu"], key=lambda r: r["pipelined_verifies_per_s"])
    results["best_verifies_per_s_chip"] = best["pipelined_verifies_per_s"]
    results["best_batch"] = best["batch"]
    results["vs_cpu_core"] = round(
        best["pipelined_verifies_per_s"] / cpu_vps, 2
    )
    print(
        json.dumps(
            {
                "metric": "ed25519_verifies_per_sec_chip",
                "value": results["best_verifies_per_s_chip"],
                "unit": "verifies/s",
                "lane_dtype": results["lane_dtype"],
                "vs_baseline": results["vs_cpu_core"],
                "cpu_core_verifies_per_s": results[
                    "cpu_openssl_verifies_per_s_core"
                ],
                "batch": best["batch"],
                "device": results["device"],
            }
        )
    )
    if args.artifact:
        with open(args.artifact, "w") as f:
            json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()
