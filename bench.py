#!/usr/bin/env python3
"""Driver benchmark: one JSON line {"metric", "value", "unit", "vs_baseline"}.

Runs the reference's `fab local` analog — a full 4-node committee with one
worker each plus open-loop clients on localhost (benchmark/local_bench.py) —
and reports end-to-end committed TPS against the reference's local baseline
(46,149 tx/s e2e, README.md:42-58, mirrored in BASELINE.md).

Environment knobs: BENCH_DURATION (s, default 25), BENCH_RATE (starting
probe rate, default 90000), BENCH_NODES (default 4), BENCH_BATCH (bytes,
default 500000), BENCH_LATENCY_CAP_MS (sustained-point gate, default 1500),
BENCH_MAX_PROBES (default 4).

Saturation is PROBED PER RUN, not replayed from a previous round's
measurement: this host's capacity swings ±30% between hours (BASELINE.md
variance caveat), and offering a fixed rate measured in a fast window
floods the queues of a slow one — round 5 measured 32.6k tx/s at 3,037 ms
e2e latency exactly that way (the r05 review, §1).  The probe steps the offered
rate DOWN from BENCH_RATE (factor 0.7) until a run commits with e2e latency
under the cap — i.e. the committee is saturated but not drowning — then
re-runs the chosen rate for the median.  Every probe run is listed in the
JSON.  Batch size stays at the reference's 500 kB — the earlier 125 kB
"tuned" default quartered throughput by quadrupling per-batch overheads
(broadcast frames, ACK round trips, digests, store records).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# The reference's local-bench e2e TPS (4 nodes, 1 worker, 512 B tx).
BASELINE_E2E_TPS = 46_149.0


def main() -> None:
    from benchmark.local_bench import run_bench

    duration = int(os.environ.get("BENCH_DURATION", "25"))
    start_rate = int(os.environ.get("BENCH_RATE", "90000"))
    nodes = int(os.environ.get("BENCH_NODES", "4"))
    batch = int(os.environ.get("BENCH_BATCH", "500000"))
    runs = int(os.environ.get("BENCH_RUNS", "3"))
    lat_cap = float(os.environ.get("BENCH_LATENCY_CAP_MS", "1500"))
    max_probes = int(os.environ.get("BENCH_MAX_PROBES", "4"))

    def one_run(rate):
        return run_bench(
            nodes=nodes,
            workers=1,
            rate=rate,
            tx_size=512,
            duration=duration,
            base_port=7100,
            batch_size=batch,
            quiet=True,
        )

    def sustained(r):
        # Saturated-but-not-drowning: commits flow and the e2e latency is
        # bounded (an open-loop client over capacity inflates latency
        # without bound — the round-5 3 s failure mode).
        return r.end_to_end_tps > 0 and r.end_to_end_latency_ms <= lat_cap

    # Step the offered rate down from the optimistic start until one run
    # sustains; a slow host window then reports its true sustained point
    # instead of a queue-flooded one.
    probes = []  # (rate, result)
    rate = start_rate
    for _ in range(max(1, max_probes)):
        r = one_run(rate)
        probes.append((rate, r))
        if sustained(r):
            break
        rate = max(int(rate * 0.7), 5_000)

    # Chosen rate: the first sustained probe, else the best-TPS probe
    # (reported as-is — the artifact shows its over-cap latency).
    chosen_rate = next(
        (rt for rt, r in probes if sustained(r)),
        max(probes, key=lambda p: p[1].end_to_end_tps)[0],
    )
    # Re-run the chosen rate up to BENCH_RUNS total and report the MEDIAN
    # run (robust against one lucky or one degraded run; unlike max-of-N
    # it does not inflate with more runs), listing every run in the JSON.
    results = [r for rt, r in probes if rt == chosen_rate]
    while len(results) < max(1, runs):
        results.append(one_run(chosen_rate))
    ranked = sorted(results, key=lambda r: r.end_to_end_tps)
    result = ranked[len(ranked) // 2]

    # North-star microbenchmark (BASELINE.json): ed25519 verifies/sec/chip
    # on the real device, captured in the same driver artifact.  Runs in a
    # subprocess so the bench processes' environment stays untouched;
    # non-fatal (the e2e number above is reported either way).
    crypto: dict = {}
    if os.environ.get("BENCH_CRYPTO", "1") == "1":
        import subprocess

        # Cheap device probe first: a chip still held by another process
        # makes jax.devices() fail or hang, and the crypto microbench would
        # eat its whole 540 s timeout discovering that.  The probe is a
        # child that exits before the microbench starts, so it never holds
        # the chip the microbench needs.  SIGTERM, not SIGKILL
        # (subprocess.run's timeout would): it lets the probe release the
        # device cleanly — crypto is skipped either way.  (That a missing
        # device only warns here is the benchmark PR's to change, ROADMAP
        # S1.)
        probe = subprocess.Popen(
            [sys.executable, "-c", "import jax; jax.devices()"],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        try:
            device_ok = probe.wait(timeout=90) == 0
        except subprocess.TimeoutExpired:
            device_ok = False
            probe.terminate()
            try:
                probe.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        if not device_ok:
            print(
                "WARNING: TPU device probe failed/hung; skipping crypto "
                "microbench",
                file=sys.stderr,
            )
    else:
        device_ok = False
    if device_ok:
        try:
            out = subprocess.run(
                [
                    sys.executable,
                    os.path.join(REPO, "bench_crypto.py"),
                    "--batches",
                    "16384",
                    "--iters",
                    "3",
                    "--cpu-budget",
                    "0.5",
                ],
                capture_output=True,
                text=True,
                timeout=540,
            )
            last = [
                ln
                for ln in out.stdout.splitlines()
                if ln.startswith("{") and "ed25519" in ln
            ]
            if last:
                cr = json.loads(last[-1])
                crypto = {
                    "ed25519_verifies_per_sec_chip": cr["value"],
                    "ed25519_vs_cpu_core": cr["vs_baseline"],
                }
        except Exception:
            pass
    if result.end_to_end_tps > 0:
        metric, tps, baseline = (
            "end_to_end_tps_local_4n",
            result.end_to_end_tps,
            BASELINE_E2E_TPS,
        )
    else:
        # No sample join succeeded: report the consensus metric honestly
        # against the reference's consensus baseline (46,478 tx/s).
        metric, tps, baseline = (
            "consensus_tps_local_4n",
            result.consensus_tps,
            46_478.0,
        )
    # Errors are part of the artifact: a bench that publishes 0.0 with a
    # clean rc is worse than one that fails loudly (rounds 3-4 did exactly
    # that).  Zero committed transactions = failed measurement = rc 1.
    errors = [e for r in results for e in r.errors]
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(tps, 1),
                "unit": "tx/s",
                "vs_baseline": round(tps / baseline, 4),
                "offered_rate": chosen_rate,
                "probe_history": [
                    {
                        "rate": rt,
                        "e2e_tps": round(r.end_to_end_tps, 1),
                        "e2e_latency_ms": round(r.end_to_end_latency_ms, 1),
                        "sustained": sustained(r),
                    }
                    for rt, r in probes
                ],
                "runs_e2e_tps": [round(r.end_to_end_tps, 1) for r in results],
                "consensus_latency_ms": round(result.consensus_latency_ms, 1),
                "end_to_end_latency_ms": round(result.end_to_end_latency_ms, 1),
                # From the node metrics snapshots (narwhal_tpu/metrics.py):
                # where the pipeline latency actually accrues, and the
                # metrics-vs-log committed-tx cross-check of the median run.
                "stages_ms": result.stages_ms,
                "metrics_committed_tx": round(result.metrics_committed_tx, 1),
                "metrics_disagreement": result.metrics_disagreement,
                # Support-quorum spread headline (gated in
                # benchmark/trajectory.py like cert_to_commit_ms) plus
                # the slowest causal chain and who-closed-the-quorum
                # table of the median run.
                "support_arrival_ms": (
                    result.stragglers.get("gaps", {})
                    .get("support_arrival_ms", {})
                    .get("mean")
                ),
                "critical_path": result.critical_path,
                "stragglers": result.stragglers,
                # Wire-goodput & crypto-cost headline (median run): the
                # cross-revision numbers benchmark/trajectory.py tracks.
                "goodput_ratio": result.wire.get("goodput_ratio"),
                "cert_sig_bytes_fraction": result.wire.get(
                    "cert_sig_bytes_fraction"
                ),
                "empty_cert_overhead_per_committed_byte": result.wire.get(
                    "empty_cert_overhead_per_committed_byte"
                ),
                "wire_totals": result.wire.get("totals", {}),
                "crypto_verify": {
                    site: d.get("ops")
                    for site, d in result.crypto.get("verify", {}).items()
                },
                **({"errors": errors[:10]} if errors else {}),
                **crypto,
            }
        )
    )
    if result.committed_batches == 0 or tps <= 0:
        print(
            "BENCH FAILED: no committed transactions measured; "
            f"errors={errors[:10]}",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
