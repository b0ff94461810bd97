"""Paired interleaved A/B: profiler + flight recorder overhead (ISSUE 11).

The "always-on" in the sampling profiler's charter is only honest if the
committee pays ~nothing for it, so this driver measures exactly that the
way PRs 2/7 measured their instrument overhead: N interleaved pairs of
identical local_bench runs — the ON arm with the sampler at 67 Hz (it
is opt-in since PR 26) and the flight recorder's default, the OFF arm
with both stubbed (NARWHAL_PROFILE_HZ=0, NARWHAL_FLIGHT=0) — alternating arms so
host drift hits both equally, medians compared against the ≤5% committee
TPS acceptance gate.

The ON arm's final snapshots also yield the OTHER acceptance number: the
profiler's aggregated top-N self-time table, which must independently
reproduce the crypto ledger's "verify dominates" finding with zero
hand-placed instrumentation (on this host the pure-Python ed25519
fallback is the committee's compute, so `_ed25519_py.py` frames must
lead).

    python benchmark/trace_profile_ab.py --pairs 4 \
        --artifact artifacts/trace_profile_r16.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.local_bench import run_bench  # noqa: E402

_OFF_ENV = {"NARWHAL_PROFILE_HZ": "0", "NARWHAL_FLIGHT": "0"}
_ON_ENV = {"NARWHAL_PROFILE_HZ": "67", "NARWHAL_FLIGHT": "1"}


def _one_run(arm: str, idx: int, args) -> dict:
    """One bench run under the arm's env; returns the headline numbers
    (+ the aggregated profiler table on ON arms)."""
    saved = {k: os.environ.get(k) for k in _OFF_ENV}
    os.environ.update(_OFF_ENV if arm == "off" else _ON_ENV)
    workdir = os.path.join(REPO, ".bench_ab", f"{arm}-{idx}")
    try:
        result = run_bench(
            nodes=args.nodes,
            workers=1,
            rate=args.rate,
            tx_size=args.tx_size,
            duration=args.duration,
            base_port=args.base_port,
            workdir=workdir,
            quiet=True,
            progress_wait=45,
            trace_out=(
                os.path.join(workdir, "trace.json") if arm == "on" else None
            ),
        )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out = {
        "arm": arm,
        "errors": result.errors,
        "consensus_tps": result.consensus_tps,
        "consensus_latency_ms": result.consensus_latency_ms,
        "end_to_end_tps": result.end_to_end_tps,
        "end_to_end_latency_ms": result.end_to_end_latency_ms,
    }
    if arm == "on":
        out["profile_top"] = _aggregate_profile_top(workdir)
        out["trace_path"] = os.path.join(workdir, "trace.json")
        out["flight_nodes"] = sorted(
            n for n, ring in (result.flight or {}).items() if ring
        )
    return out


def _aggregate_profile_top(workdir: str, n: int = 20) -> list:
    """Committee-wide self-time table: the per-node `profile.top` tables
    of every PRIMARY snapshot summed by frame (workers mostly idle at
    bench rates; the primaries are where the paper's compute lives)."""
    agg: dict = {}
    import glob

    for path in glob.glob(os.path.join(workdir, "metrics-primary-*.json")):
        try:
            with open(path) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            continue
        for row in (snap.get("detail") or {}).get("profile.top") or []:
            rec = agg.setdefault(row["frame"], {"self": 0, "total": 0})
            rec["self"] += row.get("self", 0)
            rec["total"] += row.get("total", 0)
    total_self = sum(r["self"] for r in agg.values()) or 1
    rows = sorted(agg.items(), key=lambda kv: kv[1]["self"], reverse=True)
    return [
        {
            "frame": frame,
            "self": rec["self"],
            "total": rec["total"],
            "self_frac": round(rec["self"] / total_self, 4),
        }
        for frame, rec in rows[:n]
    ]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--pairs", type=int, default=4)
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--rate", type=int, default=3000)
    parser.add_argument("--tx-size", type=int, default=512)
    parser.add_argument("--duration", type=int, default=15)
    parser.add_argument("--base-port", type=int, default=7200)
    parser.add_argument("--gate", type=float, default=0.05,
                        help="max tolerated median consensus-TPS overhead")
    parser.add_argument("--artifact", required=True)
    args = parser.parse_args()

    runs = []
    for i in range(args.pairs):
        for arm in ("on", "off") if i % 2 == 0 else ("off", "on"):
            print(f"=== pair {i + 1}/{args.pairs}, arm {arm}",
                  file=sys.stderr)
            runs.append(_one_run(arm, i, args))

    def med(arm, key):
        vals = [
            r[key] for r in runs
            if r["arm"] == arm and not r["errors"] and r[key] > 0
        ]
        return statistics.median(vals) if vals else None

    on_tps, off_tps = med("on", "consensus_tps"), med("off", "consensus_tps")
    # The gated statistic is the MEDIAN OF PER-PAIR overheads: each pair's
    # two arms run back to back, so slow host drift (this box swings tens
    # of percent across minutes — the r09/r10 verdicts measured it)
    # cancels within a pair where it cannot cancel across arm medians.
    pair_overheads = []
    for i in range(0, len(runs) - 1, 2):
        a, c = runs[i], runs[i + 1]
        on = a if a["arm"] == "on" else c
        off = a if a["arm"] == "off" else c
        if (
            not on["errors"] and not off["errors"]
            and on["consensus_tps"] > 0 and off["consensus_tps"] > 0
        ):
            pair_overheads.append(
                round(
                    (off["consensus_tps"] - on["consensus_tps"])
                    / off["consensus_tps"],
                    4,
                )
            )
    overhead = (
        statistics.median(pair_overheads) if pair_overheads else None
    )
    profile_top = next(
        (r["profile_top"] for r in reversed(runs)
         if r["arm"] == "on" and r.get("profile_top")),
        [],
    )
    # The dominance verdict is per-FRAME (the acceptance's literal
    # claim): the table's top self-time frame must be ed25519 verify
    # math — `_point_mul` is the double-scalar multiplication only the
    # verify path runs (sign uses the `_point_mul_base` comb).  The
    # per-file aggregation rides in the artifact too, for the honest
    # caveat it carries: summing BOTH asyncio socket frames
    # (write + _read_ready) lands within a few percent of the ed25519
    # module on this host at bench rates — the one-syscall-per-frame
    # cost ROADMAP item 5 already names, independently rediscovered by
    # the sampler with zero instrumentation.
    verify_dominates = bool(
        profile_top and profile_top[0]["frame"].startswith("_ed25519_py.py:")
    )
    by_file: dict = {}
    for row in profile_top:
        fname = row["frame"].split(":", 1)[0]
        by_file[fname] = by_file.get(fname, 0) + row["self"]
    top_by_file = sorted(
        by_file.items(), key=lambda kv: kv[1], reverse=True
    )
    artifact = {
        "generated_by": "benchmark/trace_profile_ab.py",
        "config": {
            "pairs": args.pairs, "nodes": args.nodes, "rate": args.rate,
            "tx_size": args.tx_size, "duration": args.duration,
            "on_env": _ON_ENV,
            "off_env": _OFF_ENV,
        },
        "runs": runs,
        "medians": {
            "on": {
                "consensus_tps": on_tps,
                "e2e_tps": med("on", "end_to_end_tps"),
                "e2e_latency_ms": med("on", "end_to_end_latency_ms"),
            },
            "off": {
                "consensus_tps": off_tps,
                "e2e_tps": med("off", "end_to_end_tps"),
                "e2e_latency_ms": med("off", "end_to_end_latency_ms"),
            },
        },
        "pair_overheads": pair_overheads,
        "tps_overhead_fraction": (
            round(overhead, 4) if overhead is not None else None
        ),
        "gate": {"max_overhead": args.gate,
                 "statistic": "median of per-pair overheads"},
        "profile_top_committee": profile_top,
        "profile_top_by_file": [
            {"file": f, "self": s} for f, s in top_by_file[:10]
        ],
        "verify_dominates_self_time": verify_dominates,
    }
    artifact["ok"] = (
        overhead is not None
        and overhead <= args.gate
        and verify_dominates
    )
    os.makedirs(os.path.dirname(args.artifact) or ".", exist_ok=True)
    with open(args.artifact, "w") as f:
        json.dump(artifact, f, indent=1)
    print(
        f"A/B: on={on_tps} off={off_tps} tx/s, overhead="
        f"{overhead if overhead is None else round(100 * overhead, 2)}% "
        f"(gate {100 * args.gate:.0f}%), verify_dominates="
        f"{verify_dominates} -> {args.artifact}"
    )
    if profile_top:
        print("committee top self-time frames:")
        for row in profile_top[:8]:
            print(
                f"  {row['frame']}: self {row['self']} "
                f"({100 * row['self_frac']:.1f}%), total {row['total']}"
            )
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
