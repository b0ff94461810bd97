"""Cross-validate the log-scraped bench numbers against node metrics.

The log parser (benchmark/logs.py) and the metrics registry
(narwhal_tpu/metrics.py) measure the same run through two independent
channels: regex over four INFO lines vs in-process counters and the
per-digest stage-trace table.  Agreement within tolerance is the check
that neither channel silently lost data — round 5 published a number a
flooded queue had quietly corrupted, and nothing cross-checked it
(the r05 review, §1).  Disagreement beyond tolerance hard-fails the run (an
error entry, which every harness treats as fatal).

The same per-digest trace join also yields the per-stage pipeline latency
breakdown (batch-sealed → quorum → digest-at-primary → header →
certificate → commit): each process stamps wall-clock times for the
stages it owns.  On one host the stamps join directly; across hosts (or
a deliberately skewed harness) each node's stamps are first shifted by
its reconciled clock correction — the zero-mean offset vector estimated
from ReliableSender ACK round-trips (narwhal_tpu/network/clocksync.py)
and carried in every snapshot's ``clock.offset_ms.*`` gauges — so the
cross-node legs measure causality, not whose NTP daemon drifted.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# Causal stage order: the registry's definition IS the source of truth
# (a hand-copied tuple here would silently drop any future stage from
# the breakdown).
from narwhal_tpu.crypto.aggregate import (
    SCHEMES as CERT_SIG_SCHEMES,
    cert_sig_wire_bytes,
)
from narwhal_tpu.metrics import ROUND_STAGES, STAGES as STAGE_ORDER
from narwhal_tpu.network import clocksync

STAGE_LEGS: Tuple[Tuple[str, str], ...] = tuple(
    zip(STAGE_ORDER[:-1], STAGE_ORDER[1:])
)

ROUND_LEGS: Tuple[Tuple[str, str], ...] = tuple(
    zip(ROUND_STAGES[:-1], ROUND_STAGES[1:])
)


def load_snapshots(paths: List[str], errors: List[str]) -> List[dict]:
    """Load metric snapshot files, reporting (not raising on) missing or
    torn ones — the writer's atomic rewrite makes torn files a real bug,
    so they land in `errors`, but a node that died pre-boot simply has no
    file and must not mask the log-side numbers."""
    snaps = []
    for path in paths:
        if not os.path.exists(path):
            errors.append(f"metrics snapshot missing: {os.path.basename(path)}")
            continue
        try:
            with open(path) as f:
                snaps.append(json.load(f))
        except (OSError, ValueError) as e:
            errors.append(
                f"metrics snapshot unreadable: {os.path.basename(path)}: {e}"
            )
    return snaps


def loop_stall_summary(snapshots: List[dict]) -> Dict[str, dict]:
    """Per-node event-loop stall series for the bench JSON `runtime`
    section (populated when the committee ran with
    NARWHAL_LOOP_WATCHDOG_MS set — the loop-watchdog smoke arm).  Keyed
    by node pid; a node whose snapshot carries the histogram at count 0
    still appears, which is the point: "the watchdog ran and saw no
    stall" is a measurement, not an absence."""
    out: Dict[str, dict] = {}
    for snap in snapshots:
        hist = (snap.get("histograms") or {}).get("runtime.loop_stall_seconds")
        if hist is None:
            continue
        last = dict(
            (snap.get("detail") or {}).get("runtime.loop_stall_last") or {}
        )
        if "stack" in last:
            last["stack"] = str(last["stack"])[:2000]
        out[str(snap.get("pid", len(out)))] = {
            "loop_stall_seconds": {
                "count": int(hist.get("count", 0)),
                "sum_s": round(float(hist.get("sum", 0.0)), 4),
                "mean_s": round(float(hist.get("mean", 0.0)), 4),
                "buckets": hist.get("buckets", []),
            },
            "stalls": int(
                (snap.get("counters") or {}).get("runtime.loop_stalls", 0)
            ),
            "last_stall": last,
        }
    return out


# -- clock-offset correction --------------------------------------------------

_CLOCK_OFFSET_PREFIX = "clock.offset_ms."
_CLOCK_UNC_PREFIX = "clock.offset_uncertainty_ms."


def snapshot_correction_ms(snap: dict) -> float:
    """One node's reconciled wall-clock correction, from its own
    ``clock.offset_ms.*`` gauges.  Subtracting ``correction/1000`` from
    the node's stamps places them on the committee's mean clock; 0.0
    when the snapshot carries no offset gauges (pre-clocksync snapshot,
    or a node that never completed an ACK round trip), which degrades to
    the old uncorrected join rather than failing."""
    gauges = snap.get("gauges") or {}
    peers = {
        name[len(_CLOCK_OFFSET_PREFIX):]: float(v)
        for name, v in gauges.items()
        if name.startswith(_CLOCK_OFFSET_PREFIX) and v is not None
    }
    if not peers:
        return 0.0
    return clocksync.reconcile_zero_mean({"self": peers})["self"]


def clock_summary(snapshots: List[dict]) -> dict:
    """Per-node clock section for the bench JSON: the raw per-peer
    offset gauges, the reconciled correction the stage join applies, and
    the worst per-peer uncertainty bound (RTT/2 of the best sample) —
    the error bar on every cross-node leg below."""
    nodes: Dict[str, dict] = {}
    for snap in snapshots:
        if not snap.get("enabled", True):
            continue
        gauges = snap.get("gauges") or {}
        peers = {
            name[len(_CLOCK_OFFSET_PREFIX):]: round(float(v), 3)
            for name, v in gauges.items()
            if name.startswith(_CLOCK_OFFSET_PREFIX) and v is not None
        }
        if not peers:
            continue
        unc = [
            float(v)
            for name, v in gauges.items()
            if name.startswith(_CLOCK_UNC_PREFIX) and v is not None
        ]
        key = str(snap.get("pid") or snap.get("node") or len(nodes))
        nodes[key] = {
            "correction_ms": round(snapshot_correction_ms(snap), 3),
            "peer_offsets_ms": dict(sorted(peers.items())),
            "max_uncertainty_ms": round(max(unc), 3) if unc else None,
        }
    return nodes


def corrected_stage_join(
    snapshots: List[dict],
) -> Tuple[Dict[str, Dict[str, float]], Dict[str, int]]:
    """Join per-digest stage stamps across node snapshots, each node's
    stamps shifted onto the committee mean clock by its reconciled
    correction.  Earliest corrected timestamp wins per (digest, stage) —
    the same convention the log parser uses across primaries.  Returns
    (stage_ts, seal_bytes)."""
    stage_ts: Dict[str, Dict[str, float]] = {}
    seal_bytes: Dict[str, int] = {}
    for snap in snapshots:
        if not snap.get("enabled", True):
            continue
        corr_s = snapshot_correction_ms(snap) / 1000.0
        for digest, entry in snap.get("trace", {}).items():
            dst = stage_ts.setdefault(digest, {})
            for stage in STAGE_ORDER:
                t = entry.get(stage)
                if t is None:
                    continue
                t = t - corr_s
                if stage not in dst or t < dst[stage]:
                    dst[stage] = t
            b = entry.get("bytes")
            if b:
                seal_bytes.setdefault(digest, int(b))
    return stage_ts, seal_bytes


def critical_path_summary(
    stage_ts: Dict[str, Dict[str, float]], top_k: int = 3
) -> dict:
    """The slowest end-to-end causal chain through the pipeline: among
    digests carrying the full stage chain, the one with the largest
    seal→commit span, decomposed into consecutive-stage legs.  The legs
    TELESCOPE — their sum is exactly the e2e span by construction — so
    ``legs_sum_ms`` vs ``e2e_ms`` is a self-check on the join, not new
    information (the CI smoke gates on it anyway: a big gap means a
    stage was dropped from STAGE_ORDER or stamped on a different clock).
    ``slowest`` lists the top-k chains; ``path`` is the worst one."""
    chains = []
    for digest, st in stage_ts.items():
        if all(s in st for s in STAGE_ORDER):
            chains.append((st["commit"] - st["seal"], digest, st))
    chains.sort(key=lambda c: -c[0])
    out: dict = {"full_chains": len(chains)}
    slowest = []
    for e2e, digest, st in chains[:top_k]:
        legs = {
            f"{a}_to_{b}": round(1000 * (st[b] - st[a]), 3)
            for a, b in STAGE_LEGS
        }
        slowest.append(
            {
                "digest": digest,
                "e2e_ms": round(1000 * e2e, 3),
                "legs_ms": legs,
                "legs_sum_ms": round(sum(legs.values()), 3),
            }
        )
    if slowest:
        out["path"] = slowest[0]
        out["slowest"] = slowest
    return out


# -- quorum-straggler attribution ---------------------------------------------

_STRAGGLER_FAMILIES: Tuple[Tuple[str, str], ...] = (
    ("vote_quorum", "primary.quorum_straggler."),
    ("support_quorum", "consensus.support_straggler."),
)

_GAP_HISTOGRAMS: Tuple[Tuple[str, str], ...] = (
    ("vote_quorum_gap_ms", "primary.vote_quorum_gap_ms"),
    ("parent_quorum_gap_ms", "primary.parent_quorum_gap_ms"),
    ("support_arrival_ms", "consensus.support_arrival_ms"),
)


def quorum_straggler_summary(snapshots: List[dict]) -> dict:
    """Ranked who-closed-the-quorum table for the bench JSON: per
    quorum family, the authorities (by primary address) charged with
    arriving last when the quorum crossed, most-charged first, plus the
    mean first-arrival→quorum gap histograms.  A consistently-top
    authority is the committee's straggler — the node whose latency the
    quorum waits out — which is attribution the aggregate histograms
    alone cannot give."""
    counters = _agg_counters(snapshots)
    hists = _agg_histograms(snapshots)
    out: dict = {}
    for family, prefix in _STRAGGLER_FAMILIES:
        ranked = sorted(
            (
                {"address": name[len(prefix):], "count": int(v)}
                for name, v in counters.items()
                if name.startswith(prefix) and v
            ),
            key=lambda e: (-e["count"], e["address"]),
        )
        if ranked:
            out[family] = ranked
    gaps: Dict[str, dict] = {}
    for label, name in _GAP_HISTOGRAMS:
        s, c = hists.get(name, (0.0, 0))
        if c:
            gaps[label] = {"count": int(c), "mean": round(s / c, 3)}
    if gaps:
        out["gaps"] = gaps
    return out


def cross_validate(
    result,
    snapshots: List[dict],
    tx_size: int,
    tolerance: float = 0.05,
) -> dict:
    """Join stage traces across node snapshots; fill ``result``'s
    metrics fields and append a fatal error on >tolerance disagreement
    between the metrics-derived and log-scraped committed-tx totals.

    Returns the summary dict the bench JSON embeds.
    """
    # Trace-table evictions mean the stage join below is UNDER-JOINED:
    # evicted digests stamped early in the run are invisible, so the
    # breakdown is biased toward the run's tail and the metrics-side
    # committed-bytes total undercounts.  Warn loudly and annotate the
    # result instead of silently computing a biased answer.
    evictions = sum(
        int(snap.get("gauges", {}).get("metrics.trace_evictions") or 0)
        for snap in snapshots
        if snap.get("enabled", True)
    )
    if evictions > 0:
        print(
            "WARNING: stage-trace tables UNDER-JOINED — "
            f"{evictions} digest(s) evicted past NARWHAL_TRACE_CAP; "
            "the stages_ms breakdown and metrics committed-tx total are "
            "biased toward the run's tail (raise NARWHAL_TRACE_CAP or "
            "shorten the run)",
            file=sys.stderr,
        )

    # Skew-corrected earliest timestamp per (digest, stage) across every
    # snapshot — each node's stamps shifted by its reconciled offset
    # before the min-join (see corrected_stage_join).
    stage_ts, seal_bytes = corrected_stage_join(snapshots)

    committed = [d for d, st in stage_ts.items() if "commit" in st]
    metrics_bytes = sum(seal_bytes.get(d, 0) for d in committed)
    result.metrics_committed_tx = metrics_bytes / tx_size

    disagreement: Optional[float] = None
    log_tx = result.committed_bytes / tx_size
    if log_tx > 0:
        disagreement = abs(result.metrics_committed_tx - log_tx) / log_tx
        result.metrics_disagreement = disagreement
        if disagreement > tolerance:
            result.errors.append(
                "metrics cross-check FAILED: log-scraped "
                f"{log_tx:.0f} committed tx vs metrics-derived "
                f"{result.metrics_committed_tx:.0f} "
                f"({100 * disagreement:.1f}% > {100 * tolerance:.0f}% "
                "tolerance) — one measurement channel lost data"
            )
    elif committed:
        result.errors.append(
            "metrics cross-check FAILED: metrics snapshots show "
            f"{len(committed)} committed batches but the log scrape "
            "found none"
        )

    # Per-stage latency breakdown over digests carrying the full chain
    # (own-batch traces: sealed, quorum'd, proposed, certified at the
    # same authority, commit joined committee-wide).  cert→commit is now
    # subdivided (cert_inserted / commit_trigger / walk_done sub-stages),
    # but the aggregate leg stays in the output: it is the number every
    # prior artifact tracks (metrics_stage_breakdown_r07.json) and the
    # one the r09 acceptance gate compares.
    legs: Dict[str, List[float]] = {
        f"{a}_to_{b}": [] for a, b in STAGE_LEGS
    }
    cert_commit: List[float] = []
    totals: List[float] = []
    for st in stage_ts.values():
        if all(s in st for s in STAGE_ORDER):
            for a, b in STAGE_LEGS:
                legs[f"{a}_to_{b}"].append(st[b] - st[a])
            cert_commit.append(st["commit"] - st["cert"])
            totals.append(st["commit"] - st["seal"])
    if totals:
        result.stages_ms = {
            name: round(1000 * sum(v) / len(v), 2)
            for name, v in legs.items()
            if v
        }
        result.stages_ms["cert_to_commit"] = round(
            1000 * sum(cert_commit) / len(cert_commit), 2
        )
        result.stages_ms["seal_to_commit"] = round(
            1000 * sum(totals) / len(totals), 2
        )
    if evictions > 0:
        # In-band annotation next to the numbers the evictions bias.
        result.stages_ms["trace_evictions"] = float(evictions)

    # Round-cadence attribution: the per-round sub-stage legs that
    # decompose `primary.round_advance_seconds` the way the sub-stages
    # above decompose cert→commit.
    round_attr = round_attribution(snapshots)
    result.round_stages_ms = dict(round_attr.get("round_stages_ms", {}))

    return {
        "stages_ms": dict(result.stages_ms),
        "traced_full_chain": len(totals),
        "trace_evictions": evictions,
        "metrics_committed_tx": round(result.metrics_committed_tx, 1),
        "log_committed_tx": round(log_tx, 1),
        "disagreement": (
            round(disagreement, 4) if disagreement is not None else None
        ),
        "round_attribution": round_attr,
        "clock": clock_summary(snapshots),
        "critical_path": critical_path_summary(stage_ts),
        "stragglers": quorum_straggler_summary(snapshots),
    }


def round_attribution(snapshots: List[dict]) -> dict:
    """Decompose the round period from the per-round cadence traces.

    Each primary stamps ROUND_STAGES per round of its own header
    lifecycle (header_proposed → … → round_advance).  Unlike the digest
    trace these are NOT joined across nodes — every primary runs its own
    cadence loop — so legs aggregate over (node, round) pairs.  The
    leading ``advance_to_header_proposed`` leg (previous round's advance
    to this round's mint — the proposer's min/max-header-delay wait) is
    derived here, which makes the legs TELESCOPE: their sum for round r
    is exactly round_advance(r) − round_advance(r−1), the round period.
    Negative legs are meaningful — they show pipeline overlap (e.g. a
    parent quorum completing before our own certificate assembled).

    The independent cross-check is the ``primary.round_advance_seconds``
    histogram (stamped by the Proposer, not the trace): the mean of the
    telescoped per-round sums must agree with the histogram mean — a
    >10% gap means the trace is under-joined or a stage is mis-stamped,
    and is warned about loudly (bench gate material, not a run failure:
    the histogram also covers boot/tail rounds the trace join drops).
    """
    legs: Dict[str, List[float]] = {
        "advance_to_header_proposed": [],
        **{f"{a}_to_{b}": [] for a, b in ROUND_LEGS},
    }
    periods: List[float] = []
    hist_sum, hist_count = 0.0, 0
    sa_sum, sa_count = 0.0, 0
    for snap in snapshots:
        if not snap.get("enabled", True):
            continue
        h = (snap.get("histograms") or {}).get(
            "primary.round_advance_seconds"
        )
        if h and h.get("count"):
            hist_sum += h["sum"]
            hist_count += h["count"]
        sa = (snap.get("histograms") or {}).get(
            "consensus.support_arrival_ms"
        )
        if sa and sa.get("count"):
            sa_sum += sa["sum"]
            sa_count += sa["count"]
        entries: Dict[int, dict] = {}
        for key, st in (snap.get("round_trace") or {}).items():
            try:
                entries[int(key)] = st
            except (TypeError, ValueError):
                continue
        for r in sorted(entries):
            st = entries[r]
            prev = entries.get(r - 1)
            if prev is None or "round_advance" not in prev:
                continue  # no anchor for the leading leg (e.g. round 1)
            if any(s not in st for s in ROUND_STAGES):
                continue  # partial round (boot/tail) — can't telescope
            legs["advance_to_header_proposed"].append(
                st["header_proposed"] - prev["round_advance"]
            )
            for a, b in ROUND_LEGS:
                legs[f"{a}_to_{b}"].append(st[b] - st[a])
            periods.append(st["round_advance"] - prev["round_advance"])

    out: dict = {"rounds_joined": len(periods)}
    if periods:
        out["round_stages_ms"] = {
            name: round(1000 * sum(v) / len(v), 3)
            for name, v in legs.items()
            if v
        }
        out["round_period_ms"] = round(
            1000 * sum(periods) / len(periods), 3
        )
        # Telescoping makes sum(legs) == period per round by construction;
        # keep the redundant sum in the artifact as a self-check anyway.
        out["stage_sum_ms"] = round(
            1000 * sum(sum(v) for v in legs.values()) / len(periods), 3
        )
    if hist_count:
        out["round_advance_hist_ms"] = round(
            1000 * hist_sum / hist_count, 3
        )
        if periods:
            measured = out["round_advance_hist_ms"]
            if measured > 0:
                gap = abs(out["stage_sum_ms"] - measured) / measured
                out["stage_sum_vs_hist"] = round(gap, 4)
                if gap > 0.10:
                    print(
                        "WARNING: round-cadence sub-stages sum to "
                        f"{out['stage_sum_ms']:.1f} ms but the "
                        "round_advance_seconds histogram measured "
                        f"{measured:.1f} ms ({100 * gap:.1f}% apart) — "
                        "the round trace is under-joined or a stage is "
                        "mis-stamped",
                        file=sys.stderr,
                    )
    if sa_count:
        # Support-arrival spread (consensus side of the cadence story):
        # per committed-path leader, first direct supporter → the 2f+1
        # quorum-crossing arrival.  The gap between this and the round
        # period bounds what a lower-depth commit rule can save.
        out["support_arrival_ms"] = {
            "leaders": sa_count,
            "mean": round(sa_sum / sa_count, 3),
        }
    return out


# -- wire-goodput & crypto-cost ledger joins ----------------------------------

# An ed25519-signed vote inside a certificate costs a key ref (32 B
# raw key, ~1 B committee index under wire v2) + 64 B signature on the
# wire; the embedded header adds one more 64 B signature; under the
# halfagg scheme the per-vote signatures collapse to one 32·(q+1) B
# aggregate blob.  Certificates carry exactly quorum_threshold votes
# (the VotesAggregator assembles at quorum and stops), so the signature
# bytes of a cert frame are a pure function of committee size, wire
# format, and cert-sig scheme — all three are read from node gauges and
# fed to crypto.aggregate.cert_sig_wire_bytes rather than hardcoded
# here.  The fraction is computed against the RAW (pre-compression)
# cert frame size in both formats, so it keeps measuring frame anatomy,
# not deflate luck.


def _agg_counters(snapshots: List[dict]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for snap in snapshots:
        if not snap.get("enabled", True):
            continue
        for name, v in (snap.get("counters") or {}).items():
            out[name] = out.get(name, 0) + (v or 0)
    return out


def _agg_histograms(snapshots: List[dict]) -> Dict[str, Tuple[float, int]]:
    """name -> (sum, count) across snapshots."""
    out: Dict[str, Tuple[float, int]] = {}
    for snap in snapshots:
        if not snap.get("enabled", True):
            continue
        for name, h in (snap.get("histograms") or {}).items():
            if not isinstance(h, dict):
                continue
            s, c = out.get(name, (0.0, 0))
            out[name] = (s + (h.get("sum") or 0.0), c + (h.get("count") or 0))
    return out


# -- queue & backpressure accounting ------------------------------------------

def queue_pressure_summary(
    snapshots: List[dict],
    samples: Optional[List[dict]] = None,
    saturation_ratio: float = 0.8,
) -> dict:
    """Join the per-channel ``queue.<channel>.*`` series (emitted by
    ``metrics.InstrumentedQueue``) into the bench JSON ``queues``
    section.

    ``nodes`` keys each process (by snapshot pid) to its channel table —
    capacity, final depth, high-water, enqueue/dequeue/QueueFull totals,
    mean blocked-put wait and mean queue residence.  ``channels``
    aggregates committee-wide (max high-water/utilization, summed
    counters).  ``first_saturating`` is the knee attribution: with the
    scraper's 1 Hz ``samples`` timeline it names the channel whose depth
    first crossed ``saturation_ratio`` of capacity and WHEN; without a
    timeline it falls back to the channel with the highest end-of-run
    high-water utilization, PROVIDED that utilization itself crossed
    ``saturation_ratio`` — an unsaturated run honestly reports no
    attribution rather than electing whichever channel happened to sit
    deepest.  Unbounded channels (capacity 0) never saturate and are
    reported without a utilization.  Narrow pipeline windows like
    ``worker.to_quorum`` (capacity = QUORUM_WINDOW) are deliberately
    NOT excluded here, unlike in the queue_saturated health rule: the
    admission window pegging at capacity while the wide channels stay
    empty IS a knee explanation (backpressure propagated upstream of
    the node), and the health rule's min-capacity floor exists only to
    keep steady-state alerts quiet."""
    per_node: Dict[str, dict] = {}
    for snap in snapshots:
        if not snap.get("enabled", True):
            continue
        gauges = snap.get("gauges") or {}
        counters = snap.get("counters") or {}
        hists = snap.get("histograms") or {}
        channels: Dict[str, dict] = {}
        for name, depth in gauges.items():
            if not (name.startswith("queue.") and name.endswith(".depth")):
                continue
            ch = name[len("queue."):-len(".depth")]
            base = f"queue.{ch}."
            cap = float(gauges.get(base + "capacity") or 0)
            hw = float(gauges.get(base + "high_water") or 0)
            entry = {
                "capacity": int(cap),
                "depth": int(depth or 0),
                "high_water": int(hw),
                "enqueued": int(counters.get(base + "enqueued") or 0),
                "dequeued": int(counters.get(base + "dequeued") or 0),
                "full": int(counters.get(base + "full") or 0),
            }
            if cap > 0:
                entry["utilization"] = round(hw / cap, 4)
            pw = hists.get(base + "put_wait_seconds") or {}
            if pw.get("count"):
                entry["put_waits"] = int(pw["count"])
                entry["put_wait_ms_mean"] = round(
                    1000 * pw["sum"] / pw["count"], 3
                )
            res = hists.get(base + "residence_seconds") or {}
            if res.get("count"):
                entry["residence_ms_mean"] = round(
                    1000 * res["sum"] / res["count"], 3
                )
            channels[ch] = entry
        if channels:
            # Final snapshot files carry a pid; scraped samples (the
            # remote harness's snapshot proxy) carry the node name.
            key = snap.get("pid") or snap.get("node") or len(per_node)
            per_node[str(key)] = channels

    agg: Dict[str, dict] = {}
    for channels in per_node.values():
        for ch, e in channels.items():
            a = agg.setdefault(
                ch,
                {
                    "capacity": 0, "high_water": 0,
                    "enqueued": 0, "dequeued": 0, "full": 0,
                },
            )
            a["capacity"] = max(a["capacity"], e["capacity"])
            a["high_water"] = max(a["high_water"], e["high_water"])
            for k in ("enqueued", "dequeued", "full"):
                a[k] += e[k]
            if "utilization" in e:
                a["utilization"] = max(
                    a.get("utilization", 0.0), e["utilization"]
                )

    out: dict = {"nodes": per_node, "channels": agg}

    first: Optional[Tuple[float, str, float]] = None
    t0: Optional[float] = None
    for s in samples or ():
        t = s.get("t")
        g = s.get("gauges") or {}
        if t is None:
            continue
        if t0 is None or t < t0:
            t0 = float(t)
        for name, depth in g.items():
            if not (name.startswith("queue.") and name.endswith(".depth")):
                continue
            ch = name[len("queue."):-len(".depth")]
            cap = g.get(f"queue.{ch}.capacity") or 0
            if not cap or not depth:
                continue
            if depth >= saturation_ratio * cap and (
                first is None or t < first[0]
            ):
                first = (float(t), ch, depth / cap)
    if first is not None:
        out["first_saturating"] = {
            "channel": first[1],
            # Seconds since the first scrape sample, not absolute time.
            "at_s": round(first[0] - (t0 or first[0]), 2),
            "fill_ratio": round(first[2], 3),
            "mode": "timeline",
        }
    else:
        best_ch, best_u = None, 0.0
        for ch, a in agg.items():
            if a.get("utilization", 0.0) > best_u:
                best_ch, best_u = ch, a["utilization"]
        if best_ch is not None and best_u >= saturation_ratio:
            out["first_saturating"] = {
                "channel": best_ch,
                "utilization": round(best_u, 4),
                "mode": "high_water",
            }
    return out


def wire_crypto_summary(
    snapshots: List[dict],
    committed_payload_bytes: int = 0,
    quorum_weight: Optional[int] = None,
) -> dict:
    """Join the wire-goodput and crypto-cost ledgers across node
    snapshots into the ``wire`` and ``crypto`` sections of the bench
    JSON.  ``snapshots`` may be --metrics-path post-mortem files
    (local_bench) or the scraper's final per-node samples (remote_bench)
    — both carry the same counters/histograms shape.

    Headline derived metrics:

    - ``goodput_ratio`` — committed payload bytes ÷ total outbound wire
      bytes (first transmissions + retransmissions, all nodes, all
      planes).  This is the denominator ROADMAP items 1/3/5 need: the
      paper reports goodput (committed payload), and the gap between it
      and raw wire traffic is broadcast amplification + control plane +
      retries.  Frame payload bytes only (length prefixes and tiny ACK
      replies excluded on both directions alike).
    - ``cert_sig_bytes_fraction`` — fraction of a certificate frame that
      is signature material (crypto.aggregate.cert_sig_wire_bytes under
      the scheme/format the committee ran ÷ mean cert frame size): the
      byte-level number the ``halfagg`` scheme roughly halves and a
      pairing-based aggregate would collapse to ~96 B.
    - ``empty_cert_overhead_per_committed_byte`` — control-plane bytes
      (header/vote/certificate frames) attributed to EMPTY rounds, per
      committed payload byte: the "empty certs per committed byte"
      number the min_header_delay default question reduces to (ROADMAP
      item 3).

    The crypto section's ``protocol_check`` cross-validates the ledger
    against protocol arithmetic: one verified claim per peer vote, and
    per certificate arriving over the wire either quorum+1 claims
    (2f+1 votes + 1 header sig, ``individual``) or exactly 2 (one
    aggregate + 1 header sig, ``halfagg``) — within tolerance on a
    clean run; the verify cache (re-deliveries) and in-flight teardown
    account for the residue.
    """
    counters = _agg_counters(snapshots)
    hists = _agg_histograms(snapshots)

    def typed(prefix: str) -> Dict[str, float]:
        return {
            name[len(prefix):]: v
            for name, v in counters.items()
            if name.startswith(prefix)
        }

    out_frames = typed("wire.out.frames.")
    out_bytes = typed("wire.out.bytes.")
    out_raw = typed("wire.out.raw_bytes.")
    re_frames = typed("wire.out.retransmit_frames.")
    re_bytes = typed("wire.out.retransmit_bytes.")
    in_frames = typed("wire.in.frames.")
    in_bytes = typed("wire.in.bytes.")

    # Which wire format the committee spoke (wire.format_version gauge,
    # stamped by every node): drives the format-aware signature
    # arithmetic below.  Max across nodes — the flag is committee-wide.
    wire_version = 1
    # Which certificate-signature scheme it ran (crypto.cert_sig_scheme
    # gauge, an index into crypto.aggregate.SCHEMES).  Same max-across-
    # nodes read: a mixed committee is refused at the wire, so on any
    # run that produced certificates the gauge agrees everywhere.
    scheme_index = 0
    for snap in snapshots:
        if snap.get("enabled", True):
            gauges = snap.get("gauges") or {}
            v = gauges.get("wire.format_version")
            if v:
                wire_version = max(wire_version, int(v))
            s = gauges.get("crypto.cert_sig_scheme")
            if s:
                scheme_index = max(scheme_index, int(s))
    cert_scheme = CERT_SIG_SCHEMES[
        min(scheme_index, len(CERT_SIG_SCHEMES) - 1)
    ]

    types = sorted(
        set(out_bytes) | set(in_bytes) | set(re_bytes)
    )
    first_total = sum(out_bytes.values())
    raw_total = sum(out_raw.values())
    re_total = sum(re_bytes.values())
    out_total = first_total + re_total
    in_total = sum(in_bytes.values())
    sender_total = (
        counters.get("net.reliable.bytes_sent", 0)
        + counters.get("net.simple.bytes_sent", 0)
    )
    flushes = counters.get("wire.out.flushes", 0)
    fpf_sum, fpf_count = hists.get("wire.out.frames_per_flush", (0.0, 0))
    apf_sum, apf_count = hists.get("wire.out.acks_per_flush", (0.0, 0))

    wire: dict = {
        "format_version": wire_version,
        "cert_sig_scheme": cert_scheme,
        "out": {
            t: {
                "frames": int(out_frames.get(t, 0)),
                "bytes": int(out_bytes.get(t, 0)),
                "raw_bytes": int(out_raw.get(t, 0)),
                "retransmit_frames": int(re_frames.get(t, 0)),
                "retransmit_bytes": int(re_bytes.get(t, 0)),
            }
            for t in types
        },
        "in": {
            t: {
                "frames": int(in_frames.get(t, 0)),
                "bytes": int(in_bytes.get(t, 0)),
            }
            for t in types
        },
        "totals": {
            "out_bytes": int(first_total),
            "out_raw_bytes": int(raw_total),
            "out_retransmit_bytes": int(re_total),
            "out_bytes_total": int(out_total),
            "in_bytes": int(in_total),
            "committed_payload_bytes": int(committed_payload_bytes),
            # Typed ledger bytes ÷ raw sender byte counters: ~1.0 means
            # every sent byte carries a type label (the acceptance gate's
            # "per-type wire bytes sum to total sender bytes").
            "sender_coverage": (
                round(out_total / sender_total, 4) if sender_total else None
            ),
        },
        # Receiver-side bytes ÷ sender-side bytes (first + retransmit)
        # per type: <1 when frames died with a connection (or a node was
        # torn down before draining), >1 never (the receiver cannot see
        # more than was written).
        "recv_vs_sent": {
            t: round(
                in_bytes.get(t, 0)
                / (out_bytes.get(t, 0) + re_bytes.get(t, 0)),
                4,
            )
            for t in types
            if out_bytes.get(t, 0) + re_bytes.get(t, 0) > 0
        },
    }
    if out_total > 0:
        wire["goodput_ratio"] = round(
            committed_payload_bytes / out_total, 4
        )
        # Pre-compression logical bytes ÷ wire bytes (first transmissions
        # only — raw counters don't track retransmits): >1 is the wire-v2
        # compression win, 1.0 on the legacy arm.
        if first_total > 0 and raw_total > 0:
            wire["compression_ratio"] = round(raw_total / first_total, 4)
    # Coalescing series (wire v2): syscall batching as a measured
    # distribution, not an inference.  frames_per_flush covers the
    # ReliableSender data path, acks_per_flush the receivers' replies.
    if flushes:
        wire["flushes"] = int(flushes)
        if fpf_count:
            wire["frames_per_flush_mean"] = round(fpf_sum / fpf_count, 3)
        if apf_count:
            wire["acks_per_flush_mean"] = round(apf_sum / apf_count, 3)
    # Frame-anatomy metrics read the RAW (pre-compression) series so
    # they measure encoding composition under both formats.
    cert_bytes = out_raw.get("certificate", 0) or out_bytes.get(
        "certificate", 0
    )
    cert_frames = out_frames.get("certificate", 0)
    if quorum_weight and cert_frames:
        sig_bytes = cert_sig_wire_bytes(
            cert_scheme, quorum_weight, wire_version
        )
        wire["cert_sig_bytes_per_cert"] = sig_bytes
        wire["cert_sig_bytes_fraction"] = round(
            sig_bytes / (cert_bytes / cert_frames), 4
        )
    empty_h = counters.get("primary.own_headers_empty", 0)
    payload_h = counters.get("primary.own_headers_payload", 0)
    wire["empty_headers"] = int(empty_h)
    wire["payload_headers"] = int(payload_h)
    control_bytes = sum(
        out_bytes.get(t, 0) for t in ("header", "vote", "certificate")
    )
    if empty_h + payload_h > 0 and committed_payload_bytes > 0:
        empty_fraction = empty_h / (empty_h + payload_h)
        wire["empty_cert_overhead_per_committed_byte"] = round(
            control_bytes * empty_fraction / committed_payload_bytes, 6
        )

    # -- crypto section -------------------------------------------------------

    verify_sites: dict = {}
    for site, ops in sorted(typed("crypto.verify.ops.").items()):
        wall_s, calls = hists.get(f"crypto.verify.seconds.{site}", (0.0, 0))
        bsum, bcount = hists.get(
            f"crypto.verify.batch_size.{site}", (0.0, 0)
        )
        # Async batched path only: backend compute time (host prep +
        # device round trip) vs the wall histogram above, which also
        # carries event-loop yields/executor-queue wait across the
        # await — the split that stops pipelining reading as crypto
        # cost (wall >> compute means the loop overlapped other work).
        dev_s, dev_calls = hists.get(
            f"crypto.verify.device_seconds.{site}", (0.0, 0)
        )
        verify_sites[site] = {
            "ops": int(ops),
            "calls": int(calls),
            "wall_s": round(wall_s, 3),
            "mean_batch": round(bsum / bcount, 2) if bcount else None,
        }
        if dev_calls:
            verify_sites[site]["compute_s"] = round(dev_s, 3)
            verify_sites[site]["loop_overlap_s"] = round(
                max(0.0, wall_s - dev_s), 3
            )
    sign_sites: dict = {}
    for site, ops in sorted(typed("crypto.sign.ops.").items()):
        wall_s, _calls = hists.get(f"crypto.sign.seconds.{site}", (0.0, 0))
        sign_sites[site] = {"ops": int(ops), "wall_s": round(wall_s, 3)}

    claims = {
        kind: int(v) for kind, v in typed("crypto.burst_claims.").items()
    }
    crypto: dict = {
        "verify": verify_sites,
        "sign": sign_sites,
        "burst_claims": claims,
        "verify_cache": {
            "hits": int(counters.get("primary.verify_cache_hits", 0)),
            "misses": int(counters.get("primary.verify_cache_misses", 0)),
        },
    }

    # Protocol-arithmetic cross-check (see docstring).
    votes_received = counters.get("primary.votes_received", 0)
    late_votes = counters.get("primary.late_votes", 0)
    own_headers = empty_h + payload_h
    measured_vote_claims = claims.get("vote", 0) + (
        verify_sites.get("vote", {}).get("ops", 0)
    )
    expected_vote_claims = votes_received - own_headers + late_votes
    check: dict = {}
    if expected_vote_claims > 0:
        check["votes"] = {
            "measured_claims": int(measured_vote_claims),
            "expected_claims": int(expected_vote_claims),
            "ratio": round(measured_vote_claims / expected_vote_claims, 4),
        }
    certs_in = counters.get("primary.certificates_processed", 0)
    certs_own = counters.get("primary.certificates_formed", 0)
    wire_certs = certs_in - certs_own
    if quorum_weight and wire_certs > 0:
        claims_per_cert = claims.get("certificate", 0) / wire_certs
        # individual: 2f+1 vote signatures + the embedded header's
        # signature.  halfagg: ONE aggregate claim + the header's —
        # the "2f+1 → 1 verify per cert" ledger witness.
        expected_claims = (
            2 if cert_scheme == "halfagg" else quorum_weight + 1
        )
        check["certificates"] = {
            "claims": claims.get("certificate", 0),
            "wire_certs": int(wire_certs),
            "claims_per_cert": round(claims_per_cert, 3),
            "expected_claims_per_cert": expected_claims,
            "ratio": round(claims_per_cert / expected_claims, 4),
        }
    if check:
        crypto["protocol_check"] = check
    return {"wire": wire, "crypto": crypto}


# -- committee-wide timeline from scraped samples -----------------------------

_PEER_RTT_PREFIX = "net.reliable.peer.rtt_seconds."


def build_timeline(
    samples: List[dict],
    interval_s: float = 1.0,
    healthz: Optional[Dict[str, tuple]] = None,
) -> dict:
    """Turn the scraper's raw sample stream into the timeline section of
    the bench JSON:

        {"interval_s": s,
         "nodes": {name: [{"t", "round", "commit_lag", "commits",
                           "committed_batches", "txs_sealed",
                           "pending_acks", "health_firing",
                           "commit_rate_per_s", "txs_sealed_per_s",
                           "queues": {channel: depth}}, …]},
         "events": [{"node", "t", "event": "FIRING"|"cleared", "rule",
                     "subject", "detail"}, …],   # anomaly transitions
         "rtt_ms": {name: {peer_addr: {"mean_ms", "count"}}},
         "healthz": {name: {"status": code|None, "firing": [rule names]}}}

    Per-sample rates are deltas against the node's previous sample, so a
    mid-run stall shows as a rate dip AT ITS TIME — the thing the
    post-mortem snapshot can structurally never show.  The RTT matrix
    comes from each node's LAST sample (per-peer histograms are
    cumulative, so last = whole-run mean).

    The ``events`` track is the HealthMonitor's FIRING/cleared
    transitions promoted to a first-class, committee-wide list: each
    node's snapshots carry a bounded ``health.events`` ring, and the
    scraper sees it grow tick by tick — deduplicated here by (node,
    rule, subject, event, t) since the ring is cumulative across
    samples, merged with the quiesce /healthz bodies (which can carry
    transitions after the last scrape tick), and sorted by time so rule
    firings line up against the per-node rate series they explain.
    """
    by_node: Dict[str, List[dict]] = {}
    for s in sorted(samples, key=lambda s: s.get("t", 0.0)):
        by_node.setdefault(s["node"], []).append(s)

    events: List[dict] = []
    seen_events = set()

    def collect_events(name: str, health: Optional[dict]) -> None:
        for ev in (health or {}).get("events") or []:
            key = (
                name,
                ev.get("rule"),
                ev.get("subject"),
                ev.get("event"),
                ev.get("t"),
            )
            if key in seen_events:
                continue
            seen_events.add(key)
            events.append(
                {
                    "node": name,
                    "t": ev.get("t"),
                    "event": ev.get("event"),
                    "rule": ev.get("rule"),
                    "subject": ev.get("subject"),
                    "detail": ev.get("detail") or {},
                }
            )

    nodes: Dict[str, List[dict]] = {}
    rtt_ms: Dict[str, Dict[str, dict]] = {}
    for name, node_samples in by_node.items():
        series: List[dict] = []
        prev: Optional[dict] = None
        for s in node_samples:
            counters, gauges = s["counters"], s["gauges"]
            health = s.get("health") or {}
            collect_events(name, health)
            point = {
                "t": round(s["t"], 3),
                "round": gauges.get("primary.round"),
                "commit_lag": gauges.get("consensus.commit_lag_rounds"),
                "commits": counters.get(
                    "consensus.committed_certificates"
                ),
                "committed_batches": counters.get(
                    "consensus.committed_batch_digests"
                ),
                "txs_sealed": counters.get("worker.txs_sealed"),
                "pending_acks": gauges.get("net.reliable.pending_acks"),
                "health_firing": len(health.get("firing", [])),
            }
            # Non-empty InstrumentedQueue depths at this tick: the
            # per-channel series a knee reads as a FILLING queue on the
            # timeline (and the Perfetto queue-depth counter tracks).
            qdepth = {
                g[len("queue."):-len(".depth")]: v
                for g, v in gauges.items()
                if g.startswith("queue.") and g.endswith(".depth") and v
            }
            if qdepth:
                point["queues"] = qdepth
            if prev is not None and s["t"] > prev["t"]:
                dt = s["t"] - prev["t"]
                for rate_key, src_key in (
                    ("commit_rate_per_s", "commits"),
                    ("txs_sealed_per_s", "txs_sealed"),
                ):
                    a, b = prev.get(src_key), point.get(src_key)
                    if a is not None and b is not None:
                        point[rate_key] = round((b - a) / dt, 2)
            series.append(point)
            prev = point
        nodes[name] = series

        # Per-peer RTT from the node's last sample's histograms.
        last = node_samples[-1]
        peers = {}
        for hname, h in (last.get("histograms") or {}).items():
            if hname.startswith(_PEER_RTT_PREFIX) and h.get("count"):
                peers[hname[len(_PEER_RTT_PREFIX):]] = {
                    "mean_ms": round(1000 * h["sum"] / h["count"], 3),
                    "count": h["count"],
                }
        if peers:
            rtt_ms[name] = peers

    if healthz is not None:
        # Transitions between the last scrape tick and quiesce ride in
        # the /healthz bodies' events ring.
        for name, (_, body) in healthz.items():
            collect_events(name, body)
    events.sort(key=lambda ev: (ev["t"] is None, ev["t"] or 0.0))
    out = {
        "interval_s": interval_s,
        "nodes": nodes,
        "events": events,
        "rtt_ms": rtt_ms,
    }
    if healthz is not None:
        out["healthz"] = {
            name: {
                "status": status,
                "firing": [
                    f.get("rule")
                    for f in ((body or {}).get("firing") or [])
                ],
            }
            for name, (status, body) in healthz.items()
        }
    return out


def check_quiesce_health(
    healthz: Dict[str, tuple], errors: List[str]
) -> None:
    """The harness's live-health gate: any node whose /healthz reports a
    firing rule at quiesce fails the run (error entry — fatal to every
    caller).  An unreachable endpoint is NOT a failure here: nodes
    without --metrics-port (or already torn down) simply aren't gated."""
    for name, (status, body) in sorted(healthz.items()):
        if status is not None and status != 200:
            rules = ", ".join(
                f"{f.get('rule')}[{f.get('subject')}]"
                for f in ((body or {}).get("firing") or [])
            ) or "unknown"
            errors.append(
                f"health check FAILED at quiesce: {name} /healthz "
                f"returned {status} with firing rule(s): {rules}"
            )
