"""The share of the window in which one primary's verify stage held a
burst, in %: the union of [``collected``, ``replayed``] over the bursts
of its verify-stage trace, cut to the window, over the window.  Its
complement is the time the chip idles for want of work: the stage takes
one burst at a time, so while it holds none nothing is on its way to the
device.  None where the snapshot holds no table, or where the table
lost bursts of the window to its cap (evictions, and its oldest burst
younger than the window)."""

from __future__ import annotations

import trace_reduce


def stage_intervals(snap: dict) -> list:
    """[(collected, replayed)] of every burst that has both."""
    return [
        (e["collected"], e["replayed"])
        for e in snap.get("verify_trace", {}).values()
        if "collected" in e and "replayed" in e
    ]


def read(params: dict, run: dict):
    snap = run["snapshots"].get(params["node"]) or {}
    spans = stage_intervals(snap)
    if not spans or run["seconds"] <= 0:
        return None
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    evicted = snap.get("gauges", {}).get("metrics.verify_trace_evictions", 0)
    if evicted and min(a for a, _ in spans) > t0:
        return None
    cut = [(max(a, t0), min(b, t1)) for a, b in spans if b > t0 and a < t1]
    busy, _ = trace_reduce.union_ns(cut)
    return 100.0 * busy / run["seconds"]
