"""Of the device's idle time between the traced calls of one program,
the share in which the primary's verify stage held no burst, in %: the
chip idle for want of work, as against idle behind the host (the stage
held a burst that was being prepared, handed back to the loop or
replayed).

The device trace counts from the profiler session's start and the
verify-stage trace (``verify_trace`` of the node's final snapshot) is on
the wall clock, so the two are laid over each other first:

1. the profiler's clock starts when ``start_trace`` is called, and the
   harness asks for the trace ``trace_seconds`` + 0.1 s before the
   window's end (``run.py::drive``), which ``device_node.py`` notices
   within its 0.05 s poll: the offset between the two clocks lies
   between 0.01 s before and 0.07 s after that moment;
2. for every run of n consecutive single-chunk dispatches the offset is
   fitted: a call is over before its mask is fetched, and the fetch
   follows within a millisecond or two, so the offset is the largest
   that puts every call's end at or before its dispatch's ``fetched``;
   the run fits if that offset lies in the range of step 1 and every
   call then starts no earlier than ``enqueued`` less ``tolerance_ms``
   (2; the kernel starts ~0.7 ms before the launch returns);
3. one run that fits is the overlay; of several (dispatches come at a
   near-regular ~23 ms) the one whose ``enqueued`` stamps are spaced
   most like the calls' starts, if it is so by a quarter of the
   tolerance.  None otherwise: a wrong overlay is worse than no number.

The gaps are those between one traced call's end and the next one's
start; the stage is busy over the union of [``collected``,
``replayed``] of every burst."""

from __future__ import annotations

import json
import os

import trace_reduce
from .verify_busy import stage_intervals

CHIPBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ``run.py::drive`` asks for the trace this long before the end of the
# window, beyond harness.json's ``trace_seconds``; ``device_node.py``
# polls for the request every 0.05 s and calls ``start_trace`` at once.
ASKED_BEFORE_TRACE_S = 0.1
OFFSET_SLACK_S = (-0.01, 0.07)


def overlay(calls: list, dispatches: list, tolerance_s: float,
            offset_range: tuple):
    """The offset laying ``calls`` [(start, duration)] in trace time over
    consecutive ``dispatches`` [(enqueued, fetched)] on the wall clock,
    or None where no run of them fits within ``offset_range``, or where
    two do and their spacing does not tell them apart."""
    n = len(calls)
    fits = []
    for k in range(len(dispatches) - n + 1):
        span = dispatches[k:k + n]
        offset = min(d[1] - (c[0] + c[1]) for c, d in zip(calls, span))
        if not offset_range[0] <= offset <= offset_range[1] or any(
            c[0] + offset < d[0] - tolerance_s for c, d in zip(calls, span)
        ):
            continue
        spacing = max(
            abs((c[0] - calls[0][0]) - (d[0] - span[0][0]))
            for c, d in zip(calls, span)
        )
        fits.append((spacing, offset))
    fits.sort()
    if not fits or (
        len(fits) > 1 and fits[1][0] - fits[0][0] < tolerance_s / 4
    ):
        return None
    return fits[0][1]


def read(params: dict, run: dict):
    trace = run.get("trace")
    snap = run["snapshots"].get(params["node"]) or {}
    table = snap.get("verify_trace", {})
    if not trace or not table:
        return None
    calls = sorted(
        (start, dur) for name, start, dur in trace["calls"]
        if name == params["program"]
    )
    if len(calls) < 2:
        return None
    with open(os.path.join(CHIPBENCH, "harness.json")) as f:
        lead = json.load(f)["trace_seconds"] + ASKED_BEFORE_TRACE_S
    asked = run["t0"] + run["seconds"] - lead
    dispatches = sorted(
        (e["enqueued"], e["fetched"]) for e in table.values()
        if "enqueued" in e and "fetched" in e and e.get("chunks") == 1
    )
    offset = overlay(
        calls, dispatches, params["tolerance_ms"] / 1000.0,
        (asked + OFFSET_SLACK_S[0], asked + OFFSET_SLACK_S[1]),
    )
    if offset is None:
        return None
    gaps = [
        (a[0] + a[1] + offset, b[0] + offset)
        for a, b in zip(calls, calls[1:]) if b[0] > a[0] + a[1]
    ]
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    busy = stage_intervals(snap)
    behind_host = 0.0
    for a, b in gaps:
        held, _ = trace_reduce.union_ns([
            (max(s, a), min(e, b)) for s, e in busy if e > a and s < b
        ])
        behind_host += held
    return 100.0 * (idle - behind_host) / idle
