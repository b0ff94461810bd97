"""A kernel's share of its roofline, in %: the least time the chip could
take for the USEFUL verifications of the traced calls (padding rows do
no useful work) over the device time of those calls.  Useful rows per
call are the window's mean claims per dispatch, from the counters named
in the parameters; operations, bytes and peaks are ``work.py``'s."""

from __future__ import annotations

import work
from . import snapshot_hist_mean


def read(params: dict, run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    calls = trace["programs"].get(params["program"])
    per_call = snapshot_hist_mean.read(
        {"node": params["node"], "series": params["batch_size_series"]}, run
    )
    if not calls or not per_call or sum(calls) <= 0:
        return None
    peaks = work.load_peaks(run["device"]["kind"])
    least = work.least_seconds(per_call * len(calls), peaks)["seconds"]
    return 100.0 * least / sum(calls)
