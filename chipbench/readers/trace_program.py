"""Device time of one compiled program per call, from the trace: the
median duration of its executions, in ms.  The verify program runs at one
padded shape per call and both shapes carry one name; nearly every call
of these cells is the bottom rung (the device report's ``dispatched``
says how many were not), so the median is that rung's time."""

from __future__ import annotations

from . import median


def read(params: dict, run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    calls = trace["programs"].get(params["program"])
    if not calls:
        return None
    return 1000.0 * median(calls)
