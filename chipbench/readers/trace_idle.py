"""The device's idle share of the traced window, in %: 1 - the union of
the intervals in which an operation ran on the device over the window."""

from __future__ import annotations


def read(params: dict, run: dict):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
