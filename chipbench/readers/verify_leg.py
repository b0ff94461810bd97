"""A leg of the verify-stage trace of one primary (the program's
``metrics.VERIFY_STAGES``: one entry per burst of its verify stage,
wall-clock stamps ``collected``, ``submitted``, ``prepare``,
``enqueued``, ``fetched``, ``resumed``, ``replayed``), over the bursts
whose ``from`` stamp falls in the window: the median of ``to`` -
``from`` in ms, or with ``"stat": "max"`` the longest (one burst with a
second between two stages is a stall of the dispatch thread or of the
device with the loop alive).  None where the node's final snapshot holds
no such table (a program from before PR 26) or no burst with both
stamps."""

from __future__ import annotations

from . import median


def read(params: dict, run: dict):
    snap = run["snapshots"].get(params["node"]) or {}
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    frm, to = params["from"], params["to"]
    legs = [
        1000.0 * (e[to] - e[frm])
        for e in snap.get("verify_trace", {}).values()
        if frm in e and to in e and t0 <= e[frm] < t1
    ]
    if params.get("stat") == "max":
        return max(legs, default=None)
    return median(legs)
