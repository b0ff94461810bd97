"""A leg of one primary's round trace, over the rounds whose ``from``
stamp falls in the window: the median of ``to`` - ``from`` in ms, or,
with ``"period": true``, the span of the ``from`` stamps over the number
of rounds between them (whole window over rounds)."""

from __future__ import annotations

from . import median


def read(params: dict, run: dict):
    snap = run["snapshots"].get(params["node"])
    if not snap:
        return None
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    frm, to = params["from"], params.get("to")
    rounds = [
        e for e in snap.get("round_trace", {}).values()
        if frm in e and t0 <= e[frm] < t1
    ]
    if params.get("period"):
        stamps = sorted(e[frm] for e in rounds)
        if len(stamps) < 2:
            return None
        return 1000.0 * (stamps[-1] - stamps[0]) / (len(stamps) - 1)
    return_ms = [1000.0 * (e[to] - e[frm]) for e in rounds if to in e]
    return median(return_ms)
