"""A leg of the per-batch stage trace, over the nodes of one role
(``worker`` or ``primary``): every batch digest that has both stamps on
one node and whose ``from`` stamp falls in the window gives ``to`` -
``from``; the value is the median over the committee, in ms.  All nodes
share one host and one clock, so this is the plain join, without the
skew model of ``benchmark/metrics_check.py``."""

from __future__ import annotations

from . import median


def read(params: dict, run: dict):
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    frm, to = params["from"], params["to"]
    legs = []
    for node, snap in run["snapshots"].items():
        if not node.startswith(params["role"]):
            continue
        for e in snap.get("trace", {}).values():
            if frm in e and to in e and t0 <= e[frm] < t1:
                legs.append(1000.0 * (e[to] - e[frm]))
    return median(legs)
