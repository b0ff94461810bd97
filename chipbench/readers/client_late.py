"""How late the generator ran: actual send - due, over the samples due in
the window, at the percentile asked for, in ms."""

from __future__ import annotations

import joins


def read(params: dict, run: dict):
    late = [1000.0 * (s.sent - s.due) for s in run["due"]]
    if not late:
        return None
    return joins.percentile(late, params.get("percentile", 95))
