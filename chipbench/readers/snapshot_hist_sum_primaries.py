"""Sum of a histogram series over the measured window and over every
live primary: `snapshot_hist_sum` of each `primary-<i>` of the closing
scrape, added up.  0.0 where the series is there and nothing was
observed in the window (a clean run is a reading, not an absence); None
where no primary keeps the series."""

from __future__ import annotations

from readers import snapshot_hist_sum


def read(params: dict, run: dict):
    values = [
        snapshot_hist_sum.read({**params, "node": node}, run)
        for node in run["scrape1"]
        if node.startswith("primary-")
    ]
    values = [v for v in values if v is not None]
    return sum(values) if values else None
