"""Sum of a histogram series over the measured window: the difference of
the node's scrapes at the window's two ends, scaled.  0.0 where the
series is there and nothing was observed in the window (a clean run is a
reading, not an absence); None where the node does not keep the series."""

from __future__ import annotations


def read(params: dict, run: dict):
    node, series = params["node"], params["series"]
    h1 = run["scrape1"].get(node, {}).get("histograms", {}).get(series)
    if h1 is None:
        return None
    h0 = run["scrape0"].get(node, {}).get("histograms", {}).get(series)
    return params.get("scale", 1.0) * (h1["sum"] - (h0["sum"] if h0 else 0.0))
