"""Mean of a histogram series over the measured window: the difference of
the node's scrapes at the window's two ends, sum over count, scaled."""

from __future__ import annotations


def read(params: dict, run: dict):
    node, series = params["node"], params["series"]
    h0 = run["scrape0"].get(node, {}).get("histograms", {}).get(series)
    h1 = run["scrape1"].get(node, {}).get("histograms", {}).get(series)
    if h1 is None:
        return None
    count = h1["count"] - (h0["count"] if h0 else 0)
    total = h1["sum"] - (h0["sum"] if h0 else 0.0)
    if count <= 0:
        return None
    return params.get("scale", 1.0) * total / count
