"""Sum of named fields of one `detail` entry of a node's final snapshot,
scaled: what the process counted over its whole life, set-up included
(the window's scrapes carry no detail).  None where the node, the entry
or any of the fields is not there: a program that does not keep them
reports nothing."""

from __future__ import annotations


def read(params: dict, run: dict):
    detail = run["snapshots"].get(params["node"], {}).get("detail", {})
    entry = detail.get(params["detail"]) or {}
    values = [entry.get(field) for field in params["fields"]]
    if any(v is None for v in values):
        return None
    return params.get("scale", 1.0) * sum(values)
