"""One module per reader kind, found by name: ``readers/<kind>.py`` holds
``read(params, run)`` and returns the metric's value, or None where it
finds nothing to read (the harness then leaves the metric out; a reader
never returns 0 for a share of a roofline or of a peak)."""

from __future__ import annotations

import importlib
import statistics


def load(kind: str):
    return importlib.import_module(f"readers.{kind}").read


def median(values):
    """The median, or None where there is nothing to read."""
    return statistics.median(values) if values else None
