"""From samples, batches and commits to the end-to-end numbers.

The join is upstream's (``benchmark/logs.py``: sample -> the batch that
holds it -> that batch's earliest commit among the replicas).  Two things
are not: latency starts when a sample was DUE, not when it was sent, so
a stalled generator or a backed-up socket is counted; and the rate is
the payload committed inside the window over the window's length, not
over first-proposal-to-last-commit, so a stall inside the window reads
as a lower rate.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


@dataclass
class Sample:
    id: int
    due: float
    sent: float

    @property
    def client(self) -> int:
        return self.id >> 32


def read_samples(path: str) -> List[Sample]:
    out = []
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) == 3:  # a line cut short by teardown is dropped
                out.append(Sample(int(parts[0]), float(parts[1]), float(parts[2])))
    return out


def due_in_window(samples: List[Sample], t0: float, seconds: float) -> List[Sample]:
    return [s for s in samples if t0 <= s.due < t0 + seconds]


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def latencies_ms(
    due: List[Sample],
    batch_of: Dict[int, Optional[bytes]],
    commit_time: Dict[bytes, float],
) -> Tuple[List[float], int]:
    """(latency in ms of every committed sample, number that failed).  A
    sample fails if no batch holds it or its batch never committed."""
    lat, failed = [], 0
    for s in due:
        digest = batch_of.get(s.id)
        t = commit_time.get(digest) if digest is not None else None
        if t is None:
            failed += 1
        else:
            lat.append(1000.0 * (t - s.due))
    return lat, failed


def committed_tx_per_s(
    batch_bytes: Dict[bytes, int],
    commit_time: Dict[bytes, float],
    t0: float,
    seconds: float,
    tx_size: int,
) -> float:
    """Payload committed in [t0, t0 + seconds) as transactions a second."""
    total = sum(
        batch_bytes.get(d, 0)
        for d, t in commit_time.items()
        if t0 <= t < t0 + seconds
    )
    return total / tx_size / seconds
