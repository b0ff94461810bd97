"""Plain Tusk: the classic commit rule as a dictionary walk.

The benchmark's own copy of the frozen oracle
(``narwhal_tpu/consensus/golden.py``, itself upstream's
``consensus/src/lib.rs``), rewritten over the reference's own
certificate type so that it imports nothing of the program.  Slow and
simple on purpose; do not optimise it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .wire import Certificate, genesis


class PlainTusk:
    """Feed certificates in arrival order, get ordered commit sequences."""

    def __init__(self, sorted_keys: List[bytes], gc_depth: int) -> None:
        self.keys = sorted_keys
        self.n = len(sorted_keys)
        self.gc_depth = gc_depth
        gen = {c.origin: (c.digest(), c) for c in genesis(sorted_keys)}
        # round -> {origin -> (certificate digest, certificate)}
        self.dag: Dict[int, Dict[bytes, Tuple[bytes, Certificate]]] = {0: gen}
        self.last_committed_round = 0
        self.last_committed: Dict[bytes, int] = {k: 0 for k in sorted_keys}

    def validity_threshold(self) -> int:
        return (self.n + 2) // 3  # f+1 of unit stakes

    def leader(self, round_: int) -> Optional[Tuple[bytes, Certificate]]:
        return self.dag.get(round_, {}).get(self.keys[round_ % self.n])

    def process_certificate(self, cert: Certificate) -> List[Certificate]:
        self.dag.setdefault(cert.round, {})[cert.origin] = (cert.digest(), cert)
        r = cert.round - 1
        if r % 2 != 0 or r < 4:
            return []
        leader_round = r - 2
        if leader_round <= self.last_committed_round:
            return []
        got = self.leader(leader_round)
        if got is None:
            return []
        leader_digest, leader = got
        support = sum(
            1 for _, c in self.dag.get(r - 1, {}).values()
            if leader_digest in c.header.parents
        )
        if support < self.validity_threshold():
            return []
        sequence = []
        for past in reversed(self.order_leaders(leader)):
            for x in self.order_dag(past):
                self.update(x)
                sequence.append(x)
        return sequence

    def order_leaders(self, leader: Certificate) -> List[Certificate]:
        to_commit = [leader]
        for r in range(leader.round - 2, self.last_committed_round + 1, -2):
            got = self.leader(r)
            if got is None:
                continue
            prev = got[1]
            if self.linked(leader, prev):
                to_commit.append(prev)
                leader = prev
        return to_commit

    def linked(self, leader: Certificate, prev: Certificate) -> bool:
        parents = [leader]
        for r in range(leader.round - 1, prev.round - 1, -1):
            parents = [
                c for d, c in self.dag.get(r, {}).values()
                if any(d in x.header.parents for x in parents)
            ]
        return any(x is prev for x in parents)

    def order_dag(self, leader: Certificate) -> List[Certificate]:
        ordered, seen, buffer = [], set(), [leader]
        while buffer:
            x = buffer.pop()
            ordered.append(x)
            for parent in sorted(x.header.parents):
                found = None
                for d, c in self.dag.get(x.round - 1, {}).values():
                    if d == parent:
                        found = (d, c)
                        break
                if found is None:
                    continue  # ordered already, or collected
                d, c = found
                if d in seen or self.last_committed.get(c.origin, -1) >= c.round:
                    continue
                buffer.append(c)
                seen.add(d)
        ordered = [
            x for x in ordered
            if x.round + self.gc_depth >= self.last_committed_round
        ]
        ordered.sort(key=lambda x: x.round)
        return ordered

    def update(self, cert: Certificate) -> None:
        self.last_committed[cert.origin] = max(
            self.last_committed.get(cert.origin, 0), cert.round
        )
        self.last_committed_round = max(self.last_committed.values())
        last = self.last_committed_round
        for name, round_ in self.last_committed.items():
            for r in list(self.dag):
                auths = self.dag[r]
                if name in auths and r < round_:
                    del auths[name]
                if not auths or r + self.gc_depth < last:
                    del self.dag[r]
