"""Plain Tusk: the commit rules a replica may declare, as a dictionary walk.

``classic`` is the benchmark's own copy of the frozen oracle
(``narwhal_tpu/consensus/golden.py``, itself upstream's
``consensus/src/lib.rs``), rewritten over the reference's own
certificate type so that it imports nothing of the program.  ``lowdepth``
is the direct rule (Mysticeti's direct decision, arXiv:2310.14821, over
the same leader schedule), written from its statement:

- classic: the leader of even round L is decided when the first
  certificate of round L+3 arrives, if f+1 certificates of round L+1
  cite it;
- lowdepth: it is decided on arrival, as soon as 2f+1 certificates of
  round L+1 cite it and it is itself in hand.  Any certificate of a
  round above L+1 has 2f+1 parents, f+1 of them among those 2f+1, so
  every later leader is linked to this one and a replica that decides a
  later leader first orders this one in the same place.

What follows a decision is the same under both: the chain walk below the
decided leader, each linked leader's history flattened, oldest first.
Slow and simple on purpose; do not optimise it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .wire import Certificate, genesis


RULES = ("classic", "lowdepth")


class PlainTusk:
    """Feed certificates in arrival order, get ordered commit sequences."""

    def __init__(self, sorted_keys: List[bytes], gc_depth: int,
                 rule: str = "classic") -> None:
        if rule not in RULES:
            raise ValueError(f"no plain rule {rule!r}; there are {RULES}")
        self.rule = rule
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

    def quorum(self) -> int:
        return 2 * self.n // 3 + 1  # 2f+1 of unit stakes

    def leader(self, round_: int) -> Optional[Tuple[bytes, Certificate]]:
        return self.dag.get(round_, {}).get(self.keys[round_ % self.n])

    def process_certificate(self, cert: Certificate) -> List[Certificate]:
        self.dag.setdefault(cert.round, {})[cert.origin] = (cert.digest(), cert)
        if self.rule == "classic":
            # Decided by the first certificate three rounds above.
            r = cert.round - 1
            if r % 2 != 0 or r < 4:
                return []
            leader_round, needed = r - 2, self.validity_threshold()
        else:
            # Decided on arrival: a certificate of the round above adds a
            # citation; the leader itself, arriving after those that cite
            # it, makes theirs count.  Nothing else can decide a leader.
            if cert.round % 2 == 1:
                leader_round = cert.round - 1
            elif cert.origin == self.keys[cert.round % self.n]:
                leader_round = cert.round
            else:
                return []
            if leader_round < 2:
                return []
            needed = self.quorum()
        if leader_round <= self.last_committed_round:
            return []
        got = self.leader(leader_round)
        if got is None:
            return []
        leader_digest, leader = got
        support = sum(
            1 for _, c in self.dag.get(leader_round + 1, {}).values()
            if leader_digest in c.header.parents
        )
        if support < needed:
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
