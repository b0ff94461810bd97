"""Plain rule for own headers that can never commit.

A primary proposes one header a round.  Tusk commits a certificate only
if its round is above its origin's last committed round (``order_dag``'s
skip in ``tusk.py``) and not under the garbage horizon (``round +
gc_depth >= last committed round``, the filter at the end of
``order_dag``).  Both marks only rise, so once a later own round has
committed, or the committed round has passed a header's round by more
than ``gc_depth``, that header is out for good and its payload has to be
proposed again.

Written as a scan over the whole sequence for each proposed round, so
that it shares nothing with the program's incremental bookkeeping
(``narwhal_tpu/primary/proposer.py::Proposer.deliver_commit``), and
imports nothing of the program.  Slow and simple on purpose.
"""

from __future__ import annotations

from typing import Dict, Hashable, Iterable, Sequence, Tuple


def orphaned(
    committed: Sequence[Tuple[Hashable, int]],
    own: Hashable,
    proposed: Iterable[int],
    gc_depth: int,
) -> Dict[int, int]:
    """``committed``: (origin, round) of every committed certificate, in
    commit order.  ``proposed``: the rounds of ``own``'s headers.  Returns
    {round: index} for each proposed round that can never commit, where
    ``committed[index]`` is the earliest commit at which that is known.
    A proposed round that is neither committed nor in the result may
    still commit.  Raises ValueError on a sequence no Tusk emits."""
    own_rounds = [r for origin, r in committed if origin == own]
    if any(b <= a for a, b in zip(own_rounds, own_rounds[1:])):
        raise ValueError("an origin's committed rounds must rise")
    out: Dict[int, int] = {}
    for round_ in proposed:
        highest = 0
        for index, (origin, r) in enumerate(committed):
            highest = max(highest, r)
            if origin == own and r == round_:
                break  # committed: settled, not orphaned
            if (origin == own and r > round_) or round_ + gc_depth < highest:
                out[round_] = index
                break
    for round_, index in out.items():
        if (own, round_) in committed[index:]:
            raise ValueError(f"round {round_} commits after it could not")
    return out
