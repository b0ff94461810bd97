"""GarbageCollector: track the consensus round and clean up the workers.

Reference primary/src/garbage_collector.rs (72 LoC): consume committed
certificates from consensus, bump the shared consensus round, and broadcast
Cleanup(round) to our own workers.  Beyond the reference, it is where the
committed sequence reaches the primary, so it also tells the Proposer each
committed certificate (Proposer.deliver_commit settles own headers and
re-proposes the payload of those that can no longer commit).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Callable, Optional

from ..config import Committee
from ..crypto import PublicKey
from ..messages import Round, encode_cleanup
from ..network import SimpleSender
from .core import AtomicRound

log = logging.getLogger("narwhal.primary")


class GarbageCollector:
    def __init__(
        self,
        name: PublicKey,
        committee: Committee,
        consensus_round: AtomicRound,
        rx_consensus: asyncio.Queue,  # committed certificates
        committed_cb: Optional[Callable[[Round, bool], None]] = None,
    ) -> None:
        self.name = name
        # (round, is it ours) of every committed certificate, in order.
        self.committed_cb = committed_cb
        self.consensus_round = consensus_round
        self.rx_consensus = rx_consensus
        self.sender = SimpleSender()
        self.worker_addresses = [
            a.primary_to_worker
            for a in committee.authorities[name].workers.values()
        ]

    async def run(self) -> None:
        last_committed_round = 0
        while True:
            certificate = await self.rx_consensus.get()
            round = certificate.round
            if self.committed_cb is not None:
                self.committed_cb(round, certificate.origin == self.name)
            if round > last_committed_round:
                last_committed_round = round
                self.consensus_round.value = round
                for address in self.worker_addresses:
                    self.sender.send(
                        address, encode_cleanup(round), msg_type="cleanup"
                    )
