"""A Byzantine peer that sends a primary headers with bad signatures.

In honest traffic every signature is valid, so a verifier that accepts
everything would pass unseen.  This part of the traffic makes the
verifiers' rejections visible on the timed path: ``forged_per_s`` headers
a second (the workload file's number), shared evenly among the
device-backed primaries (one ``Forger`` each), go to the target's
primary-to-primary socket as raw frames, each with a signature that
OpenSSL refuses, each in the name of a validator that is down where one
is and of the target's next neighbour where all are up (the target
never receives its own headers from a peer).  The target has to put each
through its verifier, reject it and count it
(``primary.invalid_signatures``); `correct` holds each target's count to
what it was sent.  The kinds are ``chip_smoke.py::make_batch``'s: a bit flipped in
R, a bit flipped in S, a genuine signature by the wrong key, and S + L
(non-canonical).

A forged header carries a round far above the committee's and one random
parent, so that it is never stale, is well formed (its id is the hash
of its content) and can only fall at the signature, whether its author
is up or down.
"""

from __future__ import annotations

import random
import socket
import struct
import threading
import time
from typing import List

from reference.check import openssl_verify
from reference.wire import Header, header_frame

L_ORDER = (1 << 252) + 27742317777372353535851937790883648493
KINDS = ("forged_r", "forged_s", "wrong_key", "noncanonical_s")
FAR_ROUND = 1 << 40


def flip(sig: bytes, byte: int) -> bytes:
    return sig[:byte] + bytes([sig[byte] ^ 1]) + sig[byte + 1:]


def forged_header(k: int, kind: str, author, other, rng: random.Random) -> Header:
    """The k-th forgery: a header in ``author``'s name (an Identity)
    whose signature does not verify; ``other`` is any other identity."""
    parents = [rng.randbytes(32)]
    h = Header(author.name, FAR_ROUND + k, {}, parents, bytes(32), bytes(64))
    h.id = h.computed_id()
    good = author.sign(h.id)
    if kind == "forged_r":
        h.signature = flip(good, 0)
    elif kind == "forged_s":
        h.signature = flip(good, 40)
    elif kind == "wrong_key":
        h.signature = other.sign(h.id)
    elif kind == "noncanonical_s":
        s = int.from_bytes(good[32:], "little") + L_ORDER
        h.signature = good[:32] + s.to_bytes(32, "little")
    else:
        raise ValueError(kind)
    if openssl_verify(h.id, h.author, h.signature):
        raise AssertionError(f"forgery {kind} verifies")
    return h


def forged_names(ids: list, alive: int, target: int) -> tuple:
    """(the identity the forgeries name as author, the one that signs the
    wrong-key kind) for the primary of launch index ``target``: the first
    validator that is down, else the target's next neighbour; the wrong
    key is the target's own.  They differ whatever the layout."""
    author = ids[alive] if alive < len(ids) else ids[(target + 1) % len(ids)]
    return author, ids[target]


class Forger(threading.Thread):
    """Sends one forged header every ``1 / per_s`` seconds until stopped;
    ``sent`` lists (wall time, kind, header id) of every frame the peer
    acknowledged."""

    def __init__(self, address: str, ids: list, alive: int,
                 sorted_keys: List[bytes], per_s: float, seed: int,
                 target: int = 0) -> None:
        super().__init__(daemon=True)
        self.address = address
        self.author, self.other = forged_names(ids, alive, target)
        self.sorted_keys = sorted_keys
        self.period = 1.0 / per_s
        self.rng = random.Random(seed)
        self.sent: list = []
        self.error = None
        self._stop_event = threading.Event()

    def run(self) -> None:
        host, port = self.address.rsplit(":", 1)
        try:
            with socket.create_connection((host, int(port)), timeout=10) as s:
                k = 0
                next_at = time.time()
                while not self._stop_event.is_set():
                    kind = KINDS[k % len(KINDS)]
                    h = forged_header(k, kind, self.author, self.other, self.rng)
                    s.sendall(header_frame(h, self.sorted_keys))
                    # The peer answers every decoded frame with an ACK
                    # frame; waiting for it means the header is in the
                    # primary's queue, not in a socket buffer.
                    (n,) = struct.unpack("<I", self._recv(s, 4))
                    self._recv(s, n)
                    self.sent.append((time.time(), kind, h.id.hex()))
                    k += 1
                    next_at += self.period
                    self._stop_event.wait(max(0.0, next_at - time.time()))
        except OSError as e:
            self.error = e

    @staticmethod
    def _recv(s: socket.socket, n: int) -> bytes:
        out = b""
        while len(out) < n:
            chunk = s.recv(n - len(out))
            if not chunk:
                raise OSError("primary closed the forger's connection")
            out += chunk
        return out

    def stop(self) -> None:
        self._stop_event.set()
        self.join(timeout=15)
