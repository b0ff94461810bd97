"""The comparison that decides ``correct``.

What the timed run produced (every live replica's audit segment, every
live worker's store, the device-backed primaries' counters, the clients'
samples) is held
against a plain reference that imports nothing of the program: OpenSSL
for signatures, ``tusk.PlainTusk`` for the order, SHA-256 and the
clients' own bytes for the batches.  Every number compared is a count of
answers that are wrong, so every limit is 0 (exact comparison); PERF.md
gives the readings of sound runs and of the controls.

Layer by layer:

- worker: every sample due in the window is read back, byte for byte,
  from the batch behind its committed digest in EVERY live worker's store
  (``samples_misread``), and that batch is in every live replica's commit
  sequence once the drain has ended (``samples_unanswered``);
- primary and verify seam: every certificate that entered any live
  replica's commit rule carries a valid header signature and 2f+1 valid
  votes of distinct validators under OpenSSL (``certificates_invalid``:
  a device-backed primary took each through its on-chip verifier); EACH
  device-backed primary rejected exactly the forged headers it was sent
  (``verifier_reject_gap``: the gaps summed, so that one verifier of
  four that accepts is not hidden by the others); each built no program
  after ready and dispatched no shape outside the warmed ladder
  (``device_off_ladder``, summed), and each did dispatch to its device
  inside the window (``window_without_dispatch``: how many did not);
- Tusk: each replica's recorded commit sequence is what the plain rule
  makes of the certificates it was given, in the order it was given
  them, and every sequence is a prefix of the longest
  (``replica_order_mismatches``).  WHICH plain rule is the replica's own
  statement: the ``M`` record that follows its segment's ``R`` names it
  (``tusk.RULES``), and every replica of a run has to name the same one
  (``declared_rule``).  The guarantee is the one sequence; a rule is how
  a replica gets there, and it is held to the one it says it runs.
"""

from __future__ import annotations

import mmap
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from .tusk import RULES, PlainTusk
from .wire import decode_certificate, sha

LIMITS = {
    "samples_unanswered": 0,
    "samples_misread": 0,
    "certificates_invalid": 0,
    "verifier_reject_gap": 0,
    "device_off_ladder": 0,
    "window_without_dispatch": 0,
    "replica_order_mismatches": 0,
}


class StoreIndex:
    """digest -> the serialized batch, over one worker's store log
    (memory-mapped: a run's store is hundreds of MB)."""

    def __init__(self, path: Optional[str]) -> None:
        self.index: Dict[bytes, Tuple[int, int]] = {}
        self.mm = None
        if path is None:
            return
        with open(path, "rb") as f:
            if f.seek(0, 2) == 0:
                return
            self.mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        pos, n = 0, len(self.mm)
        while pos + 8 <= n:
            klen, vlen = struct.unpack_from("<II", self.mm, pos)
            end = pos + 8 + klen + vlen
            if end > n:
                break
            self.index[self.mm[pos + 8:pos + 8 + klen]] = (pos + 8 + klen, vlen)
            pos = end

    def get(self, digest: bytes):
        got = self.index.get(digest)
        if got is None:
            return None
        return memoryview(self.mm)[got[0]:got[0] + got[1]]


@dataclass
class Artifacts:
    """What a run left behind, as the comparison takes it.  The controls
    (``control.py``) alter a copy of this, never the files."""

    sorted_keys: List[bytes]
    gc_depth: int
    tx_size: int
    audits: List[List[Tuple[bytes, bytes]]]  # per live replica
    stores: List[Dict[int, StoreIndex]]      # per live node: worker id -> store
    due: list                                # joins.Sample, due in the window
    sample_worker: Dict[int, int]            # client index -> worker id
    batch_of: Dict[int, Optional[bytes]]     # sample id -> digest of its batch
    # One entry per primary that was sent forgeries (those the
    # configuration puts on a chip):
    forged_sent: List[int]                   # forgeries it acknowledged
    invalid_signatures: List[int]            # its counter at the end
    # One entry per device-backed primary, or None where there is none:
    device: Optional[List[dict]]             # its crypto.verify.device
    window_dispatches: Optional[List[int]]   # its dispatches inside the window
    # A control can stand in other bytes for a stored batch.
    store_overrides: Dict[Tuple[int, int, bytes], Optional[bytes]] = field(
        default_factory=dict
    )

    @property
    def quorum(self) -> int:
        return 2 * len(self.sorted_keys) // 3 + 1

    def stored(self, node: int, worker: int, digest: bytes):
        key = (node, worker, digest)
        if key in self.store_overrides:
            return self.store_overrides[key]
        return self.stores[node][worker].get(digest)


def openssl_verify(message: bytes, key: bytes, signature: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(key).verify(signature, message)
        return True
    except (InvalidSignature, ValueError):
        return False


def certificate_valid(cert, keys: set, quorum: int) -> bool:
    h = cert.header
    if h.author not in keys or h.id != h.computed_id():
        return False
    if not openssl_verify(h.id, h.author, h.signature):
        return False
    voters = {name for name, _ in cert.votes}
    if len(voters) != len(cert.votes) or not voters <= keys or len(voters) < quorum:
        return False
    digest = cert.digest()
    return all(openssl_verify(digest, name, sig) for name, sig in cert.votes)


def declared_rule(records: List[Tuple[bytes, bytes]]) -> Optional[str]:
    """The commit rule one replica's audit segment declares: the payload
    of the ``M`` record that stands right after its ``R``.  None where
    the segment does not start so, or names no rule the reference has."""
    if len(records) < 2 or records[0] != (b"R", b"") or records[1][0] != b"M":
        return None
    rule = records[1][1].decode("ascii", "replace")
    return rule if rule in RULES else None


def commit_rule(art: Artifacts) -> str:
    """What a run's verdict line says its replicas were held to: the one
    rule they all declared (anything else reads ``correct`` false)."""
    return "+".join(sorted({declared_rule(r) or "undeclared" for r in art.audits}))


def replay(art: Artifacts) -> Tuple[int, int, List[List[bytes]], List[set]]:
    """(order mismatches, invalid certificates, each replica's committed
    certificate digests, each replica's committed batch digests)."""
    keys = set(art.sorted_keys)
    mismatches = invalid = 0
    judged: Dict[bytes, bool] = {}
    sequences, batches = [], []
    rules = [declared_rule(records) for records in art.audits]
    for records, rule in zip(art.audits, rules):
        # A segment that declares no rule is broken whatever it holds; it
        # is still read (as classic) for what it says it committed.
        tusk = PlainTusk(art.sorted_keys, art.gc_depth, rule or "classic")
        inserted, recorded, expected = {}, [], []
        broken = rule is None
        for tag, payload in records[2:]:
            if tag == b"C":
                recorded.append(payload)
            elif tag == b"I":
                try:
                    cert = decode_certificate(payload, art.sorted_keys)
                except (ValueError, IndexError):
                    broken = True
                    break
                if payload not in judged:
                    judged[payload] = certificate_valid(cert, keys, art.quorum)
                    invalid += not judged[payload]
                inserted[cert.digest()] = cert
                expected.extend(c.digest() for c in tusk.process_certificate(cert))
            else:  # a second marker among them: one segment, one rule
                broken = True
        # A replica is cut off between a burst's records at worst, so what
        # it recorded is the head of what the plain rule commits.
        if broken or recorded != expected[:len(recorded)] or len(set(recorded)) != len(recorded):
            mismatches += 1
        sequences.append(recorded)
        batches.append({
            d for c in recorded if c in inserted
            for d in inserted[c].header.payload
        })
    longest = max(sequences, key=len) if sequences else []
    mismatches += sum(1 for s in sequences if s != longest[:len(s)])
    # Two rules in one run: every replica that declares another than
    # replica 0 counts once, whether or not the sequences have parted yet.
    mismatches += sum(1 for rule in rules if rule != rules[0])
    return mismatches, invalid, sequences, batches


def sample_rows(value, tx_size: int) -> Optional[Dict[int, bytes]]:
    """sample id -> its transaction bytes, for one stored batch; None if
    the batch is not a well-formed run of ``tx_size`` transactions."""
    data = np.frombuffer(value, dtype=np.uint8)
    if len(data) < 5 or data[0] != 0:
        return None
    n = int.from_bytes(data[1:5].tobytes(), "little")
    stride = 4 + tx_size
    if len(data) != 5 + n * stride:
        return None
    rows = data[5:].reshape(n, stride)
    if n and not (np.ascontiguousarray(rows[:, :4]).view("<u4")[:, 0] == tx_size).all():
        return None
    out = {}
    for row in rows[rows[:, 4] == 0]:
        tx = row[4:].tobytes()
        out[int.from_bytes(tx[1:9], "little")] = tx
    return out


def sample_tx(sample_id: int, size: int) -> bytes:
    """What a client sent as that sample (client.py::sample_tx)."""
    return b"\x00" + sample_id.to_bytes(8, "little") + bytes(size - 9)


def compare(art: Artifacts) -> Dict[str, int]:
    """Every number compared, by name.  Each is held to LIMITS[name]."""
    mismatches, invalid, _, batches = replay(art)

    unanswered = misread = 0
    parsed: Dict[Tuple[int, int, bytes], Optional[Dict[int, bytes]]] = {}
    for s in art.due:
        digest = art.batch_of.get(s.id)
        if digest is None or not all(digest in b for b in batches):
            unanswered += 1
            continue
        worker = art.sample_worker[s.client]
        want = sample_tx(s.id, art.tx_size)
        ok = True
        for node in range(len(art.stores)):
            key = (node, worker, digest)
            if key not in parsed:
                value = art.stored(node, worker, digest)
                parsed[key] = (
                    sample_rows(value, art.tx_size)
                    if value is not None and sha(value) == digest
                    else None
                )
            rows = parsed[key]
            ok &= rows is not None and rows.get(s.id) == want
        misread += not ok

    numbers = {
        "samples_unanswered": unanswered,
        "samples_misread": misread,
        "certificates_invalid": invalid,
        "verifier_reject_gap": sum(
            abs(counted - sent)
            for counted, sent in zip(art.invalid_signatures, art.forged_sent)
        ),
        "replica_order_mismatches": mismatches,
    }
    if art.device is not None:
        numbers["device_off_ladder"] = sum(off_ladder(d) for d in art.device)
        numbers["window_without_dispatch"] = sum(
            not n for n in art.window_dispatches
        )
    return numbers


def off_ladder(d: dict) -> int:
    """Dispatches of one verifier at a shape outside its warmed ladder,
    plus the programs it built after it said ready (1 where it never
    said)."""
    off = sum(
        n for shape, n in d.get("dispatched", {}).items()
        if int(shape) not in d.get("rungs", [])
    )
    built_late = (
        d.get("programs_built", 0) - d["programs_at_ready"]
        if d.get("programs_at_ready") is not None else 1
    )
    return off + max(0, built_late)


def verdict(numbers: Dict[str, int]) -> bool:
    return all(v <= LIMITS[k] for k, v in numbers.items())
