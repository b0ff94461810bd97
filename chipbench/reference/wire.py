"""The byte formats the reference reads and writes, decoded by its own
code: audit segments, certificates and headers (wire v2, individual
votes: the product's defaults), worker batches and the store's log.

Nothing here imports the program.  Formats were read off
``narwhal_tpu/primary/messages.py``, ``messages.py``, ``store.py`` and
``consensus/replay.py``; ``tests/test_reference.py`` holds each against
the program's own encoder where the program is importable.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

PM_HEADER = 0
WORKER_BATCH = 0
CERT_SCHEME_INDIVIDUAL = 0


def sha(data: bytes) -> bytes:
    """The protocol's 32-byte hash (SHA-256 in this framework)."""
    return hashlib.sha256(data).digest()


def uvarint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


class Reader:
    def __init__(self, data: bytes) -> None:
        self.data = data
        self.pos = 0

    def raw(self, n: int) -> bytes:
        if self.pos + n > len(self.data):
            raise ValueError("record ends inside a field")
        out = self.data[self.pos:self.pos + n]
        self.pos += n
        return out

    def u8(self) -> int:
        return self.raw(1)[0]

    def uvarint(self) -> int:
        result = shift = 0
        while True:
            b = self.u8()
            result |= (b & 0x7F) << shift
            if not b & 0x80:
                return result
            shift += 7
            if shift > 63:
                raise ValueError("uvarint exceeds 64 bits")

    def done(self) -> bool:
        return self.pos == len(self.data)


@dataclass
class Header:
    author: bytes
    round: int
    payload: Dict[bytes, int]  # batch digest -> worker id
    parents: List[bytes]
    id: bytes
    signature: bytes

    def computed_id(self) -> bytes:
        out = bytearray(self.author) + struct.pack("<Q", self.round)
        for d in sorted(self.payload):
            out += d + struct.pack("<I", self.payload[d])
        for p in sorted(self.parents):
            out += p
        return sha(bytes(out))


@dataclass
class Certificate:
    header: Header
    votes: List[Tuple[bytes, bytes]]  # (voter key, signature)

    @property
    def round(self) -> int:
        return self.header.round

    @property
    def origin(self) -> bytes:
        return self.header.author

    def digest(self) -> bytes:
        return sha(self.header.id + struct.pack("<Q", self.round) + self.origin)


def genesis(keys: List[bytes]) -> List[Certificate]:
    """One unsigned round-0 certificate per authority; a genesis header's
    id is all zeros (it is never hashed)."""
    return [
        Certificate(Header(k, 0, {}, [], bytes(32), bytes(64)), [])
        for k in keys
    ]


def key_ref(sorted_keys: List[bytes], key: bytes) -> bytes:
    return uvarint(sorted_keys.index(key) + 1)


def read_key_ref(r: Reader, sorted_keys: List[bytes]) -> bytes:
    v = r.uvarint()
    if v == 0:
        return r.raw(32)
    return sorted_keys[v - 1]


def encode_header(h: Header, sorted_keys: List[bytes]) -> bytes:
    out = bytearray(key_ref(sorted_keys, h.author))
    out += uvarint(h.round) + uvarint(len(h.payload))
    for d in sorted(h.payload):
        out += d + uvarint(h.payload[d])
    out += uvarint(len(h.parents))
    for p in sorted(h.parents):
        out += p
    return bytes(out + h.id + h.signature)


def decode_header(r: Reader, sorted_keys: List[bytes]) -> Header:
    author = read_key_ref(r, sorted_keys)
    round_ = r.uvarint()
    payload = {}
    for _ in range(r.uvarint()):
        d = r.raw(32)
        payload[d] = r.uvarint()
    parents = [r.raw(32) for _ in range(r.uvarint())]
    return Header(author, round_, payload, parents, r.raw(32), r.raw(64))


def encode_certificate(c: Certificate, sorted_keys: List[bytes]) -> bytes:
    out = bytearray(encode_header(c.header, sorted_keys))
    out.append(CERT_SCHEME_INDIVIDUAL)
    out += uvarint(len(c.votes))
    for name, sig in c.votes:
        out += key_ref(sorted_keys, name) + sig
    return bytes(out)


def decode_certificate(data: bytes, sorted_keys: List[bytes]) -> Certificate:
    r = Reader(data)
    header = decode_header(r, sorted_keys)
    if r.u8() != CERT_SCHEME_INDIVIDUAL:
        raise ValueError("certificate is not under the individual scheme")
    votes = [
        (read_key_ref(r, sorted_keys), r.raw(64)) for _ in range(r.uvarint())
    ]
    if not r.done():
        raise ValueError("bytes after the certificate")
    return Certificate(header, votes)


def header_frame(h: Header, sorted_keys: List[bytes]) -> bytes:
    """A primary-to-primary frame carrying ``h``, as a raw (non-v2
    connection) frame: [u32 length][tag][header]."""
    body = bytes([PM_HEADER]) + encode_header(h, sorted_keys)
    return struct.pack("<I", len(body)) + body


# ------------------------------------------------------------ audit segment

_LEN = struct.Struct("<I")


def read_audit(path: str) -> List[Tuple[bytes, bytes]]:
    """(tag, payload) records of one audit segment: 'R' restore blob, 'M'
    commit rule, 'I' a certificate entering the commit rule, 'C' a
    committed certificate's digest.  Stops at a torn tail."""
    with open(path, "rb") as f:
        data = f.read()
    out, pos, n = [], 0, len(data)
    while pos + 5 <= n:
        tag = data[pos:pos + 1]
        if tag not in (b"R", b"I", b"C", b"M"):
            break
        (length,) = _LEN.unpack_from(data, pos + 1)
        end = pos + 5 + length
        if end > n:
            break
        out.append((tag, data[pos + 5:end]))
        pos = end
    return out


def write_audit(path: str, records: List[Tuple[bytes, bytes]]) -> None:
    with open(path, "wb") as f:
        for tag, payload in records:
            f.write(tag + _LEN.pack(len(payload)) + payload)


# ------------------------------------------------------- batches and stores

_REC = struct.Struct("<II")


def iter_store(path: str) -> Iterator[Tuple[bytes, memoryview]]:
    """(key, value) records of a node's append-only store log."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    pos, n = 0, len(data)
    while pos + 8 <= n:
        klen, vlen = _REC.unpack_from(data, pos)
        end = pos + 8 + klen + vlen
        if end > n:
            return
        yield bytes(data[pos + 8:pos + 8 + klen]), data[pos + 8 + klen:end]
        pos = end


def batch_transactions(value) -> List[bytes]:
    """The transactions of a stored batch: [tag 0][u32 n]([u32 len][tx])*."""
    data = bytes(value)
    if data[0] != WORKER_BATCH:
        raise ValueError("stored value is not a batch")
    (n,) = struct.unpack_from("<I", data, 1)
    pos, out = 5, []
    for _ in range(n):
        (length,) = struct.unpack_from("<I", data, pos)
        out.append(data[pos + 4:pos + 4 + length])
        pos += 4 + length
    if pos != len(data):
        raise ValueError("bytes after the batch")
    return out
