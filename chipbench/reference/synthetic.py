"""The reference put in the program's place: a whole run's artifacts made
by the plain reference alone (keys from the seed, signed headers and
votes, PlainTusk's order under the rule asked for, batches with the
clients' sample bytes), in the files and shapes a real run leaves behind.
The tests hold the comparison to it: untouched it has to read all zeros,
and under every control of ``control.py`` it has to fail.
"""

from __future__ import annotations

import os
import random
import struct
from typing import List

import joins
from committee import make_identities

from . import check
from .tusk import PlainTusk
from .wire import (
    Certificate, Header, encode_certificate, genesis, read_audit, sha, write_audit,
)


def make_batch(sample_id: int, tx_size: int, n_tx: int, rng: random.Random) -> bytes:
    txs = [check.sample_tx(sample_id, tx_size)] + [
        b"\x01" + rng.randbytes(8) + bytes(tx_size - 9) for _ in range(n_tx - 1)
    ]
    body = b"".join(struct.pack("<I", len(t)) + t for t in txs)
    return b"\x00" + struct.pack("<I", len(txs)) + body


def make_run(tmpdir: str, seed: int, config: dict, rounds: int = 24,
             tx_size: int = 64, forged: int = 5,
             rule: str = "classic") -> check.Artifacts:
    rng = random.Random(seed)
    ids = make_identities(seed, config)
    alive = config["nodes"] - config["faults"]
    live = ids[:alive]
    verifiers = len(config.get("chip_primaries", [0]))
    keys = sorted(i.name for i in ids)
    gc_depth = config["parameters"]["gc_depth"]

    stores = [[] for _ in live]      # per live node: (digest, batch) records
    due, batch_of, stream = [], {}, []
    previous = [c.digest() for c in genesis(keys)]
    for r in range(1, rounds + 1):
        certs = []
        for c, author in enumerate(live):
            sid = (c << 32) + r
            batch = make_batch(sid, tx_size, 4, rng)
            digest = sha(batch)
            for s in stores:
                s.append((digest, batch))
            due.append(joins.Sample(sid, 100.0 + r * 0.1, 100.0 + r * 0.1))
            batch_of[sid] = digest
            h = Header(author.name, r, {digest: 0}, list(previous), bytes(32), bytes(64))
            h.id = h.computed_id()
            h.signature = author.sign(h.id)
            cert = Certificate(h, [])
            cert.votes = [(v.name, v.sign(cert.digest())) for v in live]
            certs.append(cert)
        stream.extend(certs)
        previous = [c.digest() for c in certs]

    audits = []
    for _ in live:
        tusk = PlainTusk(keys, gc_depth, rule)
        records = [(b"R", b""), (b"M", rule.encode("ascii"))]
        for cert in stream:
            records.append((b"I", encode_certificate(cert, keys)))
            records.extend((b"C", c.digest()) for c in tusk.process_certificate(cert))
        audits.append(records)
    committed = {
        d for tag, payload in audits[0] if tag == b"C"
        for cert in stream if cert.digest() == payload
        for d in cert.header.payload
    }
    # Only what the rule has committed by the end is due: the last rounds
    # of any run are still waiting for their leader.
    due = [s for s in due if batch_of[s.id] in committed]

    indexes = []
    for node, records in enumerate(stores):
        path = os.path.join(tmpdir, f"db-worker-{node}-0", "store.log")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            for digest, batch in records:
                f.write(struct.pack("<II", len(digest), len(batch)) + digest + batch)
        indexes.append({0: check.StoreIndex(path)})
    for node, records in enumerate(audits):
        write_audit(os.path.join(tmpdir, f"audit-primary-{node}.bin"), records)
    return check.Artifacts(
        sorted_keys=keys, gc_depth=gc_depth, tx_size=tx_size,
        audits=[
            read_audit(os.path.join(tmpdir, f"audit-primary-{n}.bin"))
            for n in range(alive)
        ],
        stores=indexes, due=due,
        sample_worker={c: 0 for c in range(alive)}, batch_of=batch_of,
        forged_sent=[forged] * verifiers, invalid_signatures=[forged] * verifiers,
        device=[{"rungs": [128, 512], "dispatched": {"128": 40},
                 "programs_built": 2, "programs_at_ready": 2}
                for _ in range(verifiers)],
        window_dispatches=[40] * verifiers,
    )
