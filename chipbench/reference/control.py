"""The controls: what the comparison has to refuse.

This system states no precision, so a control breaks one guarantee that
the configuration states, in the artifacts of a run, where the program
would have produced the wrong answer:

- ``quorum_short``: a certificate that entered primary 0's commit rule
  is left with 2f votes (guarantee: 2f+1 votes a certificate; with all
  four up a certificate may carry four, so "one vote less" is not short);
- ``forged_vote``: one vote of such a certificate has a bit of its
  signature flipped (the verifier's answer altered where it is produced:
  it accepted a signature that does not verify);
- ``verifier_accepts_all``: ONE of the primaries that were sent
  forgeries counted no invalid signature (a verifier that returns an
  all-true mask; with a chip under every primary the other three still
  reject theirs, and the one has to show);
- ``order_swapped``: two neighbouring commits of the last replica change
  places (guarantee: every replica the same order);
- ``rule_mislabelled``: the last replica's segment declares the other
  commit rule than the one it ran (guarantee: every replica of a run
  commits by one rule, the one its segment declares; on a DAG in which
  both rules decide every leader the sequences are the same and one is
  a prefix of the other, so only the declaration shows);
- ``commit_withheld``: the last replica's commit sequence stops before
  the window's last batches (an answer that never comes);
- ``batch_dropped``: a committed batch that holds a due sample is gone
  from one worker's store (guarantee: a batch is persisted, and
  acknowledged by 2f+1 workers, before its digest is proposed);
- ``sample_altered``: one byte of a due sample differs in one worker's
  stored batch (an answer altered where it is produced).

Each takes the run's Artifacts and returns an altered copy.  ``run.py
--controls`` prints what the comparison reads under each; the
benchmark's own runs never do.
"""

from __future__ import annotations

import dataclasses
import random

from . import check
from .wire import decode_certificate, encode_certificate


def _copy(art: check.Artifacts, **changes) -> check.Artifacts:
    return dataclasses.replace(
        art, audits=[list(r) for r in art.audits],
        store_overrides=dict(art.store_overrides), **changes,
    )


def _pick_insert(art, rng, replica=0):
    spots = [i for i, (tag, _) in enumerate(art.audits[replica]) if tag == b"I"]
    return spots[rng.randrange(len(spots) // 2, len(spots))]


def quorum_short(art, rng):
    out = _copy(art)
    i = _pick_insert(art, rng)
    cert = decode_certificate(out.audits[0][i][1], art.sorted_keys)
    cert.votes = cert.votes[:art.quorum - 1]
    out.audits[0][i] = (b"I", encode_certificate(cert, art.sorted_keys))
    return out


def forged_vote(art, rng):
    out = _copy(art)
    i = _pick_insert(art, rng)
    cert = decode_certificate(out.audits[0][i][1], art.sorted_keys)
    name, sig = cert.votes[0]
    cert.votes[0] = (name, bytes([sig[0] ^ 1]) + sig[1:])
    out.audits[0][i] = (b"I", encode_certificate(cert, art.sorted_keys))
    return out


def verifier_accepts_all(art, rng):
    counted = list(art.invalid_signatures)
    counted[rng.randrange(len(counted))] = 0
    return _copy(art, invalid_signatures=counted)


def order_swapped(art, rng):
    out = _copy(art)
    records = out.audits[-1]
    pairs = [
        i for i in range(len(records) - 1)
        if records[i][0] == b"C" and records[i + 1][0] == b"C"
    ]
    i = pairs[rng.randrange(len(pairs))]
    records[i], records[i + 1] = records[i + 1], records[i]
    return out


def rule_mislabelled(art, rng):
    out = _copy(art)
    ran = check.declared_rule(art.audits[-1])
    other = check.RULES[1 - check.RULES.index(ran)]
    out.audits[-1][1] = (b"M", other.encode("ascii"))
    return out


def commit_withheld(art, rng):
    out = _copy(art)
    records = out.audits[-1]
    commits = [i for i, (tag, _) in enumerate(records) if tag == b"C"]
    cut = commits[len(commits) // 2]
    out.audits[-1] = [r for i, r in enumerate(records) if i < cut or r[0] != b"C"]
    return out


def _due_batch(art, rng):
    held = [s for s in art.due if art.batch_of.get(s.id) is not None]
    s = held[rng.randrange(len(held))]
    node = rng.randrange(len(art.stores))
    return s, node, art.sample_worker[s.client], art.batch_of[s.id]


def batch_dropped(art, rng):
    out = _copy(art)
    _, node, worker, digest = _due_batch(art, rng)
    out.store_overrides[(node, worker, digest)] = None
    return out


def sample_altered(art, rng):
    out = _copy(art)
    s, node, worker, digest = _due_batch(art, rng)
    value = bytearray(art.stored(node, worker, digest))
    at = bytes(value).index(check.sample_tx(s.id, art.tx_size))
    value[at + art.tx_size - 1] ^= 1
    out.store_overrides[(node, worker, digest)] = bytes(value)
    return out


CONTROLS = {
    f.__name__: f
    for f in (quorum_short, forged_vote, verifier_accepts_all, order_swapped,
              rule_mislabelled, commit_withheld, batch_dropped, sample_altered)
}


def report(art: check.Artifacts, seed: int) -> dict:
    """control -> the numbers that pass their limit under it."""
    out = {}
    for name, fn in CONTROLS.items():
        numbers = check.compare(fn(art, random.Random(seed)))
        out[name] = {k: v for k, v in numbers.items() if v > check.LIMITS[k]}
    return out
