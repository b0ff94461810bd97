"""Four validators of four workers each with one down, small, on the CPU:
upstream's scale-out axis (the number of workers a validator runs), as the
benchmark's cell `local-4n-w4-f1.steady` runs it on the chip's host.  The
validator of sorted-key rank 3 never takes part; with n = 4 only even
ranks lead, so no leader is dead, and the three live validators are
exactly the quorum.

A simulated committee at the source's 100 ms timers, held against the
benchmark's plain reference (``chipbench/reference/``, loaded by path):

- safe and live; every replica's audit segment declares `lowdepth` and
  replays to one sequence;
- every batch that one of the twelve live workers sealed and had
  acknowledged by a quorum is committed, in a header of its own
  validator, under the id of the worker that sealed it; committed
  payloads carry all four worker ids of every live validator;
- the leader counters account for every even round below the frontier,
  and with no dead leader nearly none is skipped;
- the two histograms the scale-out path is read by,
  `primary.header_digests` and `worker.batch_fill`, observe once per
  header and once per seal.
"""

import asyncio
import importlib
import logging
import os
import sys

import pytest

from narwhal_tpu import metrics
from narwhal_tpu.faults.spec import parse_scenario
from narwhal_tpu.sim import run_sim_scenario
from narwhal_tpu.sim.committee import sim_keypairs
from narwhal_tpu.worker import worker as worker_module
from narwhal_tpu.worker.batch_maker import FILL_BUCKETS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(REPO, "chipbench")


def load_reference():
    """chipbench/ is no package and imports nothing of the program: its
    ``reference`` is found through sys.path for the length of the import
    only (as tests/test_committee_width.py loads it)."""
    sys.path.insert(0, CHIPBENCH)
    try:
        return (importlib.import_module("reference.check"),
                importlib.import_module("reference.wire"))
    finally:
        sys.path.remove(CHIPBENCH)


check, wire = load_reference()

N, WORKERS, DEAD_RANKS = 4, 4, [3]
QUORUM = 2 * N // 3 + 1
# The Quick Start's timers, batch size and GC depth.
PARAMETERS = {"max_header_delay": 100, "max_batch_delay": 100,
              "batch_size": 500_000, "gc_depth": 50}
# A batch acknowledged this long before the last one has had twenty
# rounds to commit; the ones after it may still be in flight at the end.
SETTLED_S = 2.0


def test_no_leader_is_dead_and_the_live_are_the_quorum():
    assert QUORUM == 3 == N - len(DEAD_RANKS)
    # Leader of even round r is rank r mod n: with n = 4, ranks 0 and 2.
    assert not {r % N for r in range(0, 2 * N, 2)} & set(DEAD_RANKS)


def recording_classes(acknowledged):
    """A QuorumWaiter and a Processor that note each own batch as it is
    stored after its quorum: (validator, worker id, digest, virtual time).
    The quorum waiter knows the validator, the processor of own batches
    the worker id; the queue between them joins the two."""
    owners = {}

    class QuorumWaiter(worker_module.QuorumWaiter):
        def __init__(self, name, committee, in_queue, out_queue):
            super().__init__(name, committee, in_queue, out_queue)
            owners[id(out_queue)] = name

    class Processor(worker_module.Processor):
        def __init__(self, worker_id, store, in_queue, out_queue, own_digests):
            if own_digests:
                store = _NotingStore(
                    store, owners[id(in_queue)], worker_id, acknowledged)
            super().__init__(worker_id, store, in_queue, out_queue, own_digests)

    return QuorumWaiter, Processor


class _NotingStore:
    def __init__(self, store, name, worker_id, notes):
        self._store, self._name, self._wid, self._notes = (
            store, name, worker_id, notes)

    def write(self, digest, value):
        self._notes.append((bytes(self._name), self._wid, bytes(digest),
                            asyncio.get_running_loop().time()))
        return self._store.write(digest, value)

    def __getattr__(self, attr):
        return getattr(self._store, attr)


@pytest.fixture(scope="module")
def simulated(tmp_path_factory):
    """Four validators of four workers on a seeded schedule; the one of
    rank 3 is stopped at time 0 and never returns."""
    logging.disable(logging.WARNING)
    obj = {
        "name": "sim_t_local_4n_w4_f1", "nodes": N, "workers": WORKERS,
        "rate": 600, "tx_size": 256, "duration": 12, "seed": 39,
        "parameters": dict(PARAMETERS),
    }
    names = [kp.name for kp in sim_keypairs(parse_scenario(obj, env={}))]
    ranked = sorted(names)
    dead = sorted(names.index(ranked[r]) for r in DEAD_RANKS)
    obj["crash"] = [{"node": i, "at_s": 0} for i in dead]
    workdir = str(tmp_path_factory.mktemp("sim"))
    acknowledged = []
    try:
        with pytest.MonkeyPatch.context() as patch:
            qw, proc = recording_classes(acknowledged)
            patch.setattr(worker_module, "QuorumWaiter", qw)
            patch.setattr(worker_module, "Processor", proc)
            art = run_sim_scenario(parse_scenario(obj, env={}), 39, workdir)
    finally:
        logging.disable(logging.NOTSET)
    reg = metrics.registry()
    live = [i for i in range(N) if i not in dead]
    audits = [
        wire.read_audit(os.path.join(workdir, f"audit-primary-{i}.seg0.bin"))
        for i in live
    ]
    return {
        "art": art, "ranked": ranked, "audits": audits,
        "live_keys": {bytes(names[i]) for i in live},
        "acknowledged": acknowledged,
        "counters": {k: c.value for k, c in reg.counters.items()},
        "histograms": {
            k: reg.histograms[k]
            for k in ("primary.header_digests", "worker.batch_fill")
        },
    }


def plain_artifacts(sim) -> "check.Artifacts":
    return check.Artifacts(
        sorted_keys=sim["ranked"], gc_depth=PARAMETERS["gc_depth"],
        tx_size=256, audits=sim["audits"], stores=[], due=[], sample_worker={},
        batch_of={}, forged_sent=[], invalid_signatures=[], device=None,
        window_dispatches=None,
    )


def committed_payload(sim, replica=0):
    """(author, batch digest) -> worker id over the certificates that
    replica's audit segment records as committed."""
    records = sim["audits"][replica]
    certs = {}
    for tag, payload in records[2:]:
        if tag == b"I":
            cert = wire.decode_certificate(payload, sim["ranked"])
            certs[cert.digest()] = cert
    out = {}
    for tag, payload in records[2:]:
        if tag == b"C" and payload in certs:
            header = certs[payload].header
            for digest, wid in header.payload.items():
                out[(header.author, digest)] = wid
    return out


def test_sim_committee_is_safe_and_live_with_one_down(simulated):
    verdicts = simulated["art"]["verdicts"]
    assert verdicts["safety"]["ok"], verdicts["safety"]
    assert verdicts["liveness"]["ok"], verdicts["liveness"]
    assert len(verdicts["liveness"]["nodes"]) == QUORUM


def test_every_replica_declares_lowdepth_and_replays_to_one_sequence(simulated):
    art = plain_artifacts(simulated)
    assert check.commit_rule(art) == "lowdepth"
    mismatches, _, sequences, _ = check.replay(art)
    assert mismatches == 0
    assert len(sequences) == QUORUM and min(map(len, sequences)) > 100
    longest = max(sequences, key=len)
    assert all(s == longest[:len(s)] for s in sequences)


def test_every_acknowledged_batch_is_committed_under_the_worker_that_sealed_it(
    simulated,
):
    acknowledged = simulated["acknowledged"]
    sealers = {(name, wid) for name, wid, _, _ in acknowledged}
    assert sealers == {(k, w) for k in simulated["live_keys"] for w in range(WORKERS)}
    last = max(t for _, _, _, t in acknowledged)
    settled = [(name, wid, d) for name, wid, d, t in acknowledged
               if t < last - SETTLED_S]
    assert len(settled) > 100
    committed = committed_payload(simulated)
    assert all(committed.get((name, d)) == wid for name, wid, d in settled)


def test_committed_payloads_carry_every_worker_of_every_live_validator(simulated):
    for replica in range(QUORUM):
        workers_of = {}
        for (author, _), wid in committed_payload(simulated, replica).items():
            workers_of.setdefault(author, set()).add(wid)
        assert workers_of == {
            k: set(range(WORKERS)) for k in simulated["live_keys"]}


def test_leader_counters_account_for_every_even_round_with_none_dead(simulated):
    """The registry is the committee's in a simulation: the three roads
    sum to the live replicas' frontiers as the plain rule reads them.
    No leader is dead, so a skip can come only at the boot's edge."""
    frontiers = []
    for records in simulated["audits"]:
        tusk = check.PlainTusk(
            simulated["ranked"], PARAMETERS["gc_depth"], "lowdepth"
        )
        for tag, payload in records[2:]:
            if tag == b"I":
                tusk.process_certificate(
                    wire.decode_certificate(payload, simulated["ranked"])
                )
        frontiers.append(tusk.last_committed_round // 2)
    got = {road: simulated["counters"][f"consensus.leaders_{road}"]
           for road in ("direct", "indirect", "skipped")}
    assert sum(got.values()) == sum(frontiers)
    assert got["skipped"] <= 1 and got["direct"] > 10 * QUORUM


def test_scale_out_histograms_observe_each_header_and_each_seal(simulated):
    counters, hists = simulated["counters"], simulated["histograms"]
    digests = hists["primary.header_digests"]
    assert digests.count == counters["primary.headers_proposed"] > 0
    assert digests.sum == counters["primary.payload_digests"] > 0
    fill = hists["worker.batch_fill"]
    assert fill.count == counters["worker.batches_sealed"] > 0
    # Within (0, 1.05]: nothing above the last bound.
    assert fill.bounds == FILL_BUCKETS and fill.counts[-1] == 0
    assert 0 < fill.sum / fill.count <= 1.05
