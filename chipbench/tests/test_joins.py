"""Latency and rate arithmetic against a hand-made log."""

import joins
from joins import Sample

T0, SECONDS, TX = 1000.0, 10.0, 512


def world(stall=None):
    """One sample and one 512,000 B batch every 0.5 s from 998 s to
    1012 s, each committed 0.6 s after it was due; with ``stall`` =
    (from, to) nothing commits in between and everything due then
    commits at ``to`` + 0.6."""
    samples, batch_of, commit, size = [], {}, {}, {}
    for k in range(28):
        due = 998.0 + 0.5 * k
        d = bytes([k]) * 32
        samples.append(Sample(k, due, due + 0.001))
        batch_of[k] = d
        size[d] = 512_000
        at = due + 0.6
        if stall and stall[0] <= at < stall[1]:
            at = stall[1] + 0.6
        commit[d] = at
    return samples, batch_of, commit, size


def numbers(samples, batch_of, commit, size):
    due = joins.due_in_window(samples, T0, SECONDS)
    lat, failed = joins.latencies_ms(due, batch_of, commit)
    rate = joins.committed_tx_per_s(size, commit, T0, SECONDS, TX)
    return due, lat, failed, rate


def test_steady_window():
    due, lat, failed, rate = numbers(*world())
    assert len(due) == 20 and failed == 0
    assert abs(joins.percentile(lat, 50) - 600.0) < 1e-6
    assert abs(rate - 20 * 1000 / SECONDS) < 1e-6  # 20 batches of 1,000 tx


def test_a_stall_reads_slower_and_later():
    _, lat0, _, rate0 = numbers(*world())
    _, lat1, failed, rate1 = numbers(*world(stall=(1007.0, 1011.0)))
    assert failed == 0
    assert rate1 < rate0  # what commits after the window is not the window's
    assert joins.percentile(lat1, 95) > joins.percentile(lat0, 95) + 1000.0
    assert joins.percentile(lat1, 50) >= joins.percentile(lat0, 50)


def test_due_and_never_committed_is_failed():
    samples, batch_of, commit, size = world()
    del commit[batch_of[10]]      # its batch never commits
    batch_of[11] = None           # no batch holds it
    due, lat, failed, _ = numbers(samples, batch_of, commit, size)
    assert failed == 2 and len(lat) == len(due) - 2


def test_latency_runs_from_due_not_from_sent():
    samples, batch_of, commit, size = world()
    late = [Sample(s.id, s.due, s.due + 0.4) for s in samples]
    _, lat, _, _ = numbers(late, batch_of, commit, size)
    assert abs(joins.percentile(lat, 50) - 600.0) < 1e-6


def test_percentile_is_nearest_rank():
    assert joins.percentile([1, 2, 3, 4], 50) == 2
    assert joins.percentile(list(range(1, 101)), 95) == 95
    assert joins.percentile([7], 95) == 7
