"""A whole run without the look for a chip (``--rehearse``: every primary
on OpenSSL), with the timed path broken underneath: primary 0's verifier
accepts everything.  The run has to end and `correct` has to read false,
by the number that is about the verifier.  A sound rehearsal of the same
shape reads true.  ~60 s: three committees are started."""

import json
import os
import sys

import pytest

import committee
import run

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(capsys, seed, cell="local-4n-f1.steady"):
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", "4", "--trace", "0", "--rehearse", "--rate", "600"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out
    return json.loads(out[-1])


def test_sound_rehearsal_is_correct(capsys):
    line = rehearse(capsys, 41)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 4 * 20 * 3
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"


def break_primary(monkeypatch, which):
    sound = committee.Committee.spawn_primary

    def broken(self, i):
        if i != which:
            return sound(self, i)
        args = self.node_args(i, f"db-primary-{i}", f"metrics-primary-{i}.json",
                              self.primary_metrics_port(i))
        return self.spawn(
            [sys.executable, os.path.join(HERE, "broken_node.py"), *args, "primary"],
            f"primary-{i}.log", env=self.primary_env(i))

    monkeypatch.setattr(committee.Committee, "spawn_primary", broken)


def test_verifier_that_accepts_everything_is_refused(capsys, monkeypatch):
    break_primary(monkeypatch, 0)
    line = rehearse(capsys, 42)
    assert line["correct"] is False
    gap = line["compared"]["verifier_reject_gap"]
    assert gap["value"] > gap["limit"] == 0


def test_one_accepting_verifier_of_four_is_refused(capsys, monkeypatch):
    """All four up, each sent its share of the forgeries (their author
    a live validator): three reject theirs, primary 2 accepts its own,
    and the gap is primary 2's share alone."""
    break_primary(monkeypatch, 2)
    line = rehearse(capsys, 43, "local-4n.steady")
    assert line["correct"] is False
    gap = line["compared"]["verifier_reject_gap"]
    assert gap["value"] > gap["limit"] == 0
    log = open(os.path.join(run.WORKDIR, "local-4n.steady", "primary-1.log")).read()
    assert "successfully booted" in log
