"""A whole run without the look for a chip (``--rehearse``: every primary
on OpenSSL), with the timed path broken underneath: primary 0's verifier
accepts everything.  The run has to end and `correct` has to read false,
by the number that is about the verifier.  A sound rehearsal of the same
shape reads true.  ~40 s: two committees are started."""

import json
import os
import sys

import pytest

import committee
import run

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(capsys, seed):
    rc = run.main(["--workload", "local-4n-f1.steady", "--seed", str(seed),
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


def test_verifier_that_accepts_everything_is_refused(capsys, monkeypatch):
    sound = committee.Committee.spawn_primary

    def broken(self, i):
        if i != 0:
            return sound(self, i)
        env = dict(self.env, NARWHAL_CONSENSUS_AUDIT=self.path("audit-primary-0.bin"))
        args = self.node_args(0, "db-primary-0", "metrics-primary-0.json",
                              self.primary_metrics_port(0))
        return self.spawn(
            [sys.executable, os.path.join(HERE, "broken_node.py"), *args, "primary"],
            "primary-0.log", env=env)

    monkeypatch.setattr(committee.Committee, "spawn_primary", broken)
    line = rehearse(capsys, 42)
    assert line["correct"] is False
    gap = line["compared"]["verifier_reject_gap"]
    assert gap["value"] > gap["limit"] == 0
