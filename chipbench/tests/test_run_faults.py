"""A whole run without the look for a chip (``--rehearse``: every primary
on OpenSSL), with the timed path broken underneath: primary 0's verifier
accepts everything.  The run has to end and `correct` has to read false,
by the number that is about the verifier.  A sound rehearsal of the same
shape reads true.  So does one whose every replica runs, and declares, the
direct commit rule; one replica on another rule than its peers reads
false.  The harness has no key for the rule: these tests put the program's
own variable into the children's environment underneath it.  ~100 s: five
committees are started."""

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
    assert line["commit_rule"] == "classic"
    assert list(line)[-1] == "compared"


def commit_rule_of(monkeypatch, rule, primaries=None):
    """Primaries ``primaries`` (all where None) run the program's
    ``rule``, through the one variable the program reads it from."""
    sound = committee.Committee.primary_env

    def env(self, i):
        out = sound(self, i)
        if primaries is None or i in primaries:
            out["NARWHAL_COMMIT_RULE"] = rule
        return out

    monkeypatch.setattr(committee.Committee, "primary_env", env)


@pytest.mark.parametrize("cell", ["local-4n-f1.steady", "local-4n.steady"])
def test_a_committee_on_the_direct_rule_is_correct(capsys, monkeypatch, cell):
    commit_rule_of(monkeypatch, "lowdepth")
    line = rehearse(capsys, 44, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["commit_rule"] == "lowdepth"


def test_one_replica_on_another_rule_is_refused(capsys, monkeypatch):
    """The last replica decides leaders directly, its peers by classic
    Tusk: whether or not its sequence has parted from theirs by the end,
    it declares another rule than replica 0."""
    commit_rule_of(monkeypatch, "lowdepth", primaries=[2])
    line = rehearse(capsys, 45)
    assert line["correct"] is False
    assert line["commit_rule"] == "classic+lowdepth"
    wrong = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert wrong == {"replica_order_mismatches"}


def test_a_rule_the_reference_does_not_have_is_refused(capsys, monkeypatch):
    commit_rule_of(monkeypatch, "multileader")
    line = rehearse(capsys, 46)
    assert line["correct"] is False and "undeclared" in line["commit_rule"]
    mism = line["compared"]["replica_order_mismatches"]
    assert mism["value"] >= 3 and mism["limit"] == 0


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
