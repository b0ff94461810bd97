"""Where the chips are: the configuration's ``chip_primaries`` decide
which primaries run on a device, what each one's process is shown, which
are sent forgeries, and how their reports make the run's ``device``.
None of this needs a chip."""

import json
import os
import random

import pytest

import committee
import run
from forger import KINDS, forged_header, forged_names, openssl_verify
from reference import check, control, synthetic

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
CONFIGS = ("local-4n-f1", "local-4n")
HARNESS = {"trace_seconds": 0.1}


def config(name):
    with open(os.path.join(CHIPBENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture
def make_committee(tmp_path, monkeypatch):
    """A Committee whose processes are recorded, not started."""
    made = []

    def make(name, backend="tpu", traced=False):
        com = committee.Committee(
            str(tmp_path / name), 7, config(name), HARNESS, backend, traced)
        com.spawned = []
        monkeypatch.setattr(
            com, "spawn",
            lambda cmd, logname, env=None, chip=False: com.spawned.append(
                (logname, cmd, env or com.env, chip)))
        monkeypatch.setattr(com, "wait_for_boot", lambda names, deadline, procs:
                            com.spawned.append(("booted", sorted(names))))
        made.append(com)
        return com

    yield make
    for com in made:
        com.remove_stores()


def test_one_chip_holder_gets_todays_environment(make_committee):
    com = make_committee("local-4n-f1")
    assert com.chip_nodes == [0] and com.forged_nodes == [0]
    for i in range(com.alive):
        env = com.primary_env(i)
        assert set(env) - set(com.env) == {"NARWHAL_CONSENSUS_AUDIT"}
        assert not [k for k in env if k.startswith("TPU_")
                    and k not in os.environ]


def test_each_of_several_chip_holders_is_shown_its_own_chip(make_committee):
    com = make_committee("local-4n")
    assert com.chip_nodes == [0, 1, 2, 3]
    envs = [com.primary_env(i) for i in range(4)]
    for k, env in enumerate(envs):
        assert env["TPU_VISIBLE_CHIPS"] == str(k)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert {v: env[v] for v in committee.chip_env(k)} == committee.chip_env(k)
    assert len({env["TPU_PROCESS_PORT"] for env in envs}) == 4


@pytest.mark.parametrize("backend,holders", [("tpu", [0, 1, 2, 3]), ("jax", [0]),
                                             (None, [])])
def test_who_runs_on_a_device(make_committee, backend, holders):
    """On the chip the file's primaries; a jax-cpu rehearsal primary 0
    alone, whatever the file says; an OpenSSL rehearsal nobody.  The
    forgeries go to the file's chip holders in every one of them."""
    com = make_committee("local-4n", backend=backend, traced=True)
    assert com.chip_nodes == holders and com.forged_nodes == [0, 1, 2, 3]
    com.start_nodes()
    primaries = [s for s in com.spawned if s[0].startswith("primary-")]
    assert len(primaries) == 4
    for logname, cmd, env, chip in primaries:
        i = int(logname[len("primary-"):-len(".log")])
        assert chip == (i in holders)
        assert (os.path.basename(cmd[1]) == "device_node.py") == (i in holders)
        if i in holders:
            assert cmd[cmd.index("--report") + 1] == com.path(f"device-node-{i}.json")
            # Only primary 0's chip is traced.
            assert bool(cmd[cmd.index("--trace-dir") + 1]) == (i == 0)
            assert cmd[cmd.index("--crypto-backend") + 1] == backend
            assert ("TPU_VISIBLE_CHIPS" in env) == (len(holders) > 1)


@pytest.mark.parametrize("name", CONFIGS)
def test_device_nodes_boot_before_anything_else_starts(make_committee, name):
    com = make_committee(name)
    com.start_nodes()
    order = [s[0] if s[0] != "booted" else tuple(s[1]) for s in com.spawned]
    first = [f"primary-{i}.log" for i in com.chip_nodes]
    assert order[:len(first)] == first
    assert order[len(first)] == tuple(first)
    rest = order[len(first) + 1:-1]
    assert sorted(rest) == sorted(
        [f"primary-{i}.log" for i in range(com.alive) if i not in com.chip_nodes]
        + [f"worker-{i}-0.log" for i in range(com.alive)])
    assert order[-1] == tuple(sorted(rest))


def test_chip_primaries_must_be_live(tmp_path):
    cfg = dict(config("local-4n-f1"), chip_primaries=[3])
    with pytest.raises(ValueError):
        committee.Committee(str(tmp_path / "w"), 1, cfg, HARNESS, "tpu", False)


# ------------------------------------------------------- the run's `device`


class FakeCommittee:
    def __init__(self, root, chip_nodes):
        self.root, self.chip_nodes = str(root), chip_nodes

    def path(self, name):
        return os.path.join(self.root, name)


def reports(tmp_path, rows):
    com = FakeCommittee(tmp_path, sorted(rows))
    details = {}
    for i, (count, peak, seen) in rows.items():
        with open(com.path(f"device-node-{i}.json"), "w") as f:
            json.dump({"platform": "tpu", "kind": "TPU v5 lite", "count": count,
                       "memory_peak_bytes": peak}, f)
        details[i] = {"platform": "tpu", "kind": "TPU v5 lite", "count": seen}
    return com, {"device_details": details}


def test_device_is_primary_0s_with_the_chips_summed(tmp_path):
    com, facts = reports(tmp_path, {0: (1, 15_000_000, 1), 1: (1, 15_200_000, 1),
                                    2: (1, 15_100_000, 1), 3: (1, 14_900_000, 1)})
    assert run.device_of(com, facts) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4,
        "memory_peak_bytes": 15_200_000}


def test_a_single_chip_holder_reports_what_it_saw(tmp_path):
    """Alone it is given no chip of its own: on a four-chip host it sees
    four, as it did before this key existed."""
    com, facts = reports(tmp_path, {0: (4, 15_000_000, 4)})
    assert run.device_of(com, facts)["count"] == 4


@pytest.mark.parametrize("rows,why", [
    ({0: (1, 1, 1), 1: (1, 1, 4)}, "verifier ran on"),
    ({0: (4, 1, 4), 1: (4, 1, 4)}, "more than its own chip"),
])
def test_device_reports_that_do_not_fit_are_no_result(tmp_path, rows, why):
    com, facts = reports(tmp_path, rows)
    with pytest.raises(committee.RunFailure, match=why):
        run.device_of(com, facts)


def test_a_cell_takes_as_many_chips_as_its_configuration_names(monkeypatch, capsys):
    sound = run.load_json

    def load(*parts):
        got = sound(*parts)
        return dict(got, chips=1) if parts[0] == "workloads" else got

    monkeypatch.setattr(run, "load_json", load)
    rc = run.main(["--workload", "local-4n.steady", "--seed", "1", "--seconds", "1",
                   "--rehearse"])
    assert rc == 2 and not capsys.readouterr().out


# ------------------------------------------------------------- the forgers


@pytest.mark.parametrize("name", CONFIGS)
def test_forgeries_fall_at_the_signature_whoever_is_down(name):
    """With nobody down the author is the target's neighbour, never the
    target; every kind still fails OpenSSL and is well formed."""
    cfg = config(name)
    alive = cfg["nodes"] - cfg["faults"]
    for seed in (3, 2**31 + 5):
        ids = committee.make_identities(seed, cfg)
        rng = random.Random(seed)
        for target in cfg["chip_primaries"]:
            author, other = forged_names(ids, alive, target)
            assert author is not ids[target] and author is not other
            if alive < len(ids):
                assert author in ids[alive:]
            for k, kind in enumerate(KINDS):
                h = forged_header(k, kind, author, other, rng)
                assert h.id == h.computed_id() and h.author == author.name
                assert not openssl_verify(h.id, h.author, h.signature)


def test_one_verifier_of_four_that_accepts_everything_is_refused(tmp_path):
    art = synthetic.make_run(str(tmp_path), 11, config("local-4n"))
    assert art.forged_sent == [5] * 4 and len(art.device) == 4
    assert check.verdict(check.compare(art))
    for seed in range(6):
        broken = control.verifier_accepts_all(art, random.Random(seed))
        assert sorted(broken.invalid_signatures) == [0, 5, 5, 5]
        numbers = check.compare(broken)
        assert numbers["verifier_reject_gap"] == 5 and not check.verdict(numbers)


def test_gaps_of_two_verifiers_do_not_cancel(tmp_path):
    """One counts a forgery too many (it refused an honest message), one
    a forgery too few: summed counts would read 0."""
    import dataclasses

    art = synthetic.make_run(str(tmp_path), 12, config("local-4n"))
    skewed = dataclasses.replace(art, invalid_signatures=[6, 4, 5, 5])
    assert check.compare(skewed)["verifier_reject_gap"] == 2


def test_device_numbers_are_summed_over_the_chips(tmp_path):
    import dataclasses

    art = synthetic.make_run(str(tmp_path), 13, config("local-4n"))
    late = [dict(d) for d in art.device]
    late[2]["programs_built"] = 3
    late[3]["dispatched"] = {"128": 3, "2048": 2}
    assert check.compare(
        dataclasses.replace(art, device=late))["device_off_ladder"] == 3
    idle = dataclasses.replace(art, window_dispatches=[40, 0, 40, 0])
    assert check.compare(idle)["window_without_dispatch"] == 2
