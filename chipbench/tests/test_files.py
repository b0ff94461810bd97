"""Every data file loads, every name it points to exists, and
BENCHMARK.json keeps to the contract's characters and limits."""

import glob
import json
import os
import re

import pytest

import readers

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
REPO = os.path.dirname(CHIPBENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path):
    with open(path) as f:
        return json.load(f)


BENCH = load(os.path.join(REPO, "BENCHMARK.json"))


def files(sub):
    return sorted(glob.glob(os.path.join(CHIPBENCH, sub, "*.json")))


@pytest.mark.parametrize("path", files("workloads"), ids=os.path.basename)
def test_workload_file(path):
    w = load(path)
    assert set(w) == {"config", "rate", "tx_size", "arrival", "clients", "chips",
                      "forged_per_s", "why", "who"}
    cfg = load(os.path.join(CHIPBENCH, "configs", w["config"] + ".json"))
    assert w["clients"] == (cfg["nodes"] - cfg["faults"]) * cfg["workers"]
    assert w["chips"] in (1, 4) and w["arrival"] == "steady"
    assert w["chips"] == len(cfg["chip_primaries"])


@pytest.mark.parametrize("path", files("configs"), ids=os.path.basename)
def test_config_file(path):
    c = load(path)
    assert c["name"] + ".json" == os.path.basename(path)
    assert len(c["dead_key_ranks"]) == c["faults"]
    # Nothing commits without an on-chip verifier: the live primaries
    # that hold no chip are fewer than a quorum.
    alive = c["nodes"] - c["faults"]
    holders = c["chip_primaries"]
    assert holders == sorted(set(holders)) and all(0 <= i < alive for i in holders)
    assert alive >= 2 * c["nodes"] // 3 + 1 > alive - len(holders)
    assert set(c["parameters"]) == {
        "header_size", "max_header_delay", "min_header_delay", "header_linger",
        "gc_depth", "sync_retry_delay", "sync_retry_nodes", "batch_size",
        "max_batch_delay"}
    for key in ("source", "guarantees", "assumed", "reduced", "message_delay",
                "chip_mapping"):
        assert key in c


def test_configurations_keep_apart():
    """Port blocks do not meet (a checkout's shift is the same for all),
    sources differ, and the guarantees are one text."""
    cfgs = [load(p) for p in files("configs")]
    blocks = []
    for c in cfgs:
        n, w = c["nodes"], c["workers"]
        blocks.append(set(range(c["base_port"], c["base_port"] + n * (2 + 3 * w) + n + n * w)))
    for i, a in enumerate(blocks):
        assert max(a) - min(a) < 128
        assert all(not a & b for b in blocks[i + 1:])
    assert len({c["source"] for c in cfgs}) == len(cfgs)
    assert all(c["guarantees"] == cfgs[0]["guarantees"] for c in cfgs)


@pytest.mark.parametrize("path", files("configs"), ids=os.path.basename)
def test_guarantees_name_the_property_and_no_setting_names_a_rule(path):
    """The commit rule is admitted, not set: the guarantee names the
    rules a replica may declare, `protocol_settings` names none (the
    product's default is the program's to change), and a batch handed to
    a primary is promised committed, as `samples_unanswered` holds."""
    c = load(path)
    rule = c["guarantees"]["commit_rule"]
    assert "audit segment declares" in rule and "same on every replica" in rule
    assert "classic" in rule and "lowdepth" in rule and "limit 0" in rule
    settings = c["protocol_settings"].lower()
    assert not any(w in settings for w in ("classic", "lowdepth", "commit rule"))
    assert "handed to a primary is committed" in c["guarantees"]["batch_committed"]
    assert "commit_rule" not in c["parameters"]


def test_the_harness_has_no_key_for_the_commit_rule():
    """No key, flag or variable: a rule left to a setting is what the
    program's next PR is there to remove (ROADMAP, D2)."""
    for name in ("run.py", "committee.py", "device_node.py", "harness.json"):
        with open(os.path.join(CHIPBENCH, name)) as f:
            text = f.read().lower()
        assert "commit-rule" not in text and "narwhal_commit_rule" not in text
    for path in files("workloads") + files("configs"):
        assert not any("rule" in k and k != "commit_rule" for k in load(path))


@pytest.mark.parametrize("path", files("layer_metrics"), ids=os.path.basename)
def test_layer_metric_file(path):
    spec = load(path)
    assert callable(readers.load(spec["kind"]))


def test_benchmark_json():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in names
            names.add(entry["name"])
    for c in BENCH["configs"]:
        cfg = load(os.path.join(REPO, c["file"]))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    cells = set()
    for w in BENCH["workloads"]:
        wl = load(os.path.join(CHIPBENCH, "workloads", w["name"] + ".json"))
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        cells.add(w["name"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert os.path.exists(
            os.path.join(CHIPBENCH, "layer_metrics", m["name"] + ".json"))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_what_a_checkout_keeps_outside_itself(monkeypatch):
    """Ports and tmpfs stores are named from the checkout's path; what a
    killed run of this checkout left in /dev/shm is swept at the next
    start, another checkout's is not touched."""
    import committee

    cfg = load(files("configs")[0])
    here = committee.port_base(cfg)
    assert cfg["base_port"] <= here and here + 128 <= 32768
    monkeypatch.setattr(committee, "REPO", "/some/other/checkout")
    there_tag = committee.checkout_tag()
    assert committee.port_base(cfg) != here
    monkeypatch.undo()
    if not os.access("/dev/shm", os.W_OK):
        pytest.skip("no writable /dev/shm")
    mine = f"/dev/shm/chipbench-{committee.checkout_tag()}-stale-test"
    theirs = f"/dev/shm/chipbench-{there_tag}-stale-test"
    os.makedirs(mine, exist_ok=True)
    os.makedirs(theirs, exist_ok=True)
    try:
        committee.sweep_stale_stores()
        assert not os.path.exists(mine) and os.path.exists(theirs)
    finally:
        os.rmdir(theirs)
