"""The comparison that decides `correct`, held to the reference's own
run: sound artifacts read all zeros, every control fails, and the
reference's byte formats are the program's."""

import dataclasses
import json
import os
import random

import pytest

from reference import check, control, synthetic, wire

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def config(name):
    """A configuration file; ``10n-f3`` is the committed one made wider
    (7 of 10 live, one leader in five dead), so that the reference is
    held at an n where odd ranks lead too.  No cell runs it (PERF.md,
    Open questions)."""
    file = "local-4n-f1" if name == "10n-f3" else name
    with open(os.path.join(CONFIGS, file + ".json")) as f:
        cfg = json.load(f)
    if name == "10n-f3":
        cfg.update(nodes=10, faults=3, dead_key_ranks=[4, 7, 9])
    return cfg


@pytest.fixture(scope="module", params=[
    (name, rule) for name in ("local-4n-f1", "10n-f3", "local-4n")
    for rule in check.RULES
], ids="-".join)
def art(request, tmp_path_factory):
    name, rule = request.param
    return synthetic.make_run(
        str(tmp_path_factory.mktemp(f"{name}-{rule}")), 2147483659, config(name),
        rule=rule,
    )


def test_sound_run_is_correct(art, request):
    numbers = check.compare(art)
    assert art.due, "the synthetic run committed nothing"
    assert set(numbers) == set(check.LIMITS)
    assert all(v == 0 for v in numbers.values()), numbers
    assert check.verdict(numbers)
    assert check.commit_rule(art) in request.node.callspec.id


@pytest.mark.parametrize("name", sorted(control.CONTROLS))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_refused(art, name, seed):
    numbers = check.compare(control.CONTROLS[name](art, random.Random(seed)))
    assert not check.verdict(numbers), (name, numbers)


EXPECTED = {
    "quorum_short": "certificates_invalid",
    "forged_vote": "certificates_invalid",
    "verifier_accepts_all": "verifier_reject_gap",
    "order_swapped": "replica_order_mismatches",
    "rule_mislabelled": "replica_order_mismatches",
    "commit_withheld": "samples_unanswered",
    "batch_dropped": "samples_misread",
    "sample_altered": "samples_misread",
}


def test_there_are_eight_controls():
    assert set(control.CONTROLS) == set(EXPECTED) and len(EXPECTED) == 8


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_control_fails_the_number_it_is_about_and_no_other(art, name):
    numbers = check.compare(control.CONTROLS[name](art, random.Random(7)))
    assert {k for k, v in numbers.items() if v > check.LIMITS[k]} == {EXPECTED[name]}


def with_marker(art, replica, *markers):
    """``art`` with replica's 'M' record replaced by ``markers``."""
    audits = [list(r) for r in art.audits]
    audits[replica][1:2] = [(b"M", m) for m in markers]
    return dataclasses.replace(art, audits=audits)


def test_replicas_that_declare_two_rules_are_refused(art):
    """One count for every replica that declares another rule than
    replica 0, even where its sequence is still a prefix."""
    mixed = control.rule_mislabelled(art, None)
    other = mixed.audits[-1][1][1]
    assert check.commit_rule(mixed) == "classic+lowdepth"
    numbers = check.compare(mixed)
    assert numbers["replica_order_mismatches"] >= 1
    assert all(v == 0 for k, v in numbers.items() if k != "replica_order_mismatches")
    # Replica 0 the odd one out: every other replica differs from it.
    first = check.compare(with_marker(art, 0, other))
    assert first["replica_order_mismatches"] >= len(art.audits) - 1


@pytest.mark.parametrize("markers", [
    [b"multileader"], [b"Classic"], [b""], [b"\xff\xfe"], [],
    [b"classic", b"classic"], [b"classic", b"lowdepth"],
], ids=["multileader", "case", "empty", "not-ascii", "missing", "twice", "both"])
def test_a_segment_that_declares_no_one_plain_rule_is_broken(art, markers):
    numbers = check.compare(with_marker(art, 0, *markers))
    assert numbers["replica_order_mismatches"] >= 1
    assert not check.verdict(numbers)


def test_a_marker_after_the_first_insert_is_broken(art):
    audits = [list(r) for r in art.audits]
    marker = audits[0].pop(1)
    first_insert = next(i for i, (tag, _) in enumerate(audits[0]) if tag == b"I")
    audits[0].insert(first_insert + 1, marker)
    numbers = check.compare(dataclasses.replace(art, audits=audits))
    assert numbers["replica_order_mismatches"] >= 1


def test_device_numbers(art):
    rest = art.device[1:]
    late = dataclasses.replace(
        art, device=[dict(art.device[0], programs_built=3)] + rest)
    assert check.compare(late)["device_off_ladder"] == 1
    off = dataclasses.replace(
        art, device=[dict(art.device[0], dispatched={"128": 3, "2048": 2})] + rest)
    assert check.compare(off)["device_off_ladder"] == 2
    idle = dataclasses.replace(
        art, window_dispatches=[0] * len(art.window_dispatches))
    assert check.compare(idle)["window_without_dispatch"] == len(art.device)


def test_dead_leader_schedule_is_the_configurations():
    """Every seed gives the dead validators the ranks the file names."""
    from committee import make_identities

    for name in ("local-4n-f1", "10n-f3", "local-4n"):
        cfg = config(name)
        for seed in (0, 5, 2**31 + 11):
            ids = make_identities(seed, cfg)
            dead = ids[cfg["nodes"] - cfg["faults"]:]
            assert sorted(i.rank for i in dead) == sorted(cfg["dead_key_ranks"])
            assert ids[0].rank == 0


def test_formats_are_the_programs(art):
    """Certificate and header bytes, ids and digests against the
    program's own codec (skipped where the program is not importable)."""
    pytest.importorskip("narwhal_tpu")
    from narwhal_tpu.config import Authority, Committee, PrimaryAddresses
    from narwhal_tpu.crypto import PublicKey
    from narwhal_tpu.messages import set_wire_committee
    from narwhal_tpu.primary.messages import (
        Certificate, decode_primary_message, genesis)

    committee = Committee({
        PublicKey(k): Authority(1, PrimaryAddresses("a:1", "a:2"), {})
        for k in art.sorted_keys
    })
    set_wire_committee(committee)
    assert {bytes(c.digest()) for c in genesis(committee)} == {
        c.digest() for c in wire.genesis(art.sorted_keys)}
    seen = 0
    for tag, payload in art.audits[0]:
        if tag != b"I":
            continue
        theirs = Certificate.deserialize(payload)
        ours = wire.decode_certificate(payload, art.sorted_keys)
        assert bytes(theirs.digest()) == ours.digest()
        assert bytes(theirs.header.compute_digest()) == ours.header.computed_id()
        assert theirs.serialize() == wire.encode_certificate(ours, art.sorted_keys)
        theirs.verify(committee)
        kind, header = decode_primary_message(
            wire.header_frame(ours.header, art.sorted_keys)[4:])
        assert kind == "header" and bytes(header.id) == ours.header.id
        seen += 1
    assert seen


def test_forgeries_do_not_verify():
    from committee import make_identities
    from forger import KINDS, forged_header, openssl_verify

    ids = make_identities(9, config("local-4n-f1"))
    rng = random.Random(9)
    for k, kind in enumerate(KINDS * 2):
        h = forged_header(k, kind, ids[3], ids[0], rng)
        assert h.id == h.computed_id()
        assert not openssl_verify(h.id, h.author, h.signature)
