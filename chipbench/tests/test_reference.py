"""The comparison that decides `correct`, held to the reference's own
run: sound artifacts read all zeros, every control fails, and the
reference's byte formats are the program's."""

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


@pytest.fixture(scope="module", params=["local-4n-f1", "10n-f3", "local-4n"])
def art(request, tmp_path_factory):
    return synthetic.make_run(
        str(tmp_path_factory.mktemp(request.param)), 2147483659, config(request.param)
    )


def test_sound_run_is_correct(art):
    numbers = check.compare(art)
    assert art.due, "the synthetic run committed nothing"
    assert set(numbers) == set(check.LIMITS)
    assert all(v == 0 for v in numbers.values()), numbers
    assert check.verdict(numbers)


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
    "commit_withheld": "samples_unanswered",
    "batch_dropped": "samples_misread",
    "sample_altered": "samples_misread",
}


def test_each_control_fails_the_number_it_is_about(art):
    for name, caught in control.report(art, 7).items():
        assert EXPECTED[name] in caught, (name, caught)


def test_device_numbers(art):
    import dataclasses

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
