"""Fault-injection subsystem unit tests: scenario spec validation and
seeding, the netem shaping/partition shims at the network seam, the
jittered env-tunable reconnect backoff (ISSUE 6 satellite), the new
Byzantine-detection health rules, and the audit-replay safety checker's
ability to actually CATCH violations (a checker that can't fail is not a
verdict)."""

import asyncio
import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from narwhal_tpu import metrics  # noqa: E402
from narwhal_tpu.consensus.replay import (  # noqa: E402
    AuditWriter,
    cross_node_prefix,
    read_audit,
    replay_segments,
)
from narwhal_tpu.faults import netem  # noqa: E402
from narwhal_tpu.faults.spec import (  # noqa: E402
    SpecError,
    parse_scenario,
)
from narwhal_tpu.metrics import HealthMonitor, Registry, default_rules  # noqa: E402
from narwhal_tpu.network.framing import read_frame, write_frame  # noqa: E402
from narwhal_tpu.network.reliable_sender import (  # noqa: E402
    backoff_cap,
    next_backoff,
)
from tests.common import committee, keys  # noqa: E402
from tests.test_consensus import (  # noqa: E402
    feed,
    genesis_digests,
    make_certificates,
    mock_certificate,
    sorted_names,
)


# -- scenario spec ------------------------------------------------------------

def _minimal(**overrides):
    base = {"name": "t", "byzantine": [{"node": 0, "behaviors": ["equivocate"]}]}
    base.update(overrides)
    return base


def test_spec_parses_and_env_seed_overrides():
    s = parse_scenario(_minimal(seed=5), env={})
    assert s.seed == 5 and s.byzantine_nodes() == [0]
    assert s.honest_nodes() == [1, 2, 3]
    s2 = parse_scenario(_minimal(seed=5), env={"NARWHAL_FAULT_SEED": "99"})
    assert s2.seed == 99
    # A malformed override must fail LOUD, not silently fall back to the
    # spec's own seed: the operator asked to replay a specific draw.
    with pytest.raises(SpecError):
        parse_scenario(_minimal(seed=5), env={"NARWHAL_FAULT_SEED": "0x2A"})


def test_spec_rejects_unknown_fields_and_behaviors():
    with pytest.raises(SpecError):
        parse_scenario(_minimal(bogus=1), env={})
    with pytest.raises(SpecError):
        parse_scenario(
            {"name": "t", "byzantine": [{"node": 0, "behaviors": ["fly"]}]},
            env={},
        )


def test_spec_enforces_bft_fault_bound():
    # 2 byzantine of 4 exceeds f=1.
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "byzantine": [
                    {"node": 0, "behaviors": ["equivocate"]},
                    {"node": 1, "behaviors": ["wrong_key"]},
                ],
            },
            env={},
        )
    # byzantine + crashed together exceed f=1 too.
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "byzantine": [{"node": 0, "behaviors": ["equivocate"]}],
                "crash": [{"node": 1, "at_s": 5}],
            },
            env={},
        )
    # An oversized partition group is rejected.
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "wan": {"partitions": [{"group": [0, 1], "from_s": 1}]},
            },
            env={},
        )
    # Fault planes compose against the SAME f: a within-bound byzantine
    # node plus a within-bound partitioned node is 2 faulty of 4.
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "byzantine": [{"node": 0, "behaviors": ["equivocate"]}],
                "wan": {"partitions": [{"group": [1], "from_s": 1}]},
            },
            env={},
        )


def test_spec_rejects_fault_offsets_outside_duration():
    """A timed fault landing at/after `duration` would silently stretch
    the run and push the liveness settle point outside the measured
    window — the one authoring error the spec used to let through."""
    with pytest.raises(SpecError):
        parse_scenario(
            {"name": "t", "duration": 20, "crash": [{"node": 0, "at_s": 20}]},
            env={},
        )
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "duration": 30,
                "crash": [{"node": 0, "at_s": 5, "restart_at_s": 30}],
            },
            env={},
        )
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "duration": 20,
                "wan": {"partitions": [{"group": [0], "from_s": 25}]},
            },
            env={},
        )
    with pytest.raises(SpecError):
        parse_scenario(
            {
                "name": "t",
                "duration": 20,
                "wan": {
                    "partitions": [
                        {"group": [0], "from_s": 5, "until_s": 21}
                    ]
                },
            },
            env={},
        )
    # A heal exactly at window close is fine (the runner settles after).
    # The two planes are checked separately: composing them on DIFFERENT
    # nodes would exceed f=1 and is rejected (see the bound test above).
    s = parse_scenario(
        {
            "name": "t",
            "duration": 20,
            "crash": [{"node": 0, "at_s": 5, "restart_at_s": 12}],
        },
        env={},
    )
    assert s.crash[0].restart_at_s == 12.0
    parse_scenario(
        {
            "name": "t",
            "duration": 20,
            "wan": {
                "partitions": [{"group": [1], "from_s": 5, "until_s": 20}]
            },
        },
        env={},
    )


def test_control_arm_strips_faults_keeps_knobs():
    s = parse_scenario(
        _minimal(
            env={"NARWHAL_HEALTH_PEER_RETRANS_RATE": "3"},
            parameters={"gc_depth": 8},
        ),
        env={},
    )
    c = s.control_arm()
    assert c.is_clean() and not s.is_clean()
    assert c.env == s.env and c.parameters == s.parameters
    assert c.name == "t.control"


# -- jittered, env-tunable backoff (satellite) --------------------------------

def test_backoff_jitter_and_cap():
    rng = random.Random(42)
    delay = 0.2
    sleeps = []
    for _ in range(12):
        sleep, delay = next_backoff(delay, cap=5.0, rng=rng)
        sleeps.append(sleep)
    # Delay doubles toward the cap and stays there.
    assert delay == 5.0
    # Every sleep is 50-100% of its (capped) nominal delay — never more
    # than the cap, never degenerate.
    assert all(0 < s <= 5.0 for s in sleeps)
    # Jitter actually varies (a constant schedule thundering-herds).
    tail = sleeps[-6:]
    assert max(tail) - min(tail) > 0.1


def test_backoff_desynchronizes_lockstep_peers():
    # Two peers that failed at the same instant must drift apart: after a
    # few steps their cumulative wakeup times differ materially.
    t_a = t_b = 0.0
    d_a = d_b = 0.2
    rng_a, rng_b = random.Random(1), random.Random(2)
    for _ in range(8):
        s, d_a = next_backoff(d_a, cap=60.0, rng=rng_a)
        t_a += s
        s, d_b = next_backoff(d_b, cap=60.0, rng=rng_b)
        t_b += s
    assert abs(t_a - t_b) > 1.0


def test_backoff_cap_env_override(monkeypatch):
    monkeypatch.setenv("NARWHAL_NET_BACKOFF_MAX_S", "2.5")
    assert backoff_cap() == 2.5
    sleep, nxt = next_backoff(60.0, rng=random.Random(0))
    assert sleep <= 2.5 and nxt == 2.5
    monkeypatch.setenv("NARWHAL_NET_BACKOFF_MAX_S", "garbage")
    assert backoff_cap() == 60.0
    monkeypatch.delenv("NARWHAL_NET_BACKOFF_MAX_S")
    assert backoff_cap() == 60.0


# -- netem ---------------------------------------------------------------------

def _emulator(rules=None, default=None, partitions=(), start_ts=0.0):
    return netem.NetEmulator(
        rules or {}, default, list(partitions), seed=7, node="t",
        start_ts=start_ts,
    )


def test_partition_window_timing():
    win = netem.PartitionWindow(
        peers=frozenset({"10.0.0.2:7001"}), from_s=5.0, until_s=12.0
    )
    emu = _emulator(partitions=[win], start_ts=100.0)
    assert not emu.blocked("10.0.0.2:7001", now=104.9)
    assert emu.blocked("10.0.0.2:7001", now=105.0)
    assert emu.blocked("10.0.0.2:7001", now=111.9)
    assert not emu.blocked("10.0.0.2:7001", now=112.0)  # healed
    assert not emu.blocked("10.0.0.3:7001", now=108.0)  # other peer
    forever = netem.PartitionWindow(
        peers=frozenset({"10.0.0.2:7001"}), from_s=5.0, until_s=None
    )
    emu2 = _emulator(partitions=[forever], start_ts=100.0)
    assert emu2.blocked("10.0.0.2:7001", now=1e9)


def test_no_emulator_hooks_are_passthrough():
    netem.install(None)
    try:
        assert not netem.blocked("1.2.3.4:1")
        assert netem.wrap("1.2.3.4:1", None, None) == (None, None)
    finally:
        netem.reset()


def test_netem_config_load_selects_node(tmp_path):
    cfg = {
        "seed": 3,
        "start_ts": 50.0,
        "nodes": {
            "primary-0": {
                "rules": [
                    {"dst": "9.9.9.9:1", "latency_ms": 40, "loss": 0.5},
                    {"dst": "*", "latency_ms": 10},
                ],
                "partitions": [
                    {"peers": ["9.9.9.9:2"], "from_s": 1, "until_s": 2}
                ],
            }
        },
    }
    path = tmp_path / "netem.json"
    path.write_text(json.dumps(cfg))
    emu = netem.NetEmulator.load(str(path), "primary-0")
    assert emu.shape_for("9.9.9.9:1").latency_ms == 40
    assert emu.shape_for("anything:else").latency_ms == 10  # wildcard
    assert emu.blocked("9.9.9.9:2", now=51.5)
    # A node the scenario doesn't shape loads as None (all hooks no-op).
    assert netem.NetEmulator.load(str(path), "worker-3-0") is None


def test_shaped_writer_delays_frames_in_order():
    async def go():
        received = []
        got_two = asyncio.Event()

        async def on_conn(reader, writer):
            loop = asyncio.get_running_loop()
            try:
                while True:
                    frame = await read_frame(reader)
                    received.append((loop.time(), frame))
                    if len(received) >= 2:
                        got_two.set()
            except (asyncio.IncompleteReadError, ConnectionError):
                pass
            finally:
                # Python >= 3.12: Server.wait_closed() waits for every
                # accepted connection, so the handler closes its side.
                writer.close()

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        emu = _emulator(
            rules={f"127.0.0.1:{port}": netem.Shape(latency_ms=80)}
        )
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        reader, shaped = emu.wrap(f"127.0.0.1:{port}", reader, writer)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await write_frame(shaped, b"one")
        await write_frame(shaped, b"two")
        await asyncio.wait_for(got_two.wait(), 5)
        assert [f for _, f in received] == [b"one", b"two"]  # order kept
        # Both frames arrived no earlier than the shaped latency.
        assert all(t - t0 >= 0.07 for t, _ in received)
        shaped.close()
        server.close()
        await server.wait_closed()

    asyncio.run(asyncio.wait_for(go(), 15))


def test_shaped_writer_loss_surfaces_as_connection_reset():
    async def go():
        async def on_conn(reader, writer):
            try:
                while True:
                    await read_frame(reader)
            except (asyncio.IncompleteReadError, ConnectionError):
                pass
            finally:
                # Python >= 3.12: Server.wait_closed() waits for every
                # accepted connection, so the handler closes its side.
                writer.close()

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        emu = _emulator(
            rules={f"127.0.0.1:{port}": netem.Shape(loss=1.0)}
        )
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        _, shaped = emu.wrap(f"127.0.0.1:{port}", reader, writer)
        with pytest.raises(ConnectionResetError):
            await write_frame(shaped, b"doomed")
        shaped.close()
        server.close()
        await server.wait_closed()

    asyncio.run(asyncio.wait_for(go(), 15))


def test_partition_cuts_established_connection():
    async def go():
        async def on_conn(reader, writer):
            try:
                while True:
                    await read_frame(reader)
            except (asyncio.IncompleteReadError, ConnectionError):
                pass
            finally:
                # Python >= 3.12: Server.wait_closed() waits for every
                # accepted connection, so the handler closes its side.
                writer.close()

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        addr = f"127.0.0.1:{port}"
        loop = asyncio.get_running_loop()
        # Window opens 0.2 s from now: the connection is established and
        # working BEFORE the partition begins.
        import time as _time

        emu = _emulator(
            partitions=[
                netem.PartitionWindow(
                    peers=frozenset({addr}), from_s=0.2, until_s=None
                )
            ],
            start_ts=_time.time(),
        )
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        _, shaped = emu.wrap(addr, reader, writer)
        await write_frame(shaped, b"before")  # flows while healthy
        await asyncio.sleep(0.25)
        with pytest.raises(ConnectionResetError):
            await write_frame(shaped, b"after")
        assert emu.blocked(addr)
        shaped.close()
        server.close()
        await server.wait_closed()
        _ = loop

    asyncio.run(asyncio.wait_for(go(), 15))


# -- detection rules -----------------------------------------------------------

def test_equivocation_and_invalid_signature_rules_latch():
    reg = Registry()
    mon = HealthMonitor(reg, rules=default_rules({}), interval_s=1.0)
    t = 1000.0
    assert mon.evaluate(t) == []
    reg.counter("primary.equivocations_detected").inc()
    firing = {f["rule"] for f in mon.evaluate(t + 1)}
    assert "equivocation" in firing
    reg.counter("primary.invalid_signatures").inc(3)
    firing = {f["rule"] for f in mon.evaluate(t + 2)}
    assert {"equivocation", "invalid_signature"} <= firing
    # Latched: counters are monotone, the proof doesn't expire.
    assert "equivocation" in {f["rule"] for f in mon.evaluate(t + 30)}


def test_peer_vote_silence_requires_round_progress():
    reg = Registry()
    reg.counter("primary.peer_votes.10.0.0.2:7001")
    active = reg.counter("primary.peer_votes.10.0.0.3:7001")
    rnd = reg.gauge("primary.round")
    mon = HealthMonitor(
        reg,
        rules=default_rules({"NARWHAL_HEALTH_VOTE_SILENCE_WINDOW_S": "8"}),
        interval_s=1.0,
    )
    t = 2000.0
    # Idle committee: rounds not advancing — silent even though the peer
    # counter is flat.
    rnd.set(5)
    for i in range(12):
        assert mon.evaluate(t + i) == []
    # Rounds advance, the active peer keeps voting, the silent one
    # doesn't: only the silent one is named.
    for i in range(12, 26):
        rnd.set(5 + i)
        active.inc(2)
        firing = mon.evaluate(t + i)
    subjects = {
        f["subject"] for f in firing if f["rule"] == "peer_vote_silence"
    }
    assert subjects == {"10.0.0.2:7001"}


def test_stale_replay_rule_fires_on_rate_not_trickle():
    reg = Registry()
    stale = reg.counter("primary.stale_messages")
    mon = HealthMonitor(
        reg,
        rules=default_rules(
            {"NARWHAL_HEALTH_STALE_RATE": "2",
             "NARWHAL_HEALTH_STALE_WINDOW_S": "5"}
        ),
        interval_s=1.0,
    )
    t = 3000.0
    # A slow trickle (1 per 2 s) stays under the 2/s threshold.
    for i in range(10):
        if i % 2 == 0:
            stale.inc()
        assert mon.evaluate(t + i) == []
    # A flood (10/s) fires.
    firing = []
    for i in range(10, 18):
        stale.inc(10)
        firing = mon.evaluate(t + i)
    assert "stale_replay" in {f["rule"] for f in firing}


def test_new_rules_silent_on_clean_registry():
    reg = Registry()
    reg.gauge("primary.round").set(50)
    votes = reg.counter("primary.peer_votes.10.0.0.2:7001")
    mon = HealthMonitor(reg, rules=default_rules({}), interval_s=1.0)
    t = 4000.0
    for i in range(20):
        reg.gauge("primary.round").inc(1)
        votes.inc(3)  # healthy peer votes every round
        assert mon.evaluate(t + i) == [], "rule fired on a clean node"


# -- audit replay: the checker must catch real violations ----------------------

def _write_segment(path, inserts, commits_interleaved, restore=b""):
    """commits_interleaved: {index-in-inserts: [digests to record after
    that insert]} — mirrors the runner's I/C interleaving."""
    w = AuditWriter(str(path))
    w.restore_marker(restore)
    for i, cert in enumerate(inserts):
        w.insert(cert)
        for d in commits_interleaved.get(i, []):
            w._record(b"C", bytes(d))
    w.close()


def test_replay_segment_roundtrip_clean_stream(tmp_path):
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, 6, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 7, parents)
    stream = certs + [trigger]
    # Record exactly what a live fixed-coin node would: golden's commits.
    from narwhal_tpu.consensus.golden import GoldenTusk

    golden = GoldenTusk(c, 50, fixed_coin=True)
    commits = {}
    for i, cert in enumerate(stream):
        seq = golden.process_certificate(cert)
        if seq:
            commits[i] = [x.digest() for x in seq]
    path = tmp_path / "seg0.bin"
    _write_segment(path, stream, commits)
    verdict = replay_segments(c, 50, [str(path)], fixed_coin=True)
    assert verdict["ok"], verdict["violations"]
    assert verdict["recorded_commits"] == verdict["golden_commits"] > 0


def test_replay_detects_reordered_and_forged_commits(tmp_path):
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, 6, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 7, parents)
    stream = certs + [trigger]
    from narwhal_tpu.consensus.golden import GoldenTusk

    golden = GoldenTusk(c, 50, fixed_coin=True)
    commits = {}
    for i, cert in enumerate(stream):
        seq = golden.process_certificate(cert)
        if seq:
            commits[i] = [x.digest() for x in seq]
    # Reorder two commits within a burst: byte-identity must fail.
    (k, seq) = next((k, v) for k, v in commits.items() if len(v) >= 2)
    commits[k] = [seq[1], seq[0]] + seq[2:]
    path = tmp_path / "seg_bad.bin"
    _write_segment(path, stream, commits)
    verdict = replay_segments(c, 50, [str(path)], fixed_coin=True)
    assert not verdict["ok"]
    assert any("diverges" in v for v in verdict["violations"])


def test_replay_detects_double_commit_within_segment(tmp_path):
    c = committee()
    names = sorted_names()
    certs, parents = make_certificates(1, 6, genesis_digests(c), names)
    _, trigger = mock_certificate(names[0], 7, parents)
    stream = certs + [trigger]
    from narwhal_tpu.consensus.golden import GoldenTusk

    golden = GoldenTusk(c, 50, fixed_coin=True)
    commits = {}
    for i, cert in enumerate(stream):
        seq = golden.process_certificate(cert)
        if seq:
            commits[i] = [x.digest() for x in seq]
    k, seq = next((k, v) for k, v in commits.items() if v)
    commits[k] = seq + [seq[0]]  # same digest committed twice
    path = tmp_path / "seg_dup.bin"
    _write_segment(path, stream, commits)
    verdict = replay_segments(c, 50, [str(path)], fixed_coin=True)
    assert not verdict["ok"]
    assert any("twice" in v for v in verdict["violations"])


def test_audit_writer_rolls_instead_of_appending_to_old_segment(tmp_path):
    """One segment per incarnation is the format's invariant (restore
    marker first).  A fixed NARWHAL_CONSENSUS_AUDIT path reused across a
    restart must NOT append a second 'R' mid-file (that would read as a
    false safety violation) — the writer rolls to `<path>.N` and keeps
    the old segment intact."""
    path = tmp_path / "audit.bin"
    w1 = AuditWriter(str(path))
    w1.restore_marker(b"")
    w1.close()
    assert w1.path == str(path)

    w2 = AuditWriter(str(path))
    w2.restore_marker(b"blob")
    w2.close()
    assert w2.path == str(path) + ".1"

    w3 = AuditWriter(str(path))
    w3.close()
    assert w3.path == str(path) + ".2"

    first = read_audit(str(path))
    second = read_audit(w2.path)
    assert [t for t, _ in first] == [b"R"]
    assert second == [(b"R", b"blob")]


def test_equivocate_requires_unit_stake_committee():
    """The equivocation split sizes parent sets and peer shares by COUNT
    against the stake-denominated quorum threshold — on a weighted
    committee the scenario silently voids (twin below parent quorum, or
    real header never certified), so the wrapper must refuse loudly."""
    from narwhal_tpu.faults.byzantine import _require_unit_stake

    c = committee()
    _require_unit_stake(c)  # unit stakes: fine
    weighted = committee()
    next(iter(weighted.authorities.values())).stake = 2
    with pytest.raises(SpecError, match="unit-stake"):
        _require_unit_stake(weighted)


def test_read_audit_tolerates_torn_tail(tmp_path):
    c = committee()
    names = sorted_names()
    certs, _ = make_certificates(1, 2, genesis_digests(c), names)
    path = tmp_path / "seg_torn.bin"
    _write_segment(path, certs, {})
    whole = read_audit(str(path))
    data = path.read_bytes()
    path.write_bytes(data[:-7])  # SIGKILL mid-record
    torn = read_audit(str(path))
    assert torn == whole[:-1]  # clean prefix, no exception


def test_cross_node_prefix_accepts_lag_rejects_fork():
    a = ["d1", "d2", "d3", "d4"]
    ok = cross_node_prefix({"n0": a, "n1": a[:2], "n2": a[:3]})
    assert ok["ok"] and ok["reference_node"] == "n0"
    bad = cross_node_prefix({"n0": a, "n1": ["d1", "dX"]})
    assert not bad["ok"]
    assert "diverges" in bad["violations"][0]


# -- byzantine plan ------------------------------------------------------------

def test_byzantine_plan_roundtrip_and_split():
    from narwhal_tpu.faults.byzantine import ByzantinePlan

    kps = keys()
    plan = ByzantinePlan.from_json(
        {
            "behaviors": ["withhold_votes", "equivocate"],
            "seed": 9,
            "withhold_targets": [kps[1].name.encode_base64()],
        }
    )
    assert plan.withhold_targets == {kps[1].name}
    # Deterministic under the same seed, keep+rest partitions the set,
    # and two independently-loaded plans (one per role process) agree —
    # the coordination the favored split exists for.
    addr_by_name = {f"auth{i}": f"10.0.0.{i}:7000" for i in range(5)}
    a1, b1 = plan.favored_split(addr_by_name, 3)
    plan2 = ByzantinePlan.from_json({"behaviors": ["equivocate"], "seed": 9})
    a2, b2 = plan2.favored_split(addr_by_name, 3)
    assert len(a1) == 3 and sorted(a1 + b1) == sorted(addr_by_name.values())
    assert (a1, b1) == (a2, b2)
    # A different address PLANE of the same authorities splits to the
    # same names (prefix-aligned), and a different seed re-deals.
    other_plane = {n: f"10.0.1.{i}:8000" for i, n in enumerate(sorted(addr_by_name))}
    c1, _ = plan.favored_split(other_plane, 3)
    assert {a.split(":")[0].rsplit(".", 1)[1] for a in a1} == {
        c.split(":")[0].rsplit(".", 1)[1] for c in c1
    }
    plan3 = ByzantinePlan.from_json({"behaviors": ["equivocate"], "seed": 10})
    deals = {tuple(plan3.favored_split(addr_by_name, 3)[0]), tuple(a1)}
    assert len(deals) == 2

    with pytest.raises(Exception):
        ByzantinePlan.from_json({"behaviors": ["teleport"]})


def test_log_commit_fallback_counts_post_settle_lines(tmp_path):
    """The liveness verdict's scrape-independent fallback: commit log
    lines at/after the settle timestamp count, earlier ones and
    non-commit lines don't, and unreadable/garbled lines are skipped.
    The settle reference is NAIVE LOCAL time: node/main.py formats
    %(asctime)s with logging's default localtime converter (the 'Z' is
    cosmetic), so the parser must read the stamps back in local time —
    a UTC parse would shift every stamp by the host's UTC offset and
    silently invert the verdict on any non-UTC host."""
    from benchmark.fault_bench import _log_commits_after

    log = tmp_path / "primary-0.log"
    log.write_text(
        "2026-01-01T00:00:01.000Z INFO narwhal.consensus "
        "Committed B1(aaaa) -> d1d1\n"
        "2026-01-01T00:00:05.000Z INFO narwhal.consensus "
        "Committed B2(bbbb) -> d2d2\n"
        "garbage line without a timestamp Committed B9(zzzz) -> d9d9\n"
        "2026-01-01T00:00:09.000Z WARNING narwhal.metrics HEALTH "
        "anomaly FIRING rule=commit_stall\n"
        "2026-01-01T00:00:07.000Z INFO narwhal.consensus "
        "Committed B7(eeee)\n"  # EMPTY header: no payload digest, no count
        "2026-01-01T00:00:10.000Z INFO narwhal.consensus "
        "Committed B3(cccc) -> d3d3\n"
    )
    import datetime

    settle = datetime.datetime(2026, 1, 1, 0, 0, 5).timestamp()
    assert _log_commits_after([str(log)], settle) == 2  # B2 + B3
    assert _log_commits_after([str(log)], settle + 100) == 0
    assert _log_commits_after([str(tmp_path / "missing.log")], settle) == 0


def test_log_commit_fallback_incremental_state(tmp_path):
    """With a shared ``state`` dict the fallback scans each log's bytes
    once: appended lines are picked up by the next call, the running
    count persists, and a torn (newline-less) tail is deferred to the
    next poll instead of being miscounted."""
    import datetime

    from benchmark.fault_bench import _log_commits_after

    line = (
        "2026-01-01T00:00:0{s}.000Z INFO narwhal.consensus "
        "Committed B{s}(aaaa) -> dddd\n"
    )
    settle = datetime.datetime(2026, 1, 1, 0, 0, 0).timestamp()
    log = tmp_path / "primary-0.log"
    log.write_text(line.format(s=1))
    state: dict = {}
    assert _log_commits_after([str(log)], settle, state) == 1
    # Append one complete line and one torn tail.
    with open(log, "a") as f:
        f.write(line.format(s=2))
        f.write("2026-01-01T00:00:03.000Z INFO narwhal.consensus Comm")
    assert _log_commits_after([str(log)], settle, state) == 2
    # Complete the torn line: only the tail is re-scanned, count -> 3.
    with open(log, "a") as f:
        f.write("itted B3(cccc) -> d3d3\n")
    # The torn fragment completes into a line whose prefix parses.
    assert _log_commits_after([str(log)], settle, state) == 3
    offset, count = state[str(log)]
    assert count == 3 and offset == log.stat().st_size
