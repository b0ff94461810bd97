"""The plain commit rules (``reference/tusk.py``) on seeded DAGs: the
direct rule against the program's ``LowDepthTusk`` AND against the
program's frozen oracle (``consensus/golden_lowdepth.py``), the classic
rule against ``Tusk`` and ``golden.py`` on the same DAGs.  The reference
was written from the rules' statements and imports nothing of the
program (held here over its sources); the program is reached only through
the bytes of a certificate, as a run's audit segment hands them over.

ISSUE 32 asked for this file in tier-1 (``tests/``); a benchmark PR adds
no file outside the benchmark's own directory, so it stands here
(PERF.md, Open questions).
"""

import ast
import json
import os
import random

import pytest

from committee import make_identities
from reference.tusk import RULES, PlainTusk
from reference.wire import Certificate, Header, encode_certificate, genesis

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
REFERENCE = os.path.join(CHIPBENCH, "reference")

# nodes, dead key ranks: the one-chip cell's committee (every second
# leader dead), the four-chip cell's, and one at which odd ranks lead too.
COMMITTEES = {
    "4n-f1": (4, [2]),
    "4n": (4, []),
    "10n-f3": (10, [4, 7, 9]),
}
SHAPES = ("random", "late_leader", "thin_support", "gc_tail", "out_of_order")
SEEDS = (3, 2**31 + 7)


def certificate(author, round_, parents):
    h = Header(author, round_, {}, sorted(parents), bytes(32), bytes(64))
    h.id = h.computed_id()
    return Certificate(h, [])


def make_dag(name, shape, seed):
    """(sorted keys, gc_depth, certificates in arrival order).

    Every round each live validator but now and then one cites a quorum
    or more of the round below, drawn from the seed.  ``thin_support``
    gives every second leader (rounds 2, 6, 10, ...) exactly 2f citations
    where the committee allows (one short of the direct rule's gate,
    over the classic rule's) and the others a full round of them, so
    that the thin ones are reached only by the chain walk from the next;
    ``late_leader`` delivers each leader after the round that cites it;
    ``gc_tail`` runs long under a garbage depth of 4; ``out_of_order``
    delivers children up to two rounds ahead of their parents."""
    nodes, dead = COMMITTEES[name]
    rng = random.Random(f"{name}:{shape}:{seed}")
    ids = make_identities(seed, {"nodes": nodes, "faults": len(dead),
                                 "dead_key_ranks": dead})
    keys = sorted(i.name for i in ids)
    live = sorted(i.name for i in ids[:nodes - len(dead)])
    quorum = 2 * nodes // 3 + 1
    rounds = 40 if shape == "gc_tail" else 18
    previous = {c.origin: c.digest() for c in genesis(keys)}
    certs = []
    for r in range(1, rounds + 1):
        authors = list(live)
        if len(authors) > quorum and rng.random() < 0.3:
            authors.remove(rng.choice(authors))
        leader = keys[(r - 1) % nodes] if r % 2 == 1 else None
        citing = len(authors)
        if shape == "thin_support" and r % 4 == 3:
            citing = min(len(authors), quorum - 1)  # 2f of unit stakes
        this = {}
        for k, author in enumerate(authors):
            others = [d for o, d in previous.items() if o != leader]
            take = rng.randint(min(quorum, len(previous)), len(previous))
            if leader in previous and k < citing and (
                    shape == "thin_support" or rng.random() < 0.8):
                parents = [previous[leader]] + rng.sample(others, take - 1)
            elif len(others) >= quorum or leader not in previous:
                parents = rng.sample(others, min(len(others), max(quorum, take - 1)))
            else:  # three live of four: a header has to cite them all
                parents = list(previous.values())
            cert = certificate(author, r, parents)
            certs.append(cert)
            this[author] = cert.digest()
        previous = this

    def leads(c):
        return c.round % 2 == 0 and c.origin == keys[c.round % nodes]

    if shape == "late_leader":
        certs.sort(key=lambda c: c.round + (1.5 if leads(c) else 0.0))
    elif shape == "out_of_order":
        certs.sort(key=lambda c: c.round + rng.uniform(-2.2, 0.0))
    else:  # parents first, the rest of a round in any order
        certs.sort(key=lambda c: c.round + rng.random() * 0.99)
    return keys, (4 if shape == "gc_tail" else 50), certs


def plain(keys, gc_depth, certs, rule):
    tusk = PlainTusk(keys, gc_depth, rule)
    return [[c.digest() for c in tusk.process_certificate(x)] for x in certs]


def programs(keys, gc_depth, certs, classes):
    """Each program class's commits per arrival, fed the same bytes."""
    from narwhal_tpu.config import Authority, Committee, PrimaryAddresses
    from narwhal_tpu.crypto import PublicKey
    from narwhal_tpu.messages import set_wire_committee
    from narwhal_tpu.primary.messages import Certificate as Theirs

    committee = Committee({
        PublicKey(k): Authority(1, PrimaryAddresses("a:1", "a:2"), {}) for k in keys
    })
    set_wire_committee(committee)
    payloads = [encode_certificate(c, keys) for c in certs]
    out = []
    for cls in classes:
        tusk = cls(committee, gc_depth=gc_depth)
        out.append([
            [bytes(c.digest()) for c in tusk.process_certificate(Theirs.deserialize(p))]
            for p in payloads
        ])
    return out


CASES = [(n, s, seed) for n in COMMITTEES for s in SHAPES for seed in SEEDS]
IDS = [f"{n}-{s}-{seed}" for n, s, seed in CASES]


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def dag(request):
    return make_dag(*request.param)


def test_plain_direct_rule_is_the_programs_and_its_oracles(dag):
    """Arrival by arrival, not only the whole sequence: the direct rule
    is about WHEN a leader is decided."""
    pytest.importorskip("narwhal_tpu")
    from narwhal_tpu.consensus.golden_lowdepth import GoldenLowDepthTusk
    from narwhal_tpu.consensus.tusk import LowDepthTusk

    ours = plain(*dag, "lowdepth")
    live, frozen = programs(*dag, (LowDepthTusk, GoldenLowDepthTusk))
    assert ours == live
    assert ours == frozen
    assert sum(map(len, ours)), "nothing committed: the case proves nothing"


def test_plain_classic_rule_is_unchanged(dag):
    pytest.importorskip("narwhal_tpu")
    from narwhal_tpu.consensus.golden import GoldenTusk
    from narwhal_tpu.consensus.tusk import Tusk

    ours = plain(*dag, "classic")
    live, frozen = programs(*dag, (Tusk, GoldenTusk))
    assert ours == live
    assert ours == frozen
    assert sum(map(len, ours))


@pytest.mark.parametrize("case", [
    c for c in CASES if c[1] in ("random", "thin_support")
], ids=lambda c: "-".join(map(str, c)))
def test_both_rules_emit_one_sequence(case):
    """On causally complete arrivals the two rules order alike: what one
    has emitted is the head of what the other has (a leader that f+1 cite
    is linked to every later leader, so the direct rule's chain walk
    takes it where the classic rule decided it).  The guarantee is that
    sequence; the rule decides only how soon."""
    keys, gc_depth, certs = make_dag(*case)
    classic, direct = (
        [d for burst in plain(keys, gc_depth, certs, rule) for d in burst]
        for rule in RULES
    )
    short = min(len(classic), len(direct))
    assert short and classic[:short] == direct[:short]


def leader_rounds(keys, certs, burst):
    by_digest = {c.digest(): c for c in certs}
    return [
        c.round for c in map(by_digest.get, burst)
        if c.round % 2 == 0 and c.origin == keys[c.round % len(keys)]
    ]


def test_thin_support_is_reached_by_the_chain_walk():
    """A leader with exactly 2f citations is not decided on arrival: no
    burst ends on one.  The next leader with a quorum of them takes it
    along, ahead of itself; the classic rule decides it by itself."""
    keys, gc_depth, certs = make_dag("4n", "thin_support", 5)
    direct = [leader_rounds(keys, certs, b)
              for b in plain(keys, gc_depth, certs, "lowdepth") if b]
    assert direct and all(led == sorted(led) and led[-1] % 4 == 0 for led in direct)
    assert any(r % 4 == 2 for led in direct for r in led)
    classic = [leader_rounds(keys, certs, b)
               for b in plain(keys, gc_depth, certs, "classic") if b]
    assert any(led[-1] % 4 == 2 for led in classic)


def test_a_late_leader_is_decided_by_its_own_arrival():
    keys, gc_depth, certs = make_dag("10n-f3", "late_leader", 3)
    bursts = plain(keys, gc_depth, certs, "lowdepth")
    deciders = [c for c, burst in zip(certs, bursts) if burst]
    assert deciders and all(c.round % 2 == 0 for c in deciders)


def test_unknown_rule_is_refused():
    with pytest.raises(ValueError):
        PlainTusk([bytes([i]) * 32 for i in range(4)], 50, "multileader")


@pytest.mark.parametrize(
    "module", sorted(f for f in os.listdir(REFERENCE) if f.endswith(".py")))
def test_reference_imports_nothing_of_the_program(module):
    """From the source alone, deferred imports too (as tier-1's
    ``tests/test_layering.py`` reads the program's)."""
    with open(os.path.join(REFERENCE, module)) as f:
        tree = ast.parse(f.read(), module)
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and not node.level:
            names.add(node.module)
    assert not {n for n in names if n.split(".")[0] in ("narwhal_tpu", "benchmark")}


def test_configurations_admit_exactly_the_plain_rules():
    """The guarantee's text names every rule the reference has, by the
    name a segment declares it under."""
    for name in ("local-4n-f1", "local-4n"):
        with open(os.path.join(CHIPBENCH, "configs", name + ".json")) as f:
            text = json.load(f)["guarantees"]["commit_rule"]
        assert all(rule in text for rule in RULES)
        assert "multileader" not in text
