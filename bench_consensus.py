#!/usr/bin/env python3
"""KernelTusk microbenchmark: device-resident commit path vs golden Python.

The reference's commit rule does one `linked()` BFS per earlier leader per
commit attempt (consensus/src/lib.rs:224-259); KernelTusk collapses the
whole chain into one jitted scan over a device-resident dense window
(narwhal_tpu/ops/reachability.py).  This measures BOTH protocol phases for
both implementations over identical DAG state at committee sizes
N ∈ {4, 20, 50} and a gc_depth-50 window:

- **insert** — the certificate-arrival path.  Python: one dict insert.
  Kernel: one dict insert + an O(1) staging append (all window resolution
  is deferred to the commit opportunity).  Reported as the min wall time
  of inserting `span` PRE-CREATED full rounds over `--build-reps`
  interleaved passes — certificate construction/hashing is excluded (it
  is identical for both arms and an order of magnitude heavier than the
  arrival path, so timing it in-loop drowned the comparison in jitter).
- **commit** — one commit opportunity.  Python: `order_leaders` (the
  linked-BFS chain walk).  Kernel: flush the staged arrivals since the
  last opportunity (two rounds' worth — one `window_apply` scatter
  dispatch at steady state) + one `leader_commit_scan` dispatch + the
  W-bool committed-bitmap fetch.  The per-iteration re-staging makes the
  kernel number an honest STEADY-STATE cost, not an empty-pending fast
  path.
- **commit burst** (PR 4) — a full multi-leader commit: odd rounds
  delivered first so nothing commits until one trigger certificate
  flattens the ENTIRE chain in a single `process_certificate` call.
  Three arms over identical streams: the frozen r06 dict walk
  (`consensus/golden.py`, the equivalence oracle), the live indexed walk
  (`consensus/tusk.py` — digest-index parent resolution, incremental
  support, one GC sweep per burst), and the device kernel (whose burst
  pays the catch-up window flush).  The acceptance gate (ISSUE r09) is
  indexed ≥ 2× the dict walk at N ≥ 20 over a 50-round DAG.

Floor honesty: every kernel commit pays one device round trip (dispatch
plus the bitmap fetch) whatever the scan costs.  The artifact reports
that round-trip floor as measured on the device it ran on, the raw
speedup, and the floor-subtracted speedup side by side — the acceptance
gate (ISSUE r06) is floor-subtracted commit speedup > 1 at N ≥ 20 AND
kernel insert ≤ Python insert.  Not measured on the v5e host yet.

    python bench_consensus.py --sizes 4 20 50 --span 48 --iters 9 \
        --artifact artifacts/consensus_bench.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from narwhal_tpu.config import (  # noqa: E402
    Authority,
    Committee,
    PrimaryAddresses,
    WorkerAddresses,
)
from narwhal_tpu.crypto import KeyPair  # noqa: E402
from narwhal_tpu.consensus.golden import GoldenTusk  # noqa: E402
from narwhal_tpu.consensus.golden_multileader import (  # noqa: E402
    GoldenMultiLeaderTusk,
)
from narwhal_tpu.consensus.tusk import MultiLeaderTusk, Tusk  # noqa: E402
from narwhal_tpu.primary.messages import Certificate, Header, genesis  # noqa: E402


def make_committee(n: int, return_keypairs: bool = False):
    """Seeded stake-1 loopback committee — the shared microbench fixture
    (bench_cadence.py imports this; keep the one construction site)."""
    kps = [
        KeyPair.generate(rng_seed=i.to_bytes(32, "little")) for i in range(n)
    ]
    auths = {}
    for kp in kps:
        auths[kp.name] = Authority(
            stake=1,
            primary=PrimaryAddresses("127.0.0.1:0", "127.0.0.1:0"),
            workers={0: WorkerAddresses("127.0.0.1:0", "127.0.0.1:0", "127.0.0.1:0")},
        )
    committee = Committee(auths)
    return (committee, kps) if return_keypairs else committee


def mock_certificate(origin, round_, parents) -> Certificate:
    header = Header(
        author=origin, round=round_, payload={}, parents=set(parents)
    )
    return Certificate(header=header, votes=[])


def make_dag_certs(committee: Committee, span: int):
    """Pre-create `span` full rounds of certificates (the densest, worst
    case) OUTSIDE any timed region: certificate construction and header
    hashing are identical for both implementations and an order of
    magnitude heavier than the arrival path itself — timing them alongside
    the inserts drowned the comparison in shared-core jitter.  Returns
    (certs_in_arrival_order, tail_certs) where tail_certs are the last two
    rounds — the re-staging unit for steady-state commit measurement."""
    names = sorted(committee.authorities.keys())
    parents = {c.digest() for c in genesis(committee)}
    certs, rounds = [], []
    for r in range(1, span + 1):
        nxt = set()
        this_round = []
        for name in names:
            cert = mock_certificate(name, r, parents)
            certs.append(cert)
            this_round.append(cert)
            nxt.add(cert.digest())
        rounds.append(this_round)
        parents = nxt
    tail = [c for rnd in rounds[-2:] for c in rnd]
    return certs, tail


def build_state(tusk: Tusk, certs) -> float:
    """Feed pre-created certificates through insert_certificate (the
    arrival path, commit rule bypassed); returns the wall seconds of the
    insert loop alone."""
    t0 = time.perf_counter()
    for cert in certs:
        tusk.insert_certificate(cert)
    return time.perf_counter() - t0


def find_anchor(tusk: Tusk, committee: Committee, span: int):
    anchor_round = span if span % 2 == 0 else span - 1
    n = len(committee.authorities)
    leader_name = tusk._sorted_keys[
        0 if tusk.fixed_coin else anchor_round % n
    ]
    return tusk.state.dag[anchor_round][leader_name][1]


def bench_pair(kernel_cls, committee, span, iters, build_reps):
    """Measure BOTH implementations with interleaved timed regions: on a
    shared-core host, back-to-back phases land in different scheduling
    windows and a ±5× jitter swamps the comparison (observed while
    building this bench); alternating python/kernel inside each rep makes
    both arms share the same noise."""
    # Absorb jit compiles / cache loads outside every timed region.
    kernel_cls(committee, gc_depth=50, fixed_coin=True).prewarm()
    certs, tail = make_dag_certs(committee, span)

    py_ins, ke_ins = [], []
    py = ke = None
    for rep in range(max(1, build_reps)):
        builds = [
            (Tusk, py_ins),
            (kernel_cls, ke_ins),
        ]
        if rep % 2:  # alternate order to cancel slow-window drift
            builds.reverse()
        for cls, sink in builds:
            tusk = cls(committee, gc_depth=50, fixed_coin=True)
            sink.append(build_state(tusk, certs))
            if cls is Tusk:
                py = tusk
            else:
                ke = tusk
    py_anchor = find_anchor(py, committee, span)
    ke_anchor = find_anchor(ke, committee, span)

    # First kernel call: flushes the ENTIRE span in chunked scatter
    # dispatches (the catch-up worst case); reported separately.
    t0 = time.perf_counter()
    ke_chain = ke.order_leaders(ke_anchor)
    first_call_s = time.perf_counter() - t0

    py_commit, ke_commit = [], []
    py_chain = None
    for _ in range(iters):
        t0 = time.perf_counter()
        py_chain = py.order_leaders(py_anchor)
        py_commit.append(time.perf_counter() - t0)
        # Steady state for the kernel: a commit opportunity arrives every
        # two rounds, so each measured call flushes two rounds' worth of
        # staged certificates (idempotent device scatter) before the scan.
        ke._pending.extend(tail)
        t0 = time.perf_counter()
        ke_chain = ke.order_leaders(ke_anchor)
        ke_commit.append(time.perf_counter() - t0)
    # Insert reports min-of-reps: the arms differ by one list append per
    # certificate, far below this host's scheduling jitter, and min is the
    # least-noise estimator for identical CPU-bound work.  Commit reports
    # the median of the interleaved iterations.
    return {
        "python": {
            "insert_s": min(py_ins),
            "commit_s": statistics.median(py_commit),
            "chain": [bytes(c.digest()) for c in py_chain],
        },
        "kernel": {
            "insert_s": min(ke_ins),
            "commit_s": statistics.median(ke_commit),
            "first_call_s": first_call_s,
            "chain": [bytes(c.digest()) for c in ke_chain],
        },
    }


def make_burst_certs(committee: Committee, rounds: int):
    """A multi-leader commit-burst stream: odd rounds delivered before
    even rounds, so NO arrival can trigger a commit (odd-round arrivals
    find no even-round leader yet; even-round arrivals never run the
    commit check) — until one final trigger certificate commits the
    ENTIRE chain of linked leaders in a single process_certificate call.
    This is the worst case for the golden walk's per-certificate
    ``State.update`` full sweep (quadratic in burst size) and the shape
    the indexed walk's batched sweep targets."""
    names = sorted(committee.authorities.keys())
    parents = {c.digest() for c in genesis(committee)}
    certs = []
    for r in range(1, rounds + 1):
        nxt = set()
        for name in names:
            cert = mock_certificate(name, r, parents)
            certs.append(cert)
            nxt.add(cert.digest())
        parents = nxt
    order = sorted(certs, key=lambda c: (c.round % 2 == 0, c.round))
    trigger = mock_certificate(names[0], rounds + 1, parents)
    return order, trigger


def bench_commit_burst(
    kernel_cls, committee: Committee, rounds: int, iters: int, floor_s: float
):
    """One multi-leader burst commit, measured per implementation arm:
    the frozen r06 dict walk (GoldenTusk — the oracle), the indexed walk
    (Tusk), and the device kernel.  State is rebuilt per iteration (the
    burst consumes it); only the trigger call is timed.  Arms interleave
    inside each iteration so shared-core scheduling noise hits all three
    equally (same rationale as bench_pair).  Returns median seconds per
    arm plus the burst size; asserts all arms commit byte-identical
    sequences."""
    order, trigger = make_burst_certs(committee, rounds)
    gc_depth = rounds + 4
    arms = [("dict_walk", GoldenTusk), ("indexed", Tusk)]
    if kernel_cls is not None:
        arms.append(("kernel", kernel_cls))
    times = {name: [] for name, _ in arms}
    chains = {}
    for rep in range(max(1, iters)):
        plan = list(arms)
        if rep % 2:  # alternate order to cancel slow-window drift
            plan.reverse()
        for name, cls in plan:
            tusk = cls(committee, gc_depth=gc_depth, fixed_coin=True)
            for cert in order:
                tusk.process_certificate(cert)
            t0 = time.perf_counter()
            seq = tusk.process_certificate(trigger)
            times[name].append(time.perf_counter() - t0)
            chains[name] = [bytes(x.digest()) for x in seq]
    want = chains["dict_walk"]
    assert want, "burst fixture committed nothing"
    for name, chain in chains.items():
        assert chain == want, (
            f"commit-burst sequences diverge: {name} emitted "
            f"{len(chain)} certs vs dict_walk {len(want)}"
        )
    out = {
        "burst_rounds": rounds,
        "burst_committed_certs": len(want),
        "dict_walk_ms": round(
            statistics.median(times["dict_walk"]) * 1e3, 3
        ),
        "indexed_ms": round(statistics.median(times["indexed"]) * 1e3, 3),
    }
    out["indexed_speedup_vs_dict"] = round(
        statistics.median(times["dict_walk"])
        / statistics.median(times["indexed"]),
        2,
    )
    if kernel_cls is not None:
        ke = statistics.median(times["kernel"])
        out["kernel_ms"] = round(ke * 1e3, 3)
        # Floor honesty, same policy as the steady-state commit phase:
        # the kernel burst pays one committed-bitmap fetch.
        out["kernel_ms_floor_subtracted"] = round(
            max(ke - floor_s, 0.0) * 1e3, 3
        )
    return out


def make_ml_burst_certs(committee: Committee, rounds: int):
    """A commit-burst stream for the MULTILEADER rule.  The classic burst
    shape (odd rounds first) does not defer multileader commits — every
    even round's slot anchors the moment its odd-round support quorum
    lands — so this stream starves the quorum instead: every round is
    delivered ascending, but each odd round ships only 2f stake of
    certificates (one short of the 2f+1 the direct anchor needs, and
    with zero non-support, so every slot stays UNDECIDED — never dead).
    Nothing can commit until one trigger certificate — the withheld
    round-(rounds-1) support cert — closes the top anchor's quorum and
    flattens the ENTIRE slot chain in a single process_certificate
    call."""
    names = sorted(committee.authorities.keys())
    quorum = committee.quorum_threshold()
    parents = {c.digest() for c in genesis(committee)}
    order, trigger = [], None
    for r in range(1, rounds + 1):
        nxt = set()
        stake = 0
        for name in names:
            cert = mock_certificate(name, r, parents)
            nxt.add(cert.digest())
            if r % 2 == 0:
                order.append(cert)
            elif stake + committee.stake(name) < quorum:
                order.append(cert)
                stake += committee.stake(name)
            elif trigger is None and r == rounds - 1:
                trigger = cert  # the quorum-closing support cert
        parents = nxt
    return order, trigger


def bench_commit_burst_multileader(committee: Committee, rounds: int, iters: int):
    """The multileader commit-burst arm (ISSUE r19).  The rule commits a
    DIFFERENT sequence than classic by design (slot anchors, cone-based
    indirect members), so it cannot be judged against the dict_walk arm:
    it gets its own oracle pair — the frozen naive walk
    (``golden_multileader.py``) vs the live indexed rule — interleaved
    exactly like the classic arms, asserted byte-identical to each
    other."""
    order, trigger = make_ml_burst_certs(committee, rounds)
    gc_depth = rounds + 4
    arms = [
        ("ml_dict_walk", GoldenMultiLeaderTusk),
        ("ml_indexed", MultiLeaderTusk),
    ]
    times = {name: [] for name, _ in arms}
    chains = {}
    for rep in range(max(1, iters)):
        plan = list(arms)
        if rep % 2:  # alternate order to cancel slow-window drift
            plan.reverse()
        for name, cls in plan:
            tusk = cls(committee, gc_depth=gc_depth, fixed_coin=True)
            for cert in order:
                tusk.process_certificate(cert)
            t0 = time.perf_counter()
            seq = tusk.process_certificate(trigger)
            times[name].append(time.perf_counter() - t0)
            chains[name] = [bytes(x.digest()) for x in seq]
    want = chains["ml_dict_walk"]
    assert want, "multileader burst fixture committed nothing"
    assert chains["ml_indexed"] == want, (
        "multileader commit-burst sequences diverge: indexed emitted "
        f"{len(chains['ml_indexed'])} certs vs its oracle {len(want)}"
    )
    return {
        "burst_rounds": rounds,
        "burst_committed_certs": len(want),
        "ml_dict_walk_ms": round(
            statistics.median(times["ml_dict_walk"]) * 1e3, 3
        ),
        "ml_indexed_ms": round(
            statistics.median(times["ml_indexed"]) * 1e3, 3
        ),
        "ml_indexed_speedup_vs_dict": round(
            statistics.median(times["ml_dict_walk"])
            / statistics.median(times["ml_indexed"]),
            2,
        ),
    }


def measure_roundtrip_floor():
    """Fixed device round-trip floor on this host: median wall time of a
    trivial jitted compute + result fetch — what every kernel commit pays
    before the scan does any work."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.int32)
    np.asarray(f(x))
    ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        np.asarray(f(x))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[3]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+", default=[4, 20, 50])
    ap.add_argument("--span", type=int, default=48)
    ap.add_argument("--iters", type=int, default=9)
    ap.add_argument("--build-reps", type=int, default=3)
    ap.add_argument(
        "--burst-rounds",
        type=int,
        default=50,
        help="Rounds in the multi-leader commit-burst phase (odd rounds "
        "delivered first; one trigger commits the whole chain).  Must be "
        "even — the trigger at rounds+1 only fires the commit rule from "
        "an odd round; odd values are rounded up.",
    )
    ap.add_argument("--burst-iters", type=int, default=5)
    ap.add_argument("--artifact", type=str, default=None)
    args = ap.parse_args()
    if args.burst_rounds % 2:
        args.burst_rounds += 1  # see --burst-rounds help: must be even

    import jax

    from narwhal_tpu.ops.reachability import KernelTusk

    floor_s = measure_roundtrip_floor()
    rtt_floor_ms = round(floor_s * 1e3, 3)
    print(json.dumps({"device_roundtrip_floor_ms": rtt_floor_ms}))

    results = []
    for n in args.sizes:
        committee = make_committee(n)
        burst = bench_commit_burst(
            KernelTusk, committee, args.burst_rounds, args.burst_iters,
            floor_s,
        )
        ml_burst = bench_commit_burst_multileader(
            committee, args.burst_rounds, args.burst_iters
        )
        pair = bench_pair(
            KernelTusk, committee, args.span, args.iters, args.build_reps
        )
        py, ke = pair["python"], pair["kernel"]
        assert py["chain"] == ke["chain"], (
            f"commit chains diverge at N={n}: "
            f"python {len(py['chain'])} vs kernel {len(ke['chain'])}"
        )
        ke_commit_floorsub = max(ke["commit_s"] - floor_s, 0.0)
        # When the separately-measured floor swallows the whole commit time
        # the floor-subtracted estimate is degenerate (dividing by ~0 would
        # print an absurd speedup and could spuriously pass the acceptance
        # gate); report null and let acceptance fall back to the raw ratio.
        fs_speedup = (
            round(py["commit_s"] / ke_commit_floorsub, 2)
            if ke_commit_floorsub > 0.1 * ke["commit_s"]
            else None
        )
        row = {
            "committee": n,
            "span_rounds": args.span,
            "leaders_in_chain": len(py["chain"]),
            # arrival path (insert loop over span rounds, min of build-reps)
            "python_insert_ms": round(py["insert_s"] * 1e3, 2),
            "kernel_insert_ms": round(ke["insert_s"] * 1e3, 2),
            # commit path (per opportunity, steady state)
            "python_commit_ms": round(py["commit_s"] * 1e3, 3),
            "kernel_commit_ms": round(ke["commit_s"] * 1e3, 3),
            "kernel_commit_ms_floor_subtracted": round(
                ke_commit_floorsub * 1e3, 3
            ),
            # catch-up worst case: first call flushes the whole span
            "kernel_full_span_flush_ms": round(ke["first_call_s"] * 1e3, 2),
            "commit_speedup_raw": round(py["commit_s"] / ke["commit_s"], 2),
            "commit_speedup_floor_subtracted": fs_speedup,
            "insert_overhead_pct": round(
                (ke["insert_s"] / py["insert_s"] - 1) * 100, 1
            ),
            # Multi-leader commit burst (PR 4): r06 dict walk vs the
            # indexed walk (vs the kernel's catch-up flush) on one
            # trigger committing the whole chain.
            "commit_burst": burst,
            # Multileader burst (ISSUE r19): the live multileader rule vs
            # ITS frozen oracle — the sequences differ from classic by
            # design, so this arm pair is judged internally.
            "commit_burst_multileader": ml_burst,
        }
        results.append(row)
        print(json.dumps(row))

    # Gate on the floor-subtracted ratio where it's meaningful, else the
    # raw one (fetch-bound regime: the raw number IS the honest cost).
    def gate_speedup(r):
        fs = r["commit_speedup_floor_subtracted"]
        return fs if fs is not None else r["commit_speedup_raw"]

    acceptance = {
        "commit_speedup_floor_subtracted_gt1_at_n_ge_20": all(
            gate_speedup(r) > 1 for r in results if r["committee"] >= 20
        ),
        "kernel_insert_not_worse_than_python": all(
            r["kernel_insert_ms"] <= r["python_insert_ms"]
            for r in results
        ),
        # PR 4 gate: the indexed walk at least doubles the dict walk on
        # the multi-leader burst at committee sizes ≥ 20.
        "indexed_burst_speedup_ge2_at_n_ge_20": all(
            r["commit_burst"]["indexed_speedup_vs_dict"] >= 2
            for r in results
            if r["committee"] >= 20
        ),
        # ISSUE r19 gate: the live multileader rule at least doubles ITS
        # frozen oracle on the slot-chain burst at committee sizes ≥ 20
        # (byte-identity to that oracle is asserted inside the arm).
        "multileader_burst_speedup_ge2_at_n_ge_20": all(
            r["commit_burst_multileader"]["ml_indexed_speedup_vs_dict"] >= 2
            for r in results
            if r["committee"] >= 20
        ),
    }
    print(json.dumps({"acceptance": acceptance}))

    if args.artifact:
        os.makedirs(os.path.dirname(args.artifact) or ".", exist_ok=True)
        with open(args.artifact, "w") as f:
            json.dump(
                {
                    "device": str(jax.devices()[0]),
                    "device_roundtrip_floor_ms": rtt_floor_ms,
                    "note": (
                        "kernel_commit_ms is the steady-state cost of one "
                        "commit opportunity: flush two staged rounds "
                        "(donated scatter) + one chain scan + the W-bool "
                        "committed-bitmap fetch — the only device round "
                        "trip on the path.  The floor-subtracted column "
                        "removes that measured round-trip floor.  "
                        "kernel_full_span_flush_ms is the "
                        "catch-up worst case (whole span staged at once)."
                    ),
                    "rows": results,
                    "acceptance": acceptance,
                },
                f,
                indent=2,
            )


if __name__ == "__main__":
    main()
