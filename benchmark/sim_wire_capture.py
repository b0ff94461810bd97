"""Committee-at-scale wire-ledger capture via the deterministic sim
(ISSUE 14 satellite; the before-number ROADMAP item 4 needs).

Runs one CLEAN simulated committee at ``--nodes`` (default 20) on the
virtual clock, then reads the shared metrics registry's wire/crypto
ledgers through the same ``wire_crypto_summary`` join the socketed
benches use.  The aggregate-signature item prices itself off
``cert_sig_bytes_fraction`` and cert bytes/frame — today only the N=4
numbers exist (0.59 legacy r12 / the v2-raw figure from r18); this
captures the large-committee point where a certificate carries
2f+1 = 14 votes and the signature fraction dominates the frame.

Fidelity caveats, recorded in the artifact: the sim signs with the
sim-MAC (64-byte signatures — same wire size as ed25519, so frame
anatomy is exact) and its in-memory transport carries the v2 COMPACT
BODY encodings but not the per-connection dictionary/deflate stages
(those live in the socketed senders), so byte counts are raw-frame
figures — exactly what ``cert_sig_bytes_fraction`` is defined over.

    python benchmark/sim_wire_capture.py --nodes 20 \
        --artifact artifacts/wire_n20_r19.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from narwhal_tpu import metrics  # noqa: E402
from narwhal_tpu.faults.spec import parse_scenario  # noqa: E402
from narwhal_tpu.sim.committee import run_sim_scenario  # noqa: E402
from benchmark.metrics_check import wire_crypto_summary  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def capture(nodes: int, duration: int, rate: int, seed: int,
            workdir: str, cert_sig_scheme: str | None = None,
            commit_rule: str | None = None) -> dict:
    obj = {
        "name": f"wire_capture_n{nodes}"
        + (f"_{cert_sig_scheme}" if cert_sig_scheme else ""),
        "nodes": nodes,
        "workers": 1,
        "rate": rate,
        "tx_size": 512,
        "duration": duration,
        "seed": seed,
    }
    if cert_sig_scheme is not None:
        # The sim committee scopes NARWHAL_CERT_SIG_SCHEME from the
        # scenario env to the run (saved/restored like the sim-MAC
        # bracket), so paired arms can share one process.
        obj["env"] = {"NARWHAL_CERT_SIG_SCHEME": cert_sig_scheme}
    scenario = parse_scenario(obj, env={})
    from narwhal_tpu.crypto.aggregate import (
        resolve_scheme,
        scheme_override,
        set_scheme,
    )

    # The registry snapshot (and its crypto.cert_sig_scheme gauge_fn)
    # is taken AFTER the sim's run bracket restores the process scheme,
    # so hold the arm's scheme across run + snapshot + summary or the
    # frame anatomy prices the wrong formula.
    prev_scheme = scheme_override()
    if cert_sig_scheme is not None:
        set_scheme(resolve_scheme(cert_sig_scheme))
    try:
        art = run_sim_scenario(
            scenario, seed + 1, workdir, commit_rule=commit_rule
        )
        # The sim committee shares ONE registry; its post-run snapshot
        # is the committee-aggregated ledger (the reset happens at the
        # START of the next run, so the counters are intact here).
        snap = metrics.registry().snapshot()
        quorum = 2 * nodes // 3 + 1  # Committee.quorum_threshold
        wc = wire_crypto_summary([snap], quorum_weight=quorum)
    finally:
        set_scheme(prev_scheme)
    return {
        "what": (
            f"Clean simulated N={nodes} committee wire/crypto ledger "
            f"({duration} virtual s, rate {rate}, seed {seed}) — the "
            "ROADMAP item 4 before-number at committee scale.  Raw-"
            "frame anatomy (sim transport: v2 compact bodies, no "
            "per-connection dictionary/deflate); sim-MAC signatures "
            "(64 B, wire-size-exact)."
        ),
        "nodes": nodes,
        "quorum": quorum,
        "commit_rule": art["commit_rule"],
        "verdicts_ok": art["ok"],
        "schedule": art["schedule"],
        "wall": art["wall"],
        # Per-leader first→2f+1 direct-support arrival spread on the
        # virtual clock — the number that decides whether smaller
        # certificate frames (halfagg) loosen the ISSUE 19 N>=10
        # support-spread wall.
        "support_arrival": art.get("support_arrival"),
        "wire": wc["wire"],
        "crypto": wc["crypto"],
        "headline": {
            "cert_sig_scheme": wc["wire"].get("cert_sig_scheme"),
            "cert_sig_bytes_fraction": wc["wire"].get(
                "cert_sig_bytes_fraction"
            ),
            "cert_sig_bytes_per_cert": wc["wire"].get(
                "cert_sig_bytes_per_cert"
            ),
            "cert_bytes_per_frame": (
                round(
                    wc["wire"]["out"]["certificate"]["bytes"]
                    / wc["wire"]["out"]["certificate"]["frames"],
                    1,
                )
                if wc["wire"].get("out", {}).get("certificate", {}).get(
                    "frames"
                )
                else None
            ),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=20)
    ap.add_argument("--duration", type=int, default=30)
    ap.add_argument("--rate", type=int, default=600)
    ap.add_argument("--seed", type=int, default=90_000)
    ap.add_argument(
        "--workdir", default=os.path.join(REPO, ".sim_wire_capture")
    )
    ap.add_argument(
        "--cert-sig-scheme",
        choices=["individual", "halfagg"],
        default=None,
        help="pin the certificate-signature scheme for this capture "
        "(scoped to the run via the scenario env; default: whatever "
        "the process/NARWHAL_CERT_SIG_SCHEME setting is)",
    )
    ap.add_argument(
        "--commit-rule",
        choices=["classic", "lowdepth", "multileader"],
        default=None,
        help="consensus commit rule for the committee (default: the "
        "product's, NARWHAL_COMMIT_RULE's registry default)",
    )
    ap.add_argument("--artifact", default="artifacts/wire_n20_r19.json")
    args = ap.parse_args(argv)

    art = capture(
        args.nodes, args.duration, args.rate, args.seed, args.workdir,
        cert_sig_scheme=args.cert_sig_scheme,
        commit_rule=args.commit_rule,
    )
    os.makedirs(os.path.dirname(args.artifact) or ".", exist_ok=True)
    with open(args.artifact, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art["headline"], indent=1))
    if not art["verdicts_ok"]:
        print("WARNING: sim verdicts not all ok — capture still "
              "recorded, inspect the artifact", file=sys.stderr)
        return 1
    certs = art["wire"].get("out", {}).get("certificate", {})
    print(
        f"n={args.nodes}: {certs.get('frames', 0):,} cert frames, "
        f"{art['headline']['cert_bytes_per_frame']} B/frame, "
        f"sig fraction {art['headline']['cert_sig_bytes_fraction']}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
