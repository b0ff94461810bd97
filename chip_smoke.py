#!/usr/bin/env python3
"""The quickest proof that narwhal-tpu still starts on the chip.

    python chip_smoke.py [--seed N] [--phase verify|committee]

Needs one TPU; there is no CPU mode.  Two phases, in this order:

- ``verify``: the batched ed25519 verifier through the package boundary
  (``crypto.backend.set_backend("tpu")`` -> ``verify_batch_mask``) at the
  bottom and top rung of its pad ladder and at one size that splits into
  chunks.  Real signatures over distinct messages and keys made from
  ``--seed``, with forged signatures, wrong keys, a non-canonical S and a
  small-order key planted at known positions; the on-chip mask must equal
  OpenSSL's (``crypto.keys.cpu_verify``) item for item.  A second process
  then repeats the first calls and must load every program whole from
  the file the first one wrote (``ops/programs.py``): nothing traced.
- ``committee``: the upstream local deployment through the normal entry
  points (``benchmark/local_bench.py::run_bench`` -> ``python -m
  narwhal_tpu.node run``): 4 validators, 1 worker each, 512 B
  transactions, 500 kB batches, 20,000 tx/s offered, every parameter the
  product's default.  Primary 0 verifies on the chip (``--crypto-backend
  tpu``), the others with OpenSSL.  Two runs.  With all four up (20 s):
  payload must commit, primary 0 must have verified bursts on the TPU in
  lockstep with its peers (its headers certified, its round theirs),
  nothing may be built after its warm-up, and every replica's commit
  sequence must pass the golden replay and be a prefix of the longest.
  With one validator down (10 s; upstream's ``faults``): the same, and
  since three of four is exactly a quorum nothing commits without the
  on-chip verifier, so certificates primary 0 authored must be among
  those committed.

A chip belongs to one process at a time, so this parent NEVER imports
JAX: each user of the chip is a child that runs to exit before the next
starts.  Earlier lines are smoke readings, not benchmark numbers; the
last line of stdout is the result, and there is none when anything fails.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(REPO, ".chip_smoke")
KEEP_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    """One reading: to stdout, and to the file the chip tool brings back
    (parent and children append to the same one)."""
    print(msg, flush=True)
    os.makedirs(KEEP_DIR, exist_ok=True)
    with open(os.path.join(KEEP_DIR, "readings.txt"), "a") as f:
        f.write(msg + "\n")


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------- set-up


def build_native() -> None:
    """Rebuild the native data plane from what git tracks: an ignored
    ``.so`` left on disk is trusted by mtime, and a failed build falls
    back to the Python twin in silence — neither may pass for a start."""
    native_dir = os.path.join(REPO, "native")
    try:
        subprocess.run(["make", "-B", "-s", "-C", native_dir], check=True)
    except (OSError, subprocess.CalledProcessError) as e:
        raise SmokeFailure(f"native data plane did not build: {e}")
    sys.path.insert(0, REPO)
    from narwhal_tpu import native

    require(native.native_available(), "native data plane built but not loadable")
    say("native data plane: rebuilt from native/dataplane.c, loaded, in use")


# ------------------------------------------------- phase verify (children)


def make_batch(rng: random.Random, n: int):
    """``n`` (message, key, signature) triples, all distinct, with bad
    items planted.  Returns (msgs, keys, sigs, expected mask, planted)."""
    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.crypto.digest import Digest
    from narwhal_tpu.crypto.keys import cpu_verify
    from narwhal_tpu.ops import ed25519 as E

    msgs, keys, sigs = [], [], []
    for _ in range(n):
        kp = KeyPair.generate(rng.randbytes(32))
        m = rng.randbytes(32)
        msgs.append(m)
        keys.append(bytes(kp.name))
        sigs.append(bytes(kp.sign(Digest(m))))

    planted = {}

    def flip(sig: bytes, byte: int) -> bytes:
        return sig[:byte] + bytes([sig[byte] ^ 1]) + sig[byte + 1:]

    def plant(pos: int, kind: str) -> None:
        planted[pos] = kind
        if kind == "forged_r":
            sigs[pos] = flip(sigs[pos], 0)
        elif kind == "forged_s":
            sigs[pos] = flip(sigs[pos], 32)
        elif kind == "wrong_key":
            keys[pos] = keys[pos - 1]
        elif kind == "noncanonical_s":
            s = int.from_bytes(sigs[pos][32:], "little") + E.L_ORDER
            sigs[pos] = sigs[pos][:32] + s.to_bytes(32, "little")
        elif kind == "small_order":
            # The identity-key forgery: k*A is the identity for every k,
            # so R = [S]B satisfies the cofactorless equation for ANY
            # message.  The kernel's strict rule must reject it.
            s = rng.randrange(1, 1 << 64)
            rx, ry = E._ref_scalarmult(s)
            keys[pos] = (1).to_bytes(32, "little")
            sigs[pos] = (ry | ((rx & 1) << 255)).to_bytes(
                32, "little"
            ) + s.to_bytes(32, "little")

    # Spread over the batch, and over both chunks when it splits.
    plant(1, "forged_r")
    plant(2, "wrong_key")
    plant(3, "noncanonical_s")
    plant(5, "small_order")
    plant(n // 2, "forged_s")
    plant(n - 2, "small_order")
    plant(n - 1, "wrong_key")

    openssl = [bool(cpu_verify(m, k, s)) for m, k, s in zip(msgs, keys, sigs)]
    # The reference is OpenSSL's verdict, except on the one documented
    # class where the system's guarantee is stricter: a small-order key is
    # rejected whatever a cofactorless verifier says of it.
    expected = [
        ok and planted.get(i) != "small_order" for i, ok in enumerate(openssl)
    ]
    require(
        expected == [i not in planted for i in range(n)],
        "reference mask disagrees with the planted positions",
    )
    small = sorted({openssl[i] for i, k in planted.items() if k == "small_order"})
    return msgs, keys, sigs, expected, planted, small


def verify_child(seed: int, report_path: str, first: bool,
                 backend: str = "tpu") -> int:
    """Runs in its own process and holds the chip until it exits."""
    t_start = time.perf_counter()
    sys.path.insert(0, REPO)
    from narwhal_tpu.crypto import backend as cb

    try:
        cb.set_backend(backend)
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 3
    import jax
    import jax.numpy as jnp
    import numpy as np

    from narwhal_tpu import ops
    from narwhal_tpu.ops import ed25519 as E

    device = ops.device_identity()
    ladder = cb.get_backend().rungs
    cache = ops.program_dir()
    say(
        "verify[{}]: device {platform} / {kind} / count {count}".format(
            "first process" if first else "second process", **device
        )
        + f"; ladder {ladder}; program files in {cache}"
    )
    rng = random.Random(seed)
    sizes = sorted({ladder[0], ladder[-1]})
    if first:
        sizes.append(ladder[-1] + 5)  # splits: one top-rung chunk + 5
    shapes, seconds_to_first_result, ok = [], None, True
    for n in sizes:
        msgs, keys, sigs, expected, planted, small = make_batch(rng, n)
        before = ops.compile_stats()
        t0 = time.perf_counter()
        mask = cb.verify_batch_mask(msgs, keys, sigs, site="chip_smoke")
        first_call = time.perf_counter() - t0
        if seconds_to_first_result is None:
            seconds_to_first_result = time.perf_counter() - t_start
        after = ops.compile_stats()
        wrong = [i for i in range(n) if bool(mask[i]) != expected[i]]
        ok = ok and not wrong
        row = {
            "n": n,
            "chunks": [pad for _, _, pad in E.chunk_plan(n, ladder)],
            "mask_equals_reference": not wrong,
            "rejected_planted": sorted(planted),
            "openssl_on_small_order": small,
            "first_call_s": round(first_call, 3),
            **{
                k: round(after[k] - before[k], 3)
                for k in ("trace_seconds", "lower_seconds", "build_seconds")
            },
            **{
                k: after[k] - before[k]
                for k in ("programs_built", "programs_from_file",
                          "program_files_rejected")
            },
        }
        if first and n in ladder:
            # Steady state.  call_ms: the package boundary, host prep
            # included.  kernel_ms: the packed array in, timed around
            # np.asarray(mask) — dispatch + device + fetch.
            calls = []
            for _ in range(20):
                t0 = time.perf_counter()
                cb.verify_batch_mask(msgs, keys, sigs, site="chip_smoke")
                calls.append(1e3 * (time.perf_counter() - t0))
            packed = jnp.asarray(E.prepare_batch(msgs, keys, sigs, n))
            program, kernel = E.verify_program(n), []
            for _ in range(20):
                t0 = time.perf_counter()
                np.asarray(program(packed))
                kernel.append(1e3 * (time.perf_counter() - t0))
            row["steady_call_ms_median"] = statistics.median(calls)
            row["steady_kernel_ms_median"] = statistics.median(kernel)
        say(f"verify shape {json.dumps(row)}")
        if wrong:
            say(f"verify: MASK MISMATCH at {wrong[:16]} (planted {planted})")
        shapes.append(row)
    stats = (jax.devices()[0].memory_stats() or {})
    report = {
        "ok": ok,
        "device": device,
        "rungs": list(ladder),
        "shapes": shapes,
        "seconds_to_first_result": round(seconds_to_first_result, 3),
        "compile": ops.compile_stats(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    say(
        "verify[{}]: {:.1f} s from process start to first result; compile "
        "ledger {}; device peak_bytes_in_use {}".format(
            "first process" if first else "second process",
            seconds_to_first_result,
            json.dumps(report["compile"]),
            report["peak_bytes_in_use"],
        )
    )
    with open(report_path, "w") as f:
        json.dump(report, f)
    return 0 if ok else 1


def run_child(name: str, seed: int, timeout: float) -> dict:
    report_path = os.path.join(WORKDIR, f"{name}.json")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", name,
         "--seed", str(seed), "--report", report_path],
        cwd=REPO, timeout=timeout,
    )
    require(proc.returncode == 0, f"{name} child exited {proc.returncode}")
    with open(report_path) as f:
        return json.load(f)


def phase_verify(seed: int) -> dict:
    first = run_child("verify-first", seed, timeout=800)
    # The child above has exited, so the chip is free again.
    second = run_child("verify-second", seed, timeout=400)
    built, from_file, rejected, traced = (
        sum(s[k] for s in second["shapes"])
        for k in ("programs_built", "programs_from_file",
                  "program_files_rejected", "trace_seconds")
    )
    say(
        f"verify: second process built {built} programs, {from_file} of them "
        f"loaded from program files ({rejected} files rejected, "
        f"{traced} s of tracing), first result after "
        f"{second['seconds_to_first_result']} s (first process: "
        f"{first['seconds_to_first_result']} s)"
    )
    require(
        built > 0 and from_file == built and traced == 0 and rejected == 0,
        "second process traced or built a program instead of loading the "
        "file the first process wrote",
    )
    require(first["device"] == second["device"], "device changed between children")
    return first["device"]


# ------------------------------------------------ phase committee (parent)

NODES = 4


def silence_of_the_down(error: str, down: set) -> bool:
    """True for a quiesce health error all of whose firing rules are
    ``peer_vote_silence`` about a validator that was never started."""
    _, _, rules = error.partition("firing rule(s): ")
    fired = re.findall(r"(\w+)\[([^\]]*)\]", rules)
    return bool(fired) and all(
        rule == "peer_vote_silence" and subject in down
        for rule, subject in fired
    )


def committee_run(label: str, seed: int, backend: str, rate: int,
                  duration: int, faults: int) -> dict:
    """One run of the upstream local deployment through run_bench, every
    parameter the product's default, primary 0 on ``backend`` and the
    last ``faults`` validators never started.  Checks what must hold in
    any such run and returns the facts the phase decides on."""
    from benchmark.local_bench import kill_stale_nodes, run_bench
    from narwhal_tpu.config import Committee, Parameters, load_keypair
    from narwhal_tpu.consensus.replay import cross_node_prefix, replay_segments

    alive = NODES - faults
    workdir = os.path.join(WORKDIR, label)
    try:
        result = run_bench(
            nodes=NODES, workers=1, rate=rate, tx_size=512, duration=duration,
            faults=faults, crypto_backend=backend, tpu_primaries=1,
            workdir=workdir, audit=True, seed=seed, progress_wait=20,
        )
    except BaseException:
        kill_stale_nodes()
        raise
    finally:
        keep_logs(workdir, label)

    tag = f"committee[{label}]"
    say(
        f"{tag} (smoke reading, not a benchmark number): "
        f"consensus {result.consensus_tps:,.0f} tx/s at "
        f"{result.consensus_latency_ms:,.0f} ms, end-to-end "
        f"{result.end_to_end_tps:,.0f} tx/s at "
        f"{result.end_to_end_latency_ms:,.0f} ms over {result.duration_s:.1f} s; "
        f"{result.committed_bytes:,} payload bytes committed; "
        f"metrics_disagreement {result.metrics_disagreement}"
    )
    say(f"{tag} stage legs, mean ms (smoke reading): {json.dumps(result.stages_ms)}")
    say(f"{tag} round legs, mean ms (smoke reading): {json.dumps(result.round_stages_ms)}")
    # A validator that was never started is silent, and its peers' health
    # rule says so: that, and only that, is expected of a crash-fault run.
    committee = Committee.load(os.path.join(workdir, "committee.json"))
    down = {
        committee.primary(
            load_keypair(os.path.join(workdir, f"node-{i}.json")).name
        ).primary_to_primary
        for i in range(alive, NODES)
    }
    errors = [e for e in result.errors if not silence_of_the_down(e, down)]
    require(not errors, f"errors in the run: {errors[:5]}")
    require(
        result.committed_bytes > 0 and result.metrics_committed_tx > 0,
        "no payload transaction committed",
    )
    require(
        result.metrics_disagreement is not None,
        "logs and metrics were not cross-checked",
    )

    # Primary 0: verified on the chip, and built nothing after warm-up.
    log0 = open(os.path.join(workdir, "primary-0.log")).read()
    boot = re.search(r"Crypto backend: (\S+) on platform (\S+) \(.*\)", log0)
    require(boot is not None, "primary 0 logged no device-backed crypto backend")
    say(f"{tag} primary 0: {boot.group(0)}")
    ready = re.search(r"Verify backend \S+ ready: .*", log0)
    require(ready is not None, "primary 0 logged no verify-backend ready line")
    say(f"{tag} primary 0: {ready.group(0)}")
    snaps = [
        json.load(open(os.path.join(workdir, f"metrics-primary-{i}.json")))
        for i in range(alive)
    ]
    snap = snaps[0]
    bursts = snap["counters"].get("crypto.verify.ops.batch_burst", 0)
    dev_s = snap["histograms"].get("crypto.verify.device_seconds.batch_burst", {})
    sizes = snap["histograms"].get("crypto.verify.batch_size.batch_burst", {})
    report = snap["detail"].get("crypto.verify.device") or {}
    say(
        f"{tag} primary 0 verify ledger (smoke reading): {bursts} ops "
        f"in {dev_s.get('count', 0)} bursts, backend compute mean "
        f"{1e3 * (dev_s.get('mean') or 0):.2f} ms per burst; batch-size "
        f"histogram (cumulative) {json.dumps(sizes.get('buckets'))}"
    )
    say(f"{tag} primary 0 device report: {json.dumps(report)}")
    require(
        boot.group(1) == backend and boot.group(2) == report.get("platform"),
        f"primary 0 logged backend {boot.group(1)} on {boot.group(2)}",
    )
    require(bursts > 0 and dev_s.get("count", 0) > 0, "primary 0 verified no burst on the device")
    require(
        report.get("programs_at_ready") is not None
        and report["programs_built"] == report["programs_at_ready"],
        "primary 0 built a program after its ready line",
    )
    require(
        set(map(int, report["dispatched"])) <= set(report["rungs"]),
        "primary 0 dispatched a padded shape outside the warmed ladder",
    )

    # Agreement: every replica's own audit segment replays through the
    # golden oracle, and every commit sequence is a prefix of the longest.
    gc_depth = Parameters.load(os.path.join(workdir, "parameters.json")).gc_depth
    sequences = {}
    for i in range(alive):
        verdict = replay_segments(
            committee, gc_depth, [os.path.join(workdir, f"audit-primary-{i}.bin")]
        )
        sequences[f"primary-{i}"] = verdict.pop("commit_digests")
        require(verdict["ok"], f"primary {i} fails the golden replay: {verdict['violations'][:3]}")
    cross = cross_node_prefix(sequences)
    say(f"{tag} agreement: commit sequence lengths {cross['lengths']}, prefix ok {cross['ok']}")
    require(cross["ok"], f"replicas disagree: {cross['violations']}")
    require(min(cross["lengths"].values()) > 0, "a replica committed nothing")

    # What primary 0 contributed: headers it proposed, certificates its
    # peers' votes made of them, and those of its payload headers that
    # another primary committed.
    created = set(re.findall(r" Created B\d+\((\S+)\) -> ", log0))
    log1 = open(os.path.join(workdir, "primary-1.log")).read()
    committed = set(re.findall(r" Committed B\d+\((\S+)\) -> ", log1))
    facts = {
        "device": {k: report.get(k) for k in ("platform", "kind", "count")},
        "proposed": snap["counters"].get("primary.headers_proposed", 0),
        "certified": snap["counters"].get("primary.certificates_formed", 0),
        "rounds": [s["gauges"].get("primary.round", 0) for s in snaps],
        "payload_headers": len(created),
        "payload_headers_committed": len(created & committed),
    }
    say(
        f"{tag}: primary 0 proposed {facts['proposed']} headers, its peers' "
        f"votes certified {facts['certified']}; final rounds {facts['rounds']}; "
        f"of its {facts['payload_headers']} payload headers "
        f"{facts['payload_headers_committed']} were committed by primary 1"
    )
    return facts


def phase_committee(seed: int, backend: str = "tpu", rate: int = 20_000,
                    duration: int = 20) -> dict:
    """Drives the committee from this process, which stays off JAX; the
    only chip user is primary 0, a child of run_bench."""
    sys.path.insert(0, REPO)

    # Run 1: all four validators up.  Primary 0 must run in lockstep —
    # propose every round, have its headers certified by its peers' votes,
    # end on their round — but whether its certificates are CITED is the
    # protocol's to decide, not the smoke's: a header cites the first 2f+1
    # certificates of the previous round, three OpenSSL peers certify in
    # ~6 ms, and one chip dispatch costs ~22 ms, so at the product's
    # defaults few or none are (PERF.md, PR 22: 0 of ~128 in five runs, 35
    # of 128 in a sixth, while the lead the start order gives lasted).
    # The count is printed as it is.
    healthy = committee_run("healthy", seed, backend, rate, duration, faults=0)
    require(
        healthy["certified"] >= 0.9 * healthy["proposed"] > 0,
        "primary 0's headers were not certified by its peers",
    )
    require(
        max(healthy["rounds"]) - healthy["rounds"][0] <= 2,
        f"primary 0 fell behind its peers: final rounds {healthy['rounds']}",
    )
    # Run 2: the same deployment with one validator down (upstream's
    # `faults` parameter).  Three of four is exactly a quorum, so every
    # certificate needs primary 0's vote and every header cites primary
    # 0's certificate: nothing commits unless the on-chip verifier
    # carries its share, and what primary 0 authored must be committed.
    degraded = committee_run(
        "one-fault", seed, backend, rate, max(10, duration // 2), faults=1
    )
    require(
        degraded["payload_headers_committed"] > 0,
        "no certificate authored by primary 0 was committed",
    )
    require(healthy["device"] == degraded["device"], "device changed between runs")
    return healthy["device"]


def keep_logs(workdir: str, label: str) -> None:
    """Copy the run's logs and snapshots where the chip tool brings them
    back from (the stores are gone already; audit segments stay behind)."""
    dst = os.path.join(KEEP_DIR, label)
    os.makedirs(dst, exist_ok=True)
    for name in sorted(os.listdir(workdir)) if os.path.isdir(workdir) else []:
        if name.endswith((".log", ".json")) and not name.startswith("node-"):
            shutil.copy(os.path.join(workdir, name), dst)


# -------------------------------------------------------------------- main

PHASES = {"verify": phase_verify, "committee": phase_committee}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--phase", choices=sorted(PHASES), default=None,
                        help="run one phase only (default: verify, then committee)")
    parser.add_argument("--child", choices=["verify-first", "verify-second"],
                        help=argparse.SUPPRESS)
    parser.add_argument("--report", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        return verify_child(args.seed, args.report, args.child == "verify-first")

    device = None
    try:
        for scratch in (WORKDIR, KEEP_DIR):
            shutil.rmtree(scratch, ignore_errors=True)
            os.makedirs(scratch)
        build_native()
        for name in [args.phase] if args.phase else ["verify", "committee"]:
            t0 = time.time()
            found = PHASES[name](args.seed)
            say(f"phase {name}: ok in {time.time() - t0:.0f} s on {json.dumps(found)}")
            require(found.get("platform") == "tpu", f"phase {name} ran on {found}")
            require(device in (None, found), f"device changed: {device} then {found}")
            device = found
        require("jax" not in sys.modules, "the parent imported JAX")
    except (SmokeFailure, subprocess.TimeoutExpired, RuntimeError) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
