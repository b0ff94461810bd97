#!/usr/bin/env python3
"""Can a host's chips be shared out, one to a process?

    python3 chipbench/chip_probe.py --chips 4 [--variant NAME ...]

Starts ``--chips`` Python processes AT ONCE, process k with the
environment ``committee.chip_env(k)`` gives device node k (variant
``chip_env``; ``none`` leaves it as it is), each running the program's verifier
(``ops/ed25519.py``) on one 128-row batch of ``chip_smoke.py::make_batch``.
Pass = as many processes as chips, each sees ``count`` 1 on platform
``tpu``, each holds another chip, the planted forgeries rejected in each.
"Another chip" is read two ways: the device files each process holds
open (``/proc/self/fd``) and the fact that all hold theirs at the same
moment (a barrier file: none exits before all have verified), which one
chip never allows.

This parent never imports JAX.  A proof, not part of a benchmark run: no
cell calls it.  Variants are tried in order and the first that passes
ends the probe; what each printed is kept in
``chiprun_out/chip_probe/<variant>-<chips>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from committee import chip_env  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "chip_probe")

# Process k's environment.  The first is what the harness gives device
# node k; it passed on the four-chip v5e host at the first try (PR 28),
# so no other recipe was ever needed or run.  "none" is what a process
# gets where one primary alone holds a chip: on one chip the cell as it
# runs, on four it shows what the variables are for.
VARIANTS = {"chip_env": chip_env, "none": lambda k: {}}


def child(k: int, n: int, barrier: str, out: str, backend: str) -> int:
    t_start = time.time()
    report = {"k": k, "pid": os.getpid(),
              "env": {v: os.environ[v] for v in sorted(os.environ)
                      if v.startswith("TPU_")}}
    try:
        sys.path.insert(0, REPO)
        import random

        from narwhal_tpu.crypto import backend as cb

        cb.set_backend(backend)  # "tpu" raises where JAX's platform is not the chip
        import jax

        import chip_smoke
        from narwhal_tpu import ops
        from narwhal_tpu.ops import ed25519 as E

        report["devices"] = [
            {"id": d.id, "platform": d.platform, "kind": d.device_kind,
             "process_index": d.process_index,
             "coords": list(getattr(d, "coords", ())),
             "core_on_chip": getattr(d, "core_on_chip", None)}
            for d in jax.devices()
        ]
        report["count"] = len(jax.devices())
        report["to_devices_s"] = time.time() - t_start
        msgs, keys, sigs, expected, planted, _ = chip_smoke.make_batch(
            random.Random(1000 + k), 128)
        t0 = time.time()
        mask = E.verify_batch_arrays(msgs, keys, sigs)
        report["first_call_s"] = time.time() - t0
        report["mask_equals_reference"] = [bool(m) for m in mask] == expected
        report["planted_rejected"] = all(not mask[i] for i in planted)
        t0 = time.time()
        for _ in range(20):
            E.verify_batch_arrays(msgs, keys, sigs)
        report["steady_call_ms"] = 1000 * (time.time() - t0) / 20
        report["compile"] = ops.compile_stats()
        held = set()
        for fd in os.listdir("/proc/self/fd"):
            try:
                link = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue
            if link.startswith(("/dev/accel", "/dev/vfio")):
                held.add(link)
        report["device_files"] = sorted(held)
        # Hold the chip until every sibling has verified on its own.
        open(f"{barrier}.{k}", "w").close()
        deadline = time.time() + 240
        while time.time() < deadline and not all(
                os.path.exists(f"{barrier}.{j}") or os.path.exists(f"{barrier}.{j}.failed")
                for j in range(n)):
            time.sleep(0.1)
        report["held_together"] = all(
            os.path.exists(f"{barrier}.{j}") for j in range(n))
        report["ok"] = True
    except Exception as e:  # the report is the point, whatever failed
        report["ok"] = False
        report["error"] = f"{type(e).__name__}: {e}"[:2000]
        open(f"{barrier}.{k}.failed", "w").close()
    with open(out, "w") as f:
        json.dump(report, f)
    return 0 if report["ok"] else 1


def probe(variant: str, n: int, timeout_s: float, backend: str) -> dict:
    work = os.path.join(REPO, ".chipbench", "probe", variant)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    base = dict(os.environ, PYTHONPATH=REPO)
    procs = []
    for k in range(n):
        env = dict(base, **VARIANTS[variant](k))
        log = open(os.path.join(work, f"child-{k}.log"), "w")
        procs.append((subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", str(k),
             "--chips", str(n), "--barrier", os.path.join(work, "verified"),
             "--out", os.path.join(work, f"child-{k}.json"), "--backend", backend],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO), log))
    deadline = time.time() + timeout_s
    for p, log in procs:
        try:
            p.wait(timeout=max(1.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        log.close()
    children = []
    for k in range(n):
        path = os.path.join(work, f"child-{k}.json")
        if os.path.exists(path):
            with open(path) as f:
                children.append(json.load(f))
        else:
            with open(os.path.join(work, f"child-{k}.log"), errors="replace") as f:
                children.append({"k": k, "ok": False, "error": "no report (killed "
                                 "at the time limit or died): " + f.read()[-1500:]})
    good = [c for c in children if c.get("ok")]
    files = [tuple(c.get("device_files", ())) for c in good]
    verdict = {
        "variant": variant, "processes": n,
        "env_of_process_1": VARIANTS[variant](min(1, n - 1)),
        "all_ran": len(good) == n,
        "each_sees_one_tpu": all(
            c["count"] == 1 and c["devices"][0]["platform"] == "tpu" for c in good),
        "forgeries_rejected_in_each": all(
            c["mask_equals_reference"] and c["planted_rejected"] for c in good),
        "held_their_chips_together": all(c.get("held_together") for c in good),
        "device_files_differ": len(set(files)) == len(files) and all(files),
        "children": children,
    }
    verdict["passed"] = bool(
        verdict["all_ran"] and verdict["each_sees_one_tpu"]
        and verdict["forgeries_rejected_in_each"]
        and verdict["held_their_chips_together"])
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{variant}-{n}.json"), "w") as f:
        json.dump(verdict, f, indent=1)
    return verdict


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=4)
    p.add_argument("--variant", nargs="*", default=["chip_env"], choices=list(VARIANTS))
    p.add_argument("--all", action="store_true", help="do not stop at the first pass")
    p.add_argument("--timeout", type=float, default=300.0)
    p.add_argument("--backend", default="tpu",
                   help="`jax` rehearses the probe itself off the chip; it cannot pass")
    p.add_argument("--child", type=int, default=None)
    p.add_argument("--barrier")
    p.add_argument("--out")
    args = p.parse_args()
    if args.child is not None:
        return child(args.child, args.chips, args.barrier, args.out, args.backend)
    passed = None
    for variant in args.variant:
        t0 = time.time()
        v = probe(variant, args.chips, args.timeout, args.backend)
        brief = {k: v[k] for k in v if k != "children"}
        brief["seconds"] = round(time.time() - t0, 1)
        print(json.dumps(brief), flush=True)
        for c in v["children"]:
            print("  " + json.dumps({k: c.get(k) for k in (
                "k", "ok", "count", "devices", "device_files", "first_call_s",
                "steady_call_ms", "to_devices_s", "error")})[:1800], flush=True)
        if v["passed"] and passed is None:
            passed = variant
            if not args.all:
                break
    print(json.dumps({"passed": passed, "chips": args.chips}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
