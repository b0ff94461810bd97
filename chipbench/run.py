#!/usr/bin/env python3
"""chipbench: one run of one cell.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process tree per run: set up a committee as the product starts it
(the configuration's ``chip_primaries`` each on a chip of its own, the
rest OpenSSL), warm up, measure for
``--seconds``, drain, tear down, hold what the run produced against the
plain reference, print one JSON line.  This parent never imports JAX
while a child needs the chip.  No chip is an error: the run exits
non-zero and prints no result.

Everything that belongs to one cell is data found by name:
``workloads/<workload>.json`` -> ``configs/<config>.json``, and for each
per-layer metric that BENCHMARK.json lists for the cell
``layer_metrics/<metric>.json`` -> ``readers/<kind>.py``.

``--rehearse`` (sandbox only; the driver's command never passes it) runs
every primary on OpenSSL, or with ``--rehearse jax`` primary 0 on the
batched verifier on jax-cpu.  It says ``platform: cpu`` and writes no
device metric.
"""

from __future__ import annotations

import time

T_START = time.time()  # process start, as near as Python lets it be read

import argparse  # noqa: E402
import base64  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import joins  # noqa: E402
import readers  # noqa: E402
import trace_reduce  # noqa: E402
from committee import Committee, RunFailure  # noqa: E402
from forger import Forger  # noqa: E402
from reference import check  # noqa: E402
from reference.wire import read_audit  # noqa: E402

WORKDIR = os.path.join(REPO, ".chipbench")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the run


def committed_digest16(log_text: str) -> set:
    """The short digests of the payload batches a primary's log commits."""
    return set(re.findall(r" Committed B\d+\(\S+\) -> (\S+)", log_text))


def sample_batches(worker_log: str) -> dict:
    """sample id -> short digest of the batch that holds it."""
    return {
        int(m.group(2)): m.group(1)
        for m in re.finditer(r" Batch (\S+) contains sample tx (\d+)", worker_log)
    }


def read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def wait_warm(com: Committee, deadline_s: float) -> None:
    """Until every live primary has committed payload."""
    deadline = time.time() + deadline_s
    while True:
        com.check_alive()
        if all(
            committed_digest16(read(com.path(f"primary-{i}.log")))
            for i in range(com.alive)
        ):
            return
        if time.time() > deadline:
            raise RunFailure("no payload committed on every live primary in the warm-up")
        time.sleep(0.25)


def all_due_committed(com: Committee, t0: float, seconds: float) -> bool:
    """True once every sample due in the window sits in a batch that
    every live primary's log has committed (the drain's end)."""
    batch16 = {}
    for i in range(com.alive):
        for w in range(com.workers):
            batch16.update(sample_batches(read(com.path(f"worker-{i}-{w}.log"))))
    committed = [
        committed_digest16(read(com.path(f"primary-{i}.log")))
        for i in range(com.alive)
    ]
    for c in range(com.alive * com.workers):
        for s in joins.due_in_window(
            joins.read_samples(com.path(f"client-{c}.samples")), t0, seconds
        ):
            d = batch16.get(s.id)
            if d is None or not all(d in got for got in committed):
                return False
    return True


def wait_rejections(com: Committee, node: int, forged: int, deadline: float) -> None:
    """Until primary ``node`` has counted every forgery it acknowledged.
    The last ones may still be in its queue, and a primary now and then
    stands still for a second or two (PERF.md, Open questions), so no
    fixed sleep is long enough; a verifier that accepts them never gets
    there, and the comparison says so after the deadline."""
    port = com.primary_metrics_port(node)
    while time.time() < deadline:
        com.check_alive()
        try:
            counted = com.scrape(port).get("counters", {}).get(
                "primary.invalid_signatures", 0
            )
        except OSError:  # standing still: ask again
            counted = -1
        if counted >= forged:
            return
        time.sleep(0.1)
    say(f"primary {node} counted fewer rejections than the {forged} forgeries sent")


def start_forgers(com: Committee, workload: dict, seed: int) -> dict:
    """launch index -> Forger: one for each primary the configuration
    puts on a chip, the workload's ``forged_per_s`` shared evenly among
    them, so that every chip's verifier has its rejections on the timed
    path."""
    forgers = {}
    if workload.get("forged_per_s"):
        share = workload["forged_per_s"] / len(com.forged_nodes)
        for k, node in enumerate(com.forged_nodes):
            forgers[node] = Forger(
                com.authority(node)["primary"]["primary_to_primary"], com.ids,
                com.alive, sorted(i.name for i in com.ids), share, seed + k,
                target=node,
            )
            forgers[node].start()
    return forgers


def drive(com: Committee, args, workload: dict, harness: dict) -> dict:
    """Set-up, window, drain.  Returns the window's facts; the committee
    is still up (the caller tears it down)."""
    com.start_nodes()
    com.start_clients(args.seed, workload)
    forgers = start_forgers(com, workload, args.seed)
    wait_warm(com, harness["warmup_deadline_s"])
    time.sleep(harness["settle_s"])
    com.check_alive()
    scrape0 = com.scrape_all()
    t0 = time.time()
    setup_s = t0 - T_START
    say(f"window opens after {setup_s:.1f} s of set-up")
    end = t0 + args.seconds
    trace_at = end - harness["trace_seconds"] - 0.1
    traced = False
    while True:
        now = time.time()
        if com.traced and not traced and now >= trace_at:
            open(com.path("trace") + ".go", "w").close()
            traced = True
        if now >= end:
            break
        com.check_alive()
        wake = trace_at if com.traced and not traced else end
        time.sleep(max(0.0, min(0.25, wake - time.time())))
    scrape1 = com.scrape_all()
    t1 = time.time()
    time.sleep(harness["drain_min_s"])
    if traced:
        # stop_trace collects from the device for seconds; the node is
        # not torn down under it.
        while not os.path.exists(com.path("trace") + ".times"):
            com.check_alive()
            if time.time() > t1 + harness["trace_wait_max_s"]:
                raise RunFailure("the trace was not written\n" + com.log_tail("primary-0.log"))
            time.sleep(0.5)
    while not all_due_committed(com, t0, args.seconds):
        com.check_alive()
        if time.time() > t1 + harness["drain_max_s"]:
            say("drain: samples still uncommitted after the longest wait")
            break
        time.sleep(1.0)
    for forger in forgers.values():
        forger.stop()
    for node, forger in forgers.items():
        if forger.error is not None or forger.is_alive():
            raise RunFailure(f"forger of primary {node} failed: {forger.error}")
    # Without forgers each of those primaries still has to have counted
    # what it was sent: nothing.
    forged = {node: 0 for node in com.forged_nodes}
    forged.update((node, len(forger.sent)) for node, forger in forgers.items())
    deadline = time.time() + harness["reject_count_wait_s"]
    for node in forgers:
        wait_rejections(com, node, forged[node], deadline)
    return {
        "t0": t0, "seconds": float(args.seconds), "setup_s": setup_s,
        "scrape0": scrape0, "scrape1": scrape1, "forged_sent": forged,
        "drain_s": time.time() - t1,
    }


# ------------------------------------------------------------ after the run


def gather(com: Committee, facts: dict) -> dict:
    """Everything the joins, the comparison and the readers take, from the
    files the run left behind."""
    run = dict(facts)
    snapshots = {}
    for i in range(com.alive):
        snapshots[f"primary-{i}"] = json.loads(read(com.path(f"metrics-primary-{i}.json")))
        for w in range(com.workers):
            snapshots[f"worker-{i}-{w}"] = json.loads(
                read(com.path(f"metrics-worker-{i}-{w}.json"))
            )
    run["snapshots"] = snapshots

    samples = []
    for c in range(com.alive * com.workers):
        samples += joins.read_samples(com.path(f"client-{c}.samples"))
    run["due"] = joins.due_in_window(samples, facts["t0"], facts["seconds"])

    # Stores first: their keys are the full digests, and the logs name a
    # batch by the first 16 characters of its base64.
    stores, full = [], {}
    for i in range(com.alive):
        per_worker = {}
        for w in range(com.workers):
            path = os.path.join(com.storedir, f"db-worker-{i}-{w}", "store.log")
            per_worker[w] = check.StoreIndex(path if os.path.exists(path) else None)
            for d in per_worker[w].index:
                full[base64.b64encode(d).decode()[:16]] = d
        stores.append(per_worker)
    run["stores"] = stores
    batch_of = {}
    for i in range(com.alive):
        for w in range(com.workers):
            for sid, d16 in sample_batches(read(com.path(f"worker-{i}-{w}.log"))).items():
                batch_of[sid] = full.get(d16)
    run["batch_of"] = batch_of

    # Commit time of a batch: the earliest among the live replicas.
    commit_time, batch_bytes = {}, {}
    for node, snap in snapshots.items():
        for h, e in snap.get("trace", {}).items():
            d = bytes.fromhex(h)
            if node.startswith("primary") and "commit" in e:
                commit_time[d] = min(e["commit"], commit_time.get(d, e["commit"]))
            if node.startswith("worker") and "bytes" in e:
                batch_bytes[d] = e["bytes"]
    run["commit_time"], run["batch_bytes"] = commit_time, batch_bytes
    # What each device-backed primary's verifier says of itself, by
    # launch index; `device_detail` is primary 0's (the readers' name).
    run["device_details"] = {
        i: snapshots[f"primary-{i}"].get("detail", {}).get("crypto.verify.device")
        for i in com.chip_nodes
    }
    run["device_detail"] = run["device_details"].get(0)
    return run


def window_dispatches(run: dict, node: int = 0):
    series = "crypto.verify.device_seconds.batch_burst"
    h1 = run["scrape1"][f"primary-{node}"].get("histograms", {}).get(series)
    h0 = run["scrape0"][f"primary-{node}"].get("histograms", {}).get(series)
    if h1 is None:
        return 0
    return h1["count"] - (h0["count"] if h0 else 0)


def device_of(com: Committee, run: dict) -> dict:
    """The run's ``device``: primary 0's report of its own chip, with
    ``count`` the SUM over the device nodes (each process sees one chip
    where several hold one) and ``memory_peak_bytes`` that of the
    fullest.  Each node's verifier has to have run on the device its
    own process saw, and all on one kind."""
    reports = {}
    for i in com.chip_nodes:
        reports[i] = json.loads(read(com.path(f"device-node-{i}.json")))
        detail = run["device_details"][i] or {}
        seen = tuple(reports[i][k] for k in ("platform", "kind", "count"))
        if tuple(detail.get(k) for k in ("platform", "kind", "count")) != seen:
            raise RunFailure(
                f"primary {i}'s verifier ran on {detail}, its process saw {reports[i]}")
    if len(reports) > 1 and any(r["count"] != 1 for r in reports.values()):
        raise RunFailure(f"a device node saw more than its own chip: {reports}")
    first = reports[com.chip_nodes[0]]
    if any((r["platform"], r["kind"]) != (first["platform"], first["kind"])
           for r in reports.values()):
        raise RunFailure(f"device nodes on different devices: {reports}")
    return dict(
        first,
        count=sum(r["count"] for r in reports.values()),
        memory_peak_bytes=max(r["memory_peak_bytes"] for r in reports.values()),
    )


def artifacts(com: Committee, run: dict, workload: dict, on_device: bool):
    gc_depth = com.config["parameters"]["gc_depth"]
    return check.Artifacts(
        sorted_keys=sorted(i.name for i in com.ids),
        gc_depth=gc_depth,
        tx_size=workload["tx_size"],
        audits=[
            read_audit(com.path(f"audit-primary-{i}.bin")) for i in range(com.alive)
        ],
        stores=run["stores"],
        due=run["due"],
        sample_worker={
            i * com.workers + w: w
            for i in range(com.alive) for w in range(com.workers)
        },
        batch_of=run["batch_of"],
        forged_sent=list(run["forged_sent"].values()),
        invalid_signatures=[
            run["snapshots"][f"primary-{i}"]["counters"].get(
                "primary.invalid_signatures", 0)
            for i in run["forged_sent"]
        ],
        device=[run["device_details"][i] or {} for i in com.chip_nodes]
        if on_device else None,
        window_dispatches=[window_dispatches(run, i) for i in com.chip_nodes]
        if on_device else None,
    )


def end_to_end(run: dict, workload: dict) -> tuple:
    lat, failed = joins.latencies_ms(run["due"], run["batch_of"], run["commit_time"])
    metrics = {"setup_s": {"value": run["setup_s"], "unit": "s"}}
    if lat:
        metrics["commit_latency_p50_ms"] = {
            "value": joins.percentile(lat, 50), "unit": "ms"}
        metrics["commit_latency_p95_ms"] = {
            "value": joins.percentile(lat, 95), "unit": "ms"}
    metrics["committed_tx_per_s"] = {
        "value": joins.committed_tx_per_s(
            run["batch_bytes"], run["commit_time"], run["t0"], run["seconds"],
            workload["tx_size"],
        ),
        "unit": "tx/s",
    }
    return metrics, failed


def per_layer(run: dict, bench: dict, cell: str) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = load_json("layer_metrics", m["name"] + ".json")
        value = readers.load(spec["kind"])(spec, run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=int, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", nargs="?", const="openssl", default=None,
                   choices=("openssl", "jax"),
                   help="sandbox only: no chip, says platform cpu")
    p.add_argument("--rate", type=int, default=None,
                   help="with --rehearse only: offered rate that a sandbox carries")
    p.add_argument("--controls", action="store_true",
                   help="proofs only: also print what the comparison reads "
                   "under each control of reference/control.py")
    args = p.parse_args(argv)
    if args.rate is not None and not args.rehearse:
        p.error("--rate is for --rehearse only: a cell's rate is its workload file's")
    if not os.path.isdir(os.path.join(REPO, "narwhal_tpu")):
        say("chipbench: the program (narwhal_tpu/) is not in this checkout")
        return 2

    bench = json.loads(read(os.path.join(REPO, "BENCHMARK.json")))
    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    harness = load_json("harness.json")
    if args.rate is not None:
        workload = dict(workload, rate=args.rate)
    if workload["chips"] != len(config["chip_primaries"]):
        say("chipbench: a cell takes as many chips as its configuration's "
            "chip_primaries names")
        return 2
    backend = {None: "tpu", "openssl": None, "jax": "jax"}[args.rehearse]
    on_device = backend is not None

    com = Committee(
        os.path.join(WORKDIR, args.workload), args.seed, config, harness,
        backend, traced=bool(args.trace) and on_device,
    )
    try:
        try:
            facts = drive(com, args, workload, harness)
        finally:
            com.teardown()
        run = gather(com, facts)
        device = {"platform": "cpu", "kind": "rehearsal", "count": 0,
                  "memory_peak_bytes": 0}
        if on_device:
            device = device_of(com, run)
        if not args.rehearse and (
            device["platform"] != "tpu" or device["count"] < workload["chips"]
        ):
            raise RunFailure(f"needs {workload['chips']} TPU chip(s), found {device}")
        run["device"] = device
        say(f"drain took {run['drain_s']:.1f} s; {len(run['due'])} samples due in the window")

        # `correct` is decided once the program is gone and its peak is read.
        t_ref = time.time()
        art = artifacts(com, run, workload, on_device)
        numbers = check.compare(art)
        correct = check.verdict(numbers)
        say(f"reference took {time.time() - t_ref:.1f} s; forged headers sent to "
            f"primaries {list(run['forged_sent'])}: {art.forged_sent}, invalid "
            f"signatures they counted: {art.invalid_signatures}")

        if args.controls:
            from reference import control

            for name, caught in control.report(art, args.seed).items():
                say(f"control {name}: fails {json.dumps(caught) if caught else 'NOTHING'}")

        e2e, failed = end_to_end(run, workload)
        line = {
            "correct": correct,
            "attempted": len(run["due"]),
            "failed": failed,
        }
        if args.trace:
            if on_device and not args.rehearse:
                times = json.loads(read(com.path("trace") + ".times"))
                # Reading the trace imports JAX; every child is gone and
                # this process is held to the CPU.
                os.environ["JAX_PLATFORMS"] = "cpu"
                # `start` is stamped after start_trace returned: the
                # profiler's own start-up is not part of the window.
                run["trace"] = trace_reduce.reduce_trace(
                    com.path("trace"), times["stop"] - times["start"]
                )
                if run["trace"] is None:
                    raise RunFailure("the trace holds no operation on a device")
                # For whoever reads the log: each traced call in trace time
                # (0 = start_trace was asked for, as near as it can be
                # told), the host's stamps on the same axis, and what the
                # counters say of the whole window.
                say("trace: " + json.dumps({
                    "calls": run["trace"]["calls"],
                    "host_start_s": times["start"] - times["asked"],
                    "host_stop_s": times["stop"] - times["asked"],
                    "window_dispatches": window_dispatches(run),
                }))
                device["busy_s"] = run["trace"]["busy_s"]
                device["window_s"] = run["trace"]["window_s"]
                line["breakdown"] = {
                    "device_ops": run["trace"]["device_ops"],
                    "idle_gaps": run["trace"]["idle_gaps"],
                }
            line["metrics"] = per_layer(run, bench, args.workload)
        else:
            line["metrics"] = e2e
        line["device"] = device
        # The rule the replicas' audit segments declared, and so the plain
        # rule each was replayed through (reference/check.py).
        line["commit_rule"] = check.commit_rule(art)
        line["compared"] = {
            k: {"value": v, "limit": check.LIMITS[k]} for k, v in numbers.items()
        }
        for k, v in numbers.items():
            say(f"compared {k} = {v} (limit {check.LIMITS[k]})")
        say(f"correct = {correct} (commit_rule {line['commit_rule']})")
        print(json.dumps(line), flush=True)
        return 0
    except RunFailure as e:
        say(f"chipbench: no result: {e}")
        return 3
    finally:
        com.teardown()
        com.remove_stores()


def on_signal(signum, frame):
    """A run that is ended from outside still stops its nodes and removes
    its stores from /dev/shm: main()'s ``finally`` does both."""
    raise RunFailure(f"ended by signal {signum}")


if __name__ == "__main__":
    for sig in (signal.SIGTERM, signal.SIGHUP, signal.SIGINT):
        signal.signal(sig, on_signal)
    sys.exit(main())
