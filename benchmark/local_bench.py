"""Local benchmark: run a full committee + clients on localhost and measure.

Reference benchmark/benchmark/local.py (`fab local`): generate keys/committee/
parameters files, launch every primary/worker/client as its own OS process,
run for `duration` seconds, kill, parse logs, print the summary.

    python benchmark/local_bench.py --nodes 4 --workers 1 --rate 20000 \
        --tx-size 512 --duration 20
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from narwhal_tpu.utils.env import env_flag  # noqa: E402
from narwhal_tpu.config import (  # noqa: E402
    Authority,
    Committee,
    Parameters,
    PrimaryAddresses,
    WorkerAddresses,
    export_keypair,
)
from narwhal_tpu.crypto import KeyPair  # noqa: E402
from benchmark.logs import parse_logs  # noqa: E402
from benchmark.metrics_check import (  # noqa: E402
    loop_stall_summary,
    build_timeline,
    check_quiesce_health,
    cross_validate,
    load_snapshots,
    queue_pressure_summary,
    wire_crypto_summary,
)
from benchmark.scraper import Scraper  # noqa: E402


def build_committee(keypairs, base_port, workers, ips=None, worker_ips=None):
    """Sequential port allocation, one block of 2+3W ports per authority
    (reference config.py:63-86).  ``ips`` optionally maps authority index →
    IP for multi-host committees; ``worker_ips[i][wid]`` additionally puts
    authority i's worker wid on its own host (the reference's
    ``collocate=False`` placement, remote.py:108-130) — default is the
    authority IP for every role, all-loopback if ``ips`` is unset."""
    port = base_port
    auths = {}
    for i, kp in enumerate(keypairs):
        primary_ip = ips[i] if ips else "127.0.0.1"

        def nxt(ip):
            nonlocal port
            a = f"{ip}:{port}"
            port += 1
            return a

        primary = PrimaryAddresses(nxt(primary_ip), nxt(primary_ip))
        ws = {}
        for wid in range(workers):
            wip = worker_ips[i][wid] if worker_ips else primary_ip
            ws[wid] = WorkerAddresses(nxt(wip), nxt(wip), nxt(wip))
        auths[kp.name] = Authority(stake=1, primary=primary, workers=ws)
    return Committee(auths)


def metrics_port(base_port, nodes, workers, node, worker=None):
    """Metrics port for one process, in the block directly above the
    committee's own ports (``build_committee`` consumes 2+3W consecutive
    ports per authority starting at ``base_port``).  One definition for
    every harness: a layout change that only updated one copy would
    silently collide metrics ports with committee ports in the other.
    ``worker=None`` addresses authority ``node``'s primary; otherwise
    its worker ``worker``."""
    mbase = base_port + nodes * (2 + 3 * workers)
    if worker is None:
        return mbase + node
    return mbase + nodes + node * workers + worker


def kill_stale_nodes() -> None:
    """Kill node/client processes left over from a previous run of THIS
    checkout — the reference harness does the same by killing its old tmux
    testbed (reference benchmark/benchmark/local.py:26-29).  Stale nodes
    squat on ports and burn CPU, silently corrupting the next measurement.
    Scoped by process cwd == this repo, so concurrent harnesses in other
    checkouts are left alone.  SIGTERM with a grace period, not SIGKILL:
    a stale node may hold the chip mid-call, and SIGTERM lets it finish
    that call, flush and release the device (see the teardown comment in
    run_bench)."""
    me = os.getpid()
    stale = []
    for pid_s in os.listdir("/proc"):
        if not pid_s.isdigit() or int(pid_s) == me:
            continue
        try:
            with open(f"/proc/{pid_s}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\x00", b" ")
            if (b"-m narwhal_tpu.node" not in cmd
                    and b"narwhal_tpu.node.benchmark_client" not in cmd):
                continue
            if os.readlink(f"/proc/{pid_s}/cwd") != REPO:
                continue
            os.kill(int(pid_s), signal.SIGTERM)
            stale.append(int(pid_s))
        except OSError:
            continue
    # Same 75 s grace as run_bench's teardown: a stale node may be
    # mid-device-call, and its graceful release can take that long.
    deadline = time.time() + 75
    for pid in stale:
        while time.time() < deadline:
            try:
                os.kill(pid, 0)
            except OSError:
                break  # gone
            time.sleep(0.2)
        else:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass


def wait_for_boot(log_paths, deadline_s: float = 60, quiet: bool = False,
                  procs=()):
    """Block until every log in ``log_paths`` contains the node boot
    sentinel ("successfully booted"), up to ``deadline_s``.  Never start
    the measured load against a committee that hasn't booted: the e2e
    window opens at the first client's "Start sending" line, so any boot
    time the clients outrun is charged to the measurement (the round-3/4
    failure measured a committee that never came up at all).  Shared with
    fault_bench so both harnesses watch the same sentinel.  ``procs``:
    the processes writing those logs — if one exits, stop waiting (it
    will never boot)."""
    deadline = time.time() + deadline_s
    pending = set(log_paths)
    while pending and time.time() < deadline:
        if any(p.poll() is not None for p in procs):
            break
        for p in list(pending):
            try:
                if "successfully booted" in open(p).read():
                    pending.discard(p)
            except OSError:
                pass
        if pending:
            time.sleep(0.2)
    if pending and not quiet:
        print(f"WARNING: nodes never booted: {pending}", file=sys.stderr)
    return not pending


# Boot deadline for a device-backed primary: two rungs of the verify
# ladder compile cold in about five minutes on a v5e host (PERF.md).
DEVICE_BOOT_DEADLINE_S = 900


def share_rate(rate: int, n_clients: int) -> int:
    """Per-client tx rate: the committee-wide rate split evenly, floor 1
    (reference local.py:78)."""
    return max(1, rate // max(1, n_clients))


def client_command(addr: str, tx_size: int, rate_share: int,
                   client_idx: int):
    """argv for one benchmark client against worker ``addr``.  The
    sample-offset keys each client's latency samples into its own id
    space so merged logs never collide.  Shared with fault_bench so the
    fault-arm load is flag-identical to the bench load."""
    return [
        sys.executable,
        "-m",
        "narwhal_tpu.node.benchmark_client",
        addr,
        "--size",
        str(tx_size),
        "--rate",
        str(rate_share),
        "--sample-offset",
        str(client_idx << 32),
        "--nodes",
        addr,
    ]


def run_bench(
    nodes: int = 4,
    workers: int = 1,
    rate: int = 20_000,
    tx_size: int = 512,
    duration: int = 20,
    base_port: int = 7000,
    faults: int = 0,
    header_size: int = 1_000,
    batch_size: int = 500_000,
    max_header_delay: int = 100,
    min_header_delay: int = 0,
    header_linger: int = 0,
    max_batch_delay: int = 100,
    workdir: str = None,
    keep_logs: bool = False,
    quiet: bool = False,
    crypto_backend: str = None,
    tpu_primaries: int = None,
    scrape_interval: float = 1.0,
    progress_wait: float = 0.0,
    loop_watchdog_ms: int = 0,
    trace_out: str = None,
    wire_v2: bool = None,
    verify_window_ms: float = None,
    commit_rule: str = None,
    cert_sig_scheme: str = None,
    audit: bool = False,
    seed: int = None,
):
    """Run one committee + clients on localhost; return the ParseResult.

    ``tpu_primaries`` limits the device flags (``crypto_backend="tpu"`` or
    ``"jax"``) to the first N primaries: a chip belongs to one process, so
    on a one-chip host a mixed committee (one device-backed primary, the
    rest CPU) is the honest way to exercise the device path end-to-end.
    ``None`` means every primary gets the flags (all-CPU or all-jax runs).

    ``audit``: every primary appends its consensus audit segment to
    ``{workdir}/audit-primary-{i}.bin`` for ``replay_segments``.

    ``progress_wait``: extra seconds (beyond ``duration``) the window may
    stretch while the scraped metrics show zero committed PAYLOAD batches
    — on a starved shared core the clients can ramp so late that the
    fixed window closes before the first client batch commits (empty
    headers commit throughout, so certificate counts can't gate this).
    0 keeps the fixed-duration behavior; requires metrics enabled.
    """
    kill_stale_nodes()
    workdir = workdir or os.path.join(REPO, ".bench")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    # Node stores go on tmpfs when available: a 25 s run writes several GB
    # of batch logs, and on a shared-core host the disk writeback of run N
    # steals the core from run N+1 (kworker/flush), corrupting the
    # measurement.  The reference benches on local NVMe where this doesn't
    # bite; tmpfs gives the same effective behavior here.
    storedir = workdir
    if os.path.isdir("/dev/shm"):
        storedir = "/dev/shm/narwhal_bench"
        shutil.rmtree(storedir, ignore_errors=True)
        os.makedirs(storedir, exist_ok=True)

    # ``seed`` makes the committee's identities (and with them the leader
    # schedule) the same on every run; None draws fresh keys.
    keypairs = [
        KeyPair.generate(
            None
            if seed is None
            else hashlib.sha256(f"local-bench:{seed}:{i}".encode()).digest()
        )
        for i in range(nodes)
    ]
    committee = build_committee(keypairs, base_port, workers)
    committee.export(f"{workdir}/committee.json")
    params = Parameters(
        header_size=header_size,
        batch_size=batch_size,
        max_header_delay=max_header_delay,
        min_header_delay=min_header_delay,
        header_linger=header_linger,
        max_batch_delay=max_batch_delay,
    )
    params.export(f"{workdir}/parameters.json")
    for i, kp in enumerate(keypairs):
        export_keypair(kp, f"{workdir}/node-{i}.json")

    # One environment for every child: this checkout on PYTHONPATH plus
    # the committee-wide arm pins below.  Only the device-flagged
    # primaries import JAX (crypto.backend defers it), so only they touch
    # the chip; this harness process never imports JAX, or it would hold
    # the chip its children need.
    env = dict(os.environ, PYTHONPATH=REPO)
    if loop_watchdog_ms:
        # Loop-stall watchdog smoke arm: every node measures its own
        # event-loop stalls into runtime.loop_stall_seconds; the bench
        # JSON's `runtime` section joins them per node after the run.
        env["NARWHAL_LOOP_WATCHDOG_MS"] = str(loop_watchdog_ms)
    if wire_v2 is not None:
        # Paired wire-format A/B arm pin: the whole committee speaks one
        # format (mixed-version committees are unsupported), so the flag
        # goes to every child uniformly; None inherits the environment.
        env["NARWHAL_WIRE_V2"] = "1" if wire_v2 else "0"
    if verify_window_ms is not None:
        # Verify-batch accumulation window (crypto A/B batched arm):
        # every primary coalesces drained bursts into one backend
        # dispatch within this window; None inherits the environment.
        env["NARWHAL_VERIFY_BATCH_WINDOW_MS"] = str(verify_window_ms)
    if commit_rule is not None:
        # Commit-rule A/B arm pin: committee-wide like the wire format
        # (a mixed-rule committee diverges by design); every child gets
        # the env knob, and each primary's boot log records the rule.
        env["NARWHAL_COMMIT_RULE"] = commit_rule
    if cert_sig_scheme is not None:
        # Cert-sig-scheme A/B arm pin: committee-wide like the commit
        # rule — a mixed-scheme committee refuses each other's
        # certificate frames by design (SchemeMismatch).
        env["NARWHAL_CERT_SIG_SCHEME"] = cert_sig_scheme
    procs = []
    primary_logs, worker_logs, client_logs = [], [], []
    metrics_paths = []
    # NARWHAL_METRICS=0 stubs the registry in every child — the knob the
    # overhead measurement flips; cross-validation is skipped since the
    # snapshots would be empty.
    metrics_on = env_flag("NARWHAL_METRICS")
    # Live scrape plane: every node also gets a --metrics-port in the
    # block directly after the committee's own ports (metrics_port), and
    # the harness polls them all during the run (benchmark/scraper.py)
    # to build the committee timeline and gate on /healthz at quiesce.
    scrape_targets = []  # (name, host, port)

    def spawn(cmd, logfile, env=env, tpu=False):
        f = open(logfile, "w")
        p = subprocess.Popen(
            cmd, stdout=f, stderr=subprocess.STDOUT, env=env, cwd=REPO
        )
        procs.append((p, f, tpu))
        return p

    def teardown():
        """SIGTERM everything, then wait per process; SIGKILL only past
        the grace period."""
        for p, f, tpu in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except ProcessLookupError:
                pass
        # PER-PROCESS grace, not one shared deadline: the SIGTERM path is
        # also what flushes each node's final metrics snapshot (the only
        # one guaranteed to carry the full stage trace), and on a loaded
        # shared core one slow shutdown must not eat the whole budget and
        # get the remaining nodes SIGKILLed un-flushed — that would
        # undercount the metrics side and spuriously hard-fail the
        # cross-check.  15 s: a healthy node flushes and exits in <2 s,
        # so the budget is only consumed by pathological shutdowns.  A
        # chip holder gets 75 s: it may be inside a device call, and
        # SIGTERM lets it finish the call and release the chip cleanly
        # (the next process to want the chip waits for that anyway).
        for p, f, tpu in procs:
            try:
                p.wait(timeout=75 if tpu else 15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            f.close()

    # Device flags go only to the device-designated primaries; any other
    # explicitly requested flag (e.g. --crypto-backend cpu) goes to every
    # node unconditionally.  "tpu" is the chip (a node given it on a host
    # with no TPU fails at boot); "jax" is the same batched verifier on
    # whatever platform JAX has — it still pays XLA warm-up at boot, so
    # it gets the same ordering and deadline.
    base_flags, device_flags = [], []
    if crypto_backend in ("tpu", "jax"):
        device_flags += ["--crypto-backend", crypto_backend]
    elif crypto_backend:
        base_flags += ["--crypto-backend", crypto_backend]

    alive = nodes - faults  # crash faults: the last `faults` nodes never boot
    n_device = 0
    if device_flags:
        n_device = alive if tpu_primaries is None else min(tpu_primaries, alive)
    if crypto_backend == "tpu" and n_device > 1:
        # Every such primary would open the same default chip, and a chip
        # belongs to one process.  Until a deployment maps processes to
        # chips (ROADMAP D1) the harness refuses rather than let all but
        # one of them fail or hang at boot.
        raise ValueError(
            f"crypto_backend='tpu' for {n_device} primaries: a chip "
            "belongs to one process and this harness assigns no chips to "
            "processes — pass tpu_primaries=1 (or 'jax', the same verifier "
            "on whatever platform JAX has)"
        )
    # A separate prewarm process earns its cost (a process start) only
    # when SEVERAL device-backed primaries follow: it compiles each
    # program once and they all load its program file, instead of every
    # one compiling cold side by side.
    # A single device-backed primary is its own prewarm — it is started
    # first, below, and the rest of the committee waits for it.  Either
    # way a failure here is fatal: carrying on would measure a committee
    # whose device path never came up.
    if n_device > 1:
        if not quiet:
            print("Prewarming device kernels...", file=sys.stderr)
        warm_cmd = [
            sys.executable,
            "-m",
            "narwhal_tpu.node",
            "prewarm",
            "--crypto-backend",
            crypto_backend,
        ]
        # subprocess.run returns only once the child has EXITED, so it
        # has released the device before any primary asks for it.
        warm = subprocess.run(warm_cmd, env=env, cwd=REPO, check=False)
        if warm.returncode != 0:
            raise RuntimeError(
                f"device prewarm exited {warm.returncode}: the "
                f"{' '.join(device_flags)} primaries cannot come up"
            )

    def primary_files(i):
        """(log, metrics snapshot path, metrics port) of primary ``i``."""
        return (
            f"{workdir}/primary-{i}.log",
            f"{workdir}/metrics-primary-{i}.json",
            metrics_port(base_port, nodes, workers, i),
        )

    def spawn_primary(i):
        on_device = i < n_device
        log, mpath, mport = primary_files(i)
        node_env = env
        if audit:
            # Per-node consensus audit segment for the golden-oracle
            # replay (consensus/replay.py), as fault_bench wires it.
            node_env = dict(
                env,
                NARWHAL_CONSENSUS_AUDIT=f"{workdir}/audit-primary-{i}.bin",
            )
        return spawn(
            [
                sys.executable,
                "-m",
                "narwhal_tpu.node",
                "run",
                "--keys",
                f"{workdir}/node-{i}.json",
                "--committee",
                f"{workdir}/committee.json",
                "--parameters",
                f"{workdir}/parameters.json",
                "--store",
                f"{storedir}/db-primary-{i}",
                "--benchmark",
                "--metrics-path",
                mpath,
                "--metrics-port",
                str(mport),
                *base_flags,
                *(device_flags if on_device else []),
                "primary",
            ],
            log,
            env=node_env,
            tpu=on_device,
        )

    # Device-backed primaries first, and nothing else until they have
    # booted: building the verify ladder takes minutes (cold) or ~30 s a
    # shape (warm cache), and three CPU primaries are a quorum — started
    # alongside, they would run thousands of empty rounds meanwhile and
    # the device-backed primary would join far beyond gc_depth behind.
    device_procs = [spawn_primary(i) for i in range(n_device)]
    device_logs = [primary_files(i)[0] for i in range(n_device)]
    if device_procs and not wait_for_boot(
        device_logs,
        deadline_s=DEVICE_BOOT_DEADLINE_S,
        quiet=quiet,
        procs=device_procs,
    ):
        teardown()
        raise RuntimeError(
            "device-backed primaries never booted; see "
            + ", ".join(device_logs)
        )
    for i in range(n_device, alive):
        spawn_primary(i)
    for i in range(alive):
        log, mpath, mport = primary_files(i)
        primary_logs.append(log)
        metrics_paths.append(mpath)
        scrape_targets.append((f"primary-{i}", "127.0.0.1", mport))
        for wid in range(workers):
            log = f"{workdir}/worker-{i}-{wid}.log"
            worker_logs.append(log)
            mpath = f"{workdir}/metrics-worker-{i}-{wid}.json"
            metrics_paths.append(mpath)
            mport = metrics_port(base_port, nodes, workers, i, wid)
            scrape_targets.append((f"worker-{i}-{wid}", "127.0.0.1", mport))
            spawn(
                [
                    sys.executable,
                    "-m",
                    "narwhal_tpu.node",
                    "run",
                    "--keys",
                    f"{workdir}/node-{i}.json",
                    "--committee",
                    f"{workdir}/committee.json",
                    "--parameters",
                    f"{workdir}/parameters.json",
                    "--store",
                    f"{storedir}/db-worker-{i}-{wid}",
                    "--benchmark",
                    "--metrics-path",
                    mpath,
                    "--metrics-port",
                    str(mport),
                    "worker",
                    "--id",
                    str(wid),
                ],
                log,
            )

    wait_for_boot(primary_logs + worker_logs, deadline_s=60, quiet=quiet)

    # One client per live worker, rate split evenly (reference local.py:78).
    committee_obj = committee
    rate_share = share_rate(rate, alive * workers)
    client_idx = 0
    for i in range(alive):
        kp = keypairs[i]
        for wid in range(workers):
            addr = committee_obj.worker(kp.name, wid).transactions
            log = f"{workdir}/client-{i}-{wid}.log"
            client_logs.append(log)
            spawn(client_command(addr, tx_size, rate_share, client_idx), log)
            client_idx += 1

    if not quiet:
        print(f"Running benchmark ({duration} s)...", file=sys.stderr)
    # The scraper runs across the whole measurement window, building the
    # committee time-series the post-mortem snapshots cannot: per-node
    # progress at each tick, so mid-run stalls have a timestamp.
    scraper = None
    healthz = {}
    flight_rings = {}
    if metrics_on:
        scraper = Scraper(scrape_targets, interval_s=scrape_interval).start()
    time.sleep(duration)
    if scraper is not None:
        scraper.wait_for_payload_commits(progress_wait, quiet=quiet)
    if scraper is not None:
        # Quiesce gate BEFORE teardown: a firing health rule on any live
        # node fails the run (appended to result.errors below).
        healthz = scraper.healthz_all()
        # The flight rings ride along: even a clean run's bench JSON
        # carries each node's last-seconds event history.
        flight_rings = scraper.flight_all()
        scraper.stop()

    teardown()

    read = lambda paths: [open(p).read() for p in paths]  # noqa: E731
    names = lambda paths: [os.path.basename(p) for p in paths]  # noqa: E731
    result = parse_logs(
        read(client_logs),
        read(worker_logs),
        read(primary_logs),
        tx_size,
        client_names=names(client_logs),
        worker_names=names(worker_logs),
        primary_names=names(primary_logs),
    )
    # Cross-check the log-scraped totals against the nodes' own metrics
    # snapshots and derive the per-stage pipeline latency breakdown.  A
    # >5% disagreement between the two measurement channels appends a
    # fatal error (every caller treats result.errors as run failure).
    if metrics_on:
        snapshots = load_snapshots(metrics_paths, result.errors)
        mc = cross_validate(result, snapshots, tx_size)
        # Clock model + causal attribution sections: the reconciled
        # per-node corrections the stage join applied, the slowest
        # end-to-end chain(s), and the ranked quorum-straggler table.
        result.clock = mc.get("clock", {})
        result.critical_path = mc.get("critical_path", {})
        result.stragglers = mc.get("stragglers", {})
        # Wire-goodput + crypto-cost ledger sections (the `wire` and
        # `crypto` keys of the bench JSON).
        result.runtime = loop_stall_summary(snapshots)
        wc = wire_crypto_summary(
            snapshots,
            committed_payload_bytes=result.committed_bytes,
            quorum_weight=committee.quorum_threshold(),
        )
        result.wire, result.crypto = wc["wire"], wc["crypto"]
        # Per-channel backpressure accounting: the scraper's 1 Hz sample
        # timeline gives first_saturating a WHEN; the final snapshots
        # give every channel its totals either way.
        result.queues = queue_pressure_summary(
            snapshots, scraper.samples if scraper else []
        )
        check_quiesce_health(healthz, result.errors)
        result.timeline = build_timeline(
            scraper.samples if scraper else [],
            interval_s=scrape_interval,
            healthz=healthz,
        )
        result.flight = flight_rings
        with open(f"{workdir}/timeline.json", "w") as f:
            json.dump(result.timeline, f, indent=1)
        if trace_out:
            # One Perfetto-loadable trace of the whole committee run:
            # the final snapshots carry the stage/round traces, flight
            # rings and profiler timelines; the scraped timeline adds
            # the committee-wide rate counters and health transitions.
            from benchmark import trace_export

            trace_export.export(
                trace_export.load_named_snapshots(metrics_paths),
                trace_out,
                timeline=result.timeline,
                flight=flight_rings,
                quiet=quiet,
            )
    if not keep_logs:
        for i in range(alive):
            shutil.rmtree(f"{storedir}/db-primary-{i}", ignore_errors=True)
            for wid in range(workers):
                shutil.rmtree(
                    f"{storedir}/db-worker-{i}-{wid}", ignore_errors=True
                )
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--workers", type=int, default=1)
    parser.add_argument("--rate", type=int, default=20_000)
    parser.add_argument("--tx-size", type=int, default=512)
    parser.add_argument("--duration", type=int, default=20)
    parser.add_argument("--faults", type=int, default=0)
    parser.add_argument("--base-port", type=int, default=7000)
    parser.add_argument(
        "--min-header-delay",
        type=int,
        default=0,
        help="Sui-style cadence floor (ms): a parent quorum plus any "
        "payload proposes after this delay instead of riding "
        "--max-header-delay; 0 = reference behavior",
    )
    parser.add_argument(
        "--header-linger",
        type=int,
        default=0,
        help="Parent-linger window (ms): a just-advanced round holds its "
        "header open this long so post-quorum parent certificates are "
        "still cited — the proposer half of the multileader commit "
        "rule; 0 = reference behavior",
    )
    parser.add_argument("--max-header-delay", type=int, default=100)
    parser.add_argument("--json", action="store_true")
    parser.add_argument(
        "--loop-watchdog-ms",
        type=int,
        default=0,
        help="Arm the event-loop stall watchdog on every node "
        "(NARWHAL_LOOP_WATCHDOG_MS) and emit the per-node `runtime` "
        "section (runtime.loop_stall_seconds series) in the bench JSON; "
        "0 = off",
    )
    parser.add_argument(
        "--trace-out",
        default=None,
        help="Export the whole run as ONE Perfetto-loadable Chrome trace "
        "(process row per node, flow arrows per committed digest, health/"
        "flight instants, sampled-CPU track) to this path — see "
        "benchmark/trace_export.py",
    )
    parser.add_argument(
        "--crypto-backend", choices=["cpu", "tpu", "jax"], default=None,
        help="Primary verification backend: tpu runs the batched "
        "verifier on the chip (the node fails at boot without one), jax "
        "runs it on whatever platform JAX has (jax-cpu for the A/B "
        "fallback arm); default inherits NARWHAL_CRYPTO_BACKEND, else cpu",
    )
    parser.add_argument(
        "--verify-window-ms", type=float, default=None,
        help="Verify-batch accumulation window for every primary "
        "(NARWHAL_VERIFY_BATCH_WINDOW_MS): coalesce drained bursts "
        "arriving within this many ms into one backend dispatch; "
        "unset inherits the environment (default off)",
    )
    parser.add_argument(
        "--commit-rule",
        choices=["classic", "lowdepth", "multileader"],
        default=None,
        help="Consensus commit rule for the whole committee "
        "(NARWHAL_COMMIT_RULE): classic = Tusk depth-3 commits, "
        "lowdepth = Mysticeti-style direct commits one round after the "
        "leader, multileader = 3 leader slots per even round anchoring "
        "on the lowest supported slot; unset inherits the environment "
        "(unset there too: the product's default, lowdepth)",
    )
    parser.add_argument(
        "--tpu-primaries",
        type=int,
        default=None,
        help="Apply the device flags to only the first N primaries "
        "(a chip belongs to one process and the harness assigns none: "
        "with --crypto-backend tpu use 1, more is refused)",
    )
    args = parser.parse_args()

    result = run_bench(
        nodes=args.nodes,
        workers=args.workers,
        rate=args.rate,
        tx_size=args.tx_size,
        duration=args.duration,
        faults=args.faults,
        base_port=args.base_port,
        min_header_delay=args.min_header_delay,
        header_linger=args.header_linger,
        max_header_delay=args.max_header_delay,
        crypto_backend=args.crypto_backend,
        tpu_primaries=args.tpu_primaries,
        loop_watchdog_ms=args.loop_watchdog_ms,
        trace_out=args.trace_out,
        verify_window_ms=args.verify_window_ms,
        commit_rule=args.commit_rule,
    )
    if result.errors:
        print("ERRORS detected in logs:", file=sys.stderr)
        for e in result.errors[:10]:
            print("  " + e, file=sys.stderr)
        sys.exit(1)
    if args.json:
        print(
            json.dumps(
                {
                    "consensus_tps": result.consensus_tps,
                    "consensus_latency_ms": result.consensus_latency_ms,
                    "end_to_end_tps": result.end_to_end_tps,
                    "end_to_end_latency_ms": result.end_to_end_latency_ms,
                    "committed_bytes": result.committed_bytes,
                    "samples": result.samples,
                    # Metrics-channel numbers: per-stage latency breakdown
                    # (seal → quorum → digest-at-primary → header → cert →
                    # commit, mean ms per leg) and the cross-check of the
                    # two measurement channels.
                    "stages_ms": result.stages_ms,
                    # Round-cadence attribution: mean ms per ROUND_STAGES
                    # sub-leg (telescoping to the round period).
                    "round_stages_ms": result.round_stages_ms,
                    "metrics_committed_tx": round(
                        result.metrics_committed_tx, 1
                    ),
                    "metrics_disagreement": result.metrics_disagreement,
                    # Wire-goodput & crypto-cost ledgers: per-type
                    # bandwidth (retransmits split out), goodput ratio,
                    # per-site sign/verify attribution + protocol check.
                    "wire": result.wire,
                    # Loop-stall watchdog series (when the run armed it):
                    # per-node runtime.loop_stall_seconds + last stack.
                    "runtime": result.runtime,
                    "crypto": result.crypto,
                    # Live committee timeline (scraper): per-node series,
                    # per-peer RTT matrix, /healthz verdicts at quiesce.
                    "timeline": result.timeline,
                    # Per-node flight-recorder rings pulled at quiesce
                    # (/debug/flight): the last-seconds event history.
                    "flight": result.flight,
                    # Per-channel queue backpressure accounting + the
                    # first-saturating attribution (knee matrix input).
                    "queues": result.queues,
                    # Clock model: per-node reconciled corrections (from
                    # the ACK-piggybacked offset estimator) applied to
                    # the cross-node stage join above.
                    "clock": result.clock,
                    # Slowest end-to-end causal chain(s): per-leg ms,
                    # telescoping to the e2e span.
                    "critical_path": result.critical_path,
                    # Ranked who-closed-the-quorum attribution + gap
                    # histogram means.
                    "stragglers": result.stragglers,
                }
            )
        )
    else:
        print(result.summary(args.rate, args.tx_size, args.nodes, args.workers))
        if result.stages_ms:
            print(" + PIPELINE STAGES (mean ms):")
            for name, ms in result.stages_ms.items():
                print(f"   {name}: {ms:,.1f} ms")
        if result.round_stages_ms:
            print(" + ROUND CADENCE (mean ms per sub-leg):")
            for name, ms in result.round_stages_ms.items():
                print(f"   {name}: {ms:,.2f} ms")
        path = result.critical_path.get("path")
        if path:
            print(
                " + CRITICAL PATH (slowest committed digest, "
                f"{path['e2e_ms']:,.1f} ms e2e):"
            )
            for name, ms in path["legs_ms"].items():
                print(f"   {name}: {ms:,.1f} ms")
        for family, label in (
            ("vote_quorum", "vote quorum"),
            ("support_quorum", "support quorum"),
        ):
            ranked = result.stragglers.get(family)
            if ranked:
                print(f" + QUORUM STRAGGLERS ({label}, most-charged first):")
                for e in ranked:
                    print(f"   {e['address']}: {e['count']:,}")
        if result.wire:
            totals = result.wire.get("totals", {})
            print(" + WIRE LEDGER:")
            print(
                f"   goodput ratio: {result.wire.get('goodput_ratio')}"
                f" ({totals.get('committed_payload_bytes', 0):,} committed B"
                f" / {totals.get('out_bytes_total', 0):,} wire B;"
                f" {totals.get('out_retransmit_bytes', 0):,} B retransmit)"
            )
            for t, d in sorted(result.wire.get("out", {}).items()):
                print(
                    f"   {t}: {d['frames']:,} frames / {d['bytes']:,} B out"
                    + (
                        f" (+{d['retransmit_bytes']:,} B retrans)"
                        if d["retransmit_bytes"]
                        else ""
                    )
                )
            if "compression_ratio" in result.wire:
                print(
                    f"   compression ratio: {result.wire['compression_ratio']}"
                    f" (raw {totals.get('out_raw_bytes', 0):,} B"
                    f" -> wire {totals.get('out_bytes', 0):,} B)"
                )
            if "frames_per_flush_mean" in result.wire:
                print(
                    f"   coalescing: {result.wire.get('flushes', 0):,}"
                    " flushes, mean frames/flush "
                    f"{result.wire['frames_per_flush_mean']}"
                    + (
                        f", mean acks/flush {result.wire['acks_per_flush_mean']}"
                        if "acks_per_flush_mean" in result.wire
                        else ""
                    )
                )
            if "cert_sig_bytes_fraction" in result.wire:
                print(
                    "   cert signature bytes fraction: "
                    f"{result.wire['cert_sig_bytes_fraction']}"
                )
            if "empty_cert_overhead_per_committed_byte" in result.wire:
                print(
                    "   empty-cert overhead per committed byte: "
                    f"{result.wire['empty_cert_overhead_per_committed_byte']}"
                )
        if result.crypto:
            print(" + CRYPTO LEDGER (verify ops by call site):")
            for site, d in result.crypto.get("verify", {}).items():
                split = (
                    f", {d['compute_s']:.2f} s compute"
                    if "compute_s" in d
                    else ""
                )
                print(
                    f"   {site}: {d['ops']:,} ops / {d['calls']:,} calls"
                    f" / {d['wall_s']:.2f} s wall{split}"
                    f" (mean batch {d['mean_batch']})"
                )
            cache = result.crypto.get("verify_cache", {})
            print(
                f"   verify cache: {cache.get('hits', 0):,} hits / "
                f"{cache.get('misses', 0):,} misses"
            )
        # Outside the stages guard: the disagreement matters MOST when the
        # stage join came up empty (missed flush, eviction).
        if result.metrics_disagreement is not None:
            print(
                f"   metrics vs log committed-tx disagreement: "
                f"{100 * result.metrics_disagreement:.2f}%"
            )
        if result.timeline.get("nodes"):
            n_samples = sum(
                len(v) for v in result.timeline["nodes"].values()
            )
            print(
                f" + TIMELINE: {n_samples} scrape samples across "
                f"{len(result.timeline['nodes'])} nodes, RTT matrix for "
                f"{len(result.timeline.get('rtt_ms', {}))} nodes "
                "(full series in .bench/timeline.json)"
            )
        if result.queues.get("channels"):
            fs = result.queues.get("first_saturating") or {}
            hot = sorted(
                result.queues["channels"].items(),
                key=lambda kv: kv[1].get("utilization", 0.0),
                reverse=True,
            )[:3]
            print(
                f" + QUEUES: {len(result.queues['channels'])} channels"
                + (
                    f", most pressured {fs['channel']} ({fs['mode']})"
                    if fs
                    else ""
                )
            )
            for ch, a in hot:
                if not a.get("high_water"):
                    continue
                print(
                    f"   {ch}: high-water {a['high_water']}/"
                    f"{a['capacity'] or '∞'}"
                    f" ({a.get('utilization', 0.0):.0%}),"
                    f" {a['enqueued']:,} enq, {a['full']:,} full"
                )


if __name__ == "__main__":
    main()
