"""One committee on localhost, as the product starts it.

Copied from ``benchmark/local_bench.py::run_bench`` (keys from the seed,
committee and parameters files, device-backed primaries first and booted
before their peers, the last ``faults`` validators never started, SIGTERM
teardown with a long grace for a chip holder) because that file lives
outside the benchmark's directory and a later PR may change it.  What is
different, and why:

- keys are ranked before roles are given out.  Tusk's leader of an even
  round r is the validator of sorted-key rank ``r % n``, so with an even
  ``n`` only even ranks ever lead.  Which ranks are down decides how
  many leaders are dead, and with keys drawn from the seed that would be
  the seed changing the work.  The configuration names the ranks that
  never start (``dead_key_ranks``); every seed gets the same schedule.
- this process never imports JAX or the program: it writes the key,
  committee and parameter files in their JSON formats itself.
- the configuration says which primaries hold a chip
  (``chip_primaries``, launch indices).  In the deployment each
  primary's host has one chip; on one host with several chips the same
  is had by giving each device-backed primary's PROCESS one chip to see
  (``chip_env``), as it is given its ports.  The program does not change:
  its ``jax.devices()[0]`` is then the process's only device.
"""

from __future__ import annotations

import base64
import glob
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# A device-backed primary builds two verify programs before it joins: ~85 s
# from a warm compile cache, ~330 s cold (PERF.md, PR 22).  The driver
# allows a compiling run 1200 s in all.
DEVICE_BOOT_DEADLINE_S = 1000
PEER_BOOT_DEADLINE_S = 90


def chip_env(k: int) -> dict:
    """What lets a process see chip ``k`` of its host and no other:
    libtpu's own process-level visibility, the documented way to run
    several one-chip processes on one host.  Proven on the four-chip v5e
    host by ``chip_probe.py`` (README, "A chip per primary")."""
    return {
        "TPU_VISIBLE_CHIPS": str(k),
        "TPU_VISIBLE_DEVICES": str(k),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(8476 + k),
    }


class RunFailure(Exception):
    """The run cannot produce a result (no chip, a node died, no boot)."""


def b64(b: bytes) -> str:
    return base64.b64encode(b).decode()


class Identity:
    """One validator: ed25519 secret seed, public key, sorted-key rank."""

    def __init__(self, secret: bytes) -> None:
        self.secret = secret
        self.sk = Ed25519PrivateKey.from_private_bytes(secret)
        self.name = self.sk.public_key().public_bytes_raw()
        self.rank = -1

    def sign(self, message: bytes) -> bytes:
        return self.sk.sign(message)


def make_identities(seed: int, config: dict) -> list:
    """``nodes`` identities from the seed, in launch order: node 0 (the
    device-backed primary) first, the validators that never start last.
    Launch order is a fixed function of key RANK (see module docstring)."""
    n = config["nodes"]
    ids = [
        Identity(hashlib.sha256(f"chipbench:{seed}:{i}".encode()).digest())
        for i in range(n)
    ]
    ids.sort(key=lambda x: x.name)
    for rank, ident in enumerate(ids):
        ident.rank = rank
    dead = sorted(config["dead_key_ranks"])
    if len(dead) != config["faults"] or not all(0 <= r < n for r in dead):
        raise ValueError("dead_key_ranks must name `faults` ranks below `nodes`")
    live = [i for i in ids if i.rank not in dead]
    return live + [ids[r] for r in dead]


def build_committee(ids: list, base_port: int, workers: int) -> dict:
    """Sequential ports, 2+3W per authority (local_bench.build_committee),
    in the committee file's JSON shape."""
    port = base_port
    auths = {}

    def nxt() -> str:
        nonlocal port
        port += 1
        return f"127.0.0.1:{port - 1}"

    for ident in ids:
        primary = {"primary_to_primary": nxt(), "worker_to_primary": nxt()}
        ws = {
            str(w): {
                "transactions": nxt(),
                "worker_to_worker": nxt(),
                "primary_to_worker": nxt(),
            }
            for w in range(workers)
        }
        auths[b64(ident.name)] = {"stake": 1, "primary": primary, "workers": ws}
    return {"authorities": auths}


def metrics_port(base_port: int, nodes: int, workers: int, node: int,
                 worker=None) -> int:
    mbase = base_port + nodes * (2 + 3 * workers)
    if worker is None:
        return mbase + node
    return mbase + nodes + node * workers + worker


def checkout_tag() -> str:
    """Names what this checkout keeps outside itself, so that two
    checkouts (the driver's parent and change) share nothing."""
    return hashlib.sha256(REPO.encode()).hexdigest()[:12]


def store_root(workdir: str) -> str:
    """Node stores go on tmpfs, as run_bench puts them: a run writes
    several GB of batch logs, and on disk (the work directory, $TMPDIR)
    that is both the write budget of a check and a writeback storm inside
    the next run.  The directory is named from this checkout's path;
    run.py removes it at exit and on SIGTERM, and ``sweep_stale_stores``
    removes what a killed run of this checkout left.  Without a writable
    /dev/shm the stores stay in the work directory."""
    if os.path.isdir("/dev/shm") and os.access("/dev/shm", os.W_OK):
        return f"/dev/shm/chipbench-{checkout_tag()}-{os.path.basename(workdir)}"
    return os.path.join(workdir, "stores")


def sweep_stale_stores() -> None:
    """A run killed outright (SIGKILL at a time or memory limit) cannot
    remove its stores.  One run at a time uses a checkout, so whatever
    carries this checkout's tag at start is stale, whichever cell left it."""
    for stale in glob.glob(f"/dev/shm/chipbench-{checkout_tag()}-*"):
        shutil.rmtree(stale, ignore_errors=True)


def port_base(config: dict) -> int:
    """The configuration's ``base_port`` moved by a block of 128 ports
    that depends on the checkout's path (up to 32,699, under the kernel's
    ephemeral range), so that two checkouts that ran at once would not
    meet on a port."""
    return config["base_port"] + 128 * (int(checkout_tag(), 16) % 200)


class Committee:
    """Files, processes and teardown of one run."""

    def __init__(self, workdir: str, seed: int, config: dict, harness: dict,
                 backend: str, traced: bool) -> None:
        self.workdir = workdir
        self.config = config
        self.harness = harness
        self.backend = backend  # "tpu" | "jax" (rehearsal) | None (OpenSSL)
        self.traced = traced
        self.nodes = config["nodes"]
        self.workers = config["workers"]
        self.alive = self.nodes - config["faults"]
        # Launch indices of the primaries that verify on a device: the
        # configuration's on the chip, primary 0 alone on jax-cpu (a
        # sandbox holds one such process), none on OpenSSL.
        named = sorted(set(config["chip_primaries"]))
        if not named or not all(0 <= i < self.alive for i in named):
            raise ValueError("chip_primaries must name live primaries")
        self.chip_nodes = {"tpu": named, "jax": [0], None: []}[backend]
        # The primaries that are sent forged headers: the configuration's
        # chip holders, whatever verifies in this run (a rehearsal's
        # OpenSSL has to reject them as well).
        self.forged_nodes = named
        self.base_port = port_base(config)
        self.ids = make_identities(seed, config)
        self.procs = []  # (Popen, log file, holds the chip)
        self.storedir = store_root(workdir)
        sweep_stale_stores()
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(self.storedir, ignore_errors=True)
        os.makedirs(workdir)
        os.makedirs(self.storedir, exist_ok=True)
        self.committee = build_committee(self.ids, self.base_port, self.workers)
        with open(self.path("committee.json"), "w") as f:
            json.dump(self.committee, f, indent=2, sort_keys=True)
        with open(self.path("parameters.json"), "w") as f:
            json.dump(config["parameters"], f, indent=2, sort_keys=True)
        for i, ident in enumerate(self.ids):
            with open(self.path(f"node-{i}.json"), "w") as f:
                json.dump({"name": b64(ident.name), "secret": b64(ident.secret)}, f)
        # The child environment: this checkout on PYTHONPATH and nothing
        # of the program's own knobs but the audit path (set per primary).
        self.env = {
            k: v for k, v in os.environ.items() if not k.startswith("NARWHAL_")
        }
        self.env["PYTHONPATH"] = REPO

    def path(self, name: str) -> str:
        return os.path.join(self.workdir, name)

    # ------------------------------------------------------------ addresses

    def authority(self, i: int) -> dict:
        return self.committee["authorities"][b64(self.ids[i].name)]

    def primary_metrics_port(self, i: int) -> int:
        return metrics_port(self.base_port, self.nodes, self.workers, i)

    def worker_metrics_port(self, i: int, w: int) -> int:
        return metrics_port(self.base_port, self.nodes, self.workers, i, w)

    # ------------------------------------------------------------ processes

    def spawn(self, cmd, logname: str, env=None, chip=False):
        f = open(self.path(logname), "w")
        p = subprocess.Popen(
            cmd, stdout=f, stderr=subprocess.STDOUT, env=env or self.env,
            cwd=REPO,
        )
        self.procs.append((p, f, chip))
        return p

    def node_args(self, i: int, store: str, mpath: str, mport: int) -> list:
        return [
            "run",
            "--keys", self.path(f"node-{i}.json"),
            "--committee", self.path("committee.json"),
            "--parameters", self.path("parameters.json"),
            "--store", os.path.join(self.storedir, store),
            "--benchmark",
            "--metrics-path", self.path(mpath),
            "--metrics-port", str(mport),
        ]

    def primary_env(self, i: int) -> dict:
        """Primary i's environment: the audit path, and for a device
        node chip k's variables, k its place among the device nodes.
        Where one primary alone holds a chip it gets none of them: its
        process sees the host's chips as it always did."""
        env = dict(
            self.env,
            NARWHAL_CONSENSUS_AUDIT=self.path(f"audit-primary-{i}.bin"),
        )
        if i in self.chip_nodes and len(self.chip_nodes) > 1:
            env.update(chip_env(self.chip_nodes.index(i)))
        return env

    def spawn_primary(self, i: int):
        on_device = i in self.chip_nodes
        env = self.primary_env(i)
        args = self.node_args(
            i, f"db-primary-{i}", f"metrics-primary-{i}.json",
            self.primary_metrics_port(i),
        )
        if on_device:
            # The same entry (narwhal_tpu.node.main.main) with the same
            # arguments, inside a wrapper that can read the chip's peak
            # memory at exit and trace it on request: only the process
            # that holds the chip can do either.  Primary 0's chip is
            # the one traced.
            cmd = [
                sys.executable, os.path.join(HERE, "device_node.py"),
                "--report", self.path(f"device-node-{i}.json"),
                "--trace-dir", self.path("trace") if self.traced and i == 0 else "",
                "--trace-seconds", str(self.harness["trace_seconds"]),
                "--", *args, "--crypto-backend", self.backend, "primary",
            ]
        else:
            cmd = [sys.executable, "-m", "narwhal_tpu.node", *args, "primary"]
        return self.spawn(cmd, f"primary-{i}.log", env=env, chip=on_device)

    def spawn_worker(self, i: int, w: int):
        args = self.node_args(
            i, f"db-worker-{i}-{w}", f"metrics-worker-{i}-{w}.json",
            self.worker_metrics_port(i, w),
        )
        return self.spawn(
            [sys.executable, "-m", "narwhal_tpu.node", *args, "worker",
             "--id", str(w)],
            f"worker-{i}-{w}.log",
        )

    def wait_for_boot(self, lognames, deadline_s: float, procs) -> None:
        deadline = time.time() + deadline_s
        pending = set(lognames)
        while pending:
            for name in list(pending):
                with open(self.path(name), errors="replace") as f:
                    if "successfully booted" in f.read():
                        pending.discard(name)
            if not pending:
                return
            dead = [p.args for p in procs if p.poll() is not None]
            if dead or time.time() > deadline:
                raise RunFailure(
                    f"never booted: {sorted(pending)}"
                    + (f"; exited: {dead[0][:6]}" if dead else "; deadline")
                    + "\n" + self.log_tail(sorted(pending)[0])
                )
            time.sleep(0.2)

    def log_tail(self, name: str, n: int = 1500) -> str:
        try:
            with open(self.path(name), errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def start_nodes(self) -> None:
        """Device-backed primaries first, together, and nothing else
        until they booted: three OpenSSL primaries are a quorum in the
        four-up deployment and any peers would time out on a validator
        that is still compiling.  (On OpenSSL alone primary 0 goes
        first, as it always did.)"""
        first = self.chip_nodes or [0]
        self.wait_for_boot(
            [f"primary-{i}.log" for i in first],
            DEVICE_BOOT_DEADLINE_S if self.backend else PEER_BOOT_DEADLINE_S,
            [self.spawn_primary(i) for i in first],
        )
        rest = [i for i in range(self.alive) if i not in first]
        procs = [self.spawn_primary(i) for i in rest]
        names = [f"primary-{i}.log" for i in rest]
        for i in range(self.alive):
            for w in range(self.workers):
                procs.append(self.spawn_worker(i, w))
                names.append(f"worker-{i}-{w}.log")
        self.wait_for_boot(names, PEER_BOOT_DEADLINE_S, procs)

    def start_clients(self, seed: int, workload: dict) -> None:
        """One seeded open-loop client per live worker, the rate split
        evenly (upstream local.py:78)."""
        n_clients = self.alive * self.workers
        share = max(1, workload["rate"] // n_clients)
        idx = 0
        for i in range(self.alive):
            for w in range(self.workers):
                addr = self.authority(i)["workers"][str(w)]["transactions"]
                self.spawn(
                    [sys.executable, os.path.join(HERE, "client.py"), addr,
                     "--size", str(workload["tx_size"]),
                     "--rate", str(share),
                     "--arrival", workload["arrival"],
                     "--client", str(idx), "--seed", str(seed),
                     "--out", self.path(f"client-{idx}.samples")],
                    f"client-{idx}.log",
                )
                idx += 1

    def check_alive(self) -> None:
        for p, f, _ in self.procs:
            if p.poll() is not None:
                name = os.path.basename(f.name)
                raise RunFailure(
                    f"{name} exited with {p.returncode} during the run\n"
                    + self.log_tail(name)
                )

    def teardown(self) -> None:
        """SIGTERM everything, then wait per process (the SIGTERM path
        flushes each node's final metrics snapshot and audit segment); a
        chip holder gets 75 s to finish its device call and let go."""
        for p, _, _ in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except ProcessLookupError:
                    pass
        for p, f, chip in self.procs:
            try:
                p.wait(timeout=75 if chip else 20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            f.close()
        self.procs = []

    def remove_stores(self) -> None:
        shutil.rmtree(self.storedir, ignore_errors=True)

    # --------------------------------------------------------------- scrape

    def scrape(self, port: int) -> dict:
        """One node's counters and histograms now (no stage trace).
        Raises OSError where the node does not answer in 5 s."""
        url = f"http://127.0.0.1:{port}/metrics.json?trace=0"
        with urllib.request.urlopen(url, timeout=5) as r:
            return json.load(r)

    def scrape_all(self) -> dict:
        out = {}
        for i in range(self.alive):
            out[f"primary-{i}"] = self.scrape(self.primary_metrics_port(i))
            for w in range(self.workers):
                out[f"worker-{i}-{w}"] = self.scrape(
                    self.worker_metrics_port(i, w)
                )
        return out
