"""What the kernel says held this process off the cores, from the files it
keeps: CPU-quota throttling, steal and pressure stalls.

The loop watchdog's thread samples a :class:`HostStat` at most ten times
a second and gives every loop stall the deltas across it, from which it
names the stall's cause (``analysis/watchdog.py``).  A sample maps each
key to its cumulative value since boot:

``cpu_throttled``     seconds the process's cgroup was held by its CPU
                      quota, SUMMED OVER CPUS as the kernel keeps it (v2
                      ``cpu.stat`` ``throttled_usec``, v1 ``throttled_time``)
``cpu_nr_throttled``  quota periods in which it was held (same file)
``cpu_steal``         wall seconds a hypervisor took the average CPU away
                      (``/proc/stat``: steal / ticks a second / online CPUs)
``memory_full``,      PSI ``total=``: time in which all (``full``) or some
``memory_some``,      (``some``) tasks stalled on that resource, from the
``io_full``,          cgroup's ``*.pressure`` under v2, else
``cpu_some``          ``/proc/pressure/*``

Which file gives which key is settled once, at construction.  A key whose
file is missing or unreadable is left out, never 0; so is ``cpu_steal``
where the ``cpu`` line reads all zeros (a kernel that keeps no CPU
accounting, as a sandbox's may).
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, List, Optional, Tuple

Sample = Dict[str, float]

# PSI lines kept per resource.
_PSI = {"memory": ("some", "full"), "io": ("full",), "cpu": ("some",)}


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except (OSError, UnicodeDecodeError):
        return None


def _fields(text: str) -> Dict[str, int]:
    """``name value`` lines as a dict; other lines are skipped."""
    out = {}
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[1].isdigit():
            out[parts[0]] = int(parts[1])
    return out


def parse_cpu_stat(text: str) -> Sample:
    """A cgroup's ``cpu.stat``, v2 (µs) or v1 (ns)."""
    f = _fields(text)
    out: Sample = {}
    if "throttled_usec" in f:
        out["cpu_throttled"] = f["throttled_usec"] / 1e6
    elif "throttled_time" in f:
        out["cpu_throttled"] = f["throttled_time"] / 1e9
    if "nr_throttled" in f:
        out["cpu_nr_throttled"] = f["nr_throttled"]
    return out


def parse_psi(resource: str, text: str) -> Sample:
    """One ``*.pressure`` file: ``<some|full> avg10=.. total=<µs>``."""
    out: Sample = {}
    for line in text.splitlines():
        kind, _, rest = line.partition(" ")
        if kind not in _PSI[resource]:
            continue
        for item in rest.split():
            if item.startswith("total=") and item[6:].isdigit():
                out[f"{resource}_{kind}"] = int(item[6:]) / 1e6
    return out


def parse_proc_stat(clk_tck: int, text: str) -> Sample:
    lines = text.splitlines()
    cpu = next((ln.split() for ln in lines if ln.startswith("cpu ")), [])
    online = sum(1 for ln in lines if ln[:3] == "cpu" and ln[3:4].isdigit())
    ticks = [int(v) for v in cpu[1:] if v.isdigit()]
    if len(ticks) < 8 or not online or not any(ticks):
        return {}
    return {"cpu_steal": ticks[7] / clk_tck / online}


def cgroup_dirs(
    self_cgroup: str, root: str
) -> Tuple[Optional[str], Optional[str]]:
    """The process's v2 cgroup directory and its v1 ``cpu`` controller's
    directory (each None where there is none), from ``/proc/self/cgroup``.
    Where the path it names is not under the mount (a container that sees
    its own cgroup as the root), the mount itself is the cgroup."""

    def under(base: str, path: str) -> Optional[str]:
        inner = os.path.join(base, path.lstrip("/"))
        if os.path.isdir(inner):
            return inner
        return base if os.path.isdir(base) else None

    v2 = v1_cpu = None
    for line in self_cgroup.splitlines():
        hid, _, rest = line.partition(":")
        controllers, _, path = rest.partition(":")
        if hid == "0" and not controllers:
            for base in (root, os.path.join(root, "unified")):
                if os.path.isfile(os.path.join(base, "cgroup.controllers")):
                    v2 = under(base, path)
                    break
        elif "cpu" in controllers.split(","):
            for sub in (controllers, "cpu"):
                v1_cpu = under(os.path.join(root, sub), path)
                if v1_cpu is not None:
                    break
    return v2, v1_cpu


Parser = Callable[[str], Sample]


class HostStat:
    """The host counters this process can read, sampled on demand."""

    def __init__(
        self, proc: str = "/proc", cgroup_root: str = "/sys/fs/cgroup"
    ):
        self_cgroup = _read(os.path.join(proc, "self", "cgroup")) or ""
        v2, v1_cpu = cgroup_dirs(self_cgroup, cgroup_root)
        candidates: List[Tuple[str, Parser]] = []
        for cpu_dir in (v2, v1_cpu):
            if cpu_dir is not None:
                stat = os.path.join(cpu_dir, "cpu.stat")
                candidates.append((stat, parse_cpu_stat))
        for resource in _PSI:
            parse = functools.partial(parse_psi, resource)
            if v2 is not None:
                candidates.append(
                    (os.path.join(v2, f"{resource}.pressure"), parse)
                )
            candidates.append(
                (os.path.join(proc, "pressure", resource), parse)
            )
        candidates.append((
            os.path.join(proc, "stat"),
            functools.partial(parse_proc_stat, os.sysconf("SC_CLK_TCK")),
        ))
        # Each key from the first file that gives it.
        self._sources: List[Tuple[str, Parser, Tuple[str, ...]]] = []
        keys: List[str] = []
        for path, parse in candidates:
            text = _read(path)
            new = [k for k in parse(text or "") if k not in keys]
            if new:
                self._sources.append((path, parse, tuple(new)))
                keys += new
        self.keys = tuple(sorted(keys))

    def sample(self) -> Sample:
        out: Sample = {}
        for path, parse, keys in self._sources:
            text = _read(path)
            if text is None:
                continue
            got = parse(text)
            out.update((k, got[k]) for k in keys if k in got)
        return out
