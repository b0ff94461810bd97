"""From a profiler trace (``.xplane.pb``) to device busy time, program
times, the top device operations and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a v5e
trace looks like (looked at by hand first; ``tests/data/`` keeps a cut
of one): a plane ``/device:TPU:<n>`` per chip; its line ``XLA Modules``
holds one event per execution of a compiled program, named
``jit_<function>(<fingerprint>)``; its line ``XLA Ops`` holds the
operations inside them, ~118,000 a call of the verify kernel, each named
by its whole HLO line.  Host planes are not read: the program writes no
annotations into the trace, so an idle gap cannot be given a host cause
here (PERF.md, for the tracing issue) and is listed as unattributed.

The traced window is the host's: from the moment ``start_trace``
returned to the moment ``stop_trace`` was called (``device_node.py``).
The stamp is taken AFTER ``start_trace`` because the profiler takes
0.05-0.07 s to start, in which nothing is recorded; stamped before it,
the window read the device 20 points too idle.  A window cut from the
device's events alone (first program start to the last) was tried and
reads too busy: the gap that holds the window's edge is dropped, and a
long gap is the likeliest to hold it (29% idle against 36% by this
window and 40% by the counters over the whole run, PERF.md).  Busy time
is every operation the trace holds; ``calls`` lists each execution in
trace time so that a reader can see they lie inside the stamps.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def load_planes(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """plane name -> line name -> [(event name, start ns, duration ns)]
    for the device planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            ]
        out[plane.name] = lines
    return out


def union_ns(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """(covered length, the gaps between the merged intervals)."""
    busy, gaps, end = 0.0, [], None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            if end is not None:
                gaps.append((end, start))
            busy += stop - start
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy, gaps


def op_kind(event_name: str) -> str:
    """``%multiply_add_fusion.140 = s32[...] fusion(...)`` ->
    ``multiply_add_fusion``: a verify call runs ~118,000 operations, each
    under a name of its own, so time is summed by kind."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def program_name(event_name: str) -> str:
    """``jit__verify_kernel(123)`` -> ``_verify_kernel``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def reduce_planes(planes: dict, window_s: float) -> Optional[dict]:
    """The trace's summary, or None where no operation ran on a device
    plane.  ``window_s`` is the traced window's length by the host's
    clock (after start_trace returned, until stop_trace was called)."""
    busy_per_chip, programs, ops, gaps_all = [], {}, {}, []
    calls = []  # [program, start s, duration s] in trace time, for the log
    for lines in planes.values():
        modules = lines.get(MODULE_LINE, [])
        op_events = lines.get(OP_LINE) or modules
        if not op_events:
            continue
        busy, gaps = union_ns([(s, s + d) for _, s, d in op_events])
        busy_per_chip.append(busy / 1e9)
        gaps_all.extend(gaps)
        for name, s, d in sorted(modules, key=lambda m: m[1]):
            programs.setdefault(program_name(name), []).append(d / 1e9)
            calls.append([program_name(name), s / 1e9, d / 1e9])
        for name, _, d in op_events:
            kind = op_kind(name)
            ops[kind] = ops.get(kind, 0.0) + d / 1e9
    if not busy_per_chip or sum(busy_per_chip) <= 0:
        return None
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": sum(busy_per_chip) / len(busy_per_chip),
        "window_s": window_s,
        "programs": programs,
        "calls": calls[:32],
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [["unattributed", (b - a) / 1e9] for a, b in top_gaps],
    }


def reduce_trace(trace_dir: str, window_s: float) -> Optional[dict]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(load_planes(path), window_s)
