"""The device-backed primary: ``narwhal_tpu.node.main.main`` with the
arguments after ``--``, exactly what ``python -m narwhal_tpu.node`` runs.

Only the process that holds the chip can read its memory or trace it,
and the program has no hook for either (PERF.md, for the tracing issue).
So this wrapper adds two things around the unchanged entry point:

- at exit, ``--report`` gets the device's identity and
  ``peak_bytes_in_use`` as JAX reports them;
- if ``--trace-dir`` is given, a thread waits for the file
  ``<trace-dir>.go`` (the harness writes it inside the measured window),
  runs ``jax.profiler`` for ``--trace-seconds`` and stops it, long before
  teardown.  Wall times go into ``<trace-dir>.times``: ``start`` is
  stamped once ``start_trace`` has RETURNED, since the profiler takes
  0.05-0.07 s to start in which nothing is recorded, and ``stop`` before
  ``stop_trace`` is called; the traced window is stop - start.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def note(msg: str) -> None:
    print(f"device_node: {msg}", file=sys.stderr, flush=True)


def trace_when_asked(trace_dir: str, seconds: float) -> None:
    go = trace_dir + ".go"
    while not os.path.exists(go):
        time.sleep(0.05)
    import jax

    # Device events are all the reduction reads, so the Python tracer and
    # the host tracer are off.  With the Python tracer on (the default)
    # stop_trace never ended under a node that is mostly Python; with the
    # host tracer on, stop_trace of a 0.25 s trace took 142-157 s inside
    # the running primary (my chip runs, PR 25), against ~30 s without.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    asked = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.time()
    note(f"trace started in {t0 - asked:.2f} s")
    time.sleep(seconds)
    t1 = time.time()
    jax.profiler.stop_trace()
    note(f"trace stopped and written in {time.time() - t1:.2f} s")
    with open(trace_dir + ".times", "w") as f:
        json.dump({"asked": asked, "start": t0, "stop": t1,
                   "written": time.time()}, f)


def device_report() -> dict:
    import jax

    devices = jax.devices()
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--report", required=True)
    p.add_argument("--trace-dir", default="")
    p.add_argument("--trace-seconds", type=float, default=2.0)
    p.add_argument("node_args", nargs=argparse.REMAINDER)
    args = p.parse_args()
    node_args = args.node_args
    if node_args[:1] == ["--"]:
        node_args = node_args[1:]
    if args.trace_dir:
        # The thread's own failure has to be seen, not swallowed.
        threading.excepthook = lambda a: note(
            f"trace thread failed: {a.exc_type.__name__}: {a.exc_value}"
        )
        threading.Thread(
            target=trace_when_asked,
            args=(args.trace_dir, args.trace_seconds),
            daemon=True,
        ).start()

    from narwhal_tpu.node.main import main as node_main

    rc = 1
    try:
        rc = node_main(node_args)
    finally:
        # JAX is imported by now if the backend came up at all.
        if "jax" in sys.modules:
            with open(args.report, "w") as f:
                json.dump(device_report(), f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
