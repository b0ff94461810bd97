"""The device profiler, reached without importing JAX.

Only the process that holds the chip can trace it, and only a node whose
verifier runs on a device has JAX loaded: the CPU node entry points never
import it (one process per chip).  So both doors here look JAX up in
``sys.modules`` and do nothing where it is absent.

- :func:`annotate` — a ``jax.profiler.TraceAnnotation``: free while no
  profiler session runs (~1 µs), and with the host tracer at level 1 it
  lands in the profiler's host plane on the device trace's own clock.
  The verify path names its phases with it (``verify.submit``,
  ``verify.dispatch`` > ``verify.prepare`` + ``verify.launch``,
  ``verify.fetch``, ``verify.replay``), each carrying the burst number
  that keys ``metrics.verify_trace()``.
- :func:`burst` / :func:`current_burst` — that burst number, carried from
  the Core to the verify seam and the backend in a context variable and
  not in a parameter: the seam's signature (which harnesses replace with
  stand-ins of their own) stays what it was.
- :func:`profile` — one profiler session of a given length, the node's
  own hook (``GET /debug/profile`` on the MetricsServer): Python tracer
  off (under a node that is mostly Python ``stop_trace`` never ended,
  PERF.md PR 25), host tracer at level 1 so the annotations are kept.
  ``start`` is stamped once ``start_trace`` has RETURNED (the profiler
  takes 0.05-0.07 s to start, in which nothing is recorded) and ``stop``
  before ``stop_trace`` is called: the traced window is stop - start, the
  stamps ``chipbench/device_node.py`` takes.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import sys
import threading
import time
from typing import Iterator, Optional

_NO_SPAN = contextlib.nullcontext()
_burst: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "narwhal_verify_burst", default=None
)
# One session at a time: the profiler is process-wide.
_session = threading.Lock()


def annotate(name: str, **fields):
    """Context manager naming a host span in the profiler's trace."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **fields)


@contextlib.contextmanager
def burst(key: str) -> Iterator[None]:
    """Verifications awaited inside belong to the verify-stage burst
    ``key``: the seam stamps its stages under it."""
    token = _burst.set(key)
    try:
        yield
    finally:
        _burst.reset(token)


def current_burst() -> Optional[str]:
    """The verify-stage burst this task is verifying for, if any (read
    on the loop: an executor thread does not inherit it)."""
    return _burst.get()


def holds_device() -> bool:
    """True where this process runs its verifier through JAX (it then
    holds whatever device JAX gave it)."""
    return "jax" in sys.modules


def _run_session(trace_dir: str, seconds: float) -> dict:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    asked = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    start = time.time()
    time.sleep(seconds)
    stop = time.time()
    jax.profiler.stop_trace()
    return {
        "trace_dir": trace_dir,
        "asked": asked,
        "start": start,
        "stop": stop,
        "written": time.time(),
        "host_tracer_level": options.host_tracer_level,
    }


async def profile(trace_dir: str, seconds: float) -> Optional[dict]:
    """Trace this process's device for ``seconds`` into ``trace_dir``,
    off the event loop (``stop_trace`` collects for a minute and more on
    a busy verifier).  Returns the stamps, or None where a session is
    already running."""
    if not _session.acquire(blocking=False):
        return None
    try:
        return await asyncio.get_running_loop().run_in_executor(
            None, _run_session, trace_dir, seconds
        )
    finally:
        _session.release()
