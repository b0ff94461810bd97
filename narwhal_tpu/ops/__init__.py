"""TPU-resident kernels (JAX/XLA) behind the framework's CPU seams.

- reachability: the Tusk commit rule's graph traversals (linked()/order_dag
  frontier walks, reference consensus/src/lib.rs:247-303) as one jitted
  boolean matrix scan over the (gc_depth x committee) certificate window.
- ed25519: batched on-device signature verification (reference
  crypto/src/lib.rs:206-219 verify_batch) — field/point arithmetic from
  32-bit lanes, vmapped over the batch.

Import is deferred by callers (crypto.backend, consensus) so the pure-CPU
protocol path never pays the JAX import cost — and never holds the chip,
which belongs to one process at a time.
"""

import os as _os

import jax as _jax

# Persistent XLA compilation cache.  One verify-kernel shape costs minutes
# to compile for the chip, and every process of a run (the prewarm child,
# a device-backed primary, each chip_smoke phase) needs the same shapes, so
# they must all land in ONE directory.  JAX reads JAX_COMPILATION_CACHE_DIR
# itself: when the environment places the cache, nothing is set here.
# Otherwise the cache lives at one fixed, git-ignored path inside the
# checkout — never under $HOME or a temp name, so a second process of the
# same run always finds what the first compiled.
CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))),
    ".jax_cache",
)
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", CACHE_DIR)

# Compile ledger: how many XLA programs this process built (compiled OR
# loaded from the persistent cache — either way a jit miss that stalls its
# caller), the seconds spent tracing/lowering/building them, and the
# persistent cache's hits and misses.  Read by the node's ready line, the
# `crypto.verify.device` snapshot detail and chip_smoke.py: "no compile
# after warm-up" and "the second process hit the cache" are counted, not
# inferred.
_DURATION_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "build_seconds",
}
_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_compile_stats = {
    "programs_built": 0,
    "trace_seconds": 0.0,
    "lower_seconds": 0.0,
    "build_seconds": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
}


def _on_duration(event: str, duration: float, **_kw) -> None:
    key = _DURATION_KEYS.get(event)
    if key is not None:
        _compile_stats[key] += duration
        if key == "build_seconds":
            _compile_stats["programs_built"] += 1


def _on_event(event: str, **_kw) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
        _compile_stats[key] += 1


_jax.monitoring.register_event_duration_secs_listener(_on_duration)
_jax.monitoring.register_event_listener(_on_event)


def compile_stats() -> dict:
    """Snapshot of this process's compile ledger (see above)."""
    return dict(_compile_stats)


def device_identity() -> dict:
    """The default device as JAX reports it.  Touches the backend: the
    calling process holds the chip from here on."""
    devices = _jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
