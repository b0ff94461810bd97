"""The device layer: the batched ed25519 verifier (JAX/XLA) behind the
crypto seam.

- ed25519: batched on-device signature verification (reference
  crypto/src/lib.rs:206-219 verify_batch) — field/point arithmetic from
  32-bit lanes (field25519), vmapped over the batch.
- programs: the verifier's compiled programs kept whole as files, so a
  process loads them without tracing.

Import is deferred by the one caller (crypto.backend) so the pure-CPU
protocol path never pays the JAX import cost — and never holds the chip,
which belongs to one process at a time.
"""

import os as _os

import jax as _jax

# One cache for compiled programs: the program files (ops/programs.py).
# JAX's persistent cache is off for the process: nothing a node jits but
# the verifier costs a second to compile, and an executable that cache
# hands back cannot be written out whole on XLA:CPU (it serializes without
# its kernels; sandbox, PR 30), so a program file only ever comes from a
# build of this process's own.
_jax.config.update("jax_enable_compilation_cache", False)

# Where the program files live.  Every process of a run (the prewarm
# child, each device-backed primary, each chip_smoke phase) needs the same
# programs, so they must all land in ONE place: the deployment's
# JAX_COMPILATION_CACHE_DIR where that is set, else one fixed, git-ignored
# path inside the checkout (never under $HOME or a temp name), so a second
# process of the same run always finds what the first compiled.
CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))),
    ".jax_cache",
)


def program_dir() -> str:
    """The directory of the program files, as the environment has it NOW."""
    return _os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


# Compile ledger: how many XLA programs this process built (compiled, or
# loaded whole from a program file: each a miss that stalls its caller),
# the seconds spent tracing / lowering / building them (`build_seconds`
# holds a program file's read + deserialize + load too: what the caller
# waited), how many of `programs_built` came from a program file without
# any trace (`programs_from_file`) and how many program files were found
# and not used (`program_files_rejected`; each is then built and written
# anew).  Read by the node's ready line, the `crypto.verify.device`
# snapshot detail and chip_smoke.py: "no compile after warm-up" and "the
# second process traced nothing" are counted, not inferred.
_DURATION_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "build_seconds",
}
_compile_stats = {
    "programs_built": 0,
    "trace_seconds": 0.0,
    "lower_seconds": 0.0,
    "build_seconds": 0.0,
    "programs_from_file": 0,
    "program_files_rejected": 0,
}


def _on_duration(event: str, duration: float, **_kw) -> None:
    key = _DURATION_KEYS.get(event)
    if key is not None:
        _compile_stats[key] += duration
        if key == "build_seconds":
            _compile_stats["programs_built"] += 1


_jax.monitoring.register_event_duration_secs_listener(_on_duration)


def count_program_file(seconds: float, used: bool) -> None:
    """A program file was read in ``seconds`` and served (``used``) or
    was rejected: the caller waited either way."""
    _compile_stats["build_seconds"] += seconds
    if used:
        _compile_stats["programs_built"] += 1
        _compile_stats["programs_from_file"] += 1
    else:
        _compile_stats["program_files_rejected"] += 1


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
