"""Test harness config: force JAX onto a virtual 8-device CPU mesh so
sharding/pjit tests run without TPU hardware (the driver separately
dry-runs the multi-chip path; see __graft_entry__.py).

The environment may set JAX_PLATFORMS to the accelerator; the tests run
on the CPU, so both the env var (read when the CPU client is created)
and the config knob are set here.  The batched verifier is selected as
`jax` in tests: `tpu` means the chip and refuses a CPU-only JAX.

On jax-cpu the verifier's pad ladder is one rung of 16 (ops/ed25519.py:
CPU_RUNGS), so batches above 16 exercise the chunk split."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = [
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:  # tests that don't need jax still run
    pass

# --- destroyed-pending-task escalation (ISSUE 9 satellite) -------------------
#
# "Task was destroyed but it is pending!" is NOT a warning: asyncio emits
# it through Task.__del__ -> loop.call_exception_handler -> the `asyncio`
# logger, so pytest's filterwarnings cannot escalate it (the
# never-awaited-coroutine RuntimeWarning half lives in pyproject.toml).
# Trap the logger instead and fail the test in whose teardown the message
# surfaces.  No forced gc.collect() here: a full collection per test
# costs whole minutes across the suite with jax loaded, and CPython's
# refcounting destroys a dropped pending task immediately in the
# non-cyclic (i.e. common) case — a cyclic straggler surfaces in a later
# test's teardown, which still names the leaked task.

import logging  # noqa: E402

import pytest  # noqa: E402

_DESTROYED_PENDING = "Task was destroyed but it is pending"


class _AsyncioErrorTrap(logging.Handler):
    def __init__(self) -> None:
        super().__init__(logging.ERROR)
        self.messages: list = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if _DESTROYED_PENDING in msg:
            self.messages.append(msg)


_asyncio_trap = _AsyncioErrorTrap()
logging.getLogger("asyncio").addHandler(_asyncio_trap)


@pytest.fixture(autouse=True)
def _fail_on_destroyed_pending_tasks():
    yield
    if _asyncio_trap.messages:
        msgs = list(_asyncio_trap.messages)
        _asyncio_trap.messages.clear()
        pytest.fail(
            "asyncio destroyed pending task(s) — a fire-and-forget task "
            "was GC'd mid-flight (use narwhal_tpu.utils.tasks.spawn):\n"
            + "\n".join(msgs)
        )
