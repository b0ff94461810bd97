"""The program files of ops/programs.py, on a stand-in program that builds
in milliseconds: a sound file is loaded without a trace, every way a file
can be wrong falls back to building, rewrites the file and is counted, and
writers that race leave one whole file.  (The real verify program makes
the same round trip once, in tests/test_ed25519.py.)"""

import hashlib
import json
import os
import stat
import sys
import threading
import time
import zlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from narwhal_tpu import ops  # noqa: E402
from narwhal_tpu.ops import programs  # noqa: E402

ROWS = 16
VARIANT = {"rung": ROWS, "field_dtype": "int32"}
ARGS = (
    jax.ShapeDtypeStruct((ROWS, 4), jnp.int32),
    jax.ShapeDtypeStruct((ROWS,), jnp.int32),
)
_A = np.arange(ROWS * 4, dtype=np.int32).reshape(ROWS, 4)
_B = np.full(ROWS, 100, np.int32)
_EXPECTED = _A.sum(axis=-1) > _B  # rows 0..5 say no


def standin():
    """The "verifier": says yes to some rows and no to others.  A new jit
    object each time, as a new process has: JAX keeps what one object
    compiled in memory and would not build it twice."""

    def _standin(a, b):
        return a.sum(axis=-1) > b

    return jax.jit(_standin)


@jax.jit
def _yes_man(a, b):
    """A foreign executable of the same signature that accepts every row."""
    return b == b


def wrong_answers(program):
    mask = np.asarray(program(jnp.asarray(_A), jnp.asarray(_B)))
    bad = np.flatnonzero(mask != _EXPECTED)
    return None if not bad.size else f"rows {bad.tolist()} accepted"


def resolve(directory):
    return programs.resolve(standin(), ARGS, VARIANT, wrong_answers, str(directory))


def key_and_path(directory):
    key = programs.program_key("_standin", VARIANT)
    return key, programs.program_path(key, VARIANT, str(directory))


def grown(before):
    after = ops.compile_stats()
    return {k: after[k] - before[k] for k in after}


def test_a_written_file_is_loaded_without_a_trace(tmp_path):
    before = ops.compile_stats()
    built = resolve(tmp_path)
    first = grown(before)
    assert first["programs_built"] == 1 and first["programs_from_file"] == 0
    assert first["trace_seconds"] > 0 and first["lower_seconds"] > 0
    key, path = key_and_path(tmp_path)
    assert os.listdir(tmp_path) == [os.path.basename(path)]
    assert os.path.basename(path) == "_standin-16-int32-cpu-cpu-d0.program"
    umask = os.umask(0)
    os.umask(umask)
    # Readable by whoever may read the compile cache beside it.
    assert stat.S_IMODE(os.stat(path).st_mode) == 0o666 & ~umask

    before = ops.compile_stats()
    loaded = resolve(tmp_path)
    second = grown(before)
    assert second["programs_built"] == 1 and second["programs_from_file"] == 1
    assert second["program_files_rejected"] == 0
    # The ledger stays honest: what the caller waited for is in
    # build_seconds (never 0.0), and nothing was traced or lowered.
    assert second["trace_seconds"] == 0.0 and second["lower_seconds"] == 0.0
    assert second["build_seconds"] > 0.0
    assert loaded is not built
    assert wrong_answers(loaded) is None and wrong_answers(built) is None
    # The device trace finds a program by its module's name.
    assert loaded.runtime_executable().hlo_modules()[0].name == "jit__standin"


def _cut_short(path, key):
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 7)


def _garbage(path, key):
    with open(path, "wb") as f:
        f.write(b"\x00not a program file")


def _other_payload(path, key):
    """A sound header over bytes that are no executable."""
    with open(path, "rb") as f:
        header = f.readline()
    packed = zlib.compress(b"no pickle at all", 1)
    header = json.loads(header)
    header.update(payload_bytes=len(packed),
                  payload_sha256=hashlib.sha256(packed).hexdigest())
    with open(path, "wb") as f:
        f.write(json.dumps(header).encode() + b"\n" + packed)


def _keyed(**changed):
    def rewrite(path, key):
        programs.store(path, dict(key, **changed), standin().lower(*ARGS).compile())
    return rewrite


def _foreign(path, key):
    programs.store(path, key, _yes_man.lower(*ARGS).compile())


@pytest.mark.parametrize(
    "spoil, rejected, why",
    [
        (lambda path, key: os.unlink(path), 0, None),  # missing: not "found"
        (_cut_short, 1, "cut short or altered"),
        (_garbage, 1, "unreadable"),
        (_other_payload, 1, "does not load"),
        (_keyed(jax="0.0.1"), 1, "key differs in jax"),
        (_keyed(jaxlib="0.0.1", platform_version="other libtpu"), 1,
         "key differs in jaxlib, platform_version"),
        (_keyed(rung=128), 1, "key differs in rung"),
        (_keyed(field_dtype="float32"), 1, "key differs in field_dtype"),
        (_keyed(source="0" * 64), 1, "key differs in source"),
        (_keyed(device_kind="TPU v5 lite", platform="tpu"), 1,
         "key differs in device_kind, platform"),
        (_foreign, 1, "wrong answers: rows [0, 1, 2, 3, 4, 5] accepted"),
    ],
    ids=["missing", "cut-short", "garbage", "unloadable", "jax", "jaxlib-libtpu",
         "rung", "dtype", "source", "device", "foreign-yes-man"],
)
def test_a_wrong_file_is_rebuilt_rewritten_and_counted(
    tmp_path, caplog, spoil, rejected, why
):
    resolve(tmp_path)
    key, path = key_and_path(tmp_path)
    spoil(path, key)
    before = ops.compile_stats()
    with caplog.at_level("WARNING", logger="narwhal.ops"):
        program = resolve(tmp_path)
    delta = grown(before)
    assert wrong_answers(program) is None  # never the file's wrong program
    assert delta["program_files_rejected"] == rejected
    assert delta["programs_from_file"] == 0 and delta["programs_built"] == 1
    assert delta["trace_seconds"] > 0  # it built
    reasons = [r.getMessage() for r in caplog.records if "not used" in r.getMessage()]
    assert len(reasons) == rejected
    if why:
        assert why in reasons[0], reasons
    # ... and the file is sound again: the next process loads it.
    loaded, why_not = programs.load(path, key, len(ARGS))
    assert why_not is None and wrong_answers(loaded) is None
    assert os.listdir(tmp_path) == [os.path.basename(path)]


def test_a_changed_source_changes_the_key(monkeypatch):
    """The key holds a digest of narwhal_tpu/ops/*.py: a process started
    after the kernel's source changed finds "key differs in source" (the
    case above) and rebuilds by itself."""
    key = programs.program_key("_standin", VARIANT)
    assert key["source"] == programs.source_digest() and len(key["source"]) == 64
    monkeypatch.setattr(programs, "source_digest", lambda: "f" * 64)
    assert programs.program_key("_standin", VARIANT) == dict(key, source="f" * 64)
    for field in ("jax", "jaxlib", "platform", "platform_version", "device_kind",
                  "device_id", "rung", "field_dtype", "flags"):
        assert field in key


def test_a_directory_that_cannot_be_written_still_gives_a_program(tmp_path, caplog):
    blocked = tmp_path / "a-file"
    blocked.write_text("in the way")
    with caplog.at_level("WARNING", logger="narwhal.ops"):
        program = resolve(blocked / "sub")
    assert wrong_answers(program) is None
    assert any("not written" in r.getMessage() for r in caplog.records)


def test_writers_at_once_leave_one_whole_file(tmp_path):
    """The device-backed primaries of a committee start together and may
    write the same file: a reader sees the old file, the new one or none,
    never a torn one."""
    key, path = key_and_path(tmp_path)
    compiled = [standin().lower(*ARGS).compile(), _yes_man.lower(*ARGS).compile()]
    torn, stop = [], threading.Event()
    deadline = time.monotonic() + 60

    def write(which):
        for _ in range(25):
            programs.store(path, key, compiled[which])

    def read():
        while not stop.is_set() and time.monotonic() < deadline:
            program, why_not = programs.load(path, key, len(ARGS))
            if why_not is not None:
                torn.append(why_not)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        writers = [threading.Thread(target=write, args=(i % 2,)) for i in range(4)]
        reader.start()
        for w in writers:
            w.start()
        for w in writers:
            w.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not reader.is_alive() and not any(w.is_alive() for w in writers)
    assert torn == []
    assert os.listdir(tmp_path) == [os.path.basename(path)]  # no temporary left
    program, why_not = programs.load(path, key, len(ARGS))
    assert why_not is None and program is not None
