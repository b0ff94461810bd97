"""Compiled programs kept as files: this package's one compile cache.

JAX's persistent cache is keyed by the LOWERED module, so a process has to
trace and lower a program again (for the verify kernel ~5.6 s a rung on
the chip's host, PERF.md, PR 27) only to compute the key of an executable
that is already on disk; it is off for the process (ops/__init__.py).
Here a compiled program is kept whole, as
`jax.experimental.serialize_executable` writes it, under a name and a key
that a process can compute WITHOUT tracing: `resolve()` loads that file
where it is sound and only otherwise traces, lowers, builds and writes it.

One file per program and device: the NAME says which program for which
device (program, variant, platform, device kind, device id), the KEY in
its first line says which build of it (jax, jaxlib, the platform's own
version, the compiler flags, a digest of this package's source), so a
stale build is overwritten where it lies and builds for different devices
lie side by side.  The key is compared before a byte of the payload is
read; the payload is unpickled only after its length and digest matched
what the writer recorded, and only ever comes from the directory this
program writes its own program files to (`ops.program_dir()`).

Every way a file can be wrong (unreadable, another key, cut short, not
loadable, wrong answers) is counted in the compile ledger
(`program_files_rejected`), logged with its reason and repaired by
building and writing the file anew.
"""

from __future__ import annotations

import functools
import glob
import hashlib
import json
import logging
import os
import re
import threading
import time
import zlib
from typing import Callable, Optional, Sequence, Tuple

import jax
import jaxlib
from jax.experimental import serialize_executable

from . import count_program_file, program_dir

log = logging.getLogger("narwhal.ops")

_HEADER_LIMIT = 1 << 16  # bytes of the key line; a real one is ~600


@functools.cache
def source_digest() -> str:
    """Digest of the modules a kernel is traced from: every `*.py` of this
    package (the simple, safe set), names and contents."""
    h = hashlib.sha256()
    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read() + b"\0")
    return h.hexdigest()


def program_key(name: str, variant: dict) -> dict:
    """Everything an executable of program ``name`` is bound to, from what
    this process can observe without tracing it.  ``variant`` is the
    caller's part (the padded shape, the lane dtype)."""
    device = jax.devices()[0]
    return {
        "program": name,
        **variant,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_id": device.id,
        "device_count": len(jax.devices()),
        # Carries libtpu's build on the chip.
        "platform_version": device.client.platform_version,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "flags": [os.environ.get(v, "") for v in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")],
        "source": source_digest(),
    }


def program_path(key: dict, variant: dict, directory: Optional[str] = None) -> str:
    """Where the program of ``key`` lies: one name per program, variant
    and device, whatever build."""
    parts = [key["program"], *variant.values(), key["platform"],
             key["device_kind"], f"d{key['device_id']}"]
    name = "-".join(re.sub(r"[^A-Za-z0-9_.]+", "_", str(p)) for p in parts)
    return os.path.join(directory or program_dir(), name + ".program")


def _trees(n_args: int):
    """The calling convention of every program kept here: ``n_args``
    positional arrays in, one array out (pytrees are not serializable,
    so they are made, not stored)."""
    return (
        jax.tree_util.tree_structure(((0,) * n_args, {})),
        jax.tree_util.tree_structure(0),
    )


def store(path: str, key: dict, compiled) -> None:
    """Write ``compiled`` under ``key`` to ``path``: a temporary name in
    the same directory, then a rename, so a reader (or a second writer:
    the device-backed primaries of a committee start together) only ever
    sees a whole file."""
    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    if (in_tree, out_tree) != _trees(in_tree.num_leaves):
        raise ValueError(f"{key['program']}: not positional arrays in, one array out")
    packed = zlib.compress(payload, 1)
    header = {
        "key": key,
        "payload_bytes": len(packed),
        "payload_sha256": hashlib.sha256(packed).hexdigest(),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # A name of this writer's own, and the mode the umask gives (mkstemp
    # would make it 0600).
    tmp = f"{path}.{os.getpid()}-{threading.get_ident()}.writing"
    try:
        with open(tmp, "wb") as f:
            f.write(json.dumps(header, sort_keys=True).encode() + b"\n")
            f.write(packed)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load(path: str, key: dict, n_args: int) -> Tuple[Optional[Callable], Optional[str]]:
    """(program, None) from a sound file, (None, why not) otherwise;
    ``why`` is None too where there is simply no file."""
    try:
        f = open(path, "rb")
    except FileNotFoundError:
        return None, None
    except OSError as e:
        return None, f"unreadable: {e}"
    with f:
        try:
            header = json.loads(f.readline(_HEADER_LIMIT))
            found = header["key"]
            size, digest = header["payload_bytes"], header["payload_sha256"]
        except (OSError, ValueError, KeyError, TypeError) as e:
            return None, f"unreadable: {type(e).__name__}: {e}"
        if not isinstance(found, dict):
            return None, "unreadable: its key is no object"
        if found != key:
            differs = sorted(
                k for k in set(key) | set(found) if key.get(k) != found.get(k)
            )
            return None, "key differs in " + ", ".join(differs)
        try:
            packed = f.read()
        except OSError as e:
            return None, f"unreadable: {e}"
    if len(packed) != size or hashlib.sha256(packed).hexdigest() != digest:
        return None, f"cut short or altered: {len(packed)} of {size} bytes"
    try:
        return serialize_executable.deserialize_and_load(
            zlib.decompress(packed), *_trees(n_args),
            # The one device it was built for (the key's `device_id`), not
            # every device the process sees.
            execution_devices=jax.devices()[:1],
        ), None
    except Exception as e:  # whatever a foreign or stale payload raises
        return None, f"does not load: {type(e).__name__}: {e}"


def resolve(
    jitted,
    abstract_args: Sequence[jax.ShapeDtypeStruct],
    variant: dict,
    wrong_answers: Callable[[Callable], Optional[str]],
    directory: Optional[str] = None,
) -> Callable:
    """The compiled program of ``jitted`` for ``abstract_args``: loaded
    from its file where that is sound and gives the known answers
    (``wrong_answers(program)`` is None), else traced, lowered, compiled
    and written.  Which of the two happened, and what it cost,
    is in the compile ledger (`ops.compile_stats()`)."""
    key = program_key(jitted.__name__, variant)
    path = program_path(key, variant, directory)
    t0 = time.perf_counter()
    program, why_not = load(path, key, len(abstract_args))
    load_s = time.perf_counter() - t0
    if program is not None:
        try:
            why_not = wrong_answers(program)
        except Exception as e:  # a payload that loads and cannot run
            why_not = f"{type(e).__name__}: {e}"
        if why_not is None:
            count_program_file(load_s, used=True)
            return program
        why_not = f"wrong answers: {why_not}"
    if why_not is not None:
        count_program_file(load_s, used=False)
        log.warning("Program file %s not used (%s): building it anew", path, why_not)
    program = jitted.lower(*abstract_args).compile()
    try:
        store(path, key, program)
    except OSError as e:
        log.warning("Program file %s not written: %s", path, e)
    return program
