"""Differential + adversarial tests for the TPU ed25519 batch verifier.

Ground truth: OpenSSL (via the `cryptography` package) for everything the
kernel ACCEPTS (our semantics are strictly more rejecting: S ≥ L,
non-canonical encodings and small-order points are rejected even where
some libraries accept), plus hand-crafted adversarial encodings for the
rejection paths.  Reference semantics: crypto/src/lib.rs:200-219
(`verify_strict` + dalek batch verification).
"""

import hashlib
import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
# The differential ground truth is OpenSSL; on hosts without the
# `cryptography` package this suite skips (the kernel still gets coverage
# from the pure-Python RFC 8032 cross-check in test_crypto.py).
pytest.importorskip("cryptography")

from cryptography.hazmat.primitives.asymmetric.ed25519 import (  # noqa: E402
    Ed25519PrivateKey,
    Ed25519PublicKey,
)

from narwhal_tpu.ops import ed25519 as E  # noqa: E402
from narwhal_tpu.ops import field25519 as F  # noqa: E402

rng = random.Random(7)


@pytest.fixture(scope="module", autouse=True)
def program_files(tmp_path_factory):
    """This file's program files (ops/programs.py) go to a directory of
    its own, never the checkout's `.jax_cache/`: the first test that
    needs the 16-row rung builds it and writes it HERE, and
    `test_the_next_backend_loads_the_program_file` loads it back."""
    from narwhal_tpu.ops import programs

    directory = tmp_path_factory.mktemp("programs")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(programs, "program_dir", lambda: str(directory))
        yield directory


def keypair():
    sk = Ed25519PrivateKey.generate()
    return sk, sk.public_key().public_bytes_raw()


def openssl_ok(msg, key, sig):
    try:
        Ed25519PublicKey.from_public_bytes(bytes(key)).verify(
            bytes(sig), bytes(msg)
        )
        return True
    except Exception:
        return False


def test_valid_signatures_accepted():
    sk, pk = keypair()
    msgs = [rng.randbytes(32) for _ in range(8)]
    sigs = [sk.sign(m) for m in msgs]
    mask = E.verify_batch_arrays(msgs, [pk] * 8, sigs)
    assert mask.all()


def test_corruptions_rejected_and_never_looser_than_openssl():
    """Random bit flips across message/key/signature: our verdict must be
    False whenever OpenSSL says False, and every acceptance of ours must
    be an OpenSSL acceptance (strictness is one-sided)."""
    sk, pk = keypair()
    cases = []
    for i in range(24):
        m = rng.randbytes(32)
        s = bytearray(sk.sign(m))
        k = bytearray(pk)
        mm = bytearray(m)
        target = rng.choice(("sig", "key", "msg", "none"))
        if target == "sig":
            s[rng.randrange(64)] ^= 1 << rng.randrange(8)
        elif target == "key":
            k[rng.randrange(32)] ^= 1 << rng.randrange(8)
        elif target == "msg":
            mm[rng.randrange(32)] ^= 1 << rng.randrange(8)
        cases.append((bytes(mm), bytes(k), bytes(s)))
    mask = E.verify_batch_arrays(*zip(*cases))
    for (m, k, s), ours in zip(cases, mask):
        ssl = openssl_ok(m, k, s)
        if ours:
            assert ssl, "kernel accepted a signature OpenSSL rejects"
        if not ssl:
            assert not ours


def test_scalar_malleability_rejected():
    """S' = S + L passes naive verifiers that skip the range check; both
    the reference (dalek) and this kernel must reject it."""
    sk, pk = keypair()
    m = rng.randbytes(32)
    sig = sk.sign(m)
    s_int = int.from_bytes(sig[32:], "little")
    forged = sig[:32] + (s_int + E.L_ORDER).to_bytes(32, "little")
    mask = E.verify_batch_arrays([m, m], [pk, pk], [sig, forged])
    assert list(mask) == [True, False]


def test_non_canonical_y_rejected():
    """Public key encoding with y ≥ p must be rejected."""
    sk, pk = keypair()
    m = rng.randbytes(32)
    sig = sk.sign(m)
    y = int.from_bytes(pk, "little") & ((1 << 255) - 1)
    # Craft a key whose y-field is ≥ p (y + p fits in 255 bits only if
    # y < 19; easier: set y-field to p + small).
    bad_y = F.P + 3
    assert bad_y < (1 << 255)
    bad_key = bad_y.to_bytes(32, "little")
    mask = E.verify_batch_arrays([m], [bad_key], [sig])
    assert not mask[0]


def test_small_order_key_rejected():
    """A = identity (small order): accepted by cofactorless math for
    k·A = identity, but verify_strict semantics reject it."""
    sk, pk = keypair()
    m = rng.randbytes(32)
    # identity point encodes as y=1, sign=0
    ident = (1).to_bytes(32, "little")
    # Build a "signature" that would pass cofactorless verification with
    # A = identity: R = [s]B for any s, since [k]A = identity.
    s = 12345
    rx, ry = E._ref_scalarmult(s)
    r_bytes = (ry | ((rx & 1) << 255)).to_bytes(32, "little")
    sig = r_bytes + s.to_bytes(32, "little")
    mask = E.verify_batch_arrays([m], [ident], [sig])
    assert not mask[0]


def test_off_curve_key_rejected():
    """A y with no valid x (x² non-square) must be rejected."""
    # Find a y in [0,p) that is not on the curve.
    d = E.D_INT
    y = 2
    while True:
        u = (y * y - 1) % F.P
        v = (d * y * y + 1) % F.P
        xx = (u * pow(v, F.P - 2, F.P)) % F.P
        if pow(xx, (F.P - 1) // 2, F.P) == F.P - 1:  # non-square
            break
        y += 1
    bad_key = y.to_bytes(32, "little")
    sk, pk = keypair()
    m = rng.randbytes(32)
    sig = sk.sign(m)
    mask = E.verify_batch_arrays([m], [bad_key], [sig])
    assert not mask[0]


def test_wrong_key_rejected():
    sk1, pk1 = keypair()
    sk2, pk2 = keypair()
    m = rng.randbytes(32)
    mask = E.verify_batch_arrays([m], [pk2], [sk1.sign(m)])
    assert not mask[0]


def test_batch_positions_independent():
    """The verdict mask lines up with batch positions across a batch
    mixing valid/invalid entries and spanning a chunk boundary."""
    sk, pk = keypair()
    msgs, keys, sigs, want = [], [], [], []
    for i in range(19):  # above the test ladder's one rung: 16 + 3
        m = rng.randbytes(32)
        s = sk.sign(m)
        if i % 3 == 0:
            s = s[:32] + bytes(32)  # S = 0 → [0]B = identity ≠ R
            want.append(False)
        else:
            want.append(True)
        msgs.append(m)
        keys.append(pk)
        sigs.append(s)
    mask = E.verify_batch_arrays(msgs, keys, sigs)
    assert list(mask) == want


def test_point_ops_match_python_reference():
    """Extended-coordinate add/double agree with the affine Python
    reference used to build the base table."""
    import jax.numpy as jnp

    for k1, k2 in [(3, 5), (7, 11), (123456789, 987654321)]:
        x1, y1 = E._ref_scalarmult(k1)
        x2, y2 = E._ref_scalarmult(k2)
        xs, ys = E._ref_scalarmult(k1 + k2)
        xd, yd = E._ref_scalarmult(2 * k1)
        p1 = (
            jnp.asarray(F.to_limbs(x1))[None],
            jnp.asarray(F.to_limbs(y1))[None],
            jnp.asarray(F.to_limbs(1))[None],
            jnp.asarray(F.to_limbs((x1 * y1) % F.P))[None],
        )
        p2 = (
            jnp.asarray(F.to_limbs(x2))[None],
            jnp.asarray(F.to_limbs(y2))[None],
            jnp.asarray(F.to_limbs(1))[None],
            jnp.asarray(F.to_limbs((x2 * y2) % F.P))[None],
        )
        ps = E.point_add(p1, p2)
        pd = E.point_double(p1)
        for point, (ex, ey) in ((ps, (xs, ys)), (pd, (xd, yd))):
            zinv = pow(F.from_limbs(np.asarray(F.canon(point[2]))[0]),
                       F.P - 2, F.P)
            gx = (F.from_limbs(np.asarray(F.canon(point[0]))[0]) * zinv) % F.P
            gy = (F.from_limbs(np.asarray(F.canon(point[1]))[0]) * zinv) % F.P
            assert (gx, gy) == (ex, ey)


def test_tpu_backend_class():
    from narwhal_tpu.crypto import backend as cb

    cb.set_backend("jax")
    try:
        sk, pk = keypair()
        from narwhal_tpu.crypto.keys import PublicKey, Signature
        from narwhal_tpu.crypto.digest import Digest

        d = Digest(hashlib.sha256(b"payload").digest())
        sig = Signature(sk.sign(bytes(d)))
        assert cb.verify(bytes(d), PublicKey(pk), sig)
        assert cb.verify_batch(d, [PublicKey(pk)], [sig])
        assert not cb.verify_batch(
            d, [PublicKey(pk)], [Signature(bytes(64))]
        )
    finally:
        cb.set_backend("cpu")


def test_tpu_name_refuses_a_cpu_only_jax():
    """`tpu` means the chip: on a JAX with no TPU it fails AT SELECTION,
    naming the platform it found, and leaves the live backend alone;
    `jax` selects the same batched verifier on whatever JAX has."""
    from narwhal_tpu.crypto import backend as cb
    from narwhal_tpu.ops.ed25519 import TpuBackend

    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        cb.set_backend("tpu")
    assert cb.get_backend().name == "cpu"
    cb.set_backend("jax")
    try:
        assert isinstance(cb.get_backend(), TpuBackend)
        assert cb.get_backend().name == "jax"
        assert cb.describe_backend().startswith("jax on platform cpu (")
    finally:
        cb.set_backend("cpu")


@pytest.mark.parametrize(
    "n, ladder, plan",
    [
        (1, (128, 512), [(0, 1, 128)]),
        (128, (128, 512), [(0, 128, 128)]),
        (129, (128, 512), [(0, 129, 512)]),
        (512, (128, 512), [(0, 512, 512)]),
        (517, (128, 512), [(0, 512, 512), (512, 517, 128)]),
        (1100, (128, 512), [(0, 512, 512), (512, 1024, 512), (1024, 1100, 128)]),
        (19, (16,), [(0, 16, 16), (16, 19, 16)]),
        # bench-10n-f3 (a certificate is 8 claims): one round at primary
        # 0, two rounds and a half after a freeze, a DRAIN_LIMIT burst of
        # certificates (128 x 8), which at this width no longer fits one
        # dispatch.
        (60, (128, 512), [(0, 60, 128)]),
        (150, (128, 512), [(0, 150, 512)]),
        (1024, (128, 512), [(0, 512, 512), (512, 1024, 512)]),
    ],
)
def test_chunk_plan_pads_to_a_rung_and_splits_above_the_top(n, ladder, plan):
    """Every dispatch shape is a rung of the ladder — the property that
    makes warm-up (which builds exactly the ladder) sufficient."""
    assert E.chunk_plan(n, ladder) == plan
    assert all(pad in ladder for _, _, pad in plan)


def test_ladder_follows_the_platform_not_a_knob(monkeypatch):
    """The chip gets CHIP_RUNGS, any other platform CPU_RUNGS; a backend
    resolves its ladder once, at construction."""
    from narwhal_tpu.ops.ed25519 import TpuBackend

    assert E.pad_ladder() == E.CPU_RUNGS
    assert E.CPU_RUNGS == (16,) and E.CHIP_RUNGS == (128, 512)
    backend = TpuBackend("jax")

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(E.jax, "devices", lambda: [Chip()])
    assert E.pad_ladder() == (128, 512)
    assert backend.rungs == (16,)  # resolved before the platform "changed"
    assert TpuBackend("jax").rungs == (128, 512)


def test_warmup_builds_the_ladder_and_reports_it():
    """After warm-up nothing is left to build: a batch of any size
    dispatches only shapes the warm-up already built, and the device
    report says so in numbers."""
    from narwhal_tpu.ops.ed25519 import TpuBackend

    backend = TpuBackend("jax")
    line = backend.warmup()
    assert line.startswith("rungs 16, ")
    assert backend.device_report()["dispatched"] == {}  # warm-up is not live
    sk, pk = keypair()
    msgs = [bytes([i]) * 32 for i in range(21)]
    assert all(backend.verify_batch_mask(msgs, [pk] * 21, [sk.sign(m) for m in msgs]))
    report = backend.device_report()
    assert report["platform"] == "cpu" and report["rungs"] == [16]
    assert report["programs_built"] == report["programs_at_ready"]
    assert report["dispatched"] == {"16": 2}  # 21 claims: chunks of 16 + 5
    # Peak device memory as the platform reports it (jax-cpu keeps no
    # allocator statistics: 0, never a missing key).
    assert report["memory_peak_bytes"] == E.memory_peak_bytes() >= 0


def test_compile_ledger_counts_builds_and_program_files_only():
    """The ledger's keys are what the ready line, the
    `crypto.verify.device` detail and the benchmark's
    `verifier.program_build_s` read: seconds of trace, lowering and
    build, and how the programs came.  JAX's persistent cache is off
    (`test_program_directory_and_no_persistent_cache`) and nothing of it
    is counted."""
    from narwhal_tpu import ops

    assert set(ops.compile_stats()) == {
        "programs_built", "trace_seconds", "lower_seconds", "build_seconds",
        "programs_from_file", "program_files_rejected",
    }


def adversarial_batch():
    """Valid, forged, malleable (S + L), small-order and wrong-key rows,
    mixed, with what OpenSSL-or-stricter says of each."""
    sk, pk = keypair()
    _, other = keypair()
    msgs, keys, sigs, want = [], [], [], []
    for i in range(12):
        m = bytes([i]) * 32
        key, sig, ok = pk, sk.sign(m), True
        if i % 6 == 1:  # forged: a signature of another message
            sig, ok = sk.sign(b"\xff" * 32), False
        elif i % 6 == 2:  # malleable
            s_int = int.from_bytes(sig[32:], "little") + E.L_ORDER
            sig, ok = sig[:32] + s_int.to_bytes(32, "little"), False
        elif i % 6 == 3:  # small order: A = identity, R = [s]B
            rx, ry = E._ref_scalarmult(777)
            key = (1).to_bytes(32, "little")
            sig = (ry | ((rx & 1) << 255)).to_bytes(32, "little") + (777).to_bytes(32, "little")
            ok = False
        elif i % 6 == 4:
            key, ok = other, False
        msgs.append(m), keys.append(key), sigs.append(sig), want.append(ok)
    return msgs, keys, sigs, want


def test_known_answers_catch_a_verifier_that_says_yes():
    """What a loaded program must pass before it may serve: the valid
    rows accepted AND the forged and the malleable row rejected, row by
    row.  The real program passes; a foreign one is named for what it
    got wrong, whichever way."""
    n = E.CPU_RUNGS[0]
    assert E.wrong_answers(E.verify_program(n), n) is None
    assert E.wrong_answers(lambda *a: np.ones(n, bool), n) == (
        "rows 1 (forged) accepted, 2 (S + L) accepted"
    )
    assert E.wrong_answers(lambda *a: np.zeros(n, bool), n).startswith(
        "rows 0 rejected, 3 rejected, 4 rejected"
    )
    assert "shape (4,)" in E.wrong_answers(lambda *a: np.ones(4, bool), n)
    args, expected = E.known_answers(n)
    assert [a.shape for a in args] == [a.shape for a in E.kernel_args(n)]
    assert [a.dtype for a in args] == [a.dtype for a in E.kernel_args(n)]
    assert expected.tolist() == [True, False, False] + [True] * (n - 3)


def test_the_next_backend_loads_the_program_file(program_files, monkeypatch):
    """The 16-row program this process built was written to its file; a
    backend that starts with nothing in memory (as a second process does)
    loads it whole: no trace, no lowering, the load's seconds in
    `build_seconds`, `programs_built == programs_at_ready` as on the
    built path, and the same verdicts as the built program gives."""
    from narwhal_tpu import ops
    from narwhal_tpu.ops import programs
    from narwhal_tpu.ops.ed25519 import TpuBackend

    n = E.CPU_RUNGS[0]
    built = E.verify_program(n)
    variant = {"rung": n, "field_dtype": "int32"}
    key = programs.program_key("_verify_kernel", variant)
    path = programs.program_path(key, variant)
    if not os.path.exists(path):
        # An earlier test FILE of this worker process built the program
        # (one a process, whoever asks first) and wrote it elsewhere.
        programs.store(path, key, built)
    assert os.listdir(program_files) == ["_verify_kernel-16-int32-cpu-cpu-d0.program"]
    msgs, keys, sigs, want = adversarial_batch()
    assert list(E.verify_batch_arrays(msgs, keys, sigs)) == want

    monkeypatch.setattr(E, "_programs", {})  # nothing resolved yet
    before = ops.compile_stats()
    backend = TpuBackend("jax")
    line = backend.warmup()
    after = ops.compile_stats()
    assert E.verify_program(n) is not built
    assert after["programs_from_file"] - before["programs_from_file"] == 1
    assert after["programs_built"] - before["programs_built"] == 1
    assert after["program_files_rejected"] == before["program_files_rejected"]
    assert after["trace_seconds"] == before["trace_seconds"]
    assert after["lower_seconds"] == before["lower_seconds"]
    assert after["build_seconds"] > before["build_seconds"]
    assert "{} of them loaded from program files".format(after["programs_from_file"]) in line
    assert backend.verify_batch_mask(msgs, keys, sigs) == want
    report = backend.device_report()
    assert report["programs_built"] == report["programs_at_ready"]
    assert report["programs_from_file"] == after["programs_from_file"]
    assert report["program_files_rejected"] == before["program_files_rejected"]
    assert report["dispatched"] == {"16": 1}


@pytest.mark.parametrize("n, pad, chunks", [(3, 16, 1), (21, [16, 16], 2)])
def test_dispatch_thread_hands_back_its_stamps(n, pad, chunks):
    """The async dispatch returns, with the mask, the dispatch thread's
    own stages of the verify-stage trace and its extras."""
    import asyncio
    import time

    from narwhal_tpu.ops.ed25519 import TpuBackend

    backend = TpuBackend("jax")
    sk, pk = keypair()
    msgs = [bytes([i]) * 32 for i in range(n)]
    sigs = [sk.sign(m) for m in msgs]
    t0 = time.time()
    mask, compute_s, stamps = asyncio.run(
        backend.averify_batch_mask_timed(msgs, [pk] * n, sigs)
    )
    t1 = time.time()
    assert mask == [True] * n
    assert t0 <= stamps["prepare"] <= stamps["enqueued"] <= stamps["fetched"] <= t1
    assert stamps["fetched"] - stamps["prepare"] <= compute_s + 0.01
    assert stamps["pad"] == pad and stamps["chunks"] == chunks
    assert stamps["cpu_s"] >= 0


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_program_directory_and_no_persistent_cache(tmp_path, placed_from_outside):
    """JAX_COMPILATION_CACHE_DIR set: that directory holds the program
    files.  Unset: one fixed path inside the checkout — never $HOME, a
    temp name, a pid or a time — so every process of a run shares it.
    Either way JAX's persistent cache is off once the package is
    imported: the program files are the one compile cache."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    expected = os.path.join(repo, ".jax_cache")
    if placed_from_outside:
        expected = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, narwhal_tpu.ops; "
         "print(narwhal_tpu.ops.program_dir()); "
         "print(jax.config.jax_enable_compilation_cache)"],
        env=env, capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [expected, "False"]


def test_chip_parents_and_cpu_entry_points_stay_off_jax():
    """A chip belongs to one process: whoever imports JAX holds it.  The
    parents of chip users (chip_smoke.py, benchmark/local_bench.py) and
    the CPU node/worker/client entry points must not import it."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import chip_smoke, benchmark.local_bench, "
         "narwhal_tpu.node.main, narwhal_tpu.node.benchmark_client, "
         "narwhal_tpu.worker.worker, narwhal_tpu.consensus.replay; "
         "sys.exit('jax' in sys.modules)"],
        env=dict(os.environ, PYTHONPATH=repo), cwd=repo,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr


def test_tpu_averify_runs_off_event_loop(monkeypatch):
    """The async verify seam must run the device round trip on the backend's
    dispatch thread, not the event loop (VERDICT r2: a synchronous device
    call would stall the primary's networking for the device latency)."""
    import asyncio
    import threading

    from narwhal_tpu.ops.ed25519 import TpuBackend
    from narwhal_tpu.crypto.digest import Digest
    from narwhal_tpu.crypto.keys import PublicKey, Signature

    sk, pk = keypair()
    d = Digest(hashlib.sha256(b"offloop").digest())
    sig = Signature(sk.sign(bytes(d)))

    backend = TpuBackend()
    threads = []
    inner = E.verify_batch_arrays

    def recording(*args, **kwargs):
        threads.append(threading.current_thread().name)
        return inner(*args, **kwargs)

    monkeypatch.setattr(E, "verify_batch_arrays", recording)

    async def go():
        # Loop stays responsive while the verify runs: a ticker task must
        # keep making progress during the await.
        ticks = []

        async def ticker():
            while True:
                ticks.append(1)
                await asyncio.sleep(0.001)

        t = asyncio.ensure_future(ticker())
        mask = await backend.averify_batch_mask(
            [bytes(d)] * 3, [PublicKey(pk)] * 3, [sig, Signature(bytes(64)), sig]
        )
        t.cancel()
        return mask, ticks

    mask, ticks = asyncio.run(go())
    assert mask == [True, False, True]
    assert threads and threads[0].startswith("tpu-verify"), threads
    assert ticks, "event loop starved during device verify"


def count_equations(jaxpr) -> int:
    """Equations of a jaxpr, those of every nested one (loop bodies,
    branches, inner jits) included."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)  # ClosedJaxpr -> Jaxpr
                if hasattr(inner, "eqns"):
                    total += count_equations(inner)
    return total


# The verify program's size, in traced equations at the chip's bottom
# rung.  Tracing and lowering cost ~0.25 ms an equation in EVERY process
# that builds the ladder, cache hit or not (PR 27: 98,258 equations were
# ~24 s a rung on the chip's host before the node could join, 22,722 are
# ~5.6 s).  The ceiling is a fifth above what PR 27 reached, so that a
# later edit cannot quietly bring the wait back; 35,000 is the most it
# may ever be raised to.  No compile, no chip: runs wherever JAX does.
VERIFY_KERNEL_EQUATIONS_CEILING = 27_000


def test_verify_program_stays_within_its_size_budget():
    b = E.CHIP_RUNGS[0]
    traced = jax.make_jaxpr(E._verify_kernel.__wrapped__)(*E.kernel_args(b))
    equations = count_equations(traced.jaxpr)
    assert VERIFY_KERNEL_EQUATIONS_CEILING <= 35_000
    assert equations <= VERIFY_KERNEL_EQUATIONS_CEILING, equations
    # One multiplication is the unit everything else is made of.
    one = jax.ShapeDtypeStruct((b, F.LIMBS), F.DTYPE)
    assert count_equations(jax.make_jaxpr(F.mul)(one, one).jaxpr) <= 200


def test_float32_lane_mode_field_ops():
    """The float32 lane dtype (NARWHAL_FIELD_DTYPE=float32) computes the
    dtype-sensitive pieces — field mul/sub/canon (scale-and-floor
    carries, the byte split before the ×38 fold, ×k chunking) at random
    values and at the weak bound's corners, and the one-hot table select
    — exactly, in a
    subprocess so the env-selected dtype is picked up at import.  Scoped
    to ops that compile in seconds; the FULL verify kernel under f32
    (several minutes of cold CPU compile) is covered by running
    `NARWHAL_FIELD_DTYPE=float32 pytest tests/test_field25519.py
    tests/test_ed25519.py`."""
    import os
    import subprocess
    import sys

    code = """
import sys
sys.path.insert(0, %r)
# Pin the CPU backend the same way conftest does.
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from narwhal_tpu.ops import field25519 as F
assert F.FP and F.DTYPE.__name__ == "float32"
rng = np.random.default_rng(3)
P = F.P
for _ in range(8):
    x = int(rng.integers(0, 1 << 62)) * int(rng.integers(0, 1 << 62)) %% P
    y = (P - 1 - x) %% P
    xl, yl = F.to_limbs(x)[None], F.to_limbs(y)[None]
    assert F.from_limbs(np.asarray(F.mul(xl, yl))[0]) %% P == x * y %% P
    assert F.from_limbs(np.asarray(F.sub(xl, yl))[0]) %% P == (x - y) %% P
    assert F.from_limbs(np.asarray(F.mul_small(xl, 121666))[0]) %% P == (
        x * 121666 %% P)
    assert F.from_limbs(np.asarray(F.canon(xl))[0]) == x
# The weak bound's corners (every limb 511, limb 0 at 293, row sums at
# 2^23, carries from the lane's whole exact range), the same table the
# int32 run parametrises.
from tests.test_field25519 import check_all_corners
assert check_all_corners() >= 40
from narwhal_tpu.ops import ed25519 as E
import jax.numpy as jnp
ws = [3, 0, 15]
pt = E._select_from_table(E._B_TABLE, jnp.asarray(ws))
for row, w in enumerate(ws):
    got = [F.from_limbs(np.asarray(c)[row]) for c in pt]
    exp_x, exp_y = E._ref_scalarmult(w)
    assert got[0] == exp_x and got[1] == exp_y and got[2] == 1, (w, got)
print("F32-OK")
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, NARWHAL_FIELD_DTYPE="float32")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0 and "F32-OK" in out.stdout, (
        out.stdout, out.stderr
    )
