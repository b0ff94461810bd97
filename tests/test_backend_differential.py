"""Differential adversarial suite: every TpuBackend verdict vs the CPU
backend's, on hostile inputs (ISSUE 14 satellite).

The safety property is one-sided by design: the batched path must NEVER
accept a signature the serial path rejects (a forgery slipping in only
when the committee runs the fast backend would be a consensus-split
machine).  The kernel is deliberately STRICTER than RFC 8032
cofactorless verifiers on small-order points (dalek `verify_strict`
semantics — see ops/ed25519.py's docstring), so on that one documented
class the verdicts legitimately diverge with the kernel on the
rejecting side; everywhere else — non-canonical scalars (S ≥ L),
non-canonical y encodings (y ≥ p), off-curve points, x=0/sign=1,
wrong keys, bit-flip corruptions, RFC 8032 vectors — the verdicts must
be EQUAL.

Ground truth is whatever `cpu_verify` rides on this host (OpenSSL via
`cryptography`, or the pure-Python RFC 8032 fallback) — i.e. exactly
the serial path a NARWHAL_CRYPTO_BACKEND=cpu committee trusts, which
is the comparison that matters for the A/B.

Marked ``slow``: the first kernel call costs an XLA compile (minutes on
a sandboxed CPU host without the persistent cache).  CI runs this file
explicitly in the check workflow, where the tier-1 test_ed25519 run has
already populated the in-job compile cache.
"""

import random

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

from narwhal_tpu.crypto import KeyPair  # noqa: E402
from narwhal_tpu.crypto import _ed25519_py as PY  # noqa: E402
from narwhal_tpu.crypto.keys import cpu_verify  # noqa: E402
from narwhal_tpu.ops import ed25519 as E  # noqa: E402
from narwhal_tpu.ops import field25519 as F  # noqa: E402

pytestmark = pytest.mark.slow

rng = random.Random(19)


def sign(kp: KeyPair, msg: bytes) -> bytes:
    """Raw-bytes signing via the pure-Python signer (works with or
    without OpenSSL and over arbitrary-length messages)."""
    a, prefix = PY._secret_expand(bytes(kp.secret))
    return PY.sign_expanded(a, prefix, bytes(kp.name), msg)


def tpu_mask(cases):
    msgs, keys, sigs = zip(*cases)
    return [bool(v) for v in E.verify_batch_arrays(msgs, keys, sigs)]


def cpu_mask(cases):
    return [bool(cpu_verify(m, k, s)) for m, k, s in cases]


def assert_never_looser(cases, context=""):
    """The one-sided safety gate: tpu accepts ⇒ cpu accepts."""
    t, c = tpu_mask(cases), cpu_mask(cases)
    for i, (tv, cv) in enumerate(zip(t, c)):
        if tv:
            assert cv, (
                f"{context}: batched path accepted case {i} that the "
                f"serial path rejects — {cases[i]!r}"
            )
    return t, c


# RFC 8032 §7.1 TEST 1-3: (secret key, public key, message) hex; the
# signatures are derived from the secret keys by the pure-Python RFC
# signer, with the PUBLISHED public keys pinned as the independent
# anchor (a signer drift would break the pk assert, not silently
# re-derive a self-consistent wrong vector).  TEST 1's signature is
# additionally pinned verbatim.
RFC8032_VECTORS = [
    (
        "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
        "",
    ),
    (
        "4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
        "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
        "72",
    ),
    (
        "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
        "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
        "af82",
    ),
]

RFC8032_TEST1_SIG = (
    "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
    "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
)


def test_rfc8032_vectors_verdict_identical():
    cases = []
    for sk, pk, m in RFC8032_VECTORS:
        sk, pk, m = bytes.fromhex(sk), bytes.fromhex(pk), bytes.fromhex(m)
        assert PY.secret_to_public(sk) == pk, "RFC pk anchor drifted"
        cases.append((m, pk, PY.sign(sk, m)))
    assert cases[0][2] == bytes.fromhex(RFC8032_TEST1_SIG)
    # Corrupted copies: each vector with one flipped message bit.
    for m, pk, sig in list(cases):
        mm = bytearray(m or b"\x00")
        mm[0] ^= 1
        cases.append((bytes(mm), pk, sig))
    t, c = assert_never_looser(cases, "rfc8032")
    assert t == c, (t, c)
    assert t[:3] == [True, True, True]
    assert t[3:] == [False, False, False]


def test_non_canonical_scalar_verdict_identical():
    """S' = S + L (signature malleability): both backends reject."""
    kp = KeyPair.generate(rng.randbytes(32))
    m = rng.randbytes(32)
    sig = sign(kp, m)
    s_int = int.from_bytes(sig[32:], "little")
    forged = sig[:32] + (s_int + E.L_ORDER).to_bytes(32, "little")
    cases = [(m, bytes(kp.name), sig), (m, bytes(kp.name), forged)]
    t, c = assert_never_looser(cases, "scalar-malleability")
    assert t == c == [True, False]


def test_non_canonical_y_and_off_curve_verdict_identical():
    kp = KeyPair.generate(rng.randbytes(32))
    m = rng.randbytes(32)
    sig = sign(kp, m)
    # y >= p in the key and in R, and an off-curve y (x² non-square).
    bad_y = (F.P + 3).to_bytes(32, "little")
    y = 2
    while True:
        u = (y * y - 1) % F.P
        v = (PY.D * y * y + 1) % F.P
        xx = (u * pow(v, F.P - 2, F.P)) % F.P
        if pow(xx, (F.P - 1) // 2, F.P) == F.P - 1:
            break
        y += 1
    off_curve = y.to_bytes(32, "little")
    cases = [
        (m, bad_y, sig),
        (m, bytes(kp.name), bad_y + sig[32:]),  # non-canonical R
        (m, off_curve, sig),
    ]
    t, c = assert_never_looser(cases, "non-canonical")
    assert t == c == [False, False, False]


def test_wrong_key_verdict_identical():
    kp1 = KeyPair.generate(rng.randbytes(32))
    kp2 = KeyPair.generate(rng.randbytes(32))
    m = rng.randbytes(32)
    cases = [(m, bytes(kp2.name), sign(kp1, m))]
    t, c = assert_never_looser(cases, "wrong-key")
    assert t == c == [False]


def _small_order_forgery():
    """A cofactorless forgery under A = identity: k·A is the identity
    for every k, so R = [S]B satisfies [S]B = R + [k]A for ANY message
    — the classic small-order-key attack `verify_strict` exists for."""
    s = 987654321
    rx, ry = E._ref_scalarmult(s)
    r_bytes = (ry | ((rx & 1) << 255)).to_bytes(32, "little")
    ident = (1).to_bytes(32, "little")
    return (rng.randbytes(32), ident, r_bytes + s.to_bytes(32, "little"))


def test_small_order_key_batched_strictly_more_rejecting():
    """The ONE documented divergence class: the serial cofactorless
    verifiers (OpenSSL / pure-Python RFC 8032) ACCEPT the identity-key
    forgery, the kernel (verify_strict semantics) rejects it.  The
    divergence is on the rejecting side — the safety property holds —
    and this test pins both facts so a backend change that silently
    flips either direction fails loudly."""
    case = _small_order_forgery()
    m, k, s = case
    t, c = tpu_mask([case]), cpu_mask([case])
    assert t == [False], "kernel must reject a small-order key"
    # The RFC 8032 cofactorless reference (the pure-Python verifier)
    # ACCEPTS this forgery — pinned so the exemption class stays
    # documented by an executable fact.  The host's cpu_verify may ride
    # OpenSSL, whose verdict we don't pin — the never-looser property
    # (tpu False here) holds under either.
    assert PY.verify(k, m, s) is True, (
        "the cofactorless reference became strict on small-order keys "
        "— fold this class back into the verdict-equality gate"
    )
    assert c in ([True], [False])  # either way, kernel is not looser


def test_truncated_signature_never_accepted():
    """Truncated/oversized raw signatures: the typed protocol seam
    (`Signature`) makes these unrepresentable in a live burst, and at
    the raw-array seam the kernel fails LOUD (ValueError) while the
    serial path returns False — neither path can accept."""
    kp = KeyPair.generate(rng.randbytes(32))
    m = rng.randbytes(32)
    sig = sign(kp, m)
    for bad in (sig[:63], sig[:32], sig + b"\x00"):
        assert cpu_verify(m, kp.name, bad) is False
        with pytest.raises(ValueError):
            E.verify_batch_arrays([m], [bytes(kp.name)], [bad])
    for bad_key in (bytes(kp.name)[:31], bytes(kp.name) + b"\x00"):
        assert cpu_verify(m, bad_key, sig) is False
        with pytest.raises(ValueError):
            E.verify_batch_arrays([m], [bad_key], [sig])


def test_bitflip_fuzz_verdicts_never_looser_and_equal_off_torsion():
    """Seeded bit-flip fuzz across message/key/signature bytes: the
    batched verdict must equal the serial one except where the flip
    lands a small-order encoding (kernel-stricter, still never-looser).
    One batch, padded shape 32 (reuses the warm compile)."""
    kp = KeyPair.generate(rng.randbytes(32))
    cases, flips = [], []
    for i in range(24):
        m = bytearray(rng.randbytes(32))
        k = bytearray(kp.name)
        s = bytearray(sign(kp, bytes(m)))
        target = rng.choice(("sig", "key", "msg", "none"))
        if target == "sig":
            s[rng.randrange(64)] ^= 1 << rng.randrange(8)
        elif target == "key":
            k[rng.randrange(32)] ^= 1 << rng.randrange(8)
        elif target == "msg":
            m[rng.randrange(32)] ^= 1 << rng.randrange(8)
        flips.append(target)
        cases.append((bytes(m), bytes(k), bytes(s)))
    t, c = assert_never_looser(cases, "bitflip-fuzz")
    for i, (tv, cv) in enumerate(zip(t, c)):
        if flips[i] == "none":
            assert tv and cv, f"untouched case {i} must verify on both"
        if tv != cv:
            # Divergence is only legal kernel-stricter, and only when
            # the corrupted encoding decodes to a small-order point.
            assert not tv and cv
            _, key, sig = cases[i]
            a = PY._point_decompress(key)
            r = PY._point_decompress(sig[:32])
            small = False
            for p in (a, r):
                if p is None:
                    continue
                q = p
                for _ in range(3):
                    q = PY._point_add(q, q)
                if PY._point_equal(q, PY._NEUTRAL):
                    small = True
            assert small, (
                f"case {i}: verdicts diverge on a non-small-order input"
            )


def test_batch_positions_and_padding_boundaries():
    """Mask positions line up across a mixed batch spanning the pad
    boundary, and agree with the serial path elementwise."""
    kp = KeyPair.generate(rng.randbytes(32))
    cases = []
    for i in range(19):  # pads to 32
        m = rng.randbytes(32)
        s = sign(kp, m)
        if i % 3 == 0:
            s = s[:32] + bytes(32)  # S = 0: [0]B = identity != R
        cases.append((m, bytes(kp.name), s))
    t, c = assert_never_looser(cases, "positions")
    assert t == c
    assert t == [i % 3 != 0 for i in range(19)]


def test_backend_seam_masks_match_cpu_backend():
    """The crypto.backend seam itself: TpuBackend.verify_batch_mask ==
    CpuBackend.verify_batch_mask over a mixed valid/hostile batch of
    typed (Digest, PublicKey, Signature) inputs — the exact call shape
    Core's burst uses."""
    from narwhal_tpu.crypto.backend import CpuBackend
    from narwhal_tpu.crypto.digest import Digest
    from narwhal_tpu.crypto.keys import PublicKey, Signature
    from narwhal_tpu.ops.ed25519 import TpuBackend

    kp = KeyPair.generate(rng.randbytes(32))
    d = Digest(rng.randbytes(32))
    good = kp.sign(d)
    msgs = [bytes(d)] * 4
    keys = [PublicKey(kp.name)] * 4
    sigs = [
        good,
        Signature(bytes(64)),
        Signature(good[:32] + (0).to_bytes(32, "little")),
        good,
    ]
    t = TpuBackend().verify_batch_mask(msgs, keys, sigs)
    c = CpuBackend().verify_batch_mask(msgs, keys, sigs)
    assert list(t) == list(c) == [True, False, False, True]
