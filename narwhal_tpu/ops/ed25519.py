"""Batched ed25519 signature verification on TPU (vmapped JAX).

The reference's per-round crypto hot loop is `Signature::verify_batch`
(crypto/src/lib.rs:206-219), called with 2f+1 signatures per certificate ×
N certificates per round (primary/src/messages.rs:189-215).  Its dalek
backend runs 51-bit-limb u128 arithmetic on the CPU; here the same batch
maps to TPU vector lanes: field elements are 32×8-bit int32 limbs
(ops/field25519.py), points are extended twisted-Edwards coordinates
(X:Y:Z:T), and the double-scalar ladder [s]B + [k](-A) runs one shared
MSB-first windowed Horner loop for the whole batch.

Verification semantics (strict, a superset of RFC 8032 rejections —
deviations from specific CPU libraries are *more* rejections, never fewer):
- reject S ≥ L (non-canonical scalar; all mainstream verifiers agree),
- reject non-canonical point encodings (y ≥ p),
- reject encodings with no valid x (not on curve) or x=0 with sign=1,
- reject small-order A or R ([8]P = identity) — dalek `verify_strict`,
- accept iff [S]B = R + [k]A with k = SHA-512(R ‖ A ‖ M) mod L, checked as
  projective point equality (equivalent to compressed-byte equality since
  only canonical encodings are admitted).

SHA-512(R‖A‖M) and the scalar window decomposition run host-side during
batch prep (measured ~8-10 µs/signature on a 1-core host, overlappable
with device compute; see bench_crypto.py); every field/curve operation
runs on device.  Differential-tested against OpenSSL over random and
adversarial inputs (tests/test_ed25519.py).
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..utils.clock import wall_now
from ..utils.devtrace import annotate, current_burst
from . import compile_stats, device_identity, programs
from . import field25519 as F

P = F.P
L_ORDER = (1 << 252) + 27742317777372353535851937790883648493

D_INT = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1_INT = pow(2, (P - 1) // 4, P)

_D = jnp.asarray(F.to_limbs(D_INT))
_2D = jnp.asarray(F.to_limbs((2 * D_INT) % P))
_SQRT_M1 = jnp.asarray(F.to_limbs(SQRT_M1_INT))
_ONE = jnp.asarray(F.to_limbs(1))
_ZERO = jnp.asarray(F.to_limbs(0))

# --------------------------------------------------------------- point ops
# A point is a tuple (X, Y, Z, T) of int32[..., LIMBS=32] with x=X/Z,
# y=Y/Z, T = XY/Z (extended homogeneous coords; Hisil–Wong–Carter–Dawson).

Point = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]


def identity_like(x: jnp.ndarray) -> Point:
    shape = x.shape[:-1] + (F.LIMBS,)
    zero = jnp.broadcast_to(_ZERO, shape)
    one = jnp.broadcast_to(_ONE, shape)
    return (zero, one, one, zero)


def point_add(p: Point, q: Point) -> Point:
    """Unified add (add-2008-hwcd-3, a=-1): complete on the prime-order
    subgroup and correct for all curve points when q is not exceptional —
    we only ever add decompressed curve points, for which it is total."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = F.mul(F.sub(y1, x1), F.sub(y2, x2))
    b = F.mul(F.add(y1, x1), F.add(y2, x2))
    c = F.mul(F.mul(_2D, t1), t2)
    d = F.mul(F.add(z1, z1), z2)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return _completed(e, f, g, h)


def _completed(e, f, g, h) -> Point:
    """(E·F, G·H, F·G, E·H): the four products that close an add or a
    doubling.  mul() reads its FIRST operand limb by limb (one broadcast
    column per limb, which the compiler extracts in two operations of
    their own), so the products are written with two first operands, not
    three: the extraction is shared."""
    return (F.mul(e, f), F.mul(g, h), F.mul(g, f), F.mul(e, h))


def point_double(p: Point) -> Point:
    """dbl-2008-hwcd for a = -1."""
    x1, y1, z1, _ = p
    a = F.square(x1)
    b = F.square(y1)
    zz = F.square(z1)
    c = F.add(zz, zz)  # 2·z² via the 1-sweep add (mul_small carries 4×)
    h = F.add(a, b)
    e = F.sub(h, F.square(F.add(x1, y1)))
    g = F.sub(a, b)
    f = F.add(c, g)
    return _completed(e, f, g, h)


def point_neg(p: Point) -> Point:
    x, y, z, t = p
    return (F.neg(x), y, z, F.neg(t))


def point_select(cond: jnp.ndarray, p: Point, q: Point) -> Point:
    return tuple(F.select(cond, a, b) for a, b in zip(p, q))


def point_eq(p: Point, q: Point) -> jnp.ndarray:
    """Projective equality: X1·Z2 == X2·Z1 and Y1·Z2 == Y2·Z1."""
    x1, y1, z1, _ = p
    x2, y2, z2, _ = q
    return F.eq(F.mul(x1, z2), F.mul(x2, z1)) & F.eq(
        F.mul(y1, z2), F.mul(y2, z1)
    )


def is_identity(p: Point) -> jnp.ndarray:
    x, y, z, _ = p
    return F.is_zero(x) & F.eq(y, z)


def is_small_order(p: Point) -> jnp.ndarray:
    """[8]P == identity (the 8-torsion subgroup)."""
    q = point_double(point_double(point_double(p)))
    return is_identity(q)


# ------------------------------------------------------------ decompression


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray,
               y_canonical: jnp.ndarray) -> Tuple[Point, jnp.ndarray]:
    """Compressed Edwards y + sign bit → extended point and validity mask.

    Rejects: non-canonical y (y ≥ p, decided host-side from the raw bytes
    and passed as `y_canonical`), y²-1/(dy²+1) a non-square, and the
    x = 0 / sign = 1 encoding (RFC 8032 §5.1.3 step 4).
    """
    y = y_limbs
    yy = F.square(y)
    u = F.sub(yy, _ONE)
    v = F.add(F.mul(_D, yy), _ONE)
    # x = u·v³·(u·v⁷)^((p-5)/8)  (RFC 8032 §5.1.3)
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    x = F.mul(F.mul(u, v3), F.pow_p58(F.mul(u, v7)))
    vxx = F.mul(v, F.square(x))
    ok_direct = F.eq(vxx, u)
    ok_twist = F.eq(vxx, F.neg(u))
    x = F.select(ok_direct, x, F.mul(_SQRT_M1, x))
    on_curve = ok_direct | ok_twist
    xc = F.canon(x)
    x_is_zero = jnp.all(xc == 0, axis=-1)
    # x = 0 with sign = 1 is invalid; otherwise flip x to match the sign.
    # Parity via % 2, not & 1: exact in both lane dtypes (f32 mod of an
    # exact integer < 2^24 is exact), and the comparison against the
    # int32 sign bit promotes losslessly.
    sign_ok = ~(x_is_zero & (sign == 1))
    flip = (xc[..., 0] % 2) != sign
    x = F.select(flip, F.neg(xc), xc)
    valid = on_curve & sign_ok & y_canonical
    point = (x, y, jnp.broadcast_to(_ONE, y.shape), F.mul(x, y))
    return point, valid


# ------------------------------------------------------- base point table

def _ref_scalarmult(k: int) -> Tuple[int, int]:
    """Host-side scalar mult with Python ints (table construction only)."""
    bx = 15112221349535400772501151409588531511454012693041857206046113283949847762202
    by = 46316835694926478169428394003475163141307993866256225615783033603165251855960

    def edwards_add(p, q):
        x1, y1 = p
        x2, y2 = q
        den = (D_INT * x1 * x2 * y1 * y2) % P
        x3 = (x1 * y2 + x2 * y1) * pow(1 + den, P - 2, P)
        y3 = (y1 * y2 + x1 * x2) * pow(1 - den, P - 2, P)
        return (x3 % P, y3 % P)

    q = (0, 1)
    b = (bx, by)
    while k > 0:
        if k & 1:
            q = edwards_add(q, b)
        b = edwards_add(b, b)
        k >>= 1
    return q


_B_TABLE_NP = np.zeros((16, 4, F.LIMBS), dtype=F.NP_DTYPE)
for _j in range(16):
    _x, _y = _ref_scalarmult(_j)
    _B_TABLE_NP[_j, 0] = F.to_limbs(_x)
    _B_TABLE_NP[_j, 1] = F.to_limbs(_y)
    _B_TABLE_NP[_j, 2] = F.to_limbs(1)
    _B_TABLE_NP[_j, 3] = F.to_limbs((_x * _y) % P)
_B_TABLE = jnp.asarray(_B_TABLE_NP)  # [16, 4, LIMBS]: j·B in extended coords


def _select_from_table(table: jnp.ndarray, w: jnp.ndarray) -> Point:
    """One-hot window select: table [..., 16, 4, LIMBS] (or constant
    [16, 4, LIMBS]), w int32[...] in [0, 16) → Point at w.

    Explicit broadcast-multiply + sum, NOT einsum: a dot_general would be
    eligible for the MXU, whose f32 matmuls run as bf16 passes — limbs
    reach 2^9, past bf16's 8-bit mantissa, so that path could silently
    round in float32 lane mode.  The elementwise form stays on the VPU
    and is exact in both dtypes (products are limb·{0,1})."""
    onehot = jax.nn.one_hot(w, 16, dtype=F.DTYPE)  # [..., 16]
    oh = onehot[..., :, None, None]  # [..., 16, 1, 1]
    sel = (oh * table).sum(axis=-3)  # [..., 4, LIMBS]
    return (sel[..., 0, :], sel[..., 1, :], sel[..., 2, :], sel[..., 3, :])


def _build_neg_a_table(neg_a: Point) -> jnp.ndarray:
    """[B, 16, 4, LIMBS]: j·(-A) for j in 0..15: 15 sequential adds, one
    loop body that writes its row (unrolled they were 37,000 of the
    kernel's 98,000 traced equations for the same device work)."""
    zero = identity_like(neg_a[0])
    first = jnp.stack(zero, axis=-2)  # [B, 4, LIMBS]
    table = jnp.broadcast_to(
        first[:, None], first.shape[:1] + (16,) + first.shape[1:]
    )

    def write_row(j, state):
        table, row = state
        row = point_add(row, neg_a)
        stacked = jnp.stack(row, axis=-2)[:, None]
        table = jax.lax.dynamic_update_slice_in_dim(table, stacked, j, axis=1)
        return table, row

    table, _ = jax.lax.fori_loop(1, 16, write_row, (table, zero))
    return table


# ------------------------------------------------------------ verification


@jax.jit
def _verify_kernel(
    a_y: jnp.ndarray,       # int32[B, LIMBS] — A's y limbs (raw 255 bits)
    a_sign: jnp.ndarray,    # int32[B]
    a_canon: jnp.ndarray,   # bool[B] — A's y < p
    r_y: jnp.ndarray,       # int32[B, LIMBS]
    r_sign: jnp.ndarray,    # int32[B]
    r_canon: jnp.ndarray,   # bool[B]
    s_windows: jnp.ndarray,  # int32[B, 64] MSB-first 4-bit windows of S
    s_ok: jnp.ndarray,      # bool[B] — S < L
    k_windows: jnp.ndarray,  # int32[B, 64] MSB-first windows of k mod L
) -> jnp.ndarray:
    # Host prep always hands int32 limb rows; the field module's lane
    # dtype may be float32 (NARWHAL_FIELD_DTYPE) — cast once at entry.
    a_y = a_y.astype(F.DTYPE)
    r_y = r_y.astype(F.DTYPE)
    # The named scopes group the device operations of a call by phase in
    # a profile, whatever a refactor does to the operations' own names.
    # They are location metadata: not part of the compile cache's key,
    # and the program is still `_verify_kernel`.
    with jax.named_scope("verify_decompress"):
        # A and R go through ONE decompression and one small-order test,
        # stacked along the batch axis: every operation is per row, so
        # 2B rows cost the launches (and the traced equations) of B.
        n = a_y.shape[0]
        both, valid = decompress(
            jnp.concatenate([a_y, r_y]),
            jnp.concatenate([a_sign, r_sign]),
            jnp.concatenate([a_canon, r_canon]),
        )
        small = is_small_order(both)
        a_point = tuple(c[:n] for c in both)
        r_point = tuple(c[n:] for c in both)
        ok = valid[:n] & valid[n:] & ~(small[:n] | small[n:])

    with jax.named_scope("verify_table"):
        neg_a = point_neg(a_point)
        a_table = _build_neg_a_table(neg_a)  # [B, 16, 4, LIMBS]

    def step(i, acc):
        # Four doublings written out: as an inner loop they traced a
        # second faster a rung and ran 0.24 ms a call slower at 128 rows
        # (2.155 against 1.913 ms, PERF.md section 5, PR 27).
        acc = point_double(point_double(point_double(point_double(acc))))
        acc = point_add(acc, _select_from_table(_B_TABLE, s_windows[:, i]))
        acc = point_add(acc, _select_from_table(a_table, k_windows[:, i]))
        return acc

    with jax.named_scope("verify_ladder"):
        start = identity_like(a_y)
        result = jax.lax.fori_loop(0, 64, step, start)

    with jax.named_scope("verify_compare"):
        return ok & s_ok & point_eq(result, r_point)


# ----------------------------------------------------------- host-side prep
#
# Fully vectorized with numpy (the kernel's feed must not become a Python
# loop): bytes → bit matrix → 8-bit limbs / 4-bit windows via one matmul
# each.  Only SHA-512 (hashlib, C speed) and the 512→mod-L reduction touch
# Python objects per signature.

_NIBBLE_W = np.array([1, 2, 4, 8], dtype=np.int32)
_LIMB_W = (1 << np.arange(F.BITS, dtype=np.int32)).astype(np.int32)
_P_BYTES_BE = np.frombuffer(P.to_bytes(32, "big"), np.uint8)
_L_BYTES_BE = np.frombuffer(L_ORDER.to_bytes(32, "big"), np.uint8)


def _bits_le(raw: np.ndarray) -> np.ndarray:
    """uint8[B, 32] → bit matrix bool[B, 256], bit i = value bit i."""
    return np.unpackbits(raw, axis=1, bitorder="little")


def _field_limbs(bits: np.ndarray) -> np.ndarray:
    """bit matrix [B, 256] (low 255 bits used) → int32[B, LIMBS] limbs."""
    pad = F.LIMBS * F.BITS - 255
    padded = np.concatenate(
        [bits[:, :255], np.zeros((bits.shape[0], pad), bits.dtype)], axis=1
    )
    return padded.reshape(-1, F.LIMBS, F.BITS).astype(np.int32) @ _LIMB_W


def _msb_windows(bits: np.ndarray) -> np.ndarray:
    """bit matrix [B, 256] → int32[B, 64] 4-bit windows, MSB-first."""
    nib = bits.reshape(-1, 64, 4).astype(np.int32) @ _NIBBLE_W
    return nib[:, ::-1]


def _lt_be(raw_le: np.ndarray, bound_be: np.ndarray) -> np.ndarray:
    """value(raw little-endian bytes) < bound, vectorized per row."""
    be = raw_le[:, ::-1]
    diff = be.astype(np.int16) - bound_be.astype(np.int16)
    nz = diff != 0
    first = np.argmax(nz, axis=1)  # first (most significant) differing byte
    any_nz = nz.any(axis=1)
    picked = diff[np.arange(len(diff)), first]
    return np.where(any_nz, picked < 0, False)


def prepare_batch(
    messages: Sequence[bytes],
    keys: Sequence[bytes],
    sigs: Sequence[bytes],
    pad_to: int,
):
    """Host prep: unpack encodings, hash-to-scalar, window-decompose.

    One join + reshape per field instead of a frombuffer per row: the
    per-signature Python loop is the host-side throughput cap once the
    device is fast (measured 8 µs/sig looped vs ~2 µs for the
    irreducible SHA-512 + mod-L), and host prep overlaps device compute
    only if it keeps up."""
    n = len(messages)

    def rows(chunks) -> np.ndarray:
        out = np.zeros((pad_to, 32), np.uint8)
        if n:
            out[:n] = np.frombuffer(b"".join(chunks), np.uint8).reshape(n, 32)
        return out

    sig_bytes = [bytes(s) for s in sigs]
    key_bytes = [bytes(k) for k in keys]
    # Fail loud on malformed lengths: the join+reshape below would
    # otherwise silently misalign rows whenever wrong lengths happen to
    # sum to n·32 (the old per-row assignment raised; keep that contract).
    if any(len(k) != 32 for k in key_bytes):
        raise ValueError("prepare_batch: every key must be 32 bytes")
    if any(len(s) != 64 for s in sig_bytes):
        raise ValueError("prepare_batch: every signature must be 64 bytes")
    akeys = rows(key_bytes)
    r_raw = rows(s[:32] for s in sig_bytes)
    s_raw = rows(s[32:64] for s in sig_bytes)
    kb = bytearray()
    for akey, sig, msg in zip(key_bytes, sig_bytes, messages):
        k = int.from_bytes(
            hashlib.sha512(sig[:32] + akey + bytes(msg)).digest(), "little"
        ) % L_ORDER
        kb += k.to_bytes(32, "little")
    k_raw = rows((kb,))

    a_bits = _bits_le(akeys)
    r_bits = _bits_le(r_raw)
    s_bits = _bits_le(s_raw)
    k_bits = _bits_le(k_raw)
    # Mask the sign bit off the y-field before the canonicality compare.
    a_field = akeys.copy()
    a_field[:, 31] &= 0x7F
    r_field = r_raw.copy()
    r_field[:, 31] &= 0x7F
    return (
        _field_limbs(a_bits),
        a_bits[:, 255].astype(np.int32),
        _lt_be(a_field, _P_BYTES_BE),
        _field_limbs(r_bits),
        r_bits[:, 255].astype(np.int32),
        _lt_be(r_field, _P_BYTES_BE),
        _msb_windows(s_bits),
        _lt_be(s_raw, _L_BYTES_BE),
        _msb_windows(k_bits),
    )


# -- the pad ladder -----------------------------------------------------------
#
# XLA compiles one program per padded batch shape.  On the chip's host one
# shape traces and lowers in ~5.6 s and builds cold in 17-25 s (PERF.md,
# PR 27); a process that finds the shape's executable whole in its program
# file (ops/programs.py) pays its load alone, ~2 s (PERF.md, PR 30).  So
# the shapes are a short fixed ladder, not every power of two up to the
# committee's worst burst: a batch pads to the smallest rung that holds
# it, and a batch above the top rung is split into top-rung chunks.  The
# pad policy and the warm-up read the SAME ladder, so no live burst —
# however large a late joiner's catch-up makes it — can reach a shape
# that was not built before the node joined.
#
# The chip's rungs were chosen from one reading of the old program on a
# v5e (ms per call, prepared arrays in, mask fetched; PERF.md, PR 22): 16
# -> 37.5, 64 -> 17.1, 128 -> 17.4, 256 -> 20.4, 512 -> 26.5, 2048 ->
# 53.9: a floor of ~17 ms whatever the call held, 16 rows the SLOWEST
# small shape, so the bottom rung is 128, the widest shape at the floor.
# Top rung 512: one DRAIN_LIMIT burst of quorum-carrying certificates at
# N=4 (128 x (3 + 1) claims) in a single dispatch.  Today's program reads
# 128 -> 2.96 and 512 -> 5.23 the same way (1.89 and 4.20 ms of device
# time; PERF.md, PR 27); the smaller shapes have not been read again.
# ROADMAP S5 re-chooses both rungs from the batch-size histograms of the
# benchmark's cells.
#
# Off the chip (jax-cpu: the tests and the A/B arms, never a speed) one
# shape takes ~85 s to build and a call costs ~2 s at 16 rows (the CPU
# compiler recomputes a product inside every shifted read of it, where
# the chip's materializes it once), so the ladder there is one small
# rung and bursts above it exercise the split.  The platform is what JAX
# reports, not a knob.

CHIP_RUNGS = (128, 512)
CPU_RUNGS = (16,)


def kernel_args(n: int, sharding=None) -> Tuple[jax.ShapeDtypeStruct, ...]:
    """`_verify_kernel`'s nine arrays at ``n`` rows, as shapes: what
    `prepare_batch(..., pad_to=n)` hands over."""

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    limbs, sign, flag, windows = (
        shape((n, F.LIMBS), jnp.int32),
        shape((n,), jnp.int32),
        shape((n,), jnp.bool_),
        shape((n, 64), jnp.int32),
    )
    return (limbs, sign, flag, limbs, sign, flag, windows, flag, windows)


def known_answers(n: int):
    """(`_verify_kernel`'s arrays, the mask it must give) for ``n`` rows
    of one valid signature with a forged row (a signature of another
    message) and a malleable one (S + L, not canonical) among them: a
    verifier is held to saying no, row by row, not only to saying yes."""
    from ..crypto import KeyPair
    from ..crypto.digest import Digest

    kp = KeyPair.generate(b"\x05" * 32)
    msg = bytes(Digest(b"\x05" * 32))
    sig = bytes(kp.sign(Digest(msg)))
    msgs, sigs = [msg] * n, [sig] * n
    msgs[1] = bytes(Digest(b"\x06" * 32))
    s_plus_l = int.from_bytes(sig[32:], "little") + L_ORDER
    sigs[2] = sig[:32] + s_plus_l.to_bytes(32, "little")
    expected = np.ones(n, dtype=bool)
    expected[1:3] = False
    return prepare_batch(msgs, [kp.name] * n, sigs, n), expected


def wrong_answers(program: Callable, n: int) -> Optional[str]:
    """None where ``program`` gives the known answers at ``n`` rows, else
    which rows it got wrong."""
    args, expected = known_answers(n)
    mask = np.asarray(program(*(jnp.asarray(a) for a in args)))
    if mask.shape != expected.shape:
        return f"a mask of shape {mask.shape} for {n} rows"
    rows = np.flatnonzero(mask != expected)
    if not rows.size:
        return None
    kind = {1: " (forged)", 2: " (S + L)"}
    return "rows " + ", ".join(
        f"{r}{kind.get(r, '')} {'accepted' if mask[r] else 'rejected'}"
        for r in rows[:8]
    )


# One compiled program per rung for the whole process, whichever backend
# or caller asked first (off the chip a build costs ~85 s: never twice).
_programs: dict = {}
_programs_lock = threading.Lock()


def verify_program(rung: int) -> Callable:
    """THE compiled `_verify_kernel` for a padded shape: live dispatch and
    warm-up call this same object.  Resolved on first use: loaded whole
    from its program file where that is sound (held to `known_answers`
    before it may serve), else lowered from the `jax.jit` definition
    above, built and written (ops/programs.py)."""
    program = _programs.get(rung)
    if program is None:
        with _programs_lock:
            program = _programs.get(rung)
            if program is None:
                program = _programs[rung] = programs.resolve(
                    _verify_kernel,
                    kernel_args(rung),
                    {"rung": rung, "field_dtype": F.NP_DTYPE.__name__},
                    lambda loaded: wrong_answers(loaded, rung),
                )
    return program


def pad_ladder() -> Tuple[int, ...]:
    """The pad ladder, ascending, for the platform JAX runs on."""
    return CHIP_RUNGS if jax.devices()[0].platform == "tpu" else CPU_RUNGS


def chunk_plan(n: int, ladder: Sequence[int]) -> List[Tuple[int, int, int]]:
    """(lo, hi, pad) per dispatch for a batch of ``n``: top-rung chunks,
    each padded to the smallest rung that holds it."""
    top = ladder[-1]
    plan = []
    for lo in range(0, n, top):
        hi = min(lo + top, n)
        plan.append((lo, hi, next(r for r in ladder if r >= hi - lo)))
    return plan


def verify_batch_arrays(
    messages,
    keys,
    sigs,
    dispatched: Optional[dict] = None,
    ladder: Optional[Sequence[int]] = None,
    stamps: Optional[dict] = None,
    dispatch: int = 0,
) -> np.ndarray:
    """Bool mask for a batch of (message, key, signature) triples, padded
    and chunked by the pad ladder above (``ladder``: the ``pad_ladder()``
    the caller resolved once; default: resolved here).  Chunks are
    dispatched back to back and fetched afterwards, so host prep of chunk
    k+1 overlaps the device's work on chunk k.  ``dispatched`` (padded
    shape -> count) is incremented per dispatch.  ``stamps`` (verify-stage
    trace, metrics.VERIFY_STAGES) receives ``enqueued`` when the last
    chunk's kernel call has returned (host preparation, transfer in and
    launch done) and ``fetched`` when the last mask is on the host, with
    ``pad`` and ``chunks``; ``dispatch`` is the burst number the profiler
    annotations carry."""
    n = len(messages)
    if n == 0:
        return np.zeros(0, dtype=bool)
    ladder = ladder or pad_ladder()
    pending = []
    chunks = chunk_plan(n, ladder)
    with annotate("verify.dispatch", dispatch=dispatch):
        for lo, hi, pad in chunks:
            with annotate("verify.prepare", dispatch=dispatch):
                args = prepare_batch(
                    messages[lo:hi], keys[lo:hi], sigs[lo:hi], pad
                )
            with annotate("verify.launch", dispatch=dispatch):
                out = verify_program(pad)(*(jnp.asarray(a) for a in args))
            pending.append((out, hi - lo))
            if dispatched is not None:
                dispatched[pad] = dispatched.get(pad, 0) + 1
    if stamps is not None:
        stamps["enqueued"] = wall_now()
    with annotate("verify.fetch", dispatch=dispatch):
        masks = [np.asarray(out)[:m] for out, m in pending]
    if stamps is not None:
        stamps["fetched"] = wall_now()
        pads = [pad for _, _, pad in chunks]
        stamps["pad"] = pads[0] if len(pads) == 1 else pads
        stamps["chunks"] = len(pads)
    return np.concatenate(masks)


def memory_peak_bytes() -> int:
    """The most device memory in use at once so far, over the visible
    devices, as the platform reports it (0 where it reports none: the
    CPU backend has no allocator statistics)."""
    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    )


class TpuBackend:
    """crypto.backend-compatible verification backend (see
    narwhal_tpu/crypto/backend.py).  ``name`` is the name it was selected
    under: "tpu" (the chip, checked at selection) or "jax" (whatever
    platform JAX has — the CPU tests and A/B arms)."""

    # A dispatch is one device round trip on the dispatch thread, ~7.5 ms
    # on a v5e at the bottom rung (PERF.md, PR 27; 22 ms before), most of
    # it host preparation and launch: the Core drives such a backend through
    # its pipelined verify stage (primary/core.py) instead of awaiting
    # each drained burst inline.
    dispatches_off_loop = True

    def __init__(self, name: str = "jax") -> None:
        # One dedicated dispatch thread: keeps device calls ordered, and
        # run_in_executor from the event loop never blocks it for the
        # device round trip (host prep + dispatch + result sync all happen
        # on this thread; numpy/hashlib/JAX release the GIL for the bulk).
        from concurrent.futures import ThreadPoolExecutor

        self.name = name
        # The pad ladder, resolved once: live dispatch and warm-up read
        # the same one.
        self.rungs: Tuple[int, ...] = pad_ladder()
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpu-verify"
        )
        self._programs_at_ready: Optional[int] = None
        # Live dispatches per padded shape: which rungs the node used.
        self._dispatched: dict = {}

    def verify(self, message: bytes, key, sig) -> bool:
        return bool(self.verify_batch_mask([message], [key], [sig])[0])

    def verify_batch_mask(
        self, messages: Sequence[bytes], keys, sigs
    ) -> List[bool]:
        return list(
            verify_batch_arrays(
                messages, keys, sigs, self._dispatched, self.rungs
            )
        )

    async def averify_batch_mask(
        self, messages: Sequence[bytes], keys, sigs
    ) -> List[bool]:
        mask, *_ = await self.averify_batch_mask_timed(messages, keys, sigs)
        return mask

    async def averify_batch_mask_timed(
        self, messages: Sequence[bytes], keys, sigs
    ) -> Tuple[List[bool], float, dict]:
        """(mask, compute_seconds, stamps): compute time is measured ON
        the dispatch thread around host prep + device round trip — the
        wall the caller observes additionally includes executor queueing
        and the event-loop wakeup, which is pipelining headroom, not
        crypto cost (the `crypto.verify.device_seconds` split).
        ``stamps`` are the dispatch thread's stages of the verify-stage
        trace (``prepare``, ``enqueued``, ``fetched``) and its extras
        (``pad``, ``chunks``, ``cpu_s``: this thread's CPU time across
        them); the seam marks them on the loop."""
        import asyncio

        # The verify-stage burst this dispatch belongs to (read here, on
        # the loop): the number its profiler annotations carry.
        seq = int(current_burst() or 0)

        def timed() -> Tuple[List[bool], float, dict]:
            stamps = {"prepare": wall_now()}
            cpu0 = time.thread_time()
            t0 = time.perf_counter()
            mask = list(
                verify_batch_arrays(
                    messages, keys, sigs, self._dispatched, self.rungs,
                    stamps=stamps, dispatch=seq,
                )
            )
            compute_s = time.perf_counter() - t0
            stamps["cpu_s"] = time.thread_time() - cpu0
            return mask, compute_s, stamps

        return await asyncio.get_running_loop().run_in_executor(
            self._executor, timed
        )

    def warmup(self) -> str:
        """Resolve the program for every rung of the pad ladder (load its
        file, or trace, lower and compile it and write the file) and hold
        each to the known answers, so no live burst pays for a program on
        the critical path and none is served by a verifier that was not
        seen to say no.  Returns a one-line account for the caller's
        ready log; the counts it states are also in the
        `crypto.verify.device` snapshot detail."""
        from .. import metrics

        ladder = self.rungs
        for n in ladder:
            wrong = wrong_answers(verify_program(n), n)
            if wrong is not None:
                raise RuntimeError(f"verify kernel at rung {n}: {wrong}")
        stats = compile_stats()
        self._programs_at_ready = stats["programs_built"]
        metrics.detail_fn("crypto.verify.device", self.device_report)
        return (
            "rungs {}, {} programs built in {:.1f} s (trace {:.1f} s), "
            "{} of them loaded from program files ({} files rejected)".format(
                ",".join(map(str, ladder)),
                stats["programs_built"],
                stats["trace_seconds"] + stats["lower_seconds"]
                + stats["build_seconds"],
                stats["trace_seconds"],
                stats["programs_from_file"],
                stats["program_files_rejected"],
            )
        )

    def device_report(self) -> dict:
        """Which device verified, with which shapes, and whether anything
        was built after warm-up (``programs_built`` above
        ``programs_at_ready`` means a live burst paid for a compile)."""
        return {
            **device_identity(),
            "memory_peak_bytes": memory_peak_bytes(),
            "rungs": list(self.rungs),
            "dispatched": {
                # dict(): one atomic copy — the dispatch thread may insert
                str(k): v for k, v in sorted(dict(self._dispatched).items())
            },
            "programs_at_ready": self._programs_at_ready,
            **compile_stats(),
        }
