"""GF(2^255 - 19) arithmetic from 32-bit vector lanes, batch-first.

TPU has no native 64-bit multiply, so field elements are 32 limbs of 8
bits (radix 2^8) held in 32-bit lanes.  The radix keeps every
intermediate exactly representable: weak limbs < 2^9, pairwise products
< 2^18, a 32-term convolution row < 2^23.  The schoolbook convolution
runs as 32 fused shifted multiply-accumulates on the VPU (see mul() for
why this beats the MXU matmul formulation on v5e); carries, folds and
comparisons are elementwise, also VPU.  Every move of a value along the
limb axis is a SHIFTED READ of an operand (_shift: one lax.pad whose
negative edges cut), never an indexed update: the v5e compiler fuses
elementwise work and shifted reads of a materialized value into one
device operation, while each `.at[].add` became a scatter of two or
three (PERF.md section 5, PR 27: they were 82% of a ladder step's
launches).  This is the TPU-shaped answer to
the reference's ed25519-dalek (crypto/src/lib.rs:206-219), whose Rust
backend uses 51-bit limbs in u128 — a layout that cannot map to vector
lanes.

Lane dtype is selected by ``NARWHAL_FIELD_DTYPE`` at import: ``int32``
(default) or ``float32``.  The f32 variant exists because the VPU is an
f32 machine first — if 32-bit integer multiply is emulated or
rate-limited, the same algorithm in floats wins.  Every f32 intermediate
is an INTEGER kept strictly below 2^24 (the f32 exact-integer range):
the 2^23 convolution-row bound fits as-is; carries use an exact
power-of-two scale + floor instead of shifts; mul splits each row sum
into bytes BEFORE the ×38 fold, so the fold never leaves the range (see
mul()).  The same differential suite
proves either dtype against Python big ints: the default test run
covers int32 plus an f32 field-op subprocess check
(tests/test_ed25519.py::test_float32_lane_mode_field_ops); the FULL
suite under f32 is `make test-f32` — run it after touching any op here.

All functions are batch-first: an element is ``[..., 32]`` of DTYPE and
every op vmaps/broadcasts over leading axes.  Limb i holds bits
[8i, 8i+8).  Outputs of mul/add/sub are *weakly reduced* (limbs < 2^9 —
see carry(); value possibly ≥ p); ``canon`` fully reduces into [0, p)
with limbs < 2^8.

Correctness strategy: every op is differential-tested against Python big
ints over random + boundary values, and every intermediate has a proven
magnitude bound (2^31 budget in int32 mode, 2^24 in float32 mode — the
tighter f32 bounds are noted where they differ).
"""

from __future__ import annotations


import numpy as np

import jax
import jax.numpy as jnp

from ..utils.env import env_str

BITS = 8
LIMBS = 32
MASK = (1 << BITS) - 1
P = (1 << 255) - 19

# 2^(BITS·LIMBS) = 2^256 ≡ 38 (mod p): folding multiplier for limbs ≥ LIMBS.
FOLD = 38

_DTYPE_ENV = env_str("NARWHAL_FIELD_DTYPE")
if _DTYPE_ENV not in ("int32", "float32"):
    # Fail loud: a typo ("f32", "fp32") silently falling back to int32
    # would mislabel every measurement made under it.
    raise ValueError(
        f"NARWHAL_FIELD_DTYPE must be 'int32' or 'float32', got "
        f"{_DTYPE_ENV!r}"
    )
FP = _DTYPE_ENV == "float32"
DTYPE = jnp.float32 if FP else jnp.int32
NP_DTYPE = np.float32 if FP else np.int32


def to_limbs(x: int) -> np.ndarray:
    """Python int → limb vector (host-side prep)."""
    return np.array([(x >> (BITS * i)) & MASK for i in range(LIMBS)],
                    dtype=NP_DTYPE)


def from_limbs(limbs) -> int:
    """Limb vector → Python int (host-side check); accepts unreduced."""
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(v) << (BITS * i) for i, v in enumerate(arr))


def _hi(c: jnp.ndarray, bits: int = BITS) -> jnp.ndarray:
    """c // 2^bits.  int32: a shift.  float32: exact scale by 2^-bits +
    floor (scaling by a power of two never rounds, and the floor of an
    exact value is exact) for integer-valued c < 2^24."""
    if FP:
        return jnp.floor(c * (1.0 / (1 << bits)))
    return c >> bits


def _lo(c: jnp.ndarray) -> jnp.ndarray:
    """The low 8 bits of every limb.  float32 subtracts the carry back
    (hi·256 ≤ c < 2^24: exact)."""
    return c - _hi(c) * (1 << BITS) if FP else c & MASK


def _shift(x: jnp.ndarray, lo: int, hi: int) -> jnp.ndarray:
    """x moved ``lo`` places up the limb axis and its width changed by
    ``lo + hi``: zeros come in where an edge is positive, limbs fall off
    where it is negative.  One lax.pad: the form the TPU compiler reads
    as an offset load inside the consumer's fusion."""
    edges = [(0, 0, 0)] * (x.ndim - 1) + [(lo, hi, 0)]
    return jax.lax.pad(x, NP_DTYPE(0), edges)


# Weight of the limb each carry lands on after the cyclic move of one
# place: the carry out of the top limb wraps to limb 0 times 38.
_WRAP = jnp.asarray(np.array([FOLD] + [1] * (LIMBS - 1), dtype=NP_DTYPE))


def _rotate(c: jnp.ndarray) -> jnp.ndarray:
    """Every limb one place up, the top limb round to limb 0: two shifted
    reads with disjoint support, so their sum is exact."""
    return _shift(c, 1, -1) + _shift(c, 1 - LIMBS, LIMBS - 1)


def _carry_once(c: jnp.ndarray) -> jnp.ndarray:
    """One vectorized carry sweep; the carry out of the top limb wraps to
    limb 0 multiplied by 38 (2^256 ≡ 38 mod p).  The limbs are moved
    first and split after: the move is then a read of the operand."""
    return _lo(c) + _hi(_rotate(c)) * _WRAP


def carry(c: jnp.ndarray, sweeps: int = 4) -> jnp.ndarray:
    """Propagate carries until every limb is weakly reduced: **< 2^9**
    (NOT < 2^8 — the final sweep can both leave a limb at 255 + carry-in
    and add the ×38 top-limb wrap to limb 0, so limb 0 reaches up to
    255 + 38 = 293).  With the default 4 sweeps, input limbs may be up to
    2^31 (int32 mode; < 2^24 in float32 mode — every in-tree caller stays
    under 2^23.3): the sweep bounds are ≤ 255 + 2^23, ≤ 255 + 2^15,
    ≤ 255 + 2^7, then < 2^9.  Every consumer is dimensioned for the 2^9
    weak bound (see mul's exactness note and sub's ZP offset).

    ``sweeps`` lets callers with tighter input bounds skip work (each
    sweep is one device operation on the hot path); every reduced-sweep
    call site must carry its own bound proof (see mul/add/sub)."""
    for _ in range(sweeps):
        c = _carry_once(c)
    return c


def _byte(c: jnp.ndarray, n: int) -> jnp.ndarray:
    """Byte n (0, 1 or 2) of every limb of c < 2^24."""
    top = _hi(c, BITS * n) if n else c
    return top if n == 2 else _lo(top)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply, weakly reduced output.

    The schoolbook convolution c[k] = Σ_{i+j=k} a_i·b_j is computed as 32
    fused shifted multiply-accumulates on the VPU in DTYPE lanes.
    Exactness: weak limbs are < 2^9 (carry()'s bound), so pairwise
    products are < 2^18 and a convolution row accumulates ≤ 32 of them →
    < 2^23 — inside int32's 2^31 budget and f32's 2^24 exact-integer
    range alike.

    Reduction, in three device operations after the convolution.  Each
    row sum is cut into its three bytes where it stands: byte n of row k
    belongs to limb k + n, so r[k] = byte0(c[k]) + byte1(c[k-1]) +
    byte2(c[k-2]) for k in 0..64 has every limb ≤ 3·255 = 765.  Limbs
    32..63 fold ×38 (2^256 ≡ 38 mod p) and r[64] = byte2(c[62]) ≤ 3
    (c[62] = a_31·b_31 < 2^18) folds ×38² = 1444, onto limb 0: limbs
    1..31 ≤ 765·39 = 29,835 and limb 0 ≤ 29,835 + 3·1444 = 34,167, in
    both dtypes (no product above 2^15.1: the f32 mode needs no split
    fold of its own).  Two carry sweeps then reach the weak bound: the
    first leaves limb 1 ≤ 255 + 133, limbs 2..31 ≤ 255 + 116 and limb 0
    ≤ 255 + 38·116 = 4,663; the second limb 1 ≤ 255 + 18, limbs 2..31 ≤
    256 and limb 0 ≤ 255 + 38 = 293 — all < 2^9.

    Why not the MXU?  The "one-hot convolution tensor" formulation — a
    single [B·32², 63] f32 matmul — was measured 1.4× SLOWER end-to-end
    on v5e: it must materialize the [B, 32²] outer product through HBM
    (66 MB round trip per multiply at B=8192) and its useful-FLOP ratio
    is 1/63, while the shifted-MAC chain fuses into one VPU kernel whose
    only HBM traffic is the operands and the result.  The outer product
    with a skewing reshape (45 traced equations against 170) was
    compiled for the v5e and left: pad, reshape and reduce each stay a
    device operation of their own (PERF.md section 5, PR 27)."""
    conv = a[..., :1] * _shift(b, 0, LIMBS - 1)
    for i in range(1, LIMBS):
        conv = conv + a[..., i : i + 1] * _shift(b, i, LIMBS - 1 - i)
    top = LIMBS - 1  # conv is 2·LIMBS - 1 wide; row `at + top` is its last
    low, high = (
        _byte(_shift(conv, -at, at - top), 0)
        + _byte(_shift(conv, 1 - at, at - top - 1), 1)
        + _byte(_shift(conv, 2 - at, at - top - 2), 2)
        for at in (0, LIMBS)
    )
    last = _byte(_shift(conv, -2 * top, top), 2)  # r[64], on limb 0
    return carry(low + high * FOLD + last * (FOLD * FOLD), sweeps=2)


def square(a: jnp.ndarray) -> jnp.ndarray:
    """Deliberately just mul(a, a): the symmetry-specialized square
    (≤16 doubled cross terms per convolution row instead of 32) was a
    measured 1.4× win ONLY in the abandoned limbs-major layout, where the
    accumulate slices ran along the compute-mapped sublane axis and
    shorter slices meant fewer tile ops.  Here the limb axis sits on
    lanes: every shifted-accumulate row is one full-width vector op
    whether half its entries are zero or not, so halving the *terms*
    saves no *ops* — the specialization buys nothing and costs an extra
    concatenate per row (see benchmark/field_layout_probe.py for the
    layout story)."""
    return mul(a, a)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b (mod p), weakly reduced.  One carry sweep suffices: both
    operands are weak (< 2^9), so the sum is < 2^10, the per-limb carry
    out is ≤ 3, and after one sweep limbs 1..31 are ≤ 255 + 3 and limb 0
    is ≤ 255 + 3·38 = 369 — all < 2^9."""
    return carry(a + b, sweeps=1)


# Borrow-free subtraction needs a limb vector ZP whose value is ≡ 0 (mod p)
# with EVERY limb ≥ 2^9 (the weak bound on an operand's limbs, see carry()):
# then (a + ZP - b) is non-negative per limb and carry() reduces it.
# Construct: put 2·MASK = 510 in every limb, then add the canonical limbs of
# the complement that makes the total a multiple of p — every final limb is
# in [510, 765]; asserted ≥ 512 below (the construction's minimum is 637).
_base = sum(2 * MASK << (BITS * i) for i in range(LIMBS))
_comp = (-_base) % P
_zp = [2 * MASK + ((_comp >> (BITS * i)) & MASK) for i in range(LIMBS)]
assert sum(v << (BITS * i) for i, v in enumerate(_zp)) % P == 0
assert all((1 << 9) <= v <= 3 * MASK for v in _zp), _zp
_ZP = jnp.asarray(np.array(_zp, dtype=NP_DTYPE))


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (mod p): the ZP offset keeps every limb non-negative.

    One carry sweep suffices: ZP's limbs are ≤ 765 (asserted above), so
    a + ZP - b ≤ 511 + 765 = 1276 per limb, the carry out of a limb is
    ≤ 4, and after the sweep limbs 1..31 are ≤ 255 + 4 and limb 0 is
    ≤ 255 + 4·38 = 407 — all < 2^9."""
    return carry(a + _ZP - b, sweeps=1)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    """-a (mod p); same bound argument as sub (ZP - a ≤ 765 per limb)."""
    return carry(_ZP - a, sweeps=1)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small non-negative constant (k ≤ 2^17).

    float32 mode splits k > 2^14 into 8-bit chunks (k·2^9 would pass the
    2^24 exact range): a·k_lo < 2^17 and a·k_hi < 2^18 land one limb
    apart, the top chunk folds ×38 into limb 0 (38·2^18 < 2^23.3), and
    every partial stays exact."""
    assert 0 <= k <= (1 << 17), k
    if FP and k > (1 << 14):
        k_hi, k_lo = k >> BITS, k & MASK
        lo_part = a * jnp.asarray(k_lo, DTYPE)
        hi_part = a * jnp.asarray(k_hi, DTYPE)
        return carry(lo_part + _rotate(hi_part) * _WRAP)
    return carry(a * jnp.asarray(k, DTYPE))


def pow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a^(2^k) — k repeated squarings (fori_loop: one compiled body)."""
    return jax.lax.fori_loop(0, k, lambda _, x: square(x), a)


def invert(a: jnp.ndarray) -> jnp.ndarray:
    """a^(p-2) — Fermat inversion, standard 2^255-21 addition chain."""
    x1 = a
    x2 = mul(square(x1), x1)          # 2^2 - 1
    x4 = mul(pow2k(x2, 2), x2)        # 2^4 - 1
    x5 = mul(square(x4), x1)          # 2^5 - 1
    x10 = mul(pow2k(x5, 5), x5)       # 2^10 - 1
    x20 = mul(pow2k(x10, 10), x10)    # 2^20 - 1
    x40 = mul(pow2k(x20, 20), x20)    # 2^40 - 1
    x50 = mul(pow2k(x40, 10), x10)    # 2^50 - 1
    x100 = mul(pow2k(x50, 50), x50)   # 2^100 - 1
    x200 = mul(pow2k(x100, 100), x100)  # 2^200 - 1
    x250 = mul(pow2k(x200, 50), x50)  # 2^250 - 1
    # p - 2 = 2^255 - 21 = (2^250-1)·2^5 + 11;  11 = 0b01011
    t = pow2k(x250, 5)
    return mul(t, mul(mul(square(square(square(x1))), square(x1)), x1))


def pow_p58(a: jnp.ndarray) -> jnp.ndarray:
    """a^((p-5)/8) = a^(2^252 - 3) = (a^(2^250-1))^4 · a."""
    x1 = a
    x2 = mul(square(x1), x1)
    x4 = mul(pow2k(x2, 2), x2)
    x5 = mul(square(x4), x1)
    x10 = mul(pow2k(x5, 5), x5)
    x20 = mul(pow2k(x10, 10), x10)
    x40 = mul(pow2k(x20, 20), x20)
    x50 = mul(pow2k(x40, 10), x10)
    x100 = mul(pow2k(x50, 50), x50)
    x200 = mul(pow2k(x100, 100), x100)
    x250 = mul(pow2k(x200, 50), x50)
    return mul(pow2k(x250, 2), x1)


_P_LIMBS = jnp.asarray(to_limbs(P))


def _sub_p(c: jnp.ndarray):
    """(c - p) with full borrow propagation.  Returns (limbs, underflow):
    underflow True means c < p (result invalid, keep c)."""
    d = c - _P_LIMBS
    d_first = jnp.moveaxis(d, -1, 0)  # [LIMBS, ...]

    def step(borrow, d_i):
        v = d_i - borrow
        neg_ = v < 0
        v = v + jnp.where(
            neg_, jnp.asarray(1 << BITS, DTYPE), jnp.asarray(0, DTYPE)
        )
        return (
            jnp.where(neg_, jnp.asarray(1, DTYPE), jnp.asarray(0, DTYPE)),
            v,
        )

    borrow0 = jnp.zeros(c.shape[:-1], dtype=DTYPE)
    borrow, limbs = jax.lax.scan(step, borrow0, d_first)
    return jnp.moveaxis(limbs, 0, -1), borrow > 0


def canon(a: jnp.ndarray) -> jnp.ndarray:
    """Fully reduce into [0, p) with strictly canonical limbs (< 2^8)."""
    c = carry(a)
    # carry() only guarantees the weak bound (limbs < 2^9, i.e. up to one
    # carry bit above a full 2^8-1 limb), and one sweep only moves such a
    # spike up one position — run LIMBS+2 sweeps so any spike exits the
    # top and wraps to a small limb-0 term, leaving every limb < 2^8.
    # (A loop on the device: 34 copies of the sweep were a third of a
    # canon's traced equations and run no faster.)
    c = jax.lax.fori_loop(0, LIMBS + 2, lambda _, x: _carry_once(x), c)
    # Value is now < 2^256 < 3p: strip multiples of p by conditional
    # subtraction until below p (3 rounds give margin).
    for _ in range(3):
        d, under = _sub_p(c)
        c = jnp.where(under[..., None], c, d)
    return c


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canon(a) == 0, axis=-1)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canon(a) == canon(b), axis=-1)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """cond ? a : b, with cond shaped [...] and a/b [..., LIMBS]."""
    return jnp.where(cond[..., None], a, b)
