"""Differential tests: GF(2^255-19) limb arithmetic vs Python big ints."""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from narwhal_tpu.ops import field25519 as F  # noqa: E402

P = F.P
rng = random.Random(0)

EDGE = [0, 1, 2, 19, (1 << 255) - 20, P - 1, P - 2, (1 << 252), F.MASK]


def rand_elems(n):
    vals = EDGE + [rng.randrange(P) for _ in range(n - len(EDGE))]
    return vals[:n]


def batch(vals):
    return jnp.asarray(np.stack([F.to_limbs(v) for v in vals]))


def test_roundtrip():
    vals = rand_elems(32)
    got = [F.from_limbs(x) for x in np.asarray(batch(vals))]
    assert got == vals


def test_add_sub_neg():
    a_vals, b_vals = rand_elems(64), list(reversed(rand_elems(64)))
    a, b = batch(a_vals), batch(b_vals)
    s = np.asarray(F.canon(F.add(a, b)))
    d = np.asarray(F.canon(F.sub(a, b)))
    n = np.asarray(F.canon(F.neg(a)))
    for i, (x, y) in enumerate(zip(a_vals, b_vals)):
        assert F.from_limbs(s[i]) == (x + y) % P
        assert F.from_limbs(d[i]) == (x - y) % P
        assert F.from_limbs(n[i]) == (-x) % P


def test_mul_square():
    a_vals, b_vals = rand_elems(64), list(reversed(rand_elems(64)))
    a, b = batch(a_vals), batch(b_vals)
    m = np.asarray(F.canon(F.mul(a, b)))
    sq = np.asarray(F.canon(F.square(a)))
    for i, (x, y) in enumerate(zip(a_vals, b_vals)):
        assert F.from_limbs(m[i]) == (x * y) % P, f"mul row {i}"
        assert F.from_limbs(sq[i]) == (x * x) % P, f"sq row {i}"


def test_mul_chain_stays_reduced():
    """Repeated muls never overflow int32 lanes (weak reduction bound)."""
    a_vals = rand_elems(16)
    a = batch(a_vals)
    acc = a
    expect = list(a_vals)
    for _ in range(50):
        acc = F.mul(acc, a)
        assert int(jnp.max(acc)) < (1 << (F.BITS + 1)), "limb escaped weak bound"
        expect = [(e * x) % P for e, x in zip(expect, a_vals)]
    got = np.asarray(F.canon(acc))
    for i, e in enumerate(expect):
        assert F.from_limbs(got[i]) == e


def test_invert():
    vals = [v for v in rand_elems(32) if v != 0]
    a = batch(vals)
    inv = np.asarray(F.canon(F.invert(a)))
    for i, v in enumerate(vals):
        assert F.from_limbs(inv[i]) == pow(v, P - 2, P)


def test_pow_p58():
    vals = rand_elems(16)
    a = batch(vals)
    r = np.asarray(F.canon(F.pow_p58(a)))
    e = (P - 5) // 8
    for i, v in enumerate(vals):
        assert F.from_limbs(r[i]) == pow(v, e, P)


def test_canon_and_eq():
    # p and 0 are the same element; 2^255-19+x ≡ x.
    a = batch([P, 0, P + 5, 5])
    c = np.asarray(F.canon(a))
    assert F.from_limbs(c[0]) == 0 and F.from_limbs(c[2]) == 5
    assert bool(F.eq(a[0], a[1])) and bool(F.eq(a[2], a[3]))
    assert not bool(F.eq(a[1], a[3]))
    assert bool(F.is_zero(a[0])) and not bool(F.is_zero(a[3]))


def test_mul_small():
    vals = rand_elems(16)
    a = batch(vals)
    r = np.asarray(F.canon(F.mul_small(a, 121666)))
    for i, v in enumerate(vals):
        assert F.from_limbs(r[i]) == (v * 121666) % P


# -- the weak bound's corners ---------------------------------------------------
#
# Operands are WEAK limb vectors (limbs < 2^9, value possibly above p), the
# form every op hands the next: the corners below are where a row sum, a
# fold or a carry comes closest to the lane's exact range.  One function
# checks one (corner, op) pair against Python integers, so the float32
# lane mode can run the same table in its subprocess
# (tests/test_ed25519.py::test_float32_lane_mode_field_ops).

WEAK = (1 << (F.BITS + 1)) - 1  # 511

CORNERS = {
    # every row sum at its maximum: row 31 is 32·511² = 8,355,872 < 2^23
    "all-511": ([WEAK] * 32, [WEAK] * 32),
    # carry()'s documented worst case for limb 0 (255 + 38)
    "limb0-293": ([293] + [255] * 31, [WEAK] * 32),
    # only conv[62] = a31·b31: the one row whose third byte folds ×38²
    "top-limbs": ([0] * 31 + [WEAK], [0] * 31 + [WEAK]),
    "alternating": ([WEAK, 0] * 16, [0, WEAK] * 16),
    # a - b at its most negative per limb, a + b at its smallest
    "zero-vs-max": ([0] * 32, [WEAK] * 32),
    "max-vs-zero": ([WEAK] * 32, [0] * 32),
}

OPS = {
    "mul": (F.mul, lambda x, y: x * y),
    "square": (lambda a, b: F.square(a), lambda x, y: x * x),
    "add": (F.add, lambda x, y: x + y),
    "sub": (F.sub, lambda x, y: x - y),
    "neg": (lambda a, b: F.neg(b), lambda x, y: -y),
    "mul_small": (lambda a, b: F.mul_small(a, 121666), lambda x, y: x * 121666),
}


def check_corner(corner: str, op: str) -> None:
    a_limbs, b_limbs = CORNERS[corner]
    a = jnp.asarray(np.array([a_limbs], dtype=F.NP_DTYPE))
    b = jnp.asarray(np.array([b_limbs], dtype=F.NP_DTYPE))
    fn, ref = OPS[op]
    want = ref(F.from_limbs(a_limbs), F.from_limbs(b_limbs)) % P
    got = np.asarray(fn(a, b))[0]
    assert got.min() >= 0 and got.max() <= WEAK, f"left the weak bound: {got}"
    assert F.from_limbs(got) % P == want
    canonical = np.asarray(F.canon(jnp.asarray(got[None])))[0]
    assert canonical.max() <= F.MASK and F.from_limbs(canonical) == want


@pytest.mark.parametrize("op", sorted(OPS))
@pytest.mark.parametrize("corner", sorted(CORNERS))
def test_weak_bound_corners(corner, op):
    check_corner(corner, op)


# carry() takes limbs up to the lane's whole exact range.
LANE_MAX = (1 << 24) - 1 if F.FP else (1 << 31) - 1

CARRY_CORNERS = {
    "every-limb-at-lane-max": [LANE_MAX] * 32,
    "top-limb-at-lane-max": [0] * 31 + [LANE_MAX],
    "limb0-at-lane-max": [LANE_MAX] + [0] * 31,
    "mul-fold-maximum": [34167] + [29835] * 31,  # mul()'s bound before its sweeps
}

CANON_CORNERS = {
    "p": F.to_limbs(P).tolist(),
    "p-plus-1": F.to_limbs(P + 1).tolist(),
    "all-255": [F.MASK] * 32,  # 2^256 - 1 ≡ 37
    "all-511": [WEAK] * 32,
    "limb0-293": [293] + [255] * 31,
    "spike-on-top": [255] * 31 + [WEAK],
}


def check_carry(corner: str) -> None:
    limbs = CARRY_CORNERS[corner]
    got = np.asarray(F.carry(jnp.asarray(np.array([limbs], dtype=F.NP_DTYPE))))[0]
    assert got.min() >= 0 and got.max() <= WEAK, got
    assert F.from_limbs(got) % P == F.from_limbs(limbs) % P


def check_canon(corner: str) -> None:
    limbs = CANON_CORNERS[corner]
    got = np.asarray(F.canon(jnp.asarray(np.array([limbs], dtype=F.NP_DTYPE))))[0]
    assert got.min() >= 0 and got.max() <= F.MASK, got
    assert F.from_limbs(got) == F.from_limbs(limbs) % P


@pytest.mark.parametrize("corner", sorted(CARRY_CORNERS))
def test_carry_corners(corner):
    check_carry(corner)


@pytest.mark.parametrize("corner", sorted(CANON_CORNERS))
def test_canon_corners(corner):
    check_canon(corner)


def check_all_corners() -> int:
    """Every corner above in this process's lane dtype; the count run."""
    for corner in CORNERS:
        for op in OPS:
            check_corner(corner, op)
    for corner in CARRY_CORNERS:
        check_carry(corner)
    for corner in CANON_CORNERS:
        check_canon(corner)
    return len(CORNERS) * len(OPS) + len(CARRY_CORNERS) + len(CANON_CORNERS)
