"""A/B the GF(2^255-19) limb layouts — the probe that mis-predicted, kept
as the cautionary record.

field25519 stores an element limbs-MINOR, int32[..., 32] with the limb
axis on the VPU lane dimension.  A mid-round-5 refactor flipped it to
limbs-major int32[32, B] on this probe's CPU-backend evidence (~4-5× for
the mul chain, 78→390 verifies/s for the full kernel): with the batch
minor-most every lane does useful work, where limbs-minor fills only 63 of
128 lanes during the convolution.  The real chip then measured the full
verify kernel 2× SLOWER limbs-major (168 → 317 ms/2048-batch; a
[32, B/128, 128] batch-blocked variant recovered only to 211 ms — both
runs recorded in artifacts/crypto_bench_r05_limbs_major.json, the
restored-layout run in artifacts/crypto_bench_r05.json).  Lane occupancy
is not the binding constraint
on v5e — locality is: limbs-minor keeps a field element's entire 63-limb
convolution row inside one (8, 128) tile, so the 32 shifted accumulates
stay register-resident, while any limbs-major variant spreads one element
across 32+ tiles and pays tile traffic per accumulate.  The CPU backend
rewards exactly the opposite (contiguous batch vectorization), which is
why it was a bad proxy.  field25519 was restored to limbs-minor; this
probe now measures the live limbs-minor mul against a verbatim copy of
the limbs-major one, as a jitted chain of K dependent field multiplies,
timed via result fetch (the device round-trip floor — a trivial jitted
compute plus fetch — is reported separately and subtracted).

    python benchmark/field_layout_probe.py --batch 8192 --chain 256 \
        --out artifacts/field_layout_probe_r05.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from narwhal_tpu.utils.env import env_str  # noqa: E402

BITS, LIMBS, MASK, FOLD = 8, 32, 255, 38


def _mul_limbs_minor(a, b):
    """The LIVE layout (kept as an inline copy so the probe's two arms
    stay symmetric): limbs on the minor axis, [..., 32] — field25519.mul
    as it was when the layouts were compared (indexed-update carries;
    since PR 27 the live mul reduces by shifted reads and its weak limbs
    differ, its value does not)."""
    import jax.numpy as jnp

    conv = jnp.zeros(a.shape[:-1] + (2 * LIMBS - 1,), jnp.int32)
    pad_base = [(0, 0)] * (b.ndim - 1)
    for i in range(LIMBS):
        conv = conv + a[..., i : i + 1] * jnp.pad(
            b, pad_base + [(i, LIMBS - 1 - i)]
        )
    hi, lo = conv[..., LIMBS:], conv[..., :LIMBS]
    c = lo.at[..., : LIMBS - 1].add(hi * FOLD)
    for _ in range(4):
        h = c >> BITS
        c = (c & MASK).at[..., 1:].add(h[..., :-1])
        c = c.at[..., 0].add(h[..., -1] * FOLD)
    return c


def _mul_limbs_major(a, b):
    """The abandoned limbs-major layout, reproduced verbatim from the
    reverted refactor: element is [32, batch...], each convolution term a
    scalar-slice times the whole operand at limb offset i."""
    import jax.numpy as jnp

    conv = jnp.zeros((2 * LIMBS - 1,) + a.shape[1:], jnp.int32)
    for i in range(LIMBS):
        conv = conv.at[i : i + LIMBS].add(a[i][None] * b)
    hi, lo = conv[LIMBS:], conv[:LIMBS]
    c = lo.at[: LIMBS - 1].add(hi * FOLD)
    for _ in range(4):
        h = c >> BITS
        c = (c & MASK).at[1:].add(h[:-1])
        c = c.at[0].add(h[-1] * FOLD)
    return c


def _chain(mul, k):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(a, b):
        def step(c, _):
            return mul(c, b), None

        c, _ = lax.scan(step, a, None, length=k)
        return c

    return run


def _time_fetch(fn, args, reps):
    np.asarray(fn(*args))  # warm/compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--chain", type=int, default=256)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, (args.batch, LIMBS), dtype=np.int32)
    b = rng.integers(0, 256, (args.batch, LIMBS), dtype=np.int32)

    # Drift guard (ADVICE.md r05): _mul_limbs_minor is a hand-maintained
    # copy of the live field25519.mul int32 path; any future edit to the
    # live mul would silently desynchronize the A/B arms.  Cross-check the
    # copy against the LIVE mul on a random sub-batch before measuring, so
    # drift fails loudly here instead of corrupting layout comparisons.
    if env_str("NARWHAL_FIELD_DTYPE") == "int32":
        from narwhal_tpu.ops import field25519 as F

        k = min(args.batch, 512)
        # Held to the live mul by VALUE (canonical limbs): two weak forms
        # of one element need not agree limb for limb.
        live = np.asarray(
            F.canon(F.mul(jnp.asarray(a[:k]), jnp.asarray(b[:k])))
        )
        copy = np.asarray(
            F.canon(
                jax.jit(_mul_limbs_minor)(
                    jnp.asarray(a[:k]), jnp.asarray(b[:k])
                )
            )
        )
        if not (live == copy).all():
            raise SystemExit(
                "field_layout_probe: _mul_limbs_minor has DRIFTED from the "
                "live field25519.mul — update the inline copy before "
                "trusting any layout measurement from this probe"
            )
    else:
        print(
            "NOTE: NARWHAL_FIELD_DTYPE != int32; live-mul drift guard "
            "skipped (the probe's arms are the int32 layouts)",
            file=sys.stderr,
        )

    # Round-trip floor: trivial jitted compute + fetch.
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.int32)
    floor = _time_fetch(f, (x,), args.reps)

    minor = _chain(_mul_limbs_minor, args.chain)
    t_minor = _time_fetch(minor, (jnp.asarray(a), jnp.asarray(b)), args.reps)

    major = _chain(_mul_limbs_major, args.chain)
    t_major = _time_fetch(
        major, (jnp.asarray(a.T.copy()), jnp.asarray(b.T.copy())), args.reps
    )

    # Cross-check the layouts agree.
    got_minor = np.asarray(minor(jnp.asarray(a), jnp.asarray(b)))
    got_major = np.asarray(
        major(jnp.asarray(a.T.copy()), jnp.asarray(b.T.copy()))
    ).T
    assert (got_minor == got_major).all(), "layouts disagree"

    per_mul = lambda t: (t - floor) / args.chain * 1e6  # noqa: E731
    result = {
        "device": str(jax.devices()[0]),
        "batch": args.batch,
        "chain_muls": args.chain,
        "fetch_floor_ms": round(floor * 1e3, 2),
        "limbs_minor_us_per_batched_mul": round(per_mul(t_minor), 2),
        "limbs_major_us_per_batched_mul": round(per_mul(t_major), 2),
        "major_over_minor_speedup": round(
            (t_minor - floor) / max(t_major - floor, 1e-9), 2
        ),
    }
    print(json.dumps(result))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f_:
            json.dump(result, f_, indent=2)


if __name__ == "__main__":
    main()
