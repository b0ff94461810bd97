"""Operations and bytes that one ed25519 verification NEEDS on the device,
whatever implements it, and the least time a chip could take for them.

This is the denominator ROADMAP S3 asked for ("a rate with no
denominator").  It counts the algorithm's device part as the program
splits it (``narwhal_tpu/ops/ed25519.py``): two point decompressions,
one 253-bit double-scalar multiplication [S]B + [k](-A), the strict
rule's small-order test of A and R, and the final comparison with R.
SHA-512(R || A || M) and its reduction mod L run on the host in
``prepare_batch`` (ROADMAP S4) and are not kernel work.

Derivation, so that a reviewer can check the count:

Field arithmetic.  An element of GF(2^255 - 19) is 32 limbs of 8 bits
(``ops/field25519.py``: BITS = 8, LIMBS = 32).
- one multiplication (M) = the 32 x 32 schoolbook products of 8-bit
  limbs, each added into its column: 1,024 products + 1,024 additions
  = 2,048 operations.  The fold of the 31 high columns (x 38) and the
  carry sweeps (under 10% more) are left out, as are field additions and
  subtractions (32 limb operations each, 1.5% of an M): the count is a
  floor of what is needed, so the share can only read low.
- one squaring (S) needs each cross product once: 32 * 33 / 2 = 528
  products + 528 additions = 1,056 operations.  (The program computes a
  square as a full M; that is the implementation, not the need.)

Curve arithmetic, extended twisted Edwards coordinates, a = -1
(Hisil-Wong-Carter-Dawson 2008):
- doubling (dbl-2008-hwcd): 4 M + 4 S;
- addition of a table entry kept as (Y-X, Y+X, 2dT, 2Z)
  (add-2008-hwcd-3): 8 M; of an affine entry (Z = 1): 7 M.

One verification:
- [S]B + [k](-A) by Straus's method with 4-bit windows over 253-bit
  scalars: 64 windows, so 63 * 4 = 252 doublings, 64 additions from the
  constant affine table of B (7 M each) and 64 from the table of -A
  (8 M each);
- the table j * (-A), j = 2..15: 7 doublings and 7 additions (8 M);
- the strict rule [8]A != 0 and [8]R != 0: 2 * 3 = 6 doublings;
  doublings in all: 252 + 7 + 6 = 265 -> 1,060 M + 1,060 S;
  additions in all: 64 * 7 + 64 * 8 + 7 * 8 = 1,016 M;
- decompression of A and of R, each x = sqrt((y^2 - 1) / (d y^2 + 1))
  through one power to (p - 5) / 8 (251 S + 11 M) and the products
  around it (y^2, v = d y^2 + 1, v^3, v^7, u v^7, u v^3 * power, the
  check v x^2 = +-u, the turn by sqrt(-1)): 255 S + 18 M each
  -> 510 S + 36 M;
- the comparison of the result with R (affine): 2 M.

Total 2,114 M + 1,570 S = 2,114 * 2,048 + 1,570 * 1,056 = 5,987,392
8-bit integer operations.

Bytes per verification: in, A, R, S and k = H(R || A || M) mod L at 32
bytes each (k is made on the host; the device has to be given it); out,
one byte, accept or reject: 129 bytes.  (The program moves more: int32
limbs and windows, 1,153 bytes a row; that again is the implementation.)

8-bit products and their sums are what the chip's published int8 peak
counts, so the compute bound uses ``int8_ops_per_s`` of ``peaks.json``.
"""

from __future__ import annotations

import json
import os

OPS_PER_MUL = 32 * 32 * 2
OPS_PER_SQUARE = (32 * 33 // 2) * 2
DOUBLINGS = 63 * 4 + 7 + 6
MULS = DOUBLINGS * 4 + 64 * 7 + 64 * 8 + 7 * 8 + 2 * 18 + 2
SQUARES = DOUBLINGS * 4 + 2 * 255

OPS_PER_VERIFY = MULS * OPS_PER_MUL + SQUARES * OPS_PER_SQUARE
BYTES_PER_VERIFY = 4 * 32 + 1


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(verifies: float, peaks: dict) -> dict:
    """The least time the chip could take for ``verifies`` verifications
    and which bound sets it."""
    compute = verifies * OPS_PER_VERIFY / peaks["int8_ops_per_s"]
    memory = verifies * BYTES_PER_VERIFY / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute" if compute >= memory else "memory",
    }
