"""What one ed25519 verification costs, counted from the algorithm and
not from the jaxpr, so that it reads the same whatever implements the
kernel; and the table of peaks.

RFC 8032 cofactorless verification of (A, R, s, msg), k = H(R|A|msg)
computed on the host: decompress A, compute [s]B - [k]A, compare with R.

  field multiplications (squarings counted as multiplications):
    decompress A: one exponentiation x^((p-5)/8): 252 + 12 = 264,
                  plus 8 for u, v, v^3, v^7, checks          ->   272
    compare with R: compress the result, one inversion 265 + 3 ->  268
    table of multiples of A for 4-bit windows: 14 additions x 8 -> 112
    64 windows x (4 doublings x 8 + 2 additions x 8)          -> 3072
                                                         total   3724
  one field multiplication on a 32-bit integer datapath: 255 bits in 20
  limbs of 13 bits, schoolbook: 20 x 20 limb products, each a multiply
  and an add                                              -> 800 ops

Padding lanes are not work: callers pass the signatures really verified.
"""

from __future__ import annotations

import json
import os

FIELD_MULS_PER_SIG = 272 + 268 + 112 + 3072
OPS_PER_FIELD_MUL = 20 * 20 * 2
OPS_PER_SIG = FIELD_MULS_PER_SIG * OPS_PER_FIELD_MUL
# in: A and R (32 B each), s and k (32 B each); out: one verdict byte
BYTES_PER_SIG = 4 * 32 + 1

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError("device_kind %r is not in benchmark/harness/"
                       "peaks.json; add it with its source" % device_kind)
    return table[device_kind]


def ed25519_roofline(n_sigs: int, kernel_s: float,
                     device_kind: str) -> dict:
    """Share of the roofline: the least time the chip could take for
    n_sigs verifications over the device time the kernel took."""
    if n_sigs <= 0 or kernel_s <= 0:
        raise ValueError("roofline needs work and time, got %r sigs in "
                         "%r s" % (n_sigs, kernel_s))
    p = peaks(device_kind)
    t_ops = n_sigs * OPS_PER_SIG / p["int_ops_per_s"]
    t_bytes = n_sigs * BYTES_PER_SIG / p["bytes_per_s"]
    return {"pct": 100.0 * max(t_ops, t_bytes) / kernel_s,
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "least_s": max(t_ops, t_bytes)}
