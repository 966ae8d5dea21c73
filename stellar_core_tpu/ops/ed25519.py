"""Batched ed25519 verification on TPU: the hot compute path.

Design (TPU-first; replaces the reference's per-call libsodium
`crypto_sign_verify_detached`, /root/reference/src/crypto/SecretKey.cpp:332):

- Verification equation (RFC 8032, cofactorless — matching the OpenSSL CPU
  backend semantics exactly): [S]B == R + [k]A with k = SHA512(R‖A‖M) mod L.
  We compute Q = [S]B + [k](−A) on-device and compare with the decompressed
  R projectively (no inversion).
- INPUT CONTRACT: one `(B, 128)` uint8 array a dispatch, 128 bytes a
  lane: A (32) | R (32) | S + 0x88…88 (32) | k + 0x88…88 (32), sign bits
  where they already sit (bit 255 of A and R). The served entry
  `verify_batch_packed` splits it on the device (`unpack_packed`, scope
  `ed25519.unpack`) into limbs, sign bits and signed digits and calls
  `verify_kernel`, the six-argument kernel body, which the differential
  tests also call directly.
- LAYOUT: all device arrays are limb-first / batch-last ((20, B) field
  elements, (64, B) scalar digits) so the batch rides the TPU lane
  dimension at full width; see ops/field.py header. The public
  `verify_kernel` still takes batch-first arrays (the host/byte layout)
  and transposes once at the jit boundary.
- Points are (x, y, z, t) TUPLES of (20, B) field elements — no stacked
  (4, 20) axis for XLA to pad; each coordinate is an independent
  full-lane array.
- Host does the byte-level work that TPUs are bad at: SHA-512 (tiny
  messages), mod L, canonicality prechecks (S < L, y < p), and one
  256-bit addition a scalar (below) — one native call a batch
  (native/prep.c), or numpy + hashlib + Python ints with
  SCT_NATIVE_PREP=0, byte for byte the same buffer. The bit-slicing
  (13-bit limbs, 4-bit windows) is shifts and masks on the device.
- Scalars use SIGNED radix-16 digits in [−8, 8). The carry-propagating
  recode of x is `nibble_i(x + 0x88…88) − 8`, digit by digit the same
  carry rule (a nibble plus its carry-in reaches 8 exactly when adding 8
  more carries out), so the host adds one constant and the device
  subtracts 8 from every nibble; x < 2^253 keeps the sum below 2^256.
  `signed_recode_nibs_np` is the digit-by-digit form, kept as the tests'
  oracle. Table magnitudes only span 0..8, so both lookup tables are
  9-wide instead of 16-wide (≈44% less masked-select traffic — the
  select is pure data movement on the VPU) and the per-item table build
  shrinks from 14 point ops to 7. Negation is a cheap conditional on the
  selected point (Edwards negation: x/T flip for extended, y±x swap for
  Niels).
- Fixed-base [S]B uses a precomputed 64×9 signed-radix-16 table of B
  multiples in Niels form (y+x, y−x, 2dxy): 64 masked-lookup additions,
  zero doublings.
- Variable-base [k](−A) builds a per-item 9-entry extended-coordinate
  table (4 doublings + 3 additions) then runs 63 iterations of 4
  doublings + 1 table addition inside a fori_loop.
- Point formulas: extended coordinates, a=−1 twisted Edwards unified
  add/double (complete on the prime-order subgroup); doublings skip the
  T output unless the next step reads it.

A pure-Python (int) implementation lives alongside for table generation and
as a test oracle.
"""

from __future__ import annotations

import hashlib
import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..parallel.device import verify_compile_options
from .field import (
    NLIMBS, LIMB_BITS, LIMB_MASK, P, _bcast, fe_add, fe_carry, fe_eq,
    fe_freeze, fe_is_zero, fe_mul, fe_mul_small, fe_neg, fe_one, fe_parity,
    fe_pow_p58, fe_sq, fe_sub, fe_zero, int_from_limbs, limbs_from_int,
)

# --- curve constants (python ints) ----------------------------------------

L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = (2 * D) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
B_Y = (4 * pow(5, P - 2, P)) % P


def _recover_x(y: int, sign: int) -> int | None:
    """Python-int point decompression (RFC 8032 §5.1.3 math)."""
    if y >= P:
        return None
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    if x2 == 0:
        return None if sign else 0
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * SQRT_M1 % P
    if (x * x - x2) % P != 0:
        return None
    if x & 1 != sign:
        x = P - x
    return x


B_X = _recover_x(B_Y, 0)


class _Pt:
    """Python-int extended-coordinate point (oracle + table generation)."""

    __slots__ = ("x", "y", "z", "t")

    def __init__(self, x, y, z=1, t=None):
        self.x, self.y, self.z = x % P, y % P, z % P
        self.t = (x * y * pow(z, P - 2, P)) % P if t is None else t % P

    @classmethod
    def identity(cls):
        return cls(0, 1, 1, 0)

    def add(self, o: "_Pt") -> "_Pt":
        a = (self.y - self.x) * (o.y - o.x) % P
        b = (self.y + self.x) * (o.y + o.x) % P
        c = self.t * D2 % P * o.t % P
        d = 2 * self.z * o.z % P
        e, f, g, h = b - a, d - c, d + c, b + a
        return _Pt(e * f % P, g * h % P, f * g % P, e * h % P)

    def dbl(self) -> "_Pt":
        a = self.x * self.x % P
        b = self.y * self.y % P
        c = 2 * self.z * self.z % P
        h = a + b
        e = h - (self.x + self.y) ** 2 % P
        g = a - b
        f = c + g
        return _Pt(e * f % P, g * h % P, f * g % P, e * h % P)

    def mul(self, n: int) -> "_Pt":
        q = _Pt.identity()
        p = self
        while n:
            if n & 1:
                q = q.add(p)
            p = p.dbl()
            n >>= 1
        return q

    def affine(self) -> tuple[int, int]:
        zi = pow(self.z, P - 2, P)
        return (self.x * zi % P, self.y * zi % P)

    def compress(self) -> bytes:
        x, y = self.affine()
        return int.to_bytes(y | ((x & 1) << 255), 32, "little")


B_POINT = _Pt(B_X, B_Y)


def verify_oracle(pub: bytes, sig: bytes, msg: bytes) -> bool:
    """Pure-Python RFC 8032 cofactorless verify — the semantics oracle both
    backends must match."""
    if len(pub) != 32 or len(sig) != 64:
        return False
    r_bytes, s_bytes = sig[:32], sig[32:]
    s = int.from_bytes(s_bytes, "little")
    if s >= L:
        return False
    ay = int.from_bytes(pub, "little")
    a_sign, ay = ay >> 255, ay & ((1 << 255) - 1)
    ry = int.from_bytes(r_bytes, "little")
    r_sign, ry = ry >> 255, ry & ((1 << 255) - 1)
    ax = _recover_x(ay, a_sign)
    rx = _recover_x(ry, r_sign)
    if ax is None or rx is None:
        return False
    k = int.from_bytes(hashlib.sha512(r_bytes + pub + msg).digest(),
                       "little") % L
    a_neg = _Pt(P - ax if ax else 0, ay)
    q = B_POINT.mul(s).add(a_neg.mul(k))  # [S]B − [k]A
    qx, qy = q.affine()
    return qx == rx and qy == ry


# --- precomputed fixed-base table (Niels form) -----------------------------

def _build_fixed_table() -> np.ndarray:
    """table[j, v] = Niels(v · 16^j · B) as 3×20 limbs: (y+x, y−x, 2dxy).
    Only magnitudes 0..8 are stored — scalars are recoded to signed
    radix-16 digits in [−8, 8) and the kernel negates the selected entry
    (a y±x swap plus an xy2d negation) when the digit is negative."""
    tab = np.zeros((64, 9, 3, NLIMBS), np.int32)
    base = B_POINT
    for j in range(64):
        acc = _Pt.identity()
        for v in range(9):
            x, y = acc.affine() if v else (0, 1)
            tab[j, v, 0] = limbs_from_int((y + x) % P)
            tab[j, v, 1] = limbs_from_int((y - x) % P)
            tab[j, v, 2] = limbs_from_int(2 * D * x % P * y % P)
            acc = acc.add(base)
        for _ in range(4):
            base = base.dbl()
    return tab


_FIXED_TABLE: np.ndarray | None = None


def fixed_table() -> np.ndarray:
    global _FIXED_TABLE
    if _FIXED_TABLE is None:
        _FIXED_TABLE = _build_fixed_table()
    return _FIXED_TABLE


# --- jax point ops: points are (x, y, z, t) tuples of (20, ...) limbs ------

Point = tuple  # (x, y, z, t)


def pt_identity(batch_shape=()) -> Point:
    return (fe_zero(batch_shape), fe_one(batch_shape),
            fe_one(batch_shape), fe_zero(batch_shape))


_D2_LIMBS = limbs_from_int(D2)
_SQRT_M1_LIMBS = limbs_from_int(SQRT_M1)
_D_LIMBS = limbs_from_int(D)


def pt_add(p: Point, q: Point) -> Point:
    """Unified a=−1 extended addition (add-2008-hwcd-3)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fe_mul(fe_sub(y1, x1), fe_sub(y2, x2))
    b = fe_mul(fe_add(y1, x1), fe_add(y2, x2))
    c = fe_mul(fe_mul(t1, _bcast(_D2_LIMBS, t1)), t2)
    d = fe_mul_small(fe_mul(z1, z2), 2)
    e = fe_sub(b, a)
    f = fe_sub(d, c)
    g = fe_add(d, c)
    h = fe_add(b, a)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_add_folded(p: Point, q: Point, need_t: bool = False) -> Point:
    """Extended add where q's T coordinate is pre-multiplied by 2d (table
    form). Ladder adds feed doublings, which never read T, so by default
    the output T (the e·h multiply) is skipped; the final window add
    passes need_t=True because the fixed-base Niels chain reads it."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2d = q
    a = fe_mul(fe_sub(y1, x1), fe_sub(y2, x2))
    b = fe_mul(fe_add(y1, x1), fe_add(y2, x2))
    c = fe_mul(t1, t2d)
    d = fe_mul_small(fe_mul(z1, z2), 2)
    e = fe_sub(b, a)
    f = fe_sub(d, c)
    g = fe_add(d, c)
    h = fe_add(b, a)
    t = fe_mul(e, h) if need_t else fe_zero(x1.shape[1:])
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), t)


def pt_add_niels(p: Point, n: tuple) -> Point:
    """Mixed addition with a precomputed Niels point (y+x, y−x, 2dxy)."""
    x1, y1, z1, t1 = p
    ypx, ymx, xy2d = n
    a = fe_mul(fe_sub(y1, x1), ymx)
    b = fe_mul(fe_add(y1, x1), ypx)
    c = fe_mul(t1, xy2d)
    d = fe_mul_small(z1, 2)
    e = fe_sub(b, a)
    f = fe_sub(d, c)
    g = fe_add(d, c)
    h = fe_add(b, a)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_dbl(p: Point, need_t: bool = True) -> Point:
    """a=−1 extended doubling (dbl-2008-hwcd). Doubling never READS the
    T coordinate, so ladder doublings whose output feeds another doubling
    pass need_t=False and skip the e·h multiply (3 of every 4 ladder
    steps). The four squarings use the symmetric half-product."""
    x1, y1, z1, _ = p
    a = fe_sq(x1)
    b = fe_sq(y1)
    c = fe_mul_small(fe_sq(z1), 2)
    h = fe_add(a, b)
    e = fe_sub(h, fe_sq(fe_add(x1, y1)))
    g = fe_sub(a, b)
    f = fe_add(c, g)
    t = fe_mul(e, h) if need_t else fe_zero(x1.shape[1:])
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), t)


def pt_neg(p: Point) -> Point:
    x, y, z, t = p
    return (fe_neg(x), y, z, fe_neg(t))


def fe_decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray):
    """Decompress (y, sign) → (x, ok). y is canonical (host-checked y < p).

    x = sqrt((y²−1)/(dy²+1)); multiply by sqrt(−1) when the first candidate
    fails; reject when neither squares to the target or x=0 with sign=1.
    """
    one = fe_one(y_limbs.shape[1:])
    y2 = fe_sq(y_limbs)
    u = fe_sub(y2, one)
    v = fe_add(fe_mul(y2, _bcast(_D_LIMBS, y2)), one)
    v3 = fe_mul(fe_sq(v), v)
    v7 = fe_mul(fe_sq(v3), v)
    x = fe_mul(fe_mul(u, v3), fe_pow_p58(fe_mul(u, v7)))
    vx2 = fe_mul(v, fe_sq(x))
    ok1 = fe_eq(vx2, u)
    ok2 = fe_eq(vx2, fe_neg(u))
    x_alt = fe_mul(x, _bcast(_SQRT_M1_LIMBS, x))
    x = jnp.where((ok2 & ~ok1)[None], x_alt, x)
    ok = ok1 | ok2
    x_is_zero = fe_is_zero(x)
    ok = ok & ~(x_is_zero & (sign == 1))
    # fix parity
    flip = (fe_parity(x) != sign)
    x = jnp.where(flip[None], fe_neg(x), x)
    return x, ok


def _select_signed9(stacks: tuple, dig: jnp.ndarray) -> tuple:
    """Signed-digit select: each stack (9, 20, B) of extended coords with
    T pre-folded by 2d, dig (B,) in [−8, 8). Selects |dig| via a masked
    sum (XLA fuses it into vector selects) then conditionally negates the
    point — Edwards negation flips x and t only."""
    mag = jnp.abs(dig)
    neg = dig < 0
    oh = (jnp.arange(9, dtype=jnp.int32)[:, None] ==
          mag[None, :]).astype(jnp.int32)             # (9, B)
    ohc = oh[:, None, :]                              # (9, 1, B)
    x, y, z, t2d = tuple(jnp.sum(s * ohc, axis=0) for s in stacks)
    x = jnp.where(neg[None], fe_neg(x), x)
    t2d = jnp.where(neg[None], fe_neg(t2d), t2d)
    return (x, y, z, t2d)


def verify_kernel(ay: jnp.ndarray, a_sign: jnp.ndarray,
                  ry: jnp.ndarray, r_sign: jnp.ndarray,
                  s_nibs: jnp.ndarray, k_nibs: jnp.ndarray) -> jnp.ndarray:
    """Batched verify core. All inputs int32, batch-first (host layout):
    ay, ry: (B, 20) canonical y limbs; a_sign, r_sign: (B,);
    s_nibs, k_nibs: (B, 64) SIGNED radix-16 digits in [−8, 8)
    (LSB-first, as unpack_packed or signed_recode_nibs_np gives them) of
    S and of k = SHA512(R‖A‖M) mod L. Returns (B,) bool.

    Internally everything is limb-first (20, B) / digit-first (64, B); the
    transposes below are the only layout shuffles in the whole kernel.
    """
    ay = jnp.moveaxis(ay, -1, 0)
    ry = jnp.moveaxis(ry, -1, 0)
    s_nibs = jnp.moveaxis(s_nibs, -1, 0)
    k_nibs = jnp.moveaxis(k_nibs, -1, 0)
    batch = ay.shape[1:]

    # the named scopes put a stage name into every op's metadata (the
    # device trace's op_name), and into nothing else: numerics untouched
    with jax.named_scope("ed25519.decompress"):
        ax, a_ok = fe_decompress(ay, a_sign)
        rx, r_ok = fe_decompress(ry, r_sign)

    with jax.named_scope("ed25519.table"):
        # A in extended coords, negated: Q = [S]B + [k](−A)
        neg_ax = fe_neg(ax)
        neg_at = fe_neg(fe_mul(ax, ay))
        a_pt = (neg_ax, ay, fe_one(batch), neg_at)

        # per-item table of v·(−A), v = 0..8 (signed digits select a
        # magnitude and negate), extended coords; entry T is
        # pre-multiplied by 2d so the ladder add does c = T1·(2d·T2) in
        # ONE multiply (Niels-style T folding)
        entries = [pt_identity(batch), a_pt]
        for v in range(2, 9):
            if v % 2 == 0:
                entries.append(pt_dbl(entries[v // 2]))
            else:
                entries.append(pt_add(entries[v - 1], a_pt))
        d2 = _bcast(_D2_LIMBS, ax)
        a_table = tuple(
            jnp.stack([e[c] if c < 3 else fe_mul(e[3], d2)
                       for e in entries], axis=0)
            for c in range(4))                       # 4 × (9, 20, B)

    # variable-base: MSB-first over 64 signed digits of k. The window
    # add's T output is never read (the next 4 doublings ignore T; the
    # 4th doubling regenerates it), so the add also skips its e·h
    # multiply.
    def vb_window(q, dig, need_t):
        q = pt_dbl(q, need_t=False)
        q = pt_dbl(q, need_t=False)
        q = pt_dbl(q, need_t=False)
        q = pt_dbl(q, need_t=True)
        return pt_add_folded(q, _select_signed9(a_table, dig),
                             need_t=need_t)

    def vb_body(i, q):
        return vb_window(q, k_nibs[63 - i], False)

    with jax.named_scope("ed25519.varbase"):
        q = jax.lax.fori_loop(0, 63, vb_body, pt_identity(batch))
        # final window peeled: its add DOES produce T, which the
        # fixed-base Niels chain below consumes
        q = vb_window(q, k_nibs[0], True)

    # fixed-base: Σ_j table[j][s_dig_j], 64 Niels additions, no doublings
    ftab = jnp.asarray(fixed_table())  # (64, 9, 3, 20) static

    def fb_body(j, acc):
        row = jax.lax.dynamic_index_in_dim(ftab, j, axis=0,
                                           keepdims=False)  # (9, 3, 20)
        dig = s_nibs[j]                                     # (B,)
        mag = jnp.abs(dig)
        fneg = (dig < 0)[None]
        oh = (jnp.arange(9, dtype=jnp.int32)[:, None] ==
              mag[None, :]).astype(jnp.int32)               # (9, B)
        # (9, 3, 20, 1) * (9, 1, 1, B) summed over v → (3, 20, B)
        sel = jnp.sum(row[..., None] * oh[:, None, None, :], axis=0)
        # Niels negation: swap (y+x, y−x), negate 2dxy
        ypx = jnp.where(fneg, sel[1], sel[0])
        ymx = jnp.where(fneg, sel[0], sel[1])
        xy2d = jnp.where(fneg, fe_neg(sel[2]), sel[2])
        return pt_add_niels(acc, (ypx, ymx, xy2d))

    with jax.named_scope("ed25519.fixedbase"):
        q = jax.lax.fori_loop(0, 64, fb_body, q)

    with jax.named_scope("ed25519.compare"):
        # projective compare with affine R: X == rx·Z and Y == ry·Z
        xq, yq, zq, _ = q
        eq = fe_eq(xq, fe_mul(rx, zq)) & fe_eq(yq, fe_mul(ry, zq))
        return a_ok & r_ok & eq


# --- host-side batch preparation, and the device-side unpack ---------------

_L_BYTES_BE = np.frombuffer(L.to_bytes(32, "big"), np.uint8)
_P_BYTES_BE = np.frombuffer(P.to_bytes(32, "big"), np.uint8)


def bytes_to_limbs_np(b: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 → (B, 20) int32 13-bit limbs (little-endian value)."""
    x = b.astype(np.int64)
    out = np.zeros((*b.shape[:-1], NLIMBS), np.int64)
    for i in range(NLIMBS):
        bit = LIMB_BITS * i
        k, r = bit >> 3, bit & 7
        v = x[..., k] >> r
        if k + 1 < 32:
            v = v | (x[..., k + 1] << (8 - r))
        if k + 2 < 32:
            v = v | (x[..., k + 2] << (16 - r))
        out[..., i] = v & LIMB_MASK
    return out.astype(np.int32)


def bytes_to_nibs_np(b: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 → (B, 64) int32 radix-16 digits, LSB-first."""
    lo = (b & 15).astype(np.int32)
    hi = (b >> 4).astype(np.int32)
    return np.stack([lo, hi], axis=-1).reshape(*b.shape[:-1], 64)


def signed_recode_nibs_np(nibs: np.ndarray) -> np.ndarray:
    """(…, 64) unsigned radix-16 digits → signed digits in [−8, 8) with
    the same value (carry-propagating recode, vectorized over the batch;
    the 64-step loop is over digit positions, not items). Values are
    < 2^253 (S and k are both < L), so digit 63 is ≤ 1 and the final
    carry is always absorbed — asserted, since an overflow here would
    silently verify a wrong equation."""
    d = nibs.astype(np.int32).copy()
    carry = np.zeros(d.shape[:-1], np.int32)
    for i in range(d.shape[-1]):
        v = d[..., i] + carry
        carry = (v >= 8).astype(np.int32)
        d[..., i] = v - (carry << 4)
    assert not carry.any(), "signed recode overflow: input >= 2^253"
    return d


def _lex_lt_be(a: np.ndarray, bound_be: np.ndarray) -> np.ndarray:
    """Vectorized big-endian lexicographic a < bound over (B, 32) uint8."""
    diff = a != bound_be[None, :]
    first = np.argmax(diff, axis=-1)
    rows = np.arange(a.shape[0])
    return np.where(diff.any(axis=-1),
                    a[rows, first] < bound_be[first], False)


def _pack32(items, n: int, width: int) -> np.ndarray:
    """List of bytes → (n, width) uint8, zero-filling wrong-length items
    and normalizing the list length to n (short lists pad with invalid
    zero rows; callers mark those pre_ok=False via the length check)."""
    items = list(items[:n]) + [b""] * (n - len(items))
    blob = b"".join(x if len(x) == width else b"\x00" * width for x in items)
    return np.frombuffer(blob, np.uint8).reshape(n, width)


PACKED_WIDTH = 128
# x + RECODE_BIAS: nibble i is the signed radix-16 digit of x, plus 8
RECODE_BIAS = int.from_bytes(b"\x88" * 32, "little")


def prepare_batch(pubs: list[bytes], sigs: list[bytes],
                  msgs: list[bytes], size: int | None = None) -> dict:
    """Host preprocessing: hashing, canonicality prechecks, the recode
    bias. Returns {"packed": the (size, 128) uint8 device input (size
    defaults to the batch; a bucket's size pads with zero lanes), and
    "pre_ok": the (n,) host-side precheck mask}. A lane that fails a
    precheck is all zero, like padding: its device verdict is masked.

    One native call (prep.c) writes the lanes in place; the Python path
    below gives the same bytes with a per-item SHA-512 + Python-int
    loop (the oracle tests select it with SCT_NATIVE_PREP=0)."""
    n = len(pubs)
    size = n if size is None else size
    if size < n:
        raise ValueError("size %d below the batch's %d" % (size, n))
    good = np.zeros(n, bool)
    for i in range(min(n, len(sigs), len(msgs))):
        good[i] = len(pubs[i]) == 32 and len(sigs[i]) == 64
    msgs = list(msgs[:n]) + [b""] * (n - len(msgs))
    pub_arr = _pack32(pubs, n, 32)
    sig_arr = _pack32(sigs, n, 64)
    packed = np.zeros((size, PACKED_WIDTH), np.uint8)

    if os.environ.get("SCT_NATIVE_PREP", "1") != "0":
        from .. import native
        pre_ok = native.prepare_packed_native(pub_arr, sig_arr, msgs,
                                              good, packed)
        if pre_ok is not None:
            return {"packed": packed, "pre_ok": pre_ok}
    r_arr = sig_arr[:, :32]
    s_arr = sig_arr[:, 32:]

    ay = pub_arr.copy()
    ay[:, 31] &= 0x7F
    ry = r_arr.copy()
    ry[:, 31] &= 0x7F

    # canonicality prechecks, big-endian lexicographic compare
    s_ok = _lex_lt_be(s_arr[:, ::-1], _L_BYTES_BE)
    ay_ok = _lex_lt_be(ay[:, ::-1], _P_BYTES_BE)
    ry_ok = _lex_lt_be(ry[:, ::-1], _P_BYTES_BE)
    pre_ok = good & s_ok & ay_ok & ry_ok

    # k = SHA512(R‖A‖M) mod L and the two biased scalars — the only
    # per-item loop
    for i in np.flatnonzero(pre_ok):
        r_b, a_b = r_arr[i].tobytes(), pub_arr[i].tobytes()
        k = int.from_bytes(hashlib.sha512(r_b + a_b + msgs[i]).digest(),
                           "little") % L
        s = int.from_bytes(s_arr[i].tobytes(), "little")
        packed[i] = np.frombuffer(
            a_b + r_b + (s + RECODE_BIAS).to_bytes(32, "little") +
            (k + RECODE_BIAS).to_bytes(32, "little"), np.uint8)
    return {"packed": packed, "pre_ok": pre_ok}


def unpack_packed_np(packed: np.ndarray) -> tuple:
    """Host mirror of unpack_packed, the long way round: the bias taken
    off again with Python ints and the digits recoded one by one
    (signed_recode_nibs_np). The differential tests' oracle for the
    device's shifts and masks; nothing served calls it."""
    a, r = packed[:, 0:32].copy(), packed[:, 32:64].copy()
    a_sign, r_sign = a[:, 31] >> 7, r[:, 31] >> 7
    a[:, 31] &= 0x7F
    r[:, 31] &= 0x7F

    def scalar_digits(cols):
        vals = [int.from_bytes(row.tobytes(), "little") for row in cols]
        raw = b"".join(max(v - RECODE_BIAS, 0).to_bytes(32, "little")
                       for v in vals)
        digs = signed_recode_nibs_np(bytes_to_nibs_np(
            np.frombuffer(raw, np.uint8).reshape(-1, 32)))
        # a zero lane (padding, a failed precheck) is no scalar's image:
        # the device reads every nibble as 0 − 8 there
        digs[[v < RECODE_BIAS for v in vals]] = -8
        return digs

    return (bytes_to_limbs_np(a), a_sign.astype(np.int32),
            bytes_to_limbs_np(r), r_sign.astype(np.int32),
            scalar_digits(packed[:, 64:96]), scalar_digits(packed[:, 96:128]))


# limb i reads bytes k, k+1, k+2 from bit offset r (k + 2 = 33 at most:
# two zero rows pad the 32 bytes)
_LIMB_BYTE = [(LIMB_BITS * i) >> 3 for i in range(NLIMBS)]
_LIMB_SHIFT = np.array([(LIMB_BITS * i) & 7 for i in range(NLIMBS)],
                       np.int32)


def unpack_packed(packed: jnp.ndarray) -> tuple:
    """(B, 128) uint8 → verify_kernel's six int32 arguments (batch-first:
    ay, a_sign, ry, r_sign, s_nibs, k_nibs). Works limb-first on the
    transposed bytes, so every shift and mask runs at full lane width;
    the transposes back meet verify_kernel's own and cancel."""
    x = packed.astype(jnp.int32).T                         # (128, B)

    def point(b):                                          # b: (32, B)
        sign = b[31] >> 7
        b = jnp.concatenate([b[:31], b[31:] & 0x7F,
                             jnp.zeros_like(b[:2])], axis=0)
        sh = _LIMB_SHIFT[:, None]
        b0, b1, b2 = (jnp.stack([b[k + j] for k in _LIMB_BYTE])
                      for j in range(3))          # static slices, (20, B)
        limbs = ((b0 >> sh) | (b1 << (8 - sh)) | (b2 << (16 - sh))) \
            & LIMB_MASK
        return limbs.T, sign

    def digits(b):                                         # b: (32, B)
        d = jnp.stack([(b & 15) - 8, (b >> 4) - 8], axis=1)
        return d.reshape(64, -1).T

    ay, a_sign = point(x[0:32])
    ry, r_sign = point(x[32:64])
    return ay, a_sign, ry, r_sign, digits(x[64:96]), digits(x[96:128])


def verify_packed(packed: jnp.ndarray) -> jnp.ndarray:
    """The served entry's body: one (B, 128) uint8 array in, (B,) bool
    out. Jitted as `verify_batch_packed` below and, sharded over the dp
    axis, by parallel/mesh.py."""
    with jax.named_scope("ed25519.unpack"):
        args = unpack_packed(packed)
    return verify_kernel(*args)


# the device module is named after the function: the benchmark finds the
# verify executable's runs in a device trace by the prefix
# `jit_verify_batch`
@partial(jax.jit, compiler_options=verify_compile_options())
def verify_batch_packed(packed):
    return verify_packed(packed)


# the six-argument kernel jitted alone: what the differential tests and
# benchmark/tests/record_trace.py call; nothing served does
@partial(jax.jit, static_argnames=())
def verify_batch_jit(ay, a_sign, ry, r_sign, s_nibs, k_nibs):
    return verify_kernel(ay, a_sign, ry, r_sign, s_nibs, k_nibs)


def verify_batch(pubs: list[bytes], sigs: list[bytes],
                 msgs: list[bytes]) -> np.ndarray:
    """End-to-end batched verify (host prep + device kernel)."""
    prep = prepare_batch(pubs, sigs, msgs)
    ok = np.asarray(verify_batch_packed(prep["packed"]))
    return ok & prep["pre_ok"]
