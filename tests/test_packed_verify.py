"""The verify dispatch's input contract (ISSUE 26): one packed uint8
array, 128 bytes a lane — A | R | S + 0x88…88 | k + 0x88…88 — that the
served entry unpacks on the device.

Differential throughout: the one-array entry against the six-argument
kernel (its arguments unpacked on the host the long way, digit by digit
through `signed_recode_nibs_np`), the pure-Python oracles and the CPU
backend; the native buffer against the SCT_NATIVE_PREP=0 one, byte for
byte. Every device call here is a 128-lane shape: two executables in
all, the served one and the six-argument one.
"""

import random

import numpy as np
import pytest

from stellar_core_tpu import native
from stellar_core_tpu.crypto import fallback as F, keys as K
from stellar_core_tpu.crypto.batch_verifier import TpuSigVerifier
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ops import ed25519 as E

from test_ed25519_adversarial import VECTORS

SIZES = (1, 29, 128, 129)
BUCKET = 128

# RFC 8032 §7.1, tests 1-3: (public key, message, signature)
RFC8032 = [
    ("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
     "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
     "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
     "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
]

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native prep lib not buildable")


def random_triples(n, seed):
    """n signed triples, one in eight with one bit flipped somewhere in
    the key, the signature (R or S: a flipped high bit of S makes it
    non-canonical) or the message."""
    rnd = random.Random(seed)
    sks = [SecretKey.from_seed(rnd.randbytes(32)) for _ in range(5)]
    out = []
    for i in range(n):
        sk = sks[i % 5]
        msg = rnd.randbytes(rnd.randrange(1, 200))
        parts = [bytearray(sk.public_key.key_bytes),
                 bytearray(sk.sign(msg)), bytearray(msg)]
        if i % 8 == 5 or n == 1:
            part = parts[rnd.randrange(3)]
            part[rnd.randrange(len(part))] ^= 1 << rnd.randrange(8)
        out.append(tuple(bytes(p) for p in parts))
    return out


def chunks_of(triples):
    for i in range(0, len(triples), BUCKET):
        yield triples[i:i + BUCKET]


def prepared(chunk):
    return E.prepare_batch([t[0] for t in chunk], [t[1] for t in chunk],
                           [t[2] for t in chunk], size=BUCKET)


def six_argument_verdicts(triples):
    """The kernel body jitted alone, fed from the same packed buffer
    through the host's digit-by-digit unpack."""
    out = []
    for chunk in chunks_of(triples):
        prep = prepared(chunk)
        ok = np.asarray(E.verify_batch_jit(
            *E.unpack_packed_np(prep["packed"])))
        # every lane, padding and failed prechecks included, reads the
        # same on the one-array entry: the unpack is the only difference
        assert (ok == np.asarray(
            E.verify_batch_packed(prep["packed"]))).all()
        out.extend((ok[:len(chunk)] & prep["pre_ok"]).tolist())
    return out


@pytest.fixture(scope="module")
def served():
    v = TpuSigVerifier()
    v.BUCKETS = (BUCKET,)
    return v


# ----------------------------------------------------------- the recode

def _digits_by_bias(x: int) -> np.ndarray:
    b = np.frombuffer((x + E.RECODE_BIAS).to_bytes(32, "little"), np.uint8)
    return E.bytes_to_nibs_np(b) - 8


def _digits_by_carry(x: int) -> np.ndarray:
    b = np.frombuffer(x.to_bytes(32, "little"), np.uint8)
    return E.signed_recode_nibs_np(E.bytes_to_nibs_np(b))


@pytest.mark.parametrize("x", [
    0, 1, 7, 8, E.L - 1, E.L,
    int("7" * 63, 16),              # all-7 nibbles: no carry anywhere
    int("8" * 63, 16) % 2 ** 253,   # every nibble carries
    int("f" * 63, 16) % 2 ** 253,
    2 ** 252, 2 ** 253 - 1,         # the largest the contract admits
], ids=["0", "1", "7", "8", "L-1", "L", "all-7", "all-8", "all-f",
        "2^252", "2^253-1"])
def test_bias_nibbles_are_the_carry_recode(x):
    got = _digits_by_bias(x)
    assert (got == _digits_by_carry(x)).all()
    assert got.min() >= -8 and got.max() < 8
    assert sum(int(d) << (4 * j) for j, d in enumerate(got)) == x


def test_bias_nibbles_are_the_carry_recode_on_random_scalars():
    rnd = random.Random(26)
    for _ in range(500):
        x = rnd.randrange(2 ** 253)
        assert (_digits_by_bias(x) == _digits_by_carry(x)).all(), hex(x)


# -------------------------------------------------------- the host buffer

@needs_native
@pytest.mark.parametrize("n", SIZES)
def test_native_and_python_buffers_are_byte_identical(n, monkeypatch):
    triples = random_triples(n, seed=n) + \
        [(p, s, m) for (_l, p, s, m) in VECTORS[:BUCKET - n if n < BUCKET
                                                else 0]]
    cols = [[t[i] for t in triples] for i in range(3)]
    size = -(-len(triples) // BUCKET) * BUCKET
    monkeypatch.setenv("SCT_NATIVE_PREP", "0")
    ref = E.prepare_batch(*cols, size=size)
    monkeypatch.setenv("SCT_NATIVE_PREP", "1")
    nat = E.prepare_batch(*cols, size=size)
    assert nat["packed"].shape == ref["packed"].shape == (size, 128)
    assert nat["packed"].dtype == ref["packed"].dtype == np.uint8
    assert nat["packed"].tobytes() == ref["packed"].tobytes()
    assert (nat["pre_ok"] == ref["pre_ok"]).all()
    assert nat["pre_ok"].shape == (len(triples),)


@pytest.mark.parametrize("native_prep", ["1", "0"])
@pytest.mark.parametrize("what", ["S=L", "S=L+1", "S=2^255-1", "A y=p",
                                  "A y=p+1 sign", "R y=p", "R all-ff",
                                  "short sig", "short key"])
def test_non_canonical_inputs_fail_pre_ok(what, native_prep, monkeypatch):
    if native_prep == "1" and not native.available():
        pytest.skip("native prep lib not buildable")
    monkeypatch.setenv("SCT_NATIVE_PREP", native_prep)
    sk = SecretKey.from_seed(b"\x26" * 32)
    msg = b"non-canonical"
    pub, sig = sk.public_key.key_bytes, sk.sign(msg)
    enc = lambda v: v.to_bytes(32, "little")   # noqa: E731
    bad_pub, bad_sig = {
        "S=L": (pub, sig[:32] + enc(E.L)),
        "S=L+1": (pub, sig[:32] + enc(E.L + 1)),
        "S=2^255-1": (pub, sig[:32] + enc(2 ** 255 - 1)),
        "A y=p": (enc(E.P), sig),
        "A y=p+1 sign": (enc((E.P + 1) | 1 << 255), sig),
        "R y=p": (pub, enc(E.P) + sig[32:]),
        "R all-ff": (pub, b"\xff" * 32 + sig[32:]),
        "short sig": (pub, sig[:63]),
        "short key": (pub[:31], sig),
    }[what]
    prep = E.prepare_batch([pub, bad_pub, pub], [sig, bad_sig, sig],
                           [msg] * 3, size=4)
    assert prep["pre_ok"].tolist() == [True, False, True]
    # the refused lane and the padding are zero; the neighbours are not
    assert not prep["packed"][1].any() and not prep["packed"][3].any()
    assert prep["packed"][0].tobytes() == prep["packed"][2].tobytes()
    assert prep["packed"][0, :64].tobytes() == pub + sig[:32]


def test_packed_lane_layout():
    """A | R | S + bias | k + bias, sign bits in place."""
    import hashlib
    pub, msg, sig = (bytes.fromhex(h) for h in RFC8032[2])
    lane = E.prepare_batch([pub], [sig], [msg])["packed"][0].tobytes()
    k = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(),
                       "little") % E.L
    s = int.from_bytes(sig[32:], "little")
    assert lane[:32] == pub and lane[32:64] == sig[:32]
    assert int.from_bytes(lane[64:96], "little") == s + E.RECODE_BIAS
    assert int.from_bytes(lane[96:], "little") == k + E.RECODE_BIAS
    assert E.RECODE_BIAS == int("88" * 32, 16)


# ------------------------------------------------------ the device unpack

def test_device_unpack_equals_the_host_oracle():
    """Limbs, sign bits and signed digits out of the jitted unpack are
    the host's, on honest lanes (both sign bits occur), refused lanes
    and padding."""
    import jax
    triples = random_triples(40, seed=3) + \
        [(p, s, m) for (_l, p, s, m) in VECTORS]
    prep = prepared(triples[:BUCKET])
    got = jax.jit(E.unpack_packed)(prep["packed"])
    want = E.unpack_packed_np(prep["packed"])
    names = ("ay", "a_sign", "ry", "r_sign", "s_nibs", "k_nibs")
    for name, g, w in zip(names, got, want):
        assert g.dtype == np.int32 and g.shape == w.shape, name
        assert (np.asarray(g) == w).all(), name
    assert set(want[1].tolist()) == {0, 1} and set(want[3].tolist()) == {0, 1}
    assert want[4].min() == -8 and want[4].max() == 7
    assert (want[0] >> 13 == 0).all() and (want[0][:, 19] < 256).all()


# ------------------------------------------------------------ the verdicts

@pytest.mark.parametrize("n", SIZES)
def test_one_array_entry_agrees_on_random_triples(served, n):
    triples = random_triples(n, seed=100 + n)
    got = served.verify_many(triples)
    assert got == six_argument_verdicts(triples)
    assert got == [E.verify_oracle(*t) for t in triples]
    assert got == [F._py_verify(*t) for t in triples]
    assert got == [K.raw_verify(*t) for t in triples]
    flipped = [i for i in range(n) if i % 8 == 5 or n == 1]
    assert not any(got[i] for i in flipped)
    assert sum(got) == n - len(flipped)


def test_one_array_entry_agrees_on_rfc8032_vectors(served):
    triples = [(bytes.fromhex(p), bytes.fromhex(s), bytes.fromhex(m))
               for (p, m, s) in RFC8032]
    # each vector once as published and once with the next one's message
    triples += [(p, s, triples[(i + 1) % 3][2])
                for i, (p, s, _m) in enumerate(triples)]
    want = [True] * 3 + [False] * 3
    assert served.verify_many(triples) == want
    assert six_argument_verdicts(triples) == want
    assert [E.verify_oracle(*t) for t in triples] == want
    assert [K.raw_verify(*t) for t in triples] == want


def test_one_array_entry_agrees_on_adversarial_vectors(served):
    triples = [(p, s, m) for (_l, p, s, m) in VECTORS]
    got = served.verify_many(triples)
    six = six_argument_verdicts(triples)
    for (label, *_), t, a, b in zip(VECTORS, triples, got, six):
        assert a == b == E.verify_oracle(*t) == F._py_verify(*t) \
            == K.raw_verify(*t), label
    assert any(got) and not all(got)


@needs_native
def test_python_prep_feeds_the_same_verdicts(served, monkeypatch):
    """SCT_NATIVE_PREP=0 end to end through the served path."""
    triples = random_triples(29, seed=7)
    want = served.verify_many(triples)
    monkeypatch.setenv("SCT_NATIVE_PREP", "0")
    assert served.verify_many(triples) == want
