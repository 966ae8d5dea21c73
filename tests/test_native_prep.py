"""Native (C) host-prep parity with the numpy/hashlib path.

The C module owns SHA-512, Barrett mod-L, canonicality prechecks and the
recode bias for the whole batch, written as one packed byte array (128
bytes a lane); any divergence from the Python path would change verify
verdicts, so parity is asserted byte for byte on the whole buffer and
verdict-for-verdict end to end.
"""

import os
import random

import numpy as np
import pytest

from stellar_core_tpu import native
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ops import ed25519 as E


pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native prep lib not buildable")


def _batch(n=200, seed=5):
    rnd = random.Random(seed)
    sks = [SecretKey.from_seed(bytes([i + 1] * 32)) for i in range(8)]
    pubs, sigs, msgs = [], [], []
    for i in range(n):
        sk = sks[i % 8]
        m = rnd.randbytes(rnd.randrange(0, 300))
        pubs.append(sk.public_key.key_bytes)
        sigs.append(sk.sign(m))
        msgs.append(m)
    # adversarial rows
    sigs[5] = sigs[5][:32] + (
        int.from_bytes(sigs[5][32:], "little") + E.L).to_bytes(32, "little")
    pubs[6] = (E.P + 3 | (1 << 255)).to_bytes(32, "little")
    sigs[7] = sigs[7][:20]
    msgs[8] = b""
    msgs[9] = rnd.randbytes(111)   # crosses first sha512 block exactly
    msgs[10] = rnd.randbytes(112)
    msgs[11] = rnd.randbytes(128 + 64)
    return pubs, sigs, msgs


def test_native_matches_numpy_prep(monkeypatch):
    pubs, sigs, msgs = _batch()
    monkeypatch.setenv("SCT_NATIVE_PREP", "0")
    ref = E.prepare_batch(pubs, sigs, msgs, size=256)
    monkeypatch.setenv("SCT_NATIVE_PREP", "1")
    nat = E.prepare_batch(pubs, sigs, msgs, size=256)
    assert (np.asarray(ref["pre_ok"]) == np.asarray(nat["pre_ok"])).all()
    assert not ref["pre_ok"][[5, 6, 7]].any() and ref["pre_ok"].sum() == 197
    assert nat["packed"].shape == (256, 128)
    assert nat["packed"].dtype == np.uint8
    assert nat["packed"].tobytes() == ref["packed"].tobytes()
    # a lane that failed a precheck, and padding, are all zero
    assert not nat["packed"][[5, 6, 7]].any()
    assert not nat["packed"][200:].any()


def test_native_mod_l_against_python_ints():
    """The Barrett reduction is the riskiest C path: cross-check k mod L
    against Python bignums on structured + random digests."""
    import hashlib
    pubs, sigs, msgs = _batch(64, seed=9)
    nat = E.prepare_batch(pubs, sigs, msgs)
    for i in range(64):
        if not nat["pre_ok"][i]:
            continue
        k = int.from_bytes(
            hashlib.sha512(sigs[i][:32] + pubs[i] + msgs[i]).digest(),
            "little") % E.L
        # the k field holds k + 0x88…88: nibble − 8 is the SIGNED
        # radix-16 digit in [−8, 8), and the digits' value is k exactly
        field = nat["packed"][i, 96:128]
        assert int.from_bytes(field.tobytes(), "little") == \
            k + E.RECODE_BIAS, i
        digs = E.bytes_to_nibs_np(field) - 8
        got = sum(int(digs[j]) << (4 * j) for j in range(64))
        assert got == k, i


def test_native_prep_feeds_kernel_correctly():
    """End-to-end: verdicts with native prep match the oracle."""
    pubs, sigs, msgs = _batch(48, seed=11)
    ok = E.verify_batch(pubs, sigs, msgs)
    want = [E.verify_oracle(p, s, m) for p, s, m in zip(pubs, sigs, msgs)]
    assert list(ok) == want
