"""The flagship device model: the batched ed25519 verification graph.

In this framework the "model" executed on TPU is not a neural network but a
fixed-function cryptographic pipeline (SURVEY.md §2.2): point
decompression + double-scalar multiplication + projective equality over a
batch axis. This module packages it with the standard model-API surface
(build inputs, forward step, sharded step) so the driver and benchmarks
treat it like any other model family.
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

from ..crypto.keys import SecretKey
from ..ops import ed25519 as E


def make_example_batch(batch: int = 256, n_keys: int = 16,
                       corrupt_every: int = 0) -> Tuple[list, list, list]:
    """Deterministic signed batch for compile checks and benches."""
    sks = [SecretKey.from_seed(bytes([i + 1] * 32)) for i in range(n_keys)]
    pubs, sigs, msgs = [], [], []
    for i in range(batch):
        sk = sks[i % n_keys]
        m = b"bench-msg-%08d" % i
        s = bytearray(sk.sign(m))
        if corrupt_every and i % corrupt_every == corrupt_every - 1:
            s[i % 64] ^= 1
        pubs.append(sk.public_key.key_bytes)
        sigs.append(bytes(s))
        msgs.append(m)
    return pubs, sigs, msgs


def device_args(pubs: List[bytes], sigs: List[bytes],
                msgs: List[bytes]) -> tuple:
    """Host (numpy) arg tuple for the jittable forward step: the one
    packed (B, 128) uint8 array of the served verify entry. Staying on
    the host matters: materializing device arrays here would initialize
    the JAX backend inside the CALLER's process — and a compile-check
    harness probing `entry()` must decide for itself when (and whether)
    to touch a possibly-wedged device. jit accepts numpy directly."""
    return (E.prepare_batch(pubs, sigs, msgs)["packed"],)


def forward(packed):
    """The jittable forward step: (B, 128) uint8 → (B,) bool."""
    return E.verify_packed(packed)
