"""Multi-chip sharding of the verify batch.

TPU-first design (SURVEY.md §2.3): consensus traffic between mutually
untrusting validators stays on TCP — collectives don't apply there. ICI
parallelism lives INSIDE the crypto backend: a verify batch is sharded
pure-data-parallel over the `dp` mesh axis (ed25519 verifies are
embarrassingly parallel — SURVEY.md §5 "long-context" note), XLA partitions
the kernel, and the only cross-chip traffic is the result gather.

No tensor/pipeline/sequence/expert axes exist in this domain: the model is
a fixed-function crypto pipeline per batch element, not a layered network —
so the mesh is 1-D. This module also provides the multi-chip "training
step" used by __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .device import verify_compile_options


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), ("dp",))


def sharded_verify_fn(mesh: Mesh):
    """jit-compiled batched ed25519 verify over the packed input
    (ops/ed25519.py: one (B, 128) uint8 array), batch axis and verdicts
    sharded over dp. Batch size must be a multiple of the mesh size."""
    from ..ops.ed25519 import verify_packed

    data = NamedSharding(mesh, P("dp"))

    @partial(jax.jit, in_shardings=data, out_shardings=data,
             compiler_options=verify_compile_options())
    def verify_batch_packed_dp(packed):
        return verify_packed(packed)

    return verify_batch_packed_dp


def multichip_verify(pubs, sigs, msgs, mesh: Optional[Mesh] = None):
    """End-to-end sharded verify: host prep → dp-sharded kernel → gather."""
    from ..ops.ed25519 import prepare_batch
    mesh = mesh or make_mesh()
    ndev = mesh.devices.size
    n = len(pubs)
    prep = prepare_batch(pubs, sigs, msgs, size=-(-n // ndev) * ndev)
    ok = np.asarray(sharded_verify_fn(mesh)(prep["packed"]))
    return ok[:n] & prep["pre_ok"]
