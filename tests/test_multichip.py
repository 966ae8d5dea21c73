"""Multi-chip sharding of the production verifier on the virtual CPU mesh.

The conftest forces an 8-device CPU platform, so these tests exercise the
same dp-sharded dispatch a v5e pod slice would use (VERDICT r2 #3: the
production TpuSigVerifier must use the mesh, not only the dryrun).
Reference analog: SURVEY.md §2.3 — verify batches shard pure
data-parallel over ICI; the only cross-chip traffic is the result gather.
"""

import jax
import pytest

from stellar_core_tpu.crypto.batch_verifier import (
    TpuSigVerifier, VerifierContext, VerifierStats)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ops.ed25519 import L, verify_oracle
from stellar_core_tpu.parallel.mesh import (
    make_mesh, multichip_verify, sharded_verify_fn,
)


def _batch(n, n_keys=4):
    sks = [SecretKey.from_seed(bytes([i + 1] * 32)) for i in range(n_keys)]
    pubs, sigs, msgs = [], [], []
    for i in range(n):
        sk = sks[i % n_keys]
        m = b"mc-%04d" % i
        pubs.append(sk.public_key.key_bytes)
        sigs.append(sk.sign(m))
        msgs.append(m)
    return pubs, sigs, msgs


@pytest.fixture(autouse=True)
def require_mesh():
    if jax.device_count() < 2:
        pytest.skip("needs the virtual multi-device CPU platform")


def test_production_verifier_uses_mesh_and_matches_oracle():
    pubs, sigs, msgs = _batch(50)
    # adversarial rows: bit flip, wrong message, non-canonical S, bad length
    sigs[7] = bytes([sigs[7][0] ^ 1]) + sigs[7][1:]
    msgs[11] = b"evil"
    s = int.from_bytes(sigs[13][32:], "little")
    sigs[13] = sigs[13][:32] + (s + L).to_bytes(32, "little")
    sigs[17] = sigs[17][:40]
    triples = list(zip(pubs, sigs, msgs))

    v = TpuSigVerifier(shard_threshold=1)
    got = v.verify_many(triples)
    want = [verify_oracle(*t) for t in triples]
    assert got == want
    # the sharded jit must actually have been taken on a multi-device host
    assert v._sharded_fn is not None
    assert v.batches_dispatched == 1  # 50 sigs -> one padded bucket


def test_multichip_verify_padding_not_multiple_of_mesh():
    # 13 items on an 8-device mesh: pads to 16, pad lanes masked out
    pubs, sigs, msgs = _batch(13)
    ok = multichip_verify(pubs, sigs, msgs, make_mesh())
    assert list(ok) == [True] * 13


def _device_args(pubs, sigs, msgs, pad_to=None):
    """The served contract: one packed (B, 128) uint8 array."""
    from stellar_core_tpu.ops.ed25519 import prepare_batch
    prep = prepare_batch(pubs, sigs, msgs, size=pad_to)
    assert prep["pre_ok"].all()
    return (prep["packed"],)


def test_weak_scaling_1_2_4_8_devices():
    """Weak scaling on the virtual mesh (VERDICT r4 weak #5): per-device
    batch held constant at 16 while the mesh grows 1->2->4->8. Asserts
    (a) exact oracle agreement at every mesh size and (b) near-constant
    per-device compiled work via XLA's cost model — the SPMD module each
    device runs must not grow with the mesh (flops(n)/flops(1) ~ 1), which
    is the compiler-level statement of weak scaling that noisy CPU wall
    timing can't make."""
    per_device = 16
    devices = jax.devices()
    flops_per_dev = {}
    for ndev in (1, 2, 4, 8):
        if len(devices) < ndev:
            pytest.skip("needs 8 virtual devices")
        n = per_device * ndev
        pubs, sigs, msgs = _batch(n)
        bad = {i for i in range(n) if i % 5 == 3}
        for i in bad:
            sigs[i] = bytes([sigs[i][0] ^ 1]) + sigs[i][1:]
        mesh = make_mesh(devices[:ndev])
        fn = sharded_verify_fn(mesh)
        args = _device_args(pubs, sigs, msgs)
        # AOT-compile once and execute THAT executable: running fn(*args)
        # and then lower().compile() separately loads two identical
        # executables per mesh (~25s each from the persistent cache on
        # CPU) — one is enough for both the verdicts and the cost model
        compiled = fn.lower(*args).compile()
        ok = list(map(bool, compiled(*args)))
        assert ok == [i not in bad for i in range(n)]
        # sample oracle agreement (full oracle over 240 sigs is slow)
        for i in (0, 3, n // 2, n - 1):
            assert ok[i] == verify_oracle(pubs[i], sigs[i], msgs[i])
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost and "flops" in cost:
            flops_per_dev[ndev] = cost["flops"]
    if len(flops_per_dev) >= 2:
        base = flops_per_dev[min(flops_per_dev)]
        for ndev, fl in flops_per_dev.items():
            assert fl <= base * 1.3 + 1e6, (
                "per-device work grew with the mesh: %r" % flops_per_dev)


def test_production_size_sharded_batch_with_uneven_tail():
    """8192-class batch through the PRODUCTION TpuSigVerifier on the mesh
    (VERDICT r4 weak #5): 8192 + 147 items -> one full sharded 8192 bucket
    plus an uneven 147 tail bucket; results must match the planted
    corruption pattern and a sampled oracle."""
    n = 8192 + 147
    pubs, sigs, msgs = _batch(n, n_keys=8)
    bad = {i for i in range(n) if i % 997 == 1}   # spread across both chunks
    for i in bad:
        sigs[i] = bytes([sigs[i][0] ^ 1]) + sigs[i][1:]
    v = TpuSigVerifier(VerifierContext(stats=VerifierStats()),
                       shard_threshold=1)
    got = v.verify_many(list(zip(pubs, sigs, msgs)))
    assert got == [i not in bad for i in range(n)]
    assert v.batches_dispatched == 2          # 8192 bucket + 147-tail bucket
    # one packed array a dispatch, 128 bytes a lane of its bucket
    assert v.ctx.stats.h2d_bytes == 128 * (8192 + 512)
    assert v.sigs_verified == n
    assert v._sharded_fn is not None          # mesh path actually taken
    for i in (0, 1, 8191, 8192, n - 1):       # sampled oracle agreement
        assert got[i] == verify_oracle(pubs[i], sigs[i], msgs[i])


def test_sharded_fn_equals_single_device_kernel():
    """The dp-sharded one-array entry against the six-argument kernel
    on one device, its arguments unpacked on the host the long way."""
    import numpy as np
    from stellar_core_tpu.ops.ed25519 import (
        unpack_packed_np, verify_batch_jit)

    pubs, sigs, msgs = _batch(16)
    sigs[3] = bytes([sigs[3][0] ^ 1]) + sigs[3][1:]
    packed, = _device_args(pubs, sigs, msgs)
    single = np.asarray(verify_batch_jit(*unpack_packed_np(packed)))
    out = sharded_verify_fn(make_mesh())(packed)
    # the one input is split over dp on its batch axis, 128 bytes a lane
    assert {s.data.shape for s in out.addressable_shards} == {(2,)}
    assert (single == np.asarray(out)).all()
    assert list(single) == [i != 3 for i in range(16)]


def test_graft_entry_returns_host_args_and_compiles():
    """__graft_entry__.entry() must stay device-free (numpy args) — the
    compile-check harness decides when to touch a device — and the
    returned fn must jit over those args with oracle-correct output."""
    import os
    import sys
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as graft
    fn, args = graft.entry()
    assert all(isinstance(a, np.ndarray) for a in args)
    out = np.asarray(jax.jit(fn)(*args))
    assert out.shape == (128,) and bool(out.all())
