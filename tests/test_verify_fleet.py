"""Multi-device verify fleet tests (ISSUE 11 tentpole).

Covers the sharded drain scheduler on forced host device counts
(N=1/2/4 sub-meshes of the conftest's virtual 8-device CPU platform):
result equality vs the single-device path, per-device drain attribution
in VerifierStats, the double-buffered staging overlap measurement, the
cockpit-driven warm-start plan (derivation pinned to the histograms,
persistence beside the XLA cache, round-trip through warmup), and the
per-device breaker ring that degrades a sick chip to an N-1 mesh
instead of an all-CPU fallback.

Real-kernel tests stick to bucket 128 sub-mesh shapes (the shapes the
multichip suite and the graft entry already compile, so the persistent
XLA cache keeps them cheap); scheduler-logic tests stub the dispatch
and staging layers and never touch a device.
"""

import json
import os

import numpy as np
import pytest

from stellar_core_tpu.crypto.batch_verifier import (
    DeviceFleetHealth, TpuSigVerifier, VerifierContext, VerifierStats,
    warmup_plan)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ops.ed25519 import verify_oracle
from stellar_core_tpu.util.faults import FaultInjector
from stellar_core_tpu.util.metrics import MetricsRegistry


def _batch(n, n_keys=6, tag=b"fleet"):
    sks = [SecretKey.from_seed(bytes([i + 1] * 32)) for i in range(n_keys)]
    out = []
    for i in range(n):
        sk = sks[i % n_keys]
        m = tag + b"-%04d" % i
        out.append((sk.public_key.key_bytes, sk.sign(m), m))
    return out


def _corrupt(triples, idxs):
    for i in idxs:
        k, s, m = triples[i]
        triples[i] = (k, bytes([s[0] ^ 1]) + s[1:], m)
    return triples


# ------------------------------------------------------------- real kernel


@pytest.fixture
def devices():
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs the virtual multi-device CPU platform")
    return jax.devices()


# one live verifier per mesh size for the whole module: the jit fns it
# holds stay warm in-memory, so the second real-kernel test doesn't
# re-pay the persistent-cache executable load (~15s per mesh on CPU)
_FLEET_CACHE = {}


def _fleet_verifier(devices, ndev, stats=None):
    v = _FLEET_CACHE.get(ndev)
    if v is None:
        v = TpuSigVerifier(shard_threshold=1, devices=devices[:ndev])
        v.BUCKETS = (128,)
        _FLEET_CACHE[ndev] = v
    v.ctx.stats = stats     # the cached engine's context, this test's cockpit
    return v


def test_sharded_drain_result_equality_n1_n2_n4(devices):
    """Acceptance pin: the same batch mix through 1-, 2- and 4-device
    fleets produces bit-identical results, matching the oracle on the
    planted corruption pattern."""
    triples = _corrupt(_batch(100), {3, 41, 97})
    want = [i not in {3, 41, 97} for i in range(100)]
    got = {}
    for ndev in (1, 2, 4):
        v = _fleet_verifier(devices, ndev)
        got[ndev] = v.verify_many(triples)
        assert got[ndev] == want, "wrong verdicts on %d device(s)" % ndev
        if ndev > 1:
            # the mesh path was actually taken, once, at bucket 128
            assert tuple(range(ndev)) in v._mesh_fns
            assert v.batches_dispatched == 1
    assert got[1] == got[2] == got[4]
    # sampled oracle agreement (full oracle over 100 sigs is slow)
    for i in (0, 3, 50, 99):
        assert got[4][i] == verify_oracle(*triples[i])


def test_per_device_drain_attribution(devices):
    """A sharded dispatch lands per-device rows in VerifierStats: every
    participating device counts its lanes, real sigs + pad split lane
    boundaries exactly, and the registry carries the dynamic
    verifier.device.<i>.* series."""
    reg = MetricsRegistry()
    st = VerifierStats(metrics=reg)
    v = _fleet_verifier(devices, 4, stats=st)
    triples = _batch(100)
    assert all(v.verify_many(triples))
    j = st.to_json()
    assert sorted(j["devices"]) == ["0", "1", "2", "3"]
    # 128-bucket over 4 devices: 32 lanes each; 100 real sigs split
    # 32+32+32+4, pad 0+0+0+28
    assert [j["devices"][str(i)]["sigs"] for i in range(4)] == \
        [32, 32, 32, 4]
    assert [j["devices"][str(i)]["pad_total"] for i in range(4)] == \
        [0, 0, 0, 28]
    assert all(j["devices"][str(i)]["drains"] == 1 for i in range(4))
    assert all(j["devices"][str(i)]["inflight"] == 0 for i in range(4))
    m = reg.to_json()
    assert m["verifier.device.0.drains"]["count"] == 1
    assert m["verifier.device.3.inflight"]["value"] == 0
    # the drain is attributed to the tpu backend once, not per device
    assert j["drains"]["by_backend"]["tpu"]["drains"] == 1
    assert j["drains"]["by_backend"]["tpu"]["sigs"] == 100


@pytest.mark.parametrize("ndev", [1, 4])
def test_one_dispatch_hands_the_device_one_packed_array(devices, ndev):
    """The input contract at the boundary (ISSUE 26): a dispatch stages
    ONE uint8 array of 128 × bucket bytes and launches with it as the
    only argument, on the single-device and the dp-sharded route."""
    st = VerifierStats(metrics=MetricsRegistry())
    v = _fleet_verifier(devices, ndev, stats=st)
    puts, calls = [], []
    real_put, real_single, real_mesh = \
        v._device_arg, v._single_fn, v._mesh_fn

    def device_arg(packed, idxs):
        puts.append((packed.dtype, packed.shape, idxs))
        return real_put(packed, idxs)

    def counting(fn):
        def call(*args):
            calls.append(args)
            return fn(*args)
        return call

    v._device_arg = device_arg
    v._single_fn = lambda: counting(real_single())
    v._mesh_fn = lambda idxs: (counting(real_mesh(idxs)[0]),
                               real_mesh(idxs)[1])
    try:
        triples = _corrupt(_batch(100), {7})
        assert v.verify_many(triples) == [i != 7 for i in range(100)]
    finally:
        del v._device_arg, v._single_fn, v._mesh_fn
    assert puts == [(np.uint8, (128, 128), tuple(range(ndev)))]
    assert len(calls) == 1 and len(calls[0]) == 1
    arg, = calls[0]
    assert arg.dtype == np.uint8 and arg.nbytes == 128 * 128
    # one transfer: committed to the device, or the lanes split over dp
    # on the batch axis, 128 bytes each
    assert {s.data.shape for s in arg.addressable_shards} == \
        {(128 // ndev, 128)}
    assert len(arg.addressable_shards) == ndev
    assert st.h2d_bytes == 128 * 128
    assert st.metrics.to_json()["verifier.h2d.bytes"]["count"] == 128 * 128
    assert v.verify_many(triples[:3]) == [True] * 3
    assert st.h2d_bytes == 2 * 128 * 128


# --------------------------------------------------- scheduler logic (stubs)


class _StubbedFleet(TpuSigVerifier):
    """TpuSigVerifier with the jax layers stubbed out: routing, staging
    hand-off, per-device accounting and breaker logic run for real; the
    'device' is a host-side echo with an optional per-dispatch delay."""

    def __init__(self, n_devices, dispatch_sleep_s=0.0, stage_sleep_s=0.0,
                 **kw):
        super().__init__(devices=list(range(n_devices)), **kw)
        self._dispatch_sleep_s = dispatch_sleep_s
        self._stage_sleep_s = stage_sleep_s
        self._devices = list(range(n_devices))   # skip the jax resolve
        self._fleet_health = DeviceFleetHealth(
            n_devices, threshold=self._dev_threshold,
            cooldown_s=self._dev_cooldown, now_fn=self._now, owner=self)
        self._platform = "stub"

    class _Lazy:
        """Defers the 'device work' to the consumer's np.asarray, like a
        real async dispatch would."""

        def __init__(self, arr, sleep_s):
            self.arr = arr
            self.sleep_s = sleep_s

        def __array__(self, dtype=None):
            import time
            if self.sleep_s:
                time.sleep(self.sleep_s)
            return self.arr

    def _mesh_fn(self, idxs):
        self._mesh_fns.setdefault(idxs, (None, None))
        return (lambda packed: self._Lazy(np.ones(len(packed), bool),
                                          self._dispatch_sleep_s)), None

    def _single_fn(self):
        return lambda packed: self._Lazy(np.ones(len(packed), bool),
                                         self._dispatch_sleep_s)

    def _stage_chunk(self, chunk, route):
        import time
        from stellar_core_tpu.ops.ed25519 import prepare_batch
        if self._stage_sleep_s:
            time.sleep(self._stage_sleep_s)
        fn, b, idxs = route
        prep = prepare_batch([t[0] for t in chunk], [t[1] for t in chunk],
                             [t[2] for t in chunk], size=b)
        # the host array stands in for the device's: same contract
        return {"arg": prep["packed"], "pre_ok": prep["pre_ok"],
                "n": len(chunk), "b": b, "fn": fn, "idxs": idxs}


def test_staging_overlap_double_buffer():
    """The double-buffer path: a multi-chunk drain packs chunk K+1 on
    the staging worker while the 'device' runs chunk K, and the overlap
    is measured into the verifier.staging.overlap-pct gauge (>0: the
    windows genuinely ran concurrently)."""
    reg = MetricsRegistry()
    st = VerifierStats(metrics=reg)
    v = _StubbedFleet(1, dispatch_sleep_s=0.05, stage_sleep_s=0.03,
                      ctx=VerifierContext(stats=st))
    v.BUCKETS = (128,)
    triples = _batch(128 * 3)           # 3 chunks -> 2 staged overlaps
    assert all(v.verify_many(triples))
    j = st.to_json()
    assert j["staging"]["chunks"] == 2
    assert j["staging"]["stalls"] == 0
    assert j["staging"]["staged_s"] > 0
    # the staging windows overlapped the device-wait windows: with a
    # 50 ms device dispatch and a 30 ms stage, overlap is most of the
    # staged time — assert the direction, not the exact ratio
    assert j["staging"]["overlap_s"] > 0
    assert j["staging"]["last_overlap_pct"] > 0
    assert reg.to_json()["verifier.staging.overlap-pct"]["value"] > 0


def test_staging_stall_fault_degrades_to_synchronous():
    """verify.staging-stall: the staging worker raises, the chunk is
    re-staged synchronously, the drain still completes correctly and
    the stall is counted."""
    reg = MetricsRegistry()
    st = VerifierStats(metrics=reg)
    faults = FaultInjector(seed=7, metrics=reg)
    faults.configure("verify.staging-stall", count=1)
    v = _StubbedFleet(1, ctx=VerifierContext(stats=st, faults=faults))
    v.BUCKETS = (128,)
    triples = _batch(128 * 2)
    assert all(v.verify_many(triples))
    j = st.to_json()
    assert j["staging"]["stalls"] == 1
    m = reg.to_json()
    assert m["verifier.staging.stall"]["count"] == 1
    assert m["fault.injected.verify.staging-stall"]["count"] == 1


def test_device_lost_trips_per_device_and_degrades_to_n_minus_1():
    """verify.device-lost: repeated losses of one chip trip ITS breaker
    (not the backend breaker) — subsequent drains run on the N-1 mesh,
    results stay correct, and the per-device breaker telemetry records
    the trip."""
    reg = MetricsRegistry()
    st = VerifierStats(metrics=reg)
    clock = {"t": 1000.0}
    faults = FaultInjector(seed=7, metrics=reg)
    faults.configure("verify.device-lost", count=2)
    v = _StubbedFleet(4, ctx=VerifierContext(stats=st, faults=faults),
                      now_fn=lambda: clock["t"],
                      device_breaker_threshold=2,
                      device_breaker_cooldown=30.0)
    v.BUCKETS = (128,)
    v.SHARD_MIN_BATCH = 1
    triples = _batch(64)
    for _ in range(3):
        assert all(v.verify_many(triples))
    health = v.fleet_health
    # device 0 (first healthy at both fires) accumulated 2 failures ->
    # tripped; the other three keep serving
    assert health.breakers[0].state == "open"
    assert health.breakers[0].trips == 1
    assert all(health.breakers[i].state == "closed" for i in (1, 2, 3))
    # drain 3 ran on the degraded 3-device mesh
    assert (1, 2, 3) in v._mesh_fns
    m = reg.to_json()
    assert m["verifier.device.trip"]["count"] == 1
    assert m["verifier.device.0.breaker"]["value"] == 1      # open
    assert m["fault.injected.verify.device-lost"]["count"] == 2
    # per-device attribution: the lost chip served no drain, the
    # surviving three served all of them
    j = st.to_json()
    assert "0" not in j["devices"]
    assert j["devices"]["1"]["drains"] == 3

    # recovery: past the cooldown the breaker half-opens, the device
    # rejoins the mesh, and one clean drain re-closes it
    clock["t"] += 31.0
    assert all(v.verify_many(triples))
    assert health.breakers[0].state == "closed"
    assert health.breakers[0].recoveries == 1
    m2 = reg.to_json()
    assert m2["verifier.device.recover"]["count"] == 1
    assert m2["verifier.device.0.breaker"]["value"] == 0
    assert st.to_json()["devices"]["0"]["drains"] == 1


def test_fleet_dispatch_failure_counts_every_participant():
    """A whole-mesh dispatch failure cannot name the guilty chip: every
    participating device's breaker counts it, and the exception still
    reaches the boundary above."""
    v = _StubbedFleet(2, ctx=VerifierContext(stats=VerifierStats()))
    v.BUCKETS = (128,)
    v.SHARD_MIN_BATCH = 1

    def boom(idxs):
        def fn(*args):
            raise RuntimeError("mesh dispatch died")
        return fn, None

    v._mesh_fn = boom
    with pytest.raises(RuntimeError):
        v.verify_many(_batch(16))
    assert [br.consecutive_failures for br in v.fleet_health.breakers] \
        == [1, 1]


# ------------------------------------------------- cockpit-driven warm start


def test_warmup_plan_pinned_to_cockpit_histograms():
    """The warm-start bucket set is provably derived from the cockpit
    histograms: device bucket dispatch counts + CPU drain sizes mapped
    onto the candidate ladder, hottest first; a mostly-padding bucket
    pulls in the next smaller shape; no evidence falls back to the full
    ladder."""
    candidates = (128, 512, 2048, 8192)
    # no stats / no traffic -> default full ladder
    assert warmup_plan(None, candidates) == (
        [128, 512, 2048, 8192], {"source": "default",
                                 "reason": "no cockpit stats"})
    st = VerifierStats()
    assert warmup_plan(st, candidates)[1]["source"] == "default"
    # device traffic: 3 drains into 512; CPU traffic: 5 drains of ~100
    # sigs (fit 128) recorded through record_drain, pad-free
    for _ in range(3):
        st.record_bucket_dispatch(512, 500, 12)
    for _ in range(5):
        st.record_drain("cpu", 100)
    buckets, info = warmup_plan(st, candidates)
    assert info["source"] == "cockpit"
    assert buckets == [128, 512]         # hottest (5 drains) first
    assert info["traffic"] == {128: 5, 512: 3}


def test_warmup_plan_low_occupancy_bucket_pulls_in_smaller_shape():
    """A mostly-padding bucket (median occupancy < 50%) pulls in the
    next smaller candidate so dispatch can split down without a cold
    compile."""
    st = VerifierStats()
    st.record_bucket_dispatch(2048, 300, 1748)   # occupancy ~14.6%
    buckets, info = warmup_plan(st, (128, 512, 2048, 8192))
    assert buckets == [2048, 512]
    assert info["low_occupancy_extra"] == [512]


def test_warmup_plan_dedups_low_occupancy_extras():
    st = VerifierStats()
    st.record_bucket_dispatch(2048, 100, 1948)   # occupancy ~4.9%
    st.record_drain("cpu", 400)                  # 512 already chosen
    buckets, info = warmup_plan(st, (128, 512, 2048))
    assert buckets == [512, 2048]                # 512 not appended twice
    assert info["low_occupancy_extra"] == []


def test_warmup_plan_persisted_and_used(tmp_path):
    """save_warmup_plan writes the cockpit plan to the node's plan path;
    a fresh verifier on the same path warms exactly that set and stamps
    source=cockpit (the warm-restart contract)."""
    plan_path = str(tmp_path / TpuSigVerifier.PLAN_BASENAME)
    st = VerifierStats()
    for _ in range(4):
        st.record_bucket_dispatch(512, 512, 0)
    v = TpuSigVerifier(VerifierContext(stats=st))
    v.warmup_plan_path = plan_path
    assert v.save_warmup_plan() == plan_path
    path = plan_path
    with open(path) as fh:
        blob = json.load(fh)
    assert blob["buckets"] == [512]
    assert blob["traffic"] == {"512": 4}

    # fresh process analog: same plan path, no cockpit history
    v2 = TpuSigVerifier(VerifierContext(stats=VerifierStats()))
    v2.warmup_plan_path = plan_path
    compiled = []
    v2._compile_bucket = compiled.append
    v2.warmup(wait=True)
    assert compiled == [512]
    w = v2.ctx.stats.warmup_json()
    assert w["state"] == "done"
    assert w["source"] == "cockpit"
    assert w["planned"] == [512]

    # a plan that no longer fits the candidate ladder is rejected
    v3 = TpuSigVerifier(VerifierContext(stats=VerifierStats()))
    v3.warmup_plan_path = plan_path
    v3.BUCKETS = (128, 2048)
    compiled3 = []
    v3._compile_bucket = compiled3.append
    v3.warmup(wait=True)
    assert compiled3 == [128, 2048]
    assert v3.ctx.stats.warmup_json()["source"] == "default"


def test_warmup_plan_not_saved_without_evidence_or_path(tmp_path):
    v = TpuSigVerifier()
    v.warmup_plan_path = str(tmp_path / "plan.json")
    assert v.save_warmup_plan() is None          # no stats at all
    v.ctx.stats = VerifierStats()
    assert v.save_warmup_plan() is None          # stats but no traffic
    v.ctx.stats.record_bucket_dispatch(512, 512, 0)
    v.warmup_plan_path = None                    # no node state dir
    assert v.save_warmup_plan() is None


def test_warmup_plan_lives_beside_the_bucket_directory(tmp_path):
    """A node keeps its plan with its own state — beside its bucket
    directory — never in the compile cache, which may be shared."""
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                      Config.test_config(0, backend="tpu-async"))
    dev = app.sig_verifier.inner
    assert dev.warmup_plan_path is None          # no buckets, no plan
    app.enable_buckets(str(tmp_path / "node" / "buckets"))
    assert dev.warmup_plan_path == \
        str(tmp_path / "node" / "warmup_buckets.json")
    dev.ctx.stats.record_bucket_dispatch(512, 512, 0)
    app.stop()                                   # persists the plan
    assert os.path.exists(dev.warmup_plan_path)


def test_unbucketed_drain_sizes_feed_bucket_traffic():
    """CPU drains (no device bucketing) are quantized and mapped onto
    the candidate ladder — the 'CPU drains included' half of the
    selection evidence; device drains (bucketed=True) don't double
    count."""
    st = VerifierStats()
    st.record_drain("cpu", 3)
    st.record_drain("cpu", 100)
    st.record_drain("cpu", 129)          # -> 256 -> candidate 512
    st.record_drain("tpu", 5000, pad=120, splits=2, bucketed=True)
    assert st.drain_sizes == {"cpu": {4: 1, 128: 1, 256: 1}}
    assert st.bucket_traffic((128, 512)) == {128: 2, 512: 1}


# ------------------------------------------------------------ fleet health


def test_device_fleet_health_gauge_sync_and_json():
    reg = MetricsRegistry()
    st = VerifierStats(metrics=reg)

    class _Owner:
        ctx = VerifierContext(stats=st)

    h = DeviceFleetHealth(2, threshold=1, cooldown_s=5.0,
                          now_fn=lambda: 0.0, owner=_Owner())
    assert h.healthy() == [0, 1]
    assert h.record_failure(1) is True           # threshold 1: trips
    assert h.healthy() == [0]
    j = h.to_json()
    assert j["devices"]["1"]["state"] == "open"
    assert reg.to_json()["verifier.device.1.breaker"]["value"] == 1
    assert reg.to_json()["verifier.device.trip"]["count"] == 1
