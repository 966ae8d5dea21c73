"""Verifier cockpit tests (ISSUE 6 tentpole).

Covers the VerifierStats aggregation layer (drain/bucket histograms,
queue depth, warmup + compile-cache observability), drain attribution
to the backend that actually served it, warmup tracer instants with
app-clock stamps, flight dumps on warmup failure / compile-cache
unavailability, the admin `verifier` endpoint, and the Prometheus
round-trip of the `verifier_*` series.
"""

import json
import os

import pytest

from stellar_core_tpu.crypto import keys as K
from stellar_core_tpu.crypto.batch_verifier import (
    CircuitBreaker, CpuSigVerifier, SigVerifier,
    TpuSigVerifier, VerifierContext, VerifierStats, make_verifier)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.util.metrics import MetricsRegistry, render_prometheus
from stellar_core_tpu.util.tracing import FlightRecorder, Tracer


def _triples(n, tag=b"cockpit"):
    out = []
    for i in range(n):
        sk = SecretKey.from_seed(bytes([i + 1] * 32))
        msg = tag + b"-%d" % i
        out.append((sk.public_key.key_bytes, sk.sign(msg), msg))
    return out


def _clear_verify_cache():
    with K._cache_lock:
        K._verify_cache.clear()


# --------------------------------------------------------------- aggregation

def test_cpu_drain_records_batch_shape_tags_and_stats():
    """CPU drains carry the same batch-shape telemetry as device drains
    (pad_waste structurally 0), so bucket-selection analysis sees ALL
    traffic (ISSUE 6 satellite)."""
    reg = MetricsRegistry()
    tr = Tracer()
    tr.enable()
    v = make_verifier("cpu", metrics=reg, tracer=tr)
    res = v.verify_many(_triples(5))
    assert all(res)
    j = v.stats.to_json()
    assert j["drains"]["by_backend"]["cpu"] == {
        "drains": 1, "sigs": 5, "pad_total": 0}
    assert j["drains"]["batch_size"]["count"] == 1
    assert j["drains"]["batch_size"]["max"] == 5
    assert j["drains"]["pad_waste"]["max"] == 0.0
    assert j["drains"]["occupancy_pct"]["min"] == 100.0
    span = [s for s in tr.spans() if s.name == "crypto.verify_many"][-1]
    assert span.tags["pad_waste"] == 0
    assert span.tags["occupancy_pct"] == 100.0
    assert span.tags["batches"] == 1
    # registry carries the same shape under verifier.*
    m = reg.to_json()
    assert m["verifier.drain.batch-size"]["count"] == 1
    assert m["verifier.drains.cpu"]["count"] == 1


def test_bucket_dispatch_histograms_and_occupancy():
    reg = MetricsRegistry()
    st = VerifierStats(metrics=reg)
    st.record_bucket_dispatch(128, 100, 28)
    st.record_bucket_dispatch(128, 64, 64)
    st.record_bucket_dispatch(512, 512, 0)
    j = st.to_json()
    b128 = j["buckets"]["128"]
    assert b128["drains"] == 2 and b128["sigs"] == 164
    assert b128["pad_waste_total"] == 92
    assert b128["occupancy_pct"]["min"] == 50.0
    assert b128["occupancy_pct"]["max"] == pytest.approx(78.125)
    assert j["buckets"]["512"]["occupancy_pct"]["max"] == 100.0
    m = reg.to_json()
    assert m["verifier.bucket.128.drains"]["count"] == 2
    assert m["verifier.bucket.512.pad-waste"]["max"] == 0.0


def test_fallback_drain_attributed_to_serving_backend():
    """A drain served by the CPU fallback (primary raising) is
    attributed to "cpu", never to the device backend — and the fallback
    span names the server (ISSUE 6 satellite: the boundary attributes
    drains to the engine that actually served them)."""

    class _FailingDevice(CpuSigVerifier):
        name = "tpu"

        def verify_many(self, triples):
            raise RuntimeError("injected device loss")

    reg = MetricsRegistry()
    tr = Tracer()
    tr.enable()
    stats = VerifierStats(metrics=reg, tracer=tr)
    ctx = VerifierContext(stats=stats, tracer=tr, metrics=reg)
    r = SigVerifier(_FailingDevice(ctx), fallback=CpuSigVerifier(ctx),
                    breaker=CircuitBreaker(threshold=2))
    _clear_verify_cache()
    res = r.verify_many(_triples(3))
    assert all(res)
    j = stats.to_json()
    assert "tpu" not in j["drains"]["by_backend"]
    assert j["drains"]["by_backend"]["cpu"]["sigs"] == 3
    span = [s for s in tr.spans() if s.name == "crypto.verify_fallback"][-1]
    assert span.tags["served_by"] == "cpu"
    assert reg.to_json()["verifier.drains.cpu"]["count"] == 1


def test_threaded_queue_depth_inflight_and_wait(monkeypatch):
    """Queue depth / inflight / queue-wait for the async path: enqueue
    raises the depth gauge, flush zeroes it and marks a batch in
    flight, completion updates the verifier.queue.wait timer."""
    import time

    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    _clear_verify_cache()
    reg = MetricsRegistry()
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    stats = VerifierStats(metrics=reg, now_fn=clock.now)
    v = SigVerifier(CpuSigVerifier(
        VerifierContext(stats=stats, metrics=reg)), clock=clock)
    triples = _triples(4, tag=b"queue")
    futs = []
    for i, (k, s, m) in enumerate(triples):
        from stellar_core_tpu.xdr import PublicKey
        futs.append(v.enqueue(PublicKey.ed25519(k), s, m))
        assert stats.queue["depth"] == i + 1
    assert reg.to_json()["verifier.queue.depth"]["value"] == 4
    clock.set_virtual_time(clock.now() + 2.5)   # queue-wait on app clock
    v.flush()
    assert stats.queue["depth"] == 0
    deadline = time.time() + 60
    while not all(f.done() for f in futs) and time.time() < deadline:
        clock.crank(False)
        time.sleep(0.002)
    assert all(f.done() for f in futs) and all(f.result() for f in futs)
    assert stats.queue["inflight"] == 0
    assert stats.queue["wait_last_max_ms"] >= 2500.0
    wait = reg.to_json()["verifier.queue.wait"]
    assert wait["count"] == 1 and wait["max"] >= 2.5


# ------------------------------------------------------ warmup observability

def _stub_warmup(v, cache_events):
    """Patch the kernel-compiling piece of warmup: each bucket 'compile'
    reports the JAX compilation-cache events listed for it (the way
    jax.monitoring would, on the compiling thread)."""
    from stellar_core_tpu.parallel import device

    def fake_compile(b):
        for ev in cache_events.get(b, ()):
            device._on_event("/jax/compilation_cache/" + ev)

    v._compile_bucket = fake_compile


def test_warmup_instants_stamps_and_cache_classification(tmp_path):
    """Warmup emits begin/bucket/end tracer instants, stamps per-bucket
    progress on the app clock, and classifies each bucket compile as a
    persistent-cache hit or miss from JAX's own cache events."""
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    clock.set_virtual_time(1000.0)
    reg = MetricsRegistry()
    tr = Tracer()
    tr.enable()
    v = TpuSigVerifier(VerifierContext(
        stats=VerifierStats(metrics=reg, tracer=tr, now_fn=clock.now)))
    v.BUCKETS = (128, 512)
    _stub_warmup(v, {128: ["compile_requests_use_cache", "cache_misses"],
                     512: ["compile_requests_use_cache", "cache_hits"]})
    v.warmup(wait=True)
    assert v._warmed
    w = v.ctx.stats.warmup_json()
    assert w["state"] == "done"
    assert w["planned"] == [128, 512]
    assert w["buckets"]["128"]["cache"] == "miss"
    assert w["buckets"]["512"]["cache"] == "hit"
    # app-clock stamps, not wall-clock
    assert w["begun_t"] == 1000.0
    assert all(b["t"] == 1000.0 for b in w["buckets"].values())
    cc = v.ctx.stats.compile_cache
    assert cc["enabled"] is True and cc["hits"] == 1 and cc["misses"] == 1
    names = [s.name for s in tr.spans()]
    assert names.count("verifier.warmup.bucket") == 2
    assert "verifier.warmup.begin" in names
    assert "verifier.warmup.end" in names
    # instants survive into the Chrome-trace export (and therefore into
    # flight dumps, which serialize the same ring)
    trace = tr.to_chrome_trace()
    assert any(e["name"] == "verifier.warmup.end" and e["ph"] == "i"
               for e in trace["traceEvents"])
    m = reg.to_json()
    assert m["verifier.warmup.state"]["value"] == 2      # done
    assert m["verifier.warmup.buckets-done"]["value"] == 2
    assert m["verifier.compile-cache.hit"]["count"] == 1
    assert m["verifier.compile-cache.miss"]["count"] == 1
    assert m["verifier.warmup.bucket-seconds"]["count"] == 2


def test_warmup_fast_compile_classifies_unknown_not_hit():
    """A compile faster than jax's persistence threshold is neither
    loaded nor written: it must classify 'unknown', never inflate the
    compile-cache hit counter (a node silently re-paying sub-threshold
    compiles every restart must not read as a healthy cache)."""
    reg = MetricsRegistry()
    v = TpuSigVerifier(VerifierContext(stats=VerifierStats(metrics=reg)))
    v.BUCKETS = (128,)
    _stub_warmup(v, {128: ["compile_requests_use_cache"]})
    v.warmup(wait=True)
    w = v.ctx.stats.warmup_json()
    assert w["state"] == "done"
    assert w["buckets"]["128"]["cache"] == "unknown"
    cc = v.ctx.stats.compile_cache
    assert cc["hits"] == 0 and cc["misses"] == 0 and cc["unknown"] == 1
    m = reg.to_json()
    assert m["verifier.compile-cache.hit"]["count"] == 0


def test_warmup_failure_dumps_flight(tmp_path):
    """A warmup failure marks the failure meter, sets the state gauge
    and leaves a flight dump; the caller that waited gets it raised."""
    reg = MetricsRegistry()
    tr = Tracer()
    tr.enable()
    fr = FlightRecorder(tr, metrics=reg, out_dir=str(tmp_path))
    v = TpuSigVerifier(VerifierContext(stats=VerifierStats(
        metrics=reg, tracer=tr, flight_recorder=fr)))
    v.BUCKETS = (128,)

    def boom(b):
        raise RuntimeError("no device")

    v._compile_bucket = boom
    with pytest.raises(RuntimeError, match="no device"):
        v.warmup(wait=True)
    assert not v._warmed
    assert v.ctx.stats.warmup["state"] == "failed"
    assert "no device" in v.ctx.stats.warmup["error"]
    m = reg.to_json()
    assert m["verifier.warmup.failure"]["count"] == 1
    assert m["verifier.warmup.state"]["value"] == 3      # failed
    dumps = [f for f in os.listdir(str(tmp_path))
             if "verify-warmup-failed" in f]
    assert len(dumps) == 1
    with open(os.path.join(str(tmp_path), dumps[0])) as fh:
        blob = json.load(fh)
    assert "no device" in blob["extra"]["error"]
    assert blob["extra"]["warmup"]["state"] == "failed"


def test_compile_cache_unavailable_dumps_flight(tmp_path):
    """A node compiling without a persistent cache marks a meter, emits
    a tracer instant and leaves a flight dump naming the error."""
    reg = MetricsRegistry()
    tr = Tracer()
    tr.enable()
    fr = FlightRecorder(tr, metrics=reg, out_dir=str(tmp_path))
    st = VerifierStats(metrics=reg, tracer=tr, flight_recorder=fr)
    st.set_compile_cache_dir(None)
    assert st.compile_cache["enabled"] is False
    assert "no persistent compile cache" in st.compile_cache["error"]
    m = reg.to_json()
    assert m["verifier.compile-cache.unavailable"]["count"] == 1
    assert m["verifier.compile-cache.enabled"]["value"] == 0
    assert any(s.name == "verifier.compile-cache.unavailable"
               for s in tr.spans())
    dumps = [f for f in os.listdir(str(tmp_path))
             if "compile-cache-unavailable" in f]
    assert len(dumps) == 1


# ----------------------------------------------------- endpoint + Prometheus

@pytest.fixture
def app():
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    cfg = Config.test_config(0, backend="cpu-resilient")
    a = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    a.start()
    yield a
    a.stop()


def _cmd(app, name, **params):
    return app.command_handler.handle_command(
        name, {k: str(v) for k, v in params.items()})


def test_admin_verifier_endpoint_live(app):
    """`verifier` returns per-bucket/drain histograms, warmup +
    compile-cache status, queue depth and breaker state for a live
    verifier (acceptance criterion)."""
    _clear_verify_cache()
    assert all(app.sig_verifier.verify_many(_triples(6, tag=b"live")))
    st, body = _cmd(app, "verifier")
    assert st == 200
    assert body["configured_backend"] == "cpu-resilient"
    assert body["verifier"] == "resilient"
    assert body["drains"]["by_backend"]["cpu"]["sigs"] == 6
    assert body["drains"]["occupancy_pct"]["count"] >= 1
    assert body["warmup"]["state"] == "idle"
    assert body["warmup"]["source"] is None     # warmup never ran
    # fleet rows (ISSUE 11) ride in the same blob: empty on a CPU-only
    # stack, but the keys are part of the endpoint contract
    assert body["devices"] == {}
    assert body["staging"]["chunks"] == 0
    assert body["staging"]["stalls"] == 0
    assert "compile_cache" in body
    assert body["queue"]["depth"] == 0
    assert body["breaker"]["state"] == "closed"
    assert body["counters"]["pending"] == 0
    assert "hits" in body["cache"]
    # the blob is JSON-serializable end to end (the HTTP layer would)
    json.dumps(body)


def test_verifier_gauges_prometheus_roundtrip(app):
    """The cockpit data appears as verifier_* series in
    metrics?format=prometheus (acceptance criterion), values matching
    the JSON export."""
    _clear_verify_cache()
    assert all(app.sig_verifier.verify_many(_triples(7, tag=b"prom")))
    st, text = _cmd(app, "metrics", format="prometheus")
    assert st == 200 and isinstance(text, str)
    values = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, _, val = line.rpartition(" ")
        values[name] = float(val)
    assert values["sct_verifier_drain_batch_size_count"] >= 1
    assert values["sct_verifier_drain_batch_size_max"] >= 7
    assert values["sct_verifier_drains_cpu_total"] >= 1
    assert values["sct_verifier_queue_depth"] == 0.0
    assert values["sct_verifier_warmup_state"] == 0.0
    assert values["sct_verifier_compile_cache_hit"] == 0.0
    assert values['sct_verifier_drain_occupancy_pct{quantile="0.5"}'] \
        == 100.0
    # JSON and Prometheus agree (same registry objects)
    st, m = _cmd(app, "metrics", filter="verifier.")
    assert st == 200
    assert m["verifier.drain.batch-size"]["count"] == \
        values["sct_verifier_drain_batch_size_count"]
