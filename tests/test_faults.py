"""Fault-injection layer + graceful-degradation units (ISSUE 3).

Covers: the FaultInjector schedule semantics (seeded determinism,
probability/count/after), the device circuit breaker state machine under
a virtual clock (closed → open → half-open → closed, trip during a drain
still returns correct verify results), peer reconnect backoff with
decorrelated jitter, BasicWork retry jitter (two co-failed works fire on
different virtual ticks), ChaosTransport drop/delay/partition, the
ArchivePool failover policy, and the admin `faults` endpoint.
"""

import pytest

from stellar_core_tpu.crypto.batch_verifier import (
    CircuitBreaker, CpuSigVerifier, SigVerifier, make_verifier,
)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.util import rnd
from stellar_core_tpu.util.faults import FaultInjector, InjectedFault
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock


# ------------------------------------------------------------ FaultInjector

def test_fault_site_count_and_after():
    f = FaultInjector(seed=7)
    f.configure("x", count=2, after=3)
    fires = [f.should_fire("x") for _ in range(8)]
    # 3 skipped evaluations, then exactly 2 fires, then exhausted
    assert fires == [False, False, False, True, True, False, False, False]


def test_fault_probability_deterministic_per_seed():
    a = FaultInjector(seed=1)
    a.configure("site", probability=0.5)
    b = FaultInjector(seed=1)
    b.configure("site", probability=0.5)
    seq_a = [a.should_fire("site") for _ in range(64)]
    seq_b = [b.should_fire("site") for _ in range(64)]
    assert seq_a == seq_b
    assert any(seq_a) and not all(seq_a)
    c = FaultInjector(seed=2)
    c.configure("site", probability=0.5)
    assert [c.should_fire("site") for _ in range(64)] != seq_a


def test_fault_sites_independent_streams():
    """Adding a second site never perturbs the first site's schedule."""
    solo = FaultInjector(seed=3)
    solo.configure("a", probability=0.5)
    seq_solo = [solo.should_fire("a") for _ in range(32)]
    duo = FaultInjector(seed=3)
    duo.configure("a", probability=0.5)
    duo.configure("b", probability=0.5)
    seq_duo = []
    for _ in range(32):
        seq_duo.append(duo.should_fire("a"))
        duo.should_fire("b")
    assert seq_solo == seq_duo


def test_fault_spec_parsing_and_metrics():
    m = MetricsRegistry()
    f = FaultInjector(seed=0, metrics=m)
    f.configure_from_spec("device.dispatch:p=1,n=2; overlay.drop:p=0.25")
    assert f.should_fire("device.dispatch")
    assert f.should_fire("device.dispatch")
    assert not f.should_fire("device.dispatch")
    assert m.to_json()["fault.injected.device.dispatch"]["count"] == 2
    js = f.to_json()
    assert js["sites"]["overlay.drop"]["probability"] == 0.25
    with pytest.raises(ValueError):
        f.configure_from_spec("bad:q=1")
    # ISSUE 5: operator-facing spec rejects sites outside the F1
    # registry, so a typo'd SCT_FAULTS dies at startup instead of
    # soaking fault-free
    with pytest.raises(ValueError, match="unknown fault site"):
        f.configure_from_spec("device.dispach:p=1")


def test_fault_unconfigured_site_is_silent():
    f = FaultInjector()
    assert not f.should_fire("nope")
    f.fire_point("nope")            # no raise
    f.configure("boom")
    with pytest.raises(InjectedFault):
        f.fire_point("boom")


def test_fault_tags_active_span():
    from stellar_core_tpu.util.tracing import Tracer
    t = Tracer()
    t.enable()
    f = FaultInjector(tracer=t)
    f.configure("overlay.drop")
    with t.span("overlay.send", cat="overlay") as sp:
        assert f.should_fire("overlay.drop")
        assert sp.tags["fault"] == "overlay.drop"
    names = [s.name for s in t.spans()]
    assert "fault.overlay.drop" in names


# ------------------------------------------------------------ CircuitBreaker

def test_breaker_state_machine_virtual_clock():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    br = CircuitBreaker(threshold=3, cooldown_s=10.0, now_fn=clock.now)
    assert br.state == CircuitBreaker.CLOSED and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == CircuitBreaker.CLOSED      # below threshold
    assert br.record_failure()                    # third trips
    assert br.state == CircuitBreaker.OPEN and br.trips == 1
    assert not br.allow()
    clock.set_virtual_time(9.9)
    assert not br.allow()                         # still cooling down
    clock.set_virtual_time(10.0)
    assert br.allow()                             # half-open probe
    assert br.state == CircuitBreaker.HALF_OPEN
    # failed probe re-opens WITHOUT a new trip event
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN and br.trips == 1
    assert not br.allow()
    clock.set_virtual_time(20.0)
    assert br.allow()
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED
    assert br.recoveries == 1
    assert br.consecutive_failures == 0


def _signed_triples(n, bad=()):
    sks = [SecretKey.from_seed(bytes([i + 1] * 32)) for i in range(n)]
    triples = []
    for i, sk in enumerate(sks):
        msg = b"msg-%d" % i
        sig = sk.sign(msg)
        if i in bad:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        triples.append((sk.public_key, sig, msg))
    return triples


def test_trip_during_drain_returns_correct_results():
    """A dispatch failure mid-drain completes every future with the same
    accept/reject decisions the healthy path would produce."""
    from stellar_core_tpu.crypto import keys as _keys
    _keys.flush_verify_cache()
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    faults = FaultInjector()
    faults.configure("device.dispatch", count=1)
    v = make_verifier("cpu-resilient", clock, faults=faults,
                      breaker_threshold=1, breaker_cooldown=5.0)
    triples = _signed_triples(6, bad={2, 4})
    futs = [v.enqueue(k, s, m) for (k, s, m) in triples]
    v.flush()                                      # dispatch fails, trips
    assert [f.result() for f in futs] == [True, True, False, True, False,
                                          True]
    assert v.breaker.state == CircuitBreaker.OPEN
    assert v.breaker.trips == 1
    # while open, drains keep completing on the fallback
    _keys.flush_verify_cache()
    futs = [v.enqueue(k, s, m) for (k, s, m) in triples]
    v.flush()
    assert [f.result() for f in futs] == [True, True, False, True, False,
                                          True]
    # past the cooldown the half-open probe succeeds and re-closes
    clock.set_virtual_time(6.0)
    _keys.flush_verify_cache()
    futs = [v.enqueue(k, s, m) for (k, s, m) in triples]
    v.flush()
    assert all(f.done() for f in futs)
    assert v.breaker.state == CircuitBreaker.CLOSED
    assert v.breaker.recoveries == 1


def test_tpu_flush_recompletes_futures_on_dispatch_exception():
    """Satellite: a raising verify_many must not strand VerifyFutures."""
    from stellar_core_tpu.crypto import keys as _keys
    from stellar_core_tpu.crypto.batch_verifier import TpuSigVerifier
    _keys.flush_verify_cache()
    v = SigVerifier(TpuSigVerifier())

    def boom(triples):
        raise RuntimeError("device gone")

    v.inner.verify_many = boom
    triples = _signed_triples(4, bad={1})
    futs = [v.enqueue(k, s, m) for (k, s, m) in triples]
    v.flush()
    assert all(f.done() for f in futs)
    assert [f.result() for f in futs] == [True, False, True, True]


def test_resilient_prewarm_routes_through_breaker():
    from stellar_core_tpu.crypto import keys as _keys
    _keys.flush_verify_cache()
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    m = MetricsRegistry(now_fn=clock.now)
    faults = FaultInjector(metrics=m)
    faults.configure("device.dispatch", count=1)
    v = make_verifier("cpu-resilient", clock, metrics=m, faults=faults,
                      breaker_threshold=1, breaker_cooldown=5.0)
    triples = [(k.key_bytes, s, msg)
               for (k, s, msg) in _signed_triples(5, bad={0})]
    out = v.prewarm_many(triples)
    assert out == [False, True, True, True, True]
    assert v.breaker.trips == 1
    assert m.to_json()["crypto.breaker.trip"]["count"] == 1


# ------------------------------------------------- peer reconnect backoff

class _StubApp:
    def __init__(self):
        self.config = Config.test_config(0)
        self.config.KNOWN_PEERS = []
        self.config.PREFERRED_PEERS = []
        self.clock = VirtualClock(ClockMode.VIRTUAL_TIME)
        self.metrics = MetricsRegistry(now_fn=self.clock.now)


def test_peer_backoff_grows_jittered_and_resets():
    from stellar_core_tpu.overlay.peer_manager import (
        PeerManager, RECONNECT_BACKOFF_BASE, RECONNECT_BACKOFF_CAP)
    app = _StubApp()
    pm = PeerManager(app)
    delays = []
    for _ in range(12):
        pm.on_connect_failure("10.0.0.1", 11625)
        rec = pm.ensure_exists("10.0.0.1", 11625)
        delays.append(rec.next_attempt - app.clock.now())
    assert all(RECONNECT_BACKOFF_BASE <= d <= RECONNECT_BACKOFF_CAP
               for d in delays)
    # growth: late delays dwarf the first one; cap respected
    assert max(delays) > delays[0]
    # success resets the ladder
    pm.on_connect_success("10.0.0.1", 11625)
    rec = pm.ensure_exists("10.0.0.1", 11625)
    assert rec.num_failures == 0 and rec.last_backoff == 0.0
    # backed-off peers are not candidates until their next_attempt
    pm.on_connect_failure("10.0.0.1", 11625)
    assert pm.candidates_to_connect(5, []) == []


def test_peer_backoff_desynchronizes_two_peers():
    """Two peers failing at the same instants must not be retried at the
    same instant — the decorrelated jitter pulls them apart."""
    from stellar_core_tpu.overlay.peer_manager import PeerManager
    app = _StubApp()
    pm = PeerManager(app)
    for _ in range(4):
        pm.on_connect_failure("10.0.0.1", 1)
        pm.on_connect_failure("10.0.0.2", 2)
    a = pm.ensure_exists("10.0.0.1", 1).next_attempt
    b = pm.ensure_exists("10.0.0.2", 2).next_attempt
    assert a != b


# ------------------------------------------------- BasicWork retry jitter

def test_work_retries_fire_on_different_virtual_ticks():
    """Satellite: two works failing on the same crank must not re-fire on
    the same virtual tick (pure 2**retries re-fired them in sync)."""
    from stellar_core_tpu.work.basic_work import BasicWork, State

    clock = VirtualClock(ClockMode.VIRTUAL_TIME)

    class Flaky(BasicWork):
        def __init__(self, name):
            super().__init__(clock, name, max_retries=3)
            self.fails_left = 1
            self.run_times = []

        def on_run(self):
            self.run_times.append(clock.now())
            if self.fails_left > 0:
                self.fails_left -= 1
                return State.FAILURE
            return State.SUCCESS

    w1, w2 = Flaky("w1"), Flaky("w2")
    w1.start()
    w2.start()
    for _ in range(200):
        if w1.is_done() and w2.is_done():
            break
        for w in (w1, w2):
            if not w.is_done():
                w.crank_work()
        clock.crank(False)
    assert w1.state == State.SUCCESS and w2.state == State.SUCCESS
    # both failed on the same first tick...
    assert w1.run_times[0] == w2.run_times[0]
    # ...but their jittered retries landed on different virtual ticks
    assert w1.run_times[1] != w2.run_times[1]


# ------------------------------------------------------- ChaosTransport

def _chaos_pair(faults_a=None):
    from stellar_core_tpu.overlay.transport import (ChaosTransport,
                                                    LoopbackTransport)
    ca = VirtualClock(ClockMode.VIRTUAL_TIME)
    cb = VirtualClock(ClockMode.VIRTUAL_TIME)
    ta, tb = LoopbackTransport.pair(ca, cb)
    wa = ChaosTransport(ta, ca, faults=faults_a)
    wb = ChaosTransport(tb, cb, faults=None)
    got_a, got_b = [], []
    wa.on_frame = got_a.append
    wb.on_frame = got_b.append
    return ca, cb, wa, wb, got_a, got_b


def _crank_both(ca, cb, n=6):
    for _ in range(n):
        ca.crank(False)
        cb.crank(False)


def test_chaos_transport_drop_and_duplicate():
    f = FaultInjector()
    f.configure("overlay.drop", count=1)     # first frame eaten
    ca, cb, wa, wb, got_a, got_b = _chaos_pair(f)
    wa.send_frame(b"one")
    wa.send_frame(b"two")
    _crank_both(ca, cb)
    assert got_b == [b"two"]
    assert wa.dropped == 1
    f.configure("overlay.duplicate", count=1)
    wa.send_frame(b"three")
    _crank_both(ca, cb)
    assert got_b == [b"two", b"three", b"three"]


def test_chaos_transport_delay_and_reorder():
    f = FaultInjector()
    f.configure("overlay.reorder", count=1)
    ca, cb, wa, wb, got_a, got_b = _chaos_pair(f)
    wa.send_frame(b"a")          # held
    wa.send_frame(b"b")          # b rides first, a follows
    _crank_both(ca, cb)
    assert got_b == [b"b", b"a"]
    f.configure("overlay.delay", count=1)
    wa.send_frame(b"c")          # delayed by delay_s of virtual time
    ca.crank_ready()
    cb.crank(False)
    assert got_b == [b"b", b"a"]
    _crank_both(ca, cb)          # advances past the delay timer
    assert got_b == [b"b", b"a", b"c"]


def test_chaos_transport_partition_and_heal():
    ca, cb, wa, wb, got_a, got_b = _chaos_pair()
    wa.send_frame(b"pre")
    _crank_both(ca, cb)
    assert got_b == [b"pre"]
    wa.set_partitioned(True)
    wb.set_partitioned(True)
    wa.send_frame(b"lost")
    wb.send_frame(b"lost-too")
    _crank_both(ca, cb)
    assert got_b == [b"pre"] and got_a == []
    wa.set_partitioned(False)
    wb.set_partitioned(False)
    wa.send_frame(b"post")
    _crank_both(ca, cb)
    assert got_b == [b"pre", b"post"]


# ------------------------------------------------- ItemFetcher give-up

def test_item_fetcher_gives_up_and_counts():
    from stellar_core_tpu.overlay.item_fetcher import (GIVEUP_REBUILDS,
                                                       ItemFetcher)

    class _Overlay:
        def __init__(self):
            self.app = _StubApp()

        def authenticated_peer_ids(self):
            return []

        def get_peer(self, pid):
            return None

    ov = _Overlay()
    fetcher = ItemFetcher(ov, lambda h: None)
    fetcher.fetch(b"\x01" * 32)
    clock = ov.app.clock
    for _ in range(GIVEUP_REBUILDS * 3):
        if not fetcher.trackers:
            break
        clock.crank(False)
    assert fetcher.num_fetching() == 0
    assert ov.app.metrics.to_json()[
        "overlay.item-fetcher.giveup"]["count"] == 1


# ------------------------------------------------------- ArchivePool

def test_archive_pool_failover_and_health():
    from stellar_core_tpu.history.archive import ArchivePool, HistoryArchive
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    a = HistoryArchive("a", get_tmpl="true {0} {1}")
    b = HistoryArchive("b", get_tmpl="true {0} {1}")
    pool = ArchivePool([a, b], now_fn=clock.now)
    first = pool.pick()
    assert first is not None
    # a failure backs the archive off and failover picks the other
    pool.report_failure(first)
    other = pool.pick()
    assert other.name != first.name
    assert pool.failovers == 1
    # excluding both still returns SOMETHING (liveness over politeness)
    assert pool.pick(exclude=["a", "b"]) is not None
    # backoff expires on the virtual clock
    clock.set_virtual_time(1000.0)
    pool.report_success(first)
    assert pool.health(first.name).consecutive_failures == 0
    # healthier archive wins the pick
    pool.report_failure(other)
    clock.set_virtual_time(2000.0)
    assert pool.pick().name == first.name


# ------------------------------------------------------- admin endpoint

def test_admin_faults_endpoint():
    from stellar_core_tpu.main.application import Application
    cfg = Config.test_config(41, backend="cpu-resilient")
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    ch = app.command_handler
    st, body = ch.handle_command("faults", {})
    assert st == 200 and body["sites"] == {}
    assert body["verify_breaker"]["state"] == "closed"
    st, body = ch.handle_command(
        "faults", {"action": "set", "site": "overlay.drop", "p": "0.5",
                   "n": "3", "after": "1"})
    assert st == 200
    assert body["sites"]["overlay.drop"]["remaining"] == 3
    assert app.faults.configured()
    st, body = ch.handle_command("faults",
                                 {"action": "clear", "site": "overlay.drop"})
    assert st == 200 and body["sites"] == {}
    st, body = ch.handle_command("faults", {"action": "bogus"})
    assert "error" in body


def test_config_and_env_arm_faults(monkeypatch):
    from stellar_core_tpu.main.application import Application
    monkeypatch.setenv("SCT_FAULTS", "archive.get-fail:n=2")
    monkeypatch.setenv("SCT_FAULTS_SEED", "9")
    cfg = Config.test_config(42)
    cfg.FAULTS = {"overlay.drop": {"p": 0.5, "n": 4}}
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    js = app.faults.to_json()
    assert js["seed"] == 9
    assert js["sites"]["overlay.drop"]["probability"] == 0.5
    assert js["sites"]["archive.get-fail"]["remaining"] == 2


def test_config_faults_table_rejects_unknown_site():
    """ISSUE 5: the config-file arming path validates against the F1
    registry like the env spec and the admin endpoint — a typo'd FAULTS
    table kills the node at startup instead of soaking fault-free."""
    from stellar_core_tpu.main.application import Application
    cfg = Config.test_config(43)
    cfg.FAULTS = {"device.dispach": {"p": 1.0}}
    with pytest.raises(ValueError, match="unknown fault site"):
        Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
