"""The streamed checkpoint drain (ISSUE 30): SigVerifier.open_drain and
ApplyCheckpointWork's close gate.

Two engines stand in for the device: a fake whose chunks land on
command (or slowly) and count every triple they are handed, and the
jax-CPU TpuSigVerifier at the 32-lane bucket. The archive is one
16-ledger checkpoint whose senders arm two more signers each at ledger
4: a replay's first feed misses the master-key triples, and the
re-collection after ledger 4 the signers' (the two drains of the
benchmark's multisig cell, small).
"""

import hashlib
import os
import threading
import time

import pytest

from stellar_core_tpu.catchup import CatchupConfiguration
from stellar_core_tpu.crypto import keys as K
from stellar_core_tpu.crypto.batch_verifier import (
    CpuSigVerifier, SigVerifier, TpuSigVerifier,
    VerifierContext, VerifierStats)
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.history.archive import HistoryArchive
from stellar_core_tpu.main.application import Application
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.util.tracing import Tracer
from stellar_core_tpu.work.basic_work import State

FREQ = 16
TIP = FREQ - 1
SENDERS = 6
WORKER = "catchup.prewarm-pipeline"


def signed_triples(n, tag=b"drain"):
    sk = SecretKey.from_seed(hashlib.sha256(tag).digest())
    out = []
    for i in range(n):
        msg = b"%s-%d" % (tag, i)
        out.append((sk.public_key.key_bytes, sk.sign(msg), msg))
    return out


def pipeline_threads():
    return [t for t in threading.enumerate()
            if t.name == WORKER and t.is_alive()]


class FakeDevice:
    """An engine as the boundary sees one. Verifies for real (so a
    corrupted signature is refused), counts what it is handed and on
    which thread, and lands a chunk only once `allow`ed when `hold`."""

    name = "tpu"
    wants_prewarm = True
    PLAN_BASENAME = "warmup_buckets.json"
    _fleet_health = None

    def __init__(self, ctx=None, lanes=8, hold=False, delay=0.0):
        self.ctx = ctx if ctx is not None else VerifierContext(
            cache=K.VerdictCache(), stats=VerifierStats())
        self.BUCKETS = (lanes,)
        self.calls = []         # (thread name, [triples])
        self.batches_dispatched = 0
        self.sigs_verified = 0
        self.delay = delay
        self.raise_on = ()      # call indices that raise
        self._permits = threading.Semaphore(0)
        self.hold = hold
        self.entered = threading.Semaphore(0)

    def allow(self, n=1):
        for _ in range(n):
            self._permits.release()

    def verify_many(self, triples):
        idx = len(self.calls)
        self.calls.append((threading.current_thread().name, list(triples)))
        self.entered.release()
        if self.hold:
            assert self._permits.acquire(timeout=20), "never allowed"
        if self.delay:
            time.sleep(self.delay)
        if idx in self.raise_on:
            raise RuntimeError("planted: chunk %d" % idx)
        self.batches_dispatched += 1
        self.sigs_verified += len(triples)
        return K.raw_verify_batch(triples)

    def warmup(self, wait=False):
        pass

    def save_warmup_plan(self):
        return None

    def sizes(self):
        return [len(t) for _n, t in self.calls]


def cached(verifier, triple):
    verdicts, _misses = verifier._cache_probe([triple])
    return verdicts[0]


# ------------------------------------------------------------- the boundary

def test_a_wait_returns_once_its_own_chunk_has_landed_and_no_later():
    dev = FakeDevice(lanes=4, hold=True)
    v = SigVerifier(dev)
    drain = v.open_drain()
    t = signed_triples(9)
    try:
        pos = [drain.feed(t[0:3]), drain.feed(t[3:6]), drain.feed(t[6:9])]
        drain.end()
        assert pos == [3, 6, 9]
        assert v.stats.to_json()["drain_stream"] == {
            "chunks": 3, "landed": 0, "gated": 0}
        assert dev.entered.acquire(timeout=10)      # chunk 0 in flight
        done = []
        waiter = threading.Thread(
            target=lambda: done.append(drain.wait(pos[0], seq=2)))
        waiter.start()
        waiter.join(0.3)
        assert waiter.is_alive() and done == []     # chunk 0 not landed
        dev.allow()
        waiter.join(10)
        assert not waiter.is_alive() and done[0] > 0.0
        # ledger 1 passed its gate while chunks 1 and 2 have not landed:
        # one chunk in flight at a time, in order
        assert dev.entered.acquire(timeout=10)
        assert dev.sizes() == [4, 4]
        assert cached(v, t[3]) is True and cached(v, t[4]) is None
        assert drain.wait(pos[0]) == 0.0            # landed: no wait
        dev.allow(2)
        assert drain.wait(pos[2]) >= 0.0
        assert dev.sizes() == [4, 4, 1]
        assert all(cached(v, x) is True for x in t)
        assert {n for n, _t in dev.calls} == {WORKER}
        assert v.stats.to_json()["drain_stream"]["landed"] == 3
        assert v.stats.to_json()["drain_stream"]["gated"] >= 1
    finally:
        dev.hold = False
        dev.allow(8)
        drain.close()
    assert pipeline_threads() == []


@pytest.mark.parametrize("groups", [[70], [1] * 70, [31, 1, 32, 6],
                                    [10, 0, 25, 35]])
@pytest.mark.parametrize("already", [0, 9])
def test_chunks_are_those_of_one_prewarm_many_over_the_same_triples(
        groups, already):
    """Cut over the MISSES at the top bucket, in feed order, whatever the
    groups the caller fed them in and whatever the cache already held."""
    t = signed_triples(sum(groups))
    known = t[5:5 + already]

    def run(streamed):
        dev = FakeDevice(lanes=32)
        v = SigVerifier(dev)
        v._cache_store([K._cache_key(*x) for x in known],
                       [True] * len(known))
        if not streamed:
            # the engine cuts a prewarm_many at its top bucket itself
            dev_cut = []
            orig = dev.verify_many

            def cut(triples):
                for i in range(0, len(triples), 32):
                    dev_cut.append(list(triples[i:i + 32]))
                return orig(triples)
            dev.verify_many = cut
            assert v.prewarm_many(t) == [True] * len(t)
            return dev_cut
        drain = v.open_drain()
        lo = 0
        for g in groups:
            drain.feed(t[lo:lo + g])
            lo += g
        drain.wait(drain.position)
        drain.close()
        assert all(cached(v, x) is True for x in t)
        return [c for _n, c in dev.calls]

    assert run(True) == run(False)


def test_jax_cpu_engine_counts_the_same_dispatches_and_pad():
    """The device engine at a tiny bucket: dispatches, signatures and
    pad of a streamed drain equal those of one prewarm_many."""
    t = signed_triples(75, b"jax")
    t[7] = (t[7][0], t[7][1][:10] + bytes([t[7][1][10] ^ 1]) + t[7][1][11:],
            t[7][2])

    def run(streamed):
        ctx = VerifierContext(cache=K.VerdictCache(), stats=VerifierStats())
        eng = TpuSigVerifier(ctx)
        eng.BUCKETS = (32,)
        v = SigVerifier(eng, fallback=CpuSigVerifier(ctx))
        if streamed:
            drain = v.open_drain()
            for lo in range(0, 75, 15):
                drain.feed(t[lo:lo + 15])
            drain.wait(drain.position)
            drain.close()
        else:
            v.prewarm_many(t)
        j = ctx.stats.to_json()
        return (eng.batches_dispatched, eng.sigs_verified, j["buckets"]
                ["32"]["drains"], j["buckets"]["32"]["pad_waste_total"],
                j["drains"]["by_backend"]["tpu"]["sigs"],
                [cached(v, x) for x in t], ctx.stats.staging["stalls"])

    streamed, whole = run(True), run(False)
    assert streamed == whole
    assert streamed[:4] == (3, 75, 3, 21)
    assert streamed[5] == [i != 7 for i in range(75)]


def test_a_verify_many_replaced_on_the_engine_is_what_the_drain_calls():
    """benchmark/control.py's accept-all replaces the attribute on the
    instance: the drain looks it up at every chunk."""
    dev = FakeDevice(lanes=4)
    v = SigVerifier(dev)
    t = signed_triples(6)
    bad = (t[0][0], bytes(64), t[0][2])
    seen = []
    dev.verify_many = lambda triples: (seen.append(len(triples))
                                       or [True] * len(triples))
    drain = v.open_drain()
    drain.feed([bad] + t)
    drain.wait(drain.position)
    drain.close()
    assert seen == [4, 3] and dev.calls == []
    assert cached(v, bad) is True       # the control's answer, not ours


def test_close_cancels_what_is_queued_and_joins_what_is_in_flight():
    dev = FakeDevice(lanes=2, hold=True)
    v = SigVerifier(dev)
    t = signed_triples(6)
    drain = v.open_drain()
    drain.feed(t)                       # three chunks handed over
    assert dev.entered.acquire(timeout=10)
    closer = threading.Thread(target=drain.close)
    closer.start()
    closer.join(0.2)
    assert closer.is_alive()            # joining chunk 0, in flight
    dev.allow()
    closer.join(10)
    assert not closer.is_alive()
    assert pipeline_threads() == []
    assert dev.sizes() == [2]           # chunks 1 and 2 never ran
    v.cache.store.clear()               # the next replay's flush
    time.sleep(0.05)
    assert all(cached(v, x) is None for x in t)     # and no late write
    assert drain.wait(drain.position) == 0.0        # a closed gate is open
    drain.feed(signed_triples(2, b"late"))          # and takes no work
    drain.end()
    assert dev.sizes() == [2] and pipeline_threads() == []


def test_the_boundary_keeps_one_drain_and_stop_closes_it():
    dev = FakeDevice(lanes=2, delay=0.05)
    v = SigVerifier(dev)
    first = v.open_drain()
    first.feed(signed_triples(6))
    second = v.open_drain()             # closes the first
    assert first._closed and pipeline_threads() == []
    second.feed(signed_triples(2, b"second"))
    v.stop()
    assert second._closed and pipeline_threads() == []
    v.stop()                            # idempotent


def test_a_chunk_that_raises_lands_with_no_verdicts():
    dev = FakeDevice(lanes=3)
    dev.raise_on = (0,)
    v = SigVerifier(dev)                # no fallback: the raise gets out
    t = signed_triples(6)
    drain = v.open_drain()
    drain.feed(t)
    assert drain.wait(drain.position) >= 0.0
    drain.close()
    assert [cached(v, x) for x in t] == [None] * 3 + [True] * 3
    assert v.stats.to_json()["drain_stream"]["landed"] == 2


def test_a_chunk_whose_dispatch_fails_is_served_by_the_fallback():
    ctx = VerifierContext(cache=K.VerdictCache(), stats=VerifierStats())
    dev = FakeDevice(ctx, lanes=3)
    dev.raise_on = (1,)
    v = SigVerifier(dev, fallback=CpuSigVerifier(ctx))
    t = signed_triples(6)
    drain = v.open_drain()
    drain.feed(t)
    drain.wait(drain.position)
    drain.close()
    assert all(cached(v, x) is True for x in t)
    assert ctx.stats.to_json()["drains"]["by_backend"]["cpu"]["sigs"] == 3


def test_the_ungated_use_runs_a_whole_prewarm_on_the_worker():
    v = SigVerifier(CpuSigVerifier(VerifierContext(cache=K.VerdictCache())),
                    max_pending=0)
    t = signed_triples(5)
    names = []
    orig = v.prewarm_many
    v.prewarm_many = lambda triples: (
        names.append(threading.current_thread().name) or orig(triples))
    drain = v.open_drain()
    drain.submit(t)
    drain.submit(t[:2])
    deadline = time.monotonic() + 10
    while len(names) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    drain.close()
    assert names == [WORKER, WORKER]
    assert all(cached(v, x) is True for x in t)
    assert pipeline_threads() == []


# ------------------------------------------------------------------- spans

def jax_cpu_boundary(tracer):
    ctx = VerifierContext(cache=K.VerdictCache(), stats=VerifierStats(),
                          tracer=tracer)
    eng = TpuSigVerifier(ctx)
    eng.BUCKETS = (32,)
    return SigVerifier(eng, fallback=CpuSigVerifier(ctx))


def test_the_workers_wait_and_stage_carry_names_of_their_own():
    """`crypto.device_wait` is the gate (the thread that replays,
    blocked); the worker's wait on a chunk is `crypto.device_wait_ahead`
    and its stage `crypto.stage_ahead`, each naming the feeding span as
    its cause. A direct dispatch keeps the old names."""
    tracer = Tracer()
    v = jax_cpu_boundary(tracer)
    tracer.enable()
    t = signed_triples(40, b"spans")
    with tracer.span("catchup.sig_prep") as feeding:
        drain = v.open_drain()
        pos = drain.feed(t)
        drain.end()
    drain.wait(pos, seq=5)
    drain.close()
    v.verify_many(signed_triples(3, b"direct"))
    tracer.disable()
    got = {}
    for s in tracer.spans():
        got.setdefault(s.name, []).append(s)
    main = threading.get_ident()
    ahead = got["crypto.device_wait_ahead"]
    assert len(ahead) == 2 and len(got["crypto.stage_ahead"]) == 2
    for s in ahead + got["crypto.stage_ahead"]:
        assert s.tid != main and s.cause == feeding.sid
    chunks = [s for s in got["crypto.prewarm"] if s.tid != main]
    assert [s.tags["n"] for s in chunks] == [32, 8]
    assert all(s.cause == feeding.sid and s.parent == 0 for s in chunks)
    probe, = got["crypto.cache_probe"]
    assert probe.parent == feeding.sid and probe.tid == main
    gates = [s for s in got["crypto.device_wait"] if s.tags
             and "seq" in s.tags]
    assert len(gates) == 1 and gates[0].tid == main
    assert gates[0].tags["seq"] == 5 and gates[0].tags["chunk"] == 1
    assert gates[0].tags["waited"] in (True, False)
    # the direct dispatch, on this thread: the names it always had
    direct = [s for s in got["crypto.device_wait"] if s not in gates]
    assert len(direct) == 1 and direct[0].tid == main
    assert [s.tid for s in got["crypto.stage"]] == [main]
    for name in ("crypto.verify_many", "crypto.dispatch", "crypto.launch",
                 "crypto.unpack"):
        assert len(got[name]) == 3, name


def test_a_drain_with_tracing_off_reads_no_clock_and_keeps_no_span():
    tracer = Tracer()
    reads = []
    tracer._now = lambda: reads.append(1) or 0.0
    ctx = VerifierContext(cache=K.VerdictCache(), tracer=tracer)
    v = SigVerifier(FakeDevice(ctx, lanes=4))
    drain = v.open_drain()
    pos = drain.feed(signed_triples(9))
    drain.wait(pos, seq=3)
    drain.close()
    assert reads == [] and tracer.spans() == []


# ----------------------------------------------------------------- replays

def node_config(n, archive_root, backend, writable=False):
    cfg = Config.test_config(n, backend=backend)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.CHECKPOINT_FREQUENCY = FREQ
    cfg.SIG_VERIFY_WARMUP = False
    cfg.VERIFY_CACHE_SCOPE = "node"     # the publisher's verdicts are not ours
    arch = HistoryArchive.local_dir("test", str(archive_root))
    d = {"get": arch.get_tmpl, "mkdir": arch.mkdir_tmpl}
    if writable:
        d["put"] = arch.put_tmpl
    cfg.HISTORY = {"test": d}
    return cfg


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    """(root, archive_root, {seq: header hash}, signatures issued)."""
    root_dir = tmp_path_factory.mktemp("drain-archive")
    archive_root = root_dir / "archive"
    os.makedirs(archive_root)
    pub = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                      node_config(0, archive_root, "cpu", writable=True))
    pub.enable_buckets(str(root_dir / "buckets-pub"))
    pub.start()
    adapter = AppLedgerAdapter(pub)
    root = adapter.root_account()
    sigs = [0]

    def submit(frame):
        assert pub.submit_transaction(frame) == 0, frame.result
        sigs[0] += len(frame.envelope.value.signatures)

    sks = [SecretKey.from_seed(hashlib.sha256(b"snd%d" % i).digest())
           for i in range(SENDERS)]
    submit(root.tx([root.op_create_account(sk.public_key, 10 ** 10)
                    for sk in sks]))
    pub.manual_close()                                          # ledger 2
    senders = [TestAccount(adapter, sk) for sk in sks]
    for s in senders:
        submit(s.tx([s.op_payment(root.account_id, 500)]))
    pub.manual_close()                                          # ledger 3
    extra = {}
    for i, s in enumerate(senders):
        ks = [SecretKey.from_seed(hashlib.sha256(
            b"signer%d/%d" % (i, j)).digest()) for j in range(2)]
        submit(s.tx([s.op_add_signer(k.public_key.key_bytes) for k in ks]
                    + [s.op_set_options(med=3)]))
        extra[i] = ks
    pub.manual_close()                                          # ledger 4
    pub.clock.set_virtual_time(pub.clock.now() + 30)
    hm = pub.history_manager
    while pub.ledger_manager.last_closed_ledger_num() < TIP:
        for i, s in enumerate(senders):
            submit(s.tx([s.op_payment(root.account_id, 700)],
                        extra_signers=extra[i]))
        pub.clock.set_virtual_time(pub.clock.now() + 1.0)
        pub.manual_close()
    issued = sigs[0]
    pub.manual_close()                  # ledger 16: checkpoint 15 queues
    pub.crank_until(lambda: hm.publish_queue() == [], max_cranks=20000)
    assert hm.published_checkpoints >= 1
    headers = dict(pub.database.execute(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
    pub.stop()
    return root_dir, archive_root, headers, issued


def replaying_node(archive, n, backend="tpu", fake=None):
    root_dir, archive_root, _headers, _issued = archive
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                      node_config(n, archive_root, backend))
    app.enable_buckets(str(root_dir / ("buckets-%d-%d" % (n, os.getpid()))))
    if fake is not None:
        fake.ctx = app.sig_verifier.ctx
        app.sig_verifier.engine = fake
    elif backend == "tpu":
        app.sig_verifier.inner.BUCKETS = (32,)
    app.start()
    return app


def replay(app, until=None, max_cranks=200000):
    work = app.catchup_manager.start_catchup(CatchupConfiguration.complete())
    for _ in range(max_cranks):
        if work.is_done() or (until is not None and until(app)):
            break
        app.crank(False)
    return work


def headers_of(app):
    return dict(app.database.execute(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())


def apply_work_of(work):
    """The ApplyCheckpointWork under a running CatchupWork."""
    from stellar_core_tpu.historywork.apply_works import ApplyCheckpointWork
    todo, seen = [work], []
    while todo:
        w = todo.pop()
        if isinstance(w, ApplyCheckpointWork):
            seen.append(w)
        for attr in ("children", "_children", "sequence"):
            todo.extend(getattr(w, attr, ()))
        if getattr(w, "inner", None) is not None:
            todo.append(w.inner)
    assert seen
    return seen


def test_no_close_dispatches_and_every_triple_goes_to_the_device_once(
        archive):
    """Also with the second feed opened while the first has chunks in
    flight: the slow device is still on the first feed's chunks when
    ledger 4 has armed the signers."""
    _root, _arch, want, issued = archive
    dev = FakeDevice(lanes=8, delay=0.02)
    app = replaying_node(archive, 1, fake=dev)
    try:
        work = replay(app)
        assert work.state == State.SUCCESS
        got = headers_of(app)
        assert all(got[s] == want[s] for s in range(2, TIP + 1))
        # every dispatch came from the drain's worker: no close dispatched
        assert {n for n, _t in dev.calls} == {WORKER}
        sent = [x for _n, ts in dev.calls for x in ts]
        assert len(sent) == len(set(sent)) == issued == dev.sigs_verified
        # cut at the top bucket over the misses: full chunks but each
        # feed's tail
        sizes = dev.sizes()
        assert sum(1 for s in sizes if s != 8) <= 2
        m = app.metrics.to_json()
        closed = TIP - 1
        assert m["catchup.drain.ledgers"]["count"] == closed
        gated = m.get("catchup.drain.ledgers_gated", {}).get("count", 0)
        assert 1 <= gated <= closed
        assert m["catchup.drain.gate_wait_ms"]["count"] == gated
        ds = app.command_handler.cmd_verifier({})["drain_stream"]
        assert ds["chunks"] == ds["landed"] == len(sizes)
        assert ds["gated"] >= gated
        assert "catchup.pipeline.stall" not in m
        assert pipeline_threads() == []     # finished: the worker is gone
    finally:
        app.stop()


def test_ledgers_close_while_later_chunks_are_in_flight(archive):
    """Gated on its own chunk only: by the time the device takes the
    last chunk of the second feed the replay is far past the ledger that
    opened it."""
    dev = FakeDevice(lanes=8, delay=0.03)
    app = replaying_node(archive, 2, fake=dev)
    lcl_at_call = []
    orig = dev.verify_many
    dev.verify_many = lambda triples: (
        lcl_at_call.append(app.ledger_manager.last_closed_ledger_num())
        or orig(triples))
    try:
        work = replay(app)
        assert work.state == State.SUCCESS
        assert app.ledger_manager.last_closed_ledger_num() == TIP
        # the first feed's chunks start before any close; the second's
        # after ledger 4, and the closes go on underneath them
        assert lcl_at_call[0] == 1
        assert max(lcl_at_call) > 5
        assert {n for n, _t in dev.calls} == {WORKER}
    finally:
        app.stop()


@pytest.mark.parametrize("how", ["reset", "stop", "finish"])
def test_no_worker_and_no_cache_write_outlive_the_replay(archive, how):
    dev = FakeDevice(lanes=8, delay=0.05)
    app = replaying_node(archive, {"reset": 3, "stop": 4, "finish": 5}[how],
                         fake=dev)
    try:
        if how == "finish":
            work = replay(app)
            assert work.state == State.SUCCESS
        else:
            work = replay(app, until=lambda a:
                          a.ledger_manager.last_closed_ledger_num() >= 6)
            assert not work.is_done() and pipeline_threads() != []
            if how == "reset":
                for w in apply_work_of(work):
                    w.on_reset()
            else:
                app.stop()
        assert pipeline_threads() == []
        calls = len(dev.calls)
        app.sig_verifier.cache.store.clear()    # the next replay's flush
        time.sleep(0.15)
        assert len(dev.calls) == calls          # queued chunks never ran
        stats = K.verify_cache_stats(app.sig_verifier.cache)
        assert stats["size"] == 0, stats        # and nothing landed late
    finally:
        app.stop()
    assert pipeline_threads() == []


@pytest.mark.parametrize("site", ["apply.pipeline-stall",
                                  "verify.staging-stall",
                                  "device.dispatch"])
def test_a_fault_still_ends_in_a_completed_correct_replay(archive, site):
    _root, _arch, want, issued = archive
    app = replaying_node(archive, {"apply.pipeline-stall": 6,
                                   "verify.staging-stall": 7,
                                   "device.dispatch": 8}[site])
    if site == "device.dispatch":
        app.faults.configure(site, probability=1.0, count=2)
    else:
        app.faults.configure(site, probability=1.0)
    try:
        work = replay(app)
        assert work.state == State.SUCCESS
        got = headers_of(app)
        assert all(got[s] == want[s] for s in range(2, TIP + 1))
        m = app.metrics.to_json()
        eng = app.sig_verifier.inner
        if site == "apply.pipeline-stall":
            # today's synchronous drain: nothing streamed, nothing gated
            assert m["catchup.pipeline.stall"]["count"] == 2
            assert "catchup.drain.ledgers" not in m
            assert eng.sigs_verified == issued
        elif site == "verify.staging-stall":
            # a streamed chunk is one dispatch: no staging job to stall
            assert "verifier.staging.stall" not in m
            assert eng.sigs_verified == issued
        else:
            assert m["crypto.verify.fallback-drain"]["count"] == 2
            by = app.command_handler.cmd_verifier({})["drains"]["by_backend"]
            assert by["cpu"]["sigs"] + eng.sigs_verified == issued
        assert pipeline_threads() == []
    finally:
        app.stop()


def test_streamed_and_synchronous_replays_agree_header_by_header(archive):
    """The jax-CPU device engine at the 32-lane bucket, streamed, against
    the same engine drained synchronously (`apply.pipeline-stall`): the
    same header chain as the publisher's, the same dispatches, the same
    signatures, the same pad."""
    _root, _arch, want, issued = archive

    def run(n, stalled):
        app = replaying_node(archive, n)
        if stalled:
            app.faults.configure("apply.pipeline-stall", probability=1.0)
        try:
            work = replay(app)
            assert work.state == State.SUCCESS
            ck = app.command_handler.cmd_verifier({})
            b = ck["buckets"]["32"]
            assert "cpu" not in ck["drains"]["by_backend"]
            return (headers_of(app), ck["counters"]["batches_dispatched"],
                    ck["counters"]["sigs_verified"], b["drains"],
                    b["pad_waste_total"], ck["staging"]["stalls"])
        finally:
            app.stop()

    streamed, whole = run(9, False), run(10, True)
    assert streamed == whole
    assert all(streamed[0][s] == want[s] for s in range(2, TIP + 1))
    assert streamed[2] == issued and streamed[5] == 0


def test_the_cpu_native_path_stays_ungated(archive):
    """Another engine, the same worker: the cpu + native replay hands
    whole prewarms to it and gates nothing."""
    _root, _arch, want, _issued = archive
    app = replaying_node(archive, 11, backend="cpu")
    from stellar_core_tpu.native import apply_engine
    if apply_engine() is None:
        pytest.skip("native apply engine not built")
    try:
        work = replay(app)
        assert work.state == State.SUCCESS
        got = headers_of(app)
        assert all(got[s] == want[s] for s in range(2, TIP + 1))
        m = app.metrics.to_json()
        assert m["catchup.pipeline.prewarm"]["count"] >= 1
        assert "catchup.drain.ledgers" not in m
        assert app.command_handler.cmd_verifier({})["drain_stream"] == {
            "chunks": 0, "landed": 0, "gated": 0}
        assert pipeline_threads() == []
    finally:
        app.stop()

