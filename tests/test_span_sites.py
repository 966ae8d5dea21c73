"""The spans inside admission, the verifier boundary and the SCP slot
(ISSUE 25): which spans one unit of work yields, how they nest, what
names their cause across threads, and that a disabled site computes
nothing. The device backend runs on jax-CPU at the 32-lane bucket.
"""

import threading
import time

import pytest

from stellar_core_tpu.crypto import keys as K
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.main.application import Application
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.simulation import topologies
from stellar_core_tpu.testing import AppLedgerAdapter
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.util.tracing import Tracer


def device_app(backend="tpu"):
    cfg = Config.test_config(0, backend=backend)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.SIG_VERIFY_WARMUP = False
    cfg.CONSENSUS_STUCK_TIMEOUT_SECONDS = 10000.0
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.sig_verifier.inner.BUCKETS = (32,)
    app.start()
    return app


@pytest.fixture(scope="module")
def tpu_app():
    app = device_app("tpu")
    yield app
    app.stop()


def fresh_trace(app) -> Tracer:
    K.flush_verify_cache()
    app.tracer.clear()
    app.tracer.enable()
    return app.tracer


def by_name(tracer) -> dict:
    out = {}
    for s in tracer.spans():
        out.setdefault(s.name, []).append(s)
    return out


def ancestors(spans, s) -> list:
    by_sid = {x.sid: x for x in spans}
    names = []
    while s.parent:
        s = by_sid[s.parent]
        names.append(s.name)
    return names


def signed_triples(n):
    sk = SecretKey.from_seed(b"s" * 32)
    out = []
    for i in range(n):
        msg = b"span-sites-%d" % i
        out.append((sk.public_key.key_bytes, sk.sign(msg), msg))
    return out


# ------------------------------------------------------------------ admission

def test_one_admitted_payment_yields_one_span_of_each(tpu_app):
    ledger = AppLedgerAdapter(tpu_app)
    root = ledger.root_account()
    dest = SecretKey.from_seed(b"d" * 32)
    frame = root.tx([root.op_create_account(dest.public_key, 10 ** 9)])
    tracer = fresh_trace(tpu_app)
    try:
        assert tpu_app.submit_transaction(frame) == 0
    finally:
        tracer.disable()
    spans = tracer.spans()
    tpu_app.manual_close()      # the next test's root sequence number
    got = by_name(tracer)
    chain = ["herder.admit", "txqueue.try_add", "crypto.prewarm",
             "crypto.verify_many", "crypto.dispatch"]
    leaves = ["crypto.launch", "crypto.device_wait", "crypto.unpack"]
    for name in chain + leaves + ["crypto.stage", "crypto.cache_probe",
                                  "tx.check_valid"]:
        assert len(got.get(name, [])) == 1, (name, sorted(got))
    # each contains the next (the resilient layer's dispatch_primary
    # sits between prewarm and verify_many)
    for outer, inner in zip(chain, chain[1:]):
        assert outer in ancestors(spans, got[inner][0]), (outer, inner)
    for name in leaves:
        assert got[name][0].parent == got["crypto.dispatch"][0].sid
    assert got["crypto.stage"][0].parent == got["crypto.verify_many"][0].sid
    assert got["crypto.cache_probe"][0].parent == \
        got["crypto.prewarm"][0].sid
    assert got["tx.check_valid"][0].parent == got["txqueue.try_add"][0].sid
    assert got["herder.admit"][0].tags == {"origin": "local", "status": 0}
    assert got["herder.admit"][0].parent == 0
    parts = sum(got[n][0].dur for n in leaves + ["crypto.stage"])
    assert parts <= got["crypto.verify_many"][0].dur
    # the benchmark's readers read names: none may look like its own
    assert not [s.name for s in spans if s.name.startswith("bench.")]
    assert "crypto.stage_ahead" not in got and all(
        s.cause == 0 for s in spans)


def test_a_drain_of_flood_received_payments_yields_one_batch_span(tpu_app):
    """ISSUE 28: the frames one crank delivered are admitted under ONE
    `herder.admit_batch`, whose shared prewarm holds the only dispatch;
    each `herder.admit` under it completes off the verdict cache."""
    ledger = AppLedgerAdapter(tpu_app)
    root = ledger.root_account()
    seq = ledger.seq_num(root.account_id)
    frames = [root.tx([root.op_create_account(
        SecretKey.from_seed(bytes([0x70 + i]) * 32).public_key, 10 ** 9)],
        seq=seq + 1 + i) for i in range(3)]
    verdicts = []
    sizes = tpu_app.metrics.new_histogram("herder.admit_batch.size")
    shared = tpu_app.metrics.new_histogram("herder.admit_batch.dispatched")
    before = (sizes.count, shared.count, shared.total)
    tracer = fresh_trace(tpu_app)
    try:
        for f in frames:
            tpu_app.herder.recv_flood_transaction(f, verdicts.append)
        assert verdicts == [] and tracer.spans() == []      # parked
        assert tpu_app.clock.crank_ready() == 1             # the drain
    finally:
        tracer.disable()
    spans = tracer.spans()
    tpu_app.manual_close()
    assert verdicts == [0, 0, 0]
    got = by_name(tracer)
    batch, = got["herder.admit_batch"]
    assert batch.parent == 0
    assert batch.tags == {"n": 3, "triples": 3, "dispatched": 3}
    admits = got["herder.admit"]
    assert [a.parent for a in admits] == [batch.sid] * 3
    assert all(a.tags == {"origin": "flood", "status": 0} for a in admits)
    # one dispatch, under the batch's own prewarm and not under an admit
    dispatch, = got["crypto.dispatch"]
    up = ancestors(spans, dispatch)
    assert "herder.admit_batch" in up and "herder.admit" not in up
    assert dispatch.tags["n"] == 3
    warm = [p for p in got["crypto.prewarm"] if p.parent == batch.sid]
    assert len(warm) == 1 and warm[0].tags["cache_hits"] == 0
    own = [p for p in got["crypto.prewarm"] if p.parent != batch.sid]
    assert len(own) == 3 and all(p.tags["cache_hits"] == 1 for p in own)
    assert len(got["txqueue.try_add"]) == len(got["tx.check_valid"]) == 3
    assert (sizes.count, shared.count, shared.total) == \
        (before[0] + 1, before[1] + 1, before[2] + 3)


def test_staging_worker_names_the_drain_as_its_cause(tpu_app):
    """Chunk 0 is staged inline (`crypto.stage`, on the drain's critical
    path); chunks 1.. on the staging worker (`crypto.stage_ahead`),
    which run beside the drain and are not its children."""
    triples = signed_triples(70)        # three 32-lane chunks
    v = tpu_app.sig_verifier.inner
    tracer = fresh_trace(tpu_app)
    try:
        assert v.verify_many(triples) == [True] * 70
    finally:
        tracer.disable()
    got = by_name(tracer)
    drain, = got["crypto.verify_many"]
    assert len(got["crypto.stage"]) == 1 and len(got["crypto.dispatch"]) == 3
    assert len(got["crypto.stage_wait"]) == 2
    assert len(got["crypto.stage_spawn"]) == 2
    ahead = got["crypto.stage_ahead"]
    assert len(ahead) == 2
    for s in ahead:
        assert s.cause == drain.sid and s.parent == 0
        assert s.tid != drain.tid
    # self time of the drain: its own children only
    pb = tracer.phase_breakdown()
    kids = sum(s.dur for n in ("crypto.stage", "crypto.dispatch",
                               "crypto.stage_spawn", "crypto.stage_wait")
               for s in got[n])
    assert pb["phases"]["crypto.verify_many:tpu@cpu"]["total_s"] == \
        pytest.approx(drain.dur - kids, abs=1e-6)


# ---------------------------------------------------------------- queue waits

def test_threaded_batch_records_a_queue_wait_per_class():
    app = device_app("tpu-async")
    try:
        v = app.sig_verifier
        assert v.name == "threaded"     # flushes on the worker
        triples = signed_triples(3)
        tracer = fresh_trace(app)
        futs = []
        for (k, s, m), cls in zip(triples, ("scp", "scp", "tx")):
            from stellar_core_tpu.xdr import PublicKey
            futs.append(v.enqueue(PublicKey.ed25519(k), s, m, cls=cls))
        time.sleep(0.01)
        with tracer.span("test.flush") as flush:
            v.flush()

        def settled():
            time.sleep(0.002)
            return all(f.done() for f in futs)

        assert app.crank_until(settled, max_cranks=100000)
        tracer.disable()
        assert [f.result() for f in futs] == [True] * 3
        got = by_name(tracer)
        batch, = got["crypto.batch_dispatch"]
        assert batch.cause == flush.sid and batch.parent == 0
        assert batch.tid != threading.get_ident()
        assert batch.tags == {"n": 3, "backend": "threaded:resilient"}
        scp, = got["crypto.queue_wait.scp"]
        tx, = got["crypto.queue_wait.tx"]
        for w in (scp, tx):
            assert w.parent == 0 and w.cause == flush.sid
            assert w.dur >= 0.01 and w.tags == {"n": 3}
        # oldest enqueue first: the scp envelopes went in before the tx
        assert scp.t0 < tx.t0 and scp.t0 + scp.dur == \
            pytest.approx(tx.t0 + tx.dur)
        assert scp.t0 + scp.dur <= batch.t0 + 1e-3
    finally:
        app.stop()


def test_enqueue_stamps_the_tracer_clock_only_while_tracing():
    app = device_app("tpu-async")
    try:
        v = app.sig_verifier
        calls = []
        app.tracer._now = lambda: calls.append(1) or 1.0
        from stellar_core_tpu.xdr import PublicKey
        (k, s, m), = signed_triples(1)
        K.flush_verify_cache()
        v.enqueue(PublicKey.ed25519(k), s, m, cls="scp")
        assert calls == [] and v._pending[0][4:] == ("scp", 0.0)
    finally:
        app.stop()


# ------------------------------------------------------------ slots and timers

def test_consensus_slots_and_timers_in_a_three_node_simulation():
    def tweak(cfg):
        cfg.TRACE_ENABLED = True

    sim = topologies.core(3, 2, cfg_tweak=tweak)
    sim.start_all_nodes()
    try:
        assert sim.crank_until(lambda: sim.have_all_externalized(6), 40000)
        for node in sim.nodes.values():
            app = node.app
            got = by_name(app.tracer)
            closed = app.ledger_manager.last_closed_ledger_num() - 1
            slots = got["scp.slot"]
            assert len(slots) == closed == len(got["scp.externalize"])
            assert sorted(s.tags["slot"] for s in slots) == \
                list(range(2, closed + 2))
            for s in slots:
                assert s.parent == 0 and s.dur >= 0.0
                assert set(s.tags) == {"slot", "timeouts",
                                       "ballot_counter"}
            fired = app.herder.scp_stats.totals["timer_fired"]
            assert len(got.get("scp.timer.fired", [])) == fired
            assert len(got.get("scp.timer.wait", [])) == fired
            assert sum(s.tags["timeouts"] for s in slots) <= fired
            for w in got.get("scp.timer.wait", []):
                assert w.tags["timer"] in ("nomination", "ballot")
            # the stamps are dropped as slots externalize
            assert all(s > closed + 1 for s in app.herder._slot_trace_t0)
    finally:
        sim.stop_all_nodes()


def test_fetch_wait_is_recorded_when_the_item_arrives():
    from stellar_core_tpu.overlay.item_fetcher import ItemFetcher

    class _App:
        clock = VirtualClock(ClockMode.VIRTUAL_TIME)
        tracer = Tracer()

    class _Overlay:
        app = _App()
        sent = []

        def authenticated_peer_ids(self):
            return ["p1", "p2"]

        def get_peer(self, pid):
            overlay = self

            class _Peer:
                def send_message(self, msg):
                    overlay.sent.append((pid, msg))
            return _Peer()

    ov = _Overlay()
    ov.app.tracer.enable()
    fetcher = ItemFetcher(ov, lambda h: ("GET", h), kind="txset")
    fetcher.fetch(b"h" * 32)
    fetcher.doesnt_have(b"h" * 32, ov.sent[0][0])
    fed = []
    fetcher.recv(b"h" * 32, fed.append)
    span, = [s for s in ov.app.tracer.spans()
             if s.name == "overlay.fetch_wait"]
    assert span.tags == {"kind": "txset", "tries": 2} and span.parent == 0
    assert len(ov.sent) == 2 and fetcher.num_fetching() == 0
    # an item nobody asked for, and one asked for with tracing off
    fetcher.recv(b"x" * 32, fed.append)
    ov.app.tracer.disable()
    fetcher.fetch(b"y" * 32)
    ov.app.tracer.enable()
    fetcher.recv(b"y" * 32, fed.append)
    assert len([s for s in ov.app.tracer.spans()
                if s.name == "overlay.fetch_wait"]) == 1


# ------------------------------------------------------------- disabled sites

def _cheap(node) -> bool:
    """A tag a disabled site may evaluate: a name, an attribute, a
    constant, a subscript or a difference of those, or len() of one."""
    import ast
    if isinstance(node, (ast.Name, ast.Constant)):
        return True
    if isinstance(node, ast.Attribute):
        return _cheap(node.value)
    if isinstance(node, ast.Subscript):
        return _cheap(node.value) and _cheap(node.slice)
    if isinstance(node, ast.BinOp) and isinstance(node.op,
                                                  (ast.Add, ast.Sub)):
        return _cheap(node.left) and _cheap(node.right)
    if isinstance(node, ast.Call):
        return isinstance(node.func, ast.Name) and node.func.id == "len" \
            and all(_cheap(a) for a in node.args) and not node.keywords
    return False


def test_span_sites_pass_only_names_and_len_as_tags():
    """Python evaluates a call's arguments before the callee can see that
    tracing is off, so a span site's tags may cost nothing: a tag that
    rounds, formats or builds a container goes behind `if sp.live:`."""
    import ast
    import glob
    import os
    import stellar_core_tpu
    root = os.path.dirname(stellar_core_tpu.__file__)
    sites, bad = 0, []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        tree = ast.parse(open(path).read(), path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) else \
                f.attr if isinstance(f, ast.Attribute) else None
            if name not in ("app_span", "tracer_span", "_span", "span"):
                continue
            if name == "span" and not (
                    node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue        # some other object's .span()
            sites += 1
            for kw in node.keywords:
                if kw.arg is not None and not _cheap(kw.value):
                    bad.append("%s:%d %s=" % (os.path.relpath(path, root),
                                              node.lineno, kw.arg))
    assert sites >= 30, sites
    assert bad == []


def test_only_the_boundarys_two_cache_methods_touch_the_verdict_store():
    """The verdict-cache policy lives in one place: in batch_verifier.py
    `cache.store.maybe_get` / `.put` appear in SigVerifier._cache_probe
    and ._cache_store and nowhere else, so no engine and no second queue
    can probe or feed the cache its own way."""
    import ast
    from stellar_core_tpu.crypto import batch_verifier
    tree = ast.parse(open(batch_verifier.__file__).read())
    sites = {}

    def walk(node, where):
        for child in ast.iter_child_nodes(node):
            inside = where
            if isinstance(child, (ast.ClassDef, ast.FunctionDef)):
                inside = where + (child.name,)
            if isinstance(child, ast.Attribute) and \
                    child.attr in ("maybe_get", "put", "store"):
                sites.setdefault(".".join(inside), set()).add(child.attr)
            walk(child, inside)

    walk(tree, ())
    assert sites == {"SigVerifier._cache_probe": {"store", "maybe_get"},
                     "SigVerifier._cache_store": {"store", "put"}}
    holders = [c.name for c in tree.body if isinstance(c, ast.ClassDef)
               and any(isinstance(n, ast.Attribute) and n.attr == "_pending"
                       for n in ast.walk(c))]
    assert holders == ["SigVerifier"]       # and one pending queue


def test_disabled_sites_read_no_clock_and_keep_no_state(tpu_app):
    """With the tracer off a site is one attribute check: the tracer's
    clock is never read (no queue-wait, slot or timer stamp) and nothing
    is kept for a span that will not be written."""
    tracer = tpu_app.tracer
    tracer.disable()
    tracer.clear()
    reads = []
    real_now = tracer._now
    tracer._now = lambda: reads.append(1) or real_now()
    try:
        K.flush_verify_cache()
        v = tpu_app.sig_verifier.inner
        assert v.verify_many(signed_triples(40)) == [True] * 40
        ledger = AppLedgerAdapter(tpu_app)
        root = ledger.root_account()
        dest = SecretKey.from_seed(b"e" * 32)
        assert tpu_app.submit_transaction(root.tx(
            [root.op_create_account(dest.public_key, 10 ** 9)])) == 0
        # and a flood-received one: parked, then drained
        verdicts = []
        other = SecretKey.from_seed(b"f" * 32)
        tpu_app.herder.recv_flood_transaction(root.tx(
            [root.op_create_account(other.public_key, 10 ** 9)],
            seq=ledger.seq_num(root.account_id) + 2), verdicts.append)
        assert tpu_app.clock.crank_ready() == 1 and verdicts == [0]
        tpu_app.manual_close()
    finally:
        tracer._now = real_now
    assert reads == [] and tracer.spans() == []
    assert tpu_app.herder._slot_trace_t0 == {}
