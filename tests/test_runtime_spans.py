"""What stops a close from beneath it (ISSUE 35): the collector's pauses
as `runtime.gc.*` spans and process totals from ONE `gc.callbacks` hook,
the bucket-merge workers as `bucket.merge` spans with the closing
thread's `bucket.merge_wait`, the `span_overlap` reader on hand-made
span lists, and a close sequence whose hashes do not know whether the
tracer was on.

The hook is the process's: other tests of this worker process may have
left tracers enabled and collections may run at any time, so every
assertion here is about this file's own tracers, about differences of
the totals, or about a hook of its own that is not installed."""

import gc
import threading
import time
from concurrent.futures import Executor, Future, ThreadPoolExecutor

import pytest

import stellar_core_tpu.xdr as X
from stellar_core_tpu.bucket.bucket_list import BucketLevel, BucketList
from stellar_core_tpu.bucket.bucket import Bucket
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.ledger.apply_stats import ApplyStats
from stellar_core_tpu.main.application import Application
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.testing import AppLedgerAdapter
from stellar_core_tpu.transactions.account_helpers import make_account_entry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.util.tracing import GC_HOOK, GcHook, Tracer

from benchmark.readers import span_overlap

PROTO = 13


@pytest.fixture
def quiet_collector():
    """No automatic collection inside a test that counts spans: a forced
    `gc.collect()` still runs the callbacks."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def gc_spans(tracer):
    return [s for s in tracer.spans() if s.name.startswith("runtime.gc.")]


# ------------------------------------------------------------ the collector

def test_forced_collection_is_one_full_span_beside_the_open_span(
        quiet_collector):
    tr = Tracer()
    tr.enable()
    full0 = GC_HOOK.collections[2]
    with tr.span("outer") as outer:
        with tr.span("inner"):
            gc.collect()
    tr.disable()
    (sp,) = gc_spans(tr)
    assert sp.name == "runtime.gc.full" and sp.cat == "runtime"
    assert sp.parent == 0 and sp.tid == threading.get_ident()
    assert sp.tags["generation"] == 2 and sp.tags["under"] == "inner"
    assert sp.tags["collected"] >= 0 and sp.tags["uncollectable"] >= 0
    by = {s.name: s for s in tr.spans()}
    assert by["inner"].t0 <= sp.t0 and \
        sp.t0 + sp.dur <= by["inner"].t0 + by["inner"].dur + 1e-6
    # no span's self time changes: `inner` keeps all of its duration,
    # `outer` loses `inner` and nothing else
    phases = tr.phase_breakdown()["phases"]
    assert phases["inner"]["total_s"] == pytest.approx(by["inner"].dur,
                                                       abs=1e-6)
    assert phases["outer"]["total_s"] == pytest.approx(
        outer.dur - by["inner"].dur, abs=1e-6)
    assert phases["runtime.gc.full"]["count"] == 1
    assert GC_HOOK.collections[2] == full0 + 1


def test_disabled_tracer_records_nothing_and_the_totals_advance(
        quiet_collector):
    tr = Tracer()
    n0, s0 = GC_HOOK.collections[2], GC_HOOK.pause_total_s
    gc.collect()
    assert tr.spans() == []
    assert GC_HOOK.collections[2] == n0 + 1
    assert GC_HOOK.pause_total_s > s0
    stats = GC_HOOK.stats()
    assert stats["collections"] == sum(GC_HOOK.collections)
    assert stats["generations"][2]["max_pause_s"] > 0.0
    timers = GC_HOOK.timers()
    assert timers["runtime.gc.full.pause"]["count"] == n0 + 1
    assert timers["runtime.gc.pause"]["count"] >= n0 + 1
    assert timers["runtime.gc.full.pause"]["type"] == "timer"
    assert 0.0 < timers["runtime.gc.full.pause"]["median"] <= \
        timers["runtime.gc.full.pause"]["max"]


def test_every_enabled_tracer_gets_the_span_a_disabled_one_none(
        quiet_collector):
    a, b, c = Tracer(), Tracer(), Tracer()
    a.enable()
    b.enable()
    gc.collect()
    b.disable()
    gc.collect()
    a.disable()
    assert [s.name for s in gc_spans(a)] == ["runtime.gc.full"] * 2
    assert [s.name for s in gc_spans(b)] == ["runtime.gc.full"]
    assert gc_spans(c) == []
    assert gc_spans(a)[0].tags["under"] == ""


def test_young_generations_share_one_name(quiet_collector):
    tr = Tracer()
    tr.enable()
    gc.collect(0)
    gc.collect(1)
    tr.disable()
    assert [(s.name, s.tags["generation"]) for s in gc_spans(tr)] == \
        [("runtime.gc.young", 0), ("runtime.gc.young", 1)]


def test_one_hook_a_process_whatever_is_built():
    Tracer()
    n = len(gc.callbacks)
    assert gc.callbacks.count(GC_HOOK) == 1
    keep = [Tracer() for _ in range(50)]
    for t in keep[:10]:
        t.enable()
    apps = []
    for i in range(3):
        cfg = Config.test_config(i)
        cfg.DATABASE = "sqlite3://:memory:"
        apps.append(Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg))
    try:
        assert len(gc.callbacks) == n
        assert gc.callbacks.count(GC_HOOK) == 1
    finally:
        for t in keep:
            t.disable()
        for app in apps:
            app.stop()


def test_a_dead_tracer_leaves_the_enrolment():
    tr = Tracer()
    tr.enable()
    assert tr in GC_HOOK.enrolled()
    ident = id(tr)
    del tr
    gc.collect()
    assert ident not in [id(t) for t in GC_HOOK.enrolled()]
    # and the next enrolment drops its dead reference
    other = Tracer()
    other.enable()
    assert all(r() is not None for r in GC_HOOK._tracers)
    other.disable()
    assert other not in GC_HOOK.enrolled()


def test_callback_cost_with_no_tracer_enabled():
    """Budget: 2 us a callback, two a collection. A hook of the test's
    own, not installed: what other tests left enabled does not count.
    Median of 10^4 pairs, generous factor for a loaded machine."""
    hook = GcHook()
    info = {"generation": 0, "collected": 3, "uncollectable": 0}
    samples = []
    for _ in range(10000):
        t0 = time.perf_counter()
        hook("start", info)
        hook("stop", info)
        samples.append(time.perf_counter() - t0)
    samples.sort()
    assert samples[len(samples) // 2] <= 4e-6 * 5
    assert hook.collections == [10000, 0, 0]
    assert hook.collected == [30000, 0, 0]


def test_a_stop_without_its_start_is_ignored():
    hook = GcHook()     # as if installed while a collection was running
    hook("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    assert hook.collections == [0, 0, 0] and hook.pause_total_s == 0.0


# ---------------------------------------------------------- the merge spans

def acct(i: int, balance: int = 10 ** 9) -> X.LedgerEntry:
    key = X.PublicKey.ed25519(i.to_bytes(32, "big"))
    return make_account_entry(key, balance, 0, 1)


class GatedExecutor(Executor):
    """One thread a job; the job starts only when someone asks for its
    result, so its future is never done before the wait."""

    def __init__(self):
        self.threads = []

    def submit(self, fn, *args, **kwargs):
        gate = threading.Event()

        class Gated(Future):
            def result(self, timeout=None):
                gate.set()
                return super().result(timeout)
        fut = Gated()

        def work():
            gate.wait(30)
            try:
                fut.set_result(fn(*args, **kwargs))
            except BaseException as e:   # noqa: BLE001 - handed to the waiter
                fut.set_exception(e)
        t = threading.Thread(target=work, name="bucket-merge-test")
        t.start()
        self.threads.append(t)
        return fut

    def join(self):
        for t in self.threads:
            t.join(30)
            assert not t.is_alive()


def by_name(tracer) -> dict:
    out = {}
    for s in tracer.spans():
        out.setdefault(s.name, []).append(s)
    return out


def test_merge_span_is_the_workers_and_names_the_close_that_kicked_it():
    tr = Tracer()
    tr.enable()
    ex = GatedExecutor()
    bl = BucketList(ex, stats=ApplyStats(tracer=tr))
    with tr.span("close.bucket_add") as add:
        bl.add_batch(1, PROTO, [acct(1), acct(2)], [], [])
    ex.join()
    tr.disable()
    spans = by_name(tr)
    (merge,) = spans["bucket.merge"]
    (wait,) = spans["bucket.merge_wait"]
    (fresh,) = spans["bucket.fresh"]
    assert merge.tid != threading.get_ident() and merge.parent == 0
    assert merge.cause == add.sid
    assert merge.tags["level"] == 0 and merge.tags["in_curr"] == 0
    assert merge.tags["in_snap"] == 3 and merge.tags["out"] == 3  # + META
    assert 0.0 <= merge.tags["cpu_ms"] <= merge.dur * 1e3 + 1.0
    assert wait.parent == add.sid and wait.tid == threading.get_ident()
    assert wait.tags == {"level": 0}
    assert wait.t0 <= merge.t0 and \
        merge.t0 + merge.dur <= wait.t0 + wait.dur + 1e-6
    assert fresh.parent == add.sid and fresh.tags == {"entries": 3}
    assert bl._stats.to_json()["buckets"]["merges"] == 1


def test_no_wait_span_where_the_merge_was_done():
    tr = Tracer()
    tr.enable()
    pool = ThreadPoolExecutor(max_workers=1)
    try:
        lev = BucketLevel(0)
        snap = Bucket.fresh(PROTO, [acct(1)], [], [])
        with tr.span("close.bucket_add"):
            lev.prepare(pool, 1, PROTO, snap, [], lambda b: b,
                        stats=ApplyStats(tracer=tr))
            lev.next._future.result(30)
            lev.commit()
    finally:
        pool.shutdown(wait=True)
    tr.disable()
    spans = by_name(tr)
    assert len(spans["bucket.merge"]) == 1
    assert "bucket.merge_wait" not in spans
    assert len(lev.curr) == 2


def test_without_workers_the_merge_is_the_callers_and_nothing_waits():
    tr = Tracer()
    tr.enable()
    bl = BucketList(None, stats=ApplyStats(tracer=tr))
    with tr.span("close.bucket_add") as add:
        bl.add_batch(1, PROTO, [acct(1)], [], [])
    tr.disable()
    spans = by_name(tr)
    (merge,) = spans["bucket.merge"]
    assert merge.tid == threading.get_ident()
    assert merge.parent == add.sid and merge.cause == add.sid
    assert "bucket.merge_wait" not in spans


def _close_sequence(tmp_path, name, trace):
    cfg = Config.test_config(0)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.TRACE_ENABLED = trace
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.enable_buckets(str(tmp_path / name))
    app.start()
    try:
        root = AppLedgerAdapter(app).root_account()
        out = []
        lm = app.ledger_manager
        for i in range(16):
            app.clock.set_virtual_time(1000.0 + 5.0 * i)
            dest = SecretKey.from_seed(bytes([i + 1]) * 32).public_key
            frame = root.tx([root.op_create_account(dest, 10 ** 9 + i)])
            assert app.submit_transaction(frame) == 0
            app.manual_close()
            out.append((lm.lcl_header.ledgerSeq, lm.lcl_hash,
                        lm.lcl_header.bucketListHash))
        bm = app.bucket_manager
        bm.bucket_list.resolve_all_futures()
        levels = [(lev.curr.get_hash(), lev.snap.get_hash())
                  for lev in bm.bucket_list.levels]
        merges = lm.apply_stats.to_json()["buckets"]["merges"]
        spans = by_name(app.tracer)
        return out, levels, merges, spans
    finally:
        app.stop()


def test_hashes_and_merge_counts_do_not_know_the_tracer(tmp_path):
    off = _close_sequence(tmp_path, "off", False)
    on = _close_sequence(tmp_path, "on", True)
    assert on[0] == off[0] and len(on[0]) == 16      # header, list hashes
    assert on[1] == off[1]                           # every level's pair
    assert on[2] == off[2] > 0                       # ApplyStats merges
    assert off[3] == {}
    spans = on[3]
    assert len(spans["bucket.merge"]) == on[2]
    closes = spans["ledger.close"]
    assert len(closes) >= 16 and len(spans["bucket.snapshot"]) == len(closes)
    for sp in closes:
        assert sp.tags["cpu_ms"] >= 0.0 and sp.tags["gc_ms"] >= 0.0
        assert sp.tags["gc_full"] >= 0
    adds = {s.sid for s in spans["close.bucket_add"]}
    # genesis enters the list outside any close: that merge has no cause
    causes = [s.cause for s in spans["bucket.merge"]]
    assert set(causes) - {0} <= adds and causes.count(0) <= 1
    assert {s.parent for s in spans["bucket.fresh"]} - {0} <= adds
    assert {s.parent for s in spans["bucket.snapshot"]} <= adds
    adopted = spans["bucket.adopt"]
    assert any(s.tags["wrote"] for s in adopted)
    assert all(s.tags["bytes"] > 0 and s.tags["entries"] > 0
               for s in adopted)
    merges = {s.sid for s in spans["bucket.merge"]}
    assert any(s.parent in merges for s in adopted)     # a merge's output
    assert any(s.parent in adds for s in adopted)       # the fresh bucket


def test_slow_close_dump_says_what_the_collector_took(tmp_path, monkeypatch):
    import json
    from stellar_core_tpu.util.slow_execution import LogSlowExecution
    cfg = Config.test_config(0)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.FLIGHT_RECORDER_DIR = str(tmp_path)
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        # every close is a slow one: (threshold, on_slow)
        monkeypatch.setattr(LogSlowExecution.__init__, "__defaults__",
                            (0.0, None))
        app.manual_close()
        path = app.flight_recorder.last_path
        assert path is not None
        extra = json.load(open(path))["extra"]
        assert extra["gc_s"] >= 0.0 and "elapsed_s" in extra
    finally:
        app.stop()


def test_footprint_and_metrics_carry_the_collector():
    cfg = Config.test_config(0)
    cfg.DATABASE = "sqlite3://:memory:"
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        gc.collect()
        st, body = app.command_handler.handle_command("footprint", {})
        assert st == 200
        g = body["process"]["gc"]
        assert g["collections"] >= 1 and len(g["generations"]) == 3
        assert g["generations"][2]["collections"] >= 1
        st, body = app.command_handler.handle_command(
            "metrics", {"filter": "runtime."})
        assert st == 200
        assert set(body) == {"runtime.gc.pause", "runtime.gc.full.pause"}
        assert body["runtime.gc.full.pause"]["count"] >= 1
        st, text = app.command_handler.handle_command(
            "metrics", {"format": "prometheus", "filter": "runtime."})
        assert "sct_runtime_gc_full_pause_count" in text
    finally:
        app.stop()


# ------------------------------------------------------------- span_overlap

def _ctx(span_lists, ledgers):
    return {"spans": span_lists, "counts": {"ledgers": ledgers}}


ARGS = {"inner": ["runtime.gc.young", "runtime.gc.full"],
        "outer": ["ledger.close"], "scale": 1000, "per_count": "ledgers"}


@pytest.mark.parametrize("spans, ledgers, want", [
    # partial overlap: 0.5 s of a 1 s pause lies in the close
    ([[("ledger.close", 10.0, 2.0, 1, 0),
       ("runtime.gc.full", 11.5, 1.0, 2, 0)]], 1, 500.0),
    # whole, and one outside every close; two ledgers
    ([[("ledger.close", 10.0, 2.0, 1, 0),
       ("runtime.gc.young", 10.5, 0.25, 2, 0),
       ("runtime.gc.young", 13.0, 0.25, 3, 0)]], 2, 125.0),
    # recorded on another thread than the close: a pause stops them all;
    # overlapping closes (two nodes, one ring) are a union, not a sum
    ([[("ledger.close", 10.0, 2.0, 1, 0),
       ("ledger.close", 11.0, 2.0, 7, 0),
       ("bucket.merge", 10.1, 1.0, 2, 0),
       ("runtime.gc.full", 11.25, 0.5, 3, 0)]], 1, 500.0),
    # each replay's list against its own closes
    ([[("ledger.close", 0.0, 1.0, 1, 0),
       ("runtime.gc.full", 0.5, 0.25, 2, 0)],
      [("ledger.close", 0.0, 1.0, 1, 0),
       ("runtime.gc.young", 5.0, 0.25, 2, 0)]], 1, 250.0),
    # no inner span (the parent's program): 0.0, not nothing
    ([[("ledger.close", 10.0, 2.0, 1, 0)]], 4, 0.0),
    ([], 4, 0.0),
    # nothing to divide by
    ([[("ledger.close", 10.0, 2.0, 1, 0),
       ("runtime.gc.full", 10.5, 1.0, 2, 0)]], 0, 0.0),
])
def test_span_overlap(spans, ledgers, want):
    got = span_overlap.read(_ctx(spans, ledgers), ARGS)
    assert got is not None and got == pytest.approx(want)


# a first node's list: set-up's collections before its catchup starts,
# the prepare's between the phase's start and the closes
REPLAY = [("runtime.gc.full", 1.0, 0.5, 1, 0),          # set-up's
          ("runtime.gc.young", 2.0, 0.25, 2, 0),        # set-up's
          ("catchup.phase.get_has", 10.0, 0.1, 3, 0),
          ("runtime.gc.full", 10.5, 0.5, 4, 0),         # the prepare's
          ("ledger.close", 11.0, 1.0, 5, 0),
          ("runtime.gc.young", 11.5, 0.25, 6, 0),
          ("ledger.close", 12.5, 1.0, 7, 0),
          ("runtime.gc.full", 13.25, 0.5, 8, 0)]        # half past the end


def test_span_overlap_hull_and_count():
    hull = dict(ARGS, outer=["catchup.phase.get_has", "ledger.close"],
                hull=True)
    ctx = _ctx([REPLAY, []], 2)
    assert span_overlap.read(ctx, hull) == pytest.approx(
        (0.5 + 0.25 + 0.25) * 1000 / 2)
    full = dict(hull, inner=["runtime.gc.full"], count=True, scale=1)
    assert span_overlap.read(ctx, full) == pytest.approx(2 / 2)
    # the union, for comparison: only what lies under a close
    assert span_overlap.read(ctx, ARGS) == pytest.approx(
        (0.25 + 0.25) * 1000 / 2)
    assert span_overlap.read(_ctx([REPLAY[:2]], 2), hull) == 0.0
