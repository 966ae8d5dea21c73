"""History publish + catchup tests.

Role parity: reference `src/history/test/HistoryTests.cpp:38-1035`
(CatchupSimulation: publish to a tmpdir file archive, generate ledgers,
catch a second app up from it) and `src/catchup/test/CatchupWorkTests.cpp`
(range arithmetic).
"""

import os

import pytest

from stellar_core_tpu.catchup import (CatchupConfiguration,
                                      calculate_catchup_range)
from stellar_core_tpu.history.archive import HistoryArchive
from stellar_core_tpu.history.checkpoints import (checkpoint_containing,
                                                  checkpoints_in_range,
                                                  first_in_checkpoint,
                                                  is_last_in_checkpoint)
from stellar_core_tpu.ledger.ledger_manager import (LedgerCloseData,
                                                    LedgerManagerState)
from stellar_core_tpu.main.application import Application
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.testing import AppLedgerAdapter
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.work.basic_work import State
from stellar_core_tpu.xdr import LedgerHeader, TransactionEnvelope

FREQ = 8  # small checkpoints so tests stay fast


# ---------------------------------------------------------------- arithmetic

def test_checkpoint_arithmetic():
    assert checkpoint_containing(1, 64) == 63
    assert checkpoint_containing(63, 64) == 63
    assert checkpoint_containing(64, 64) == 127
    assert is_last_in_checkpoint(63, 64)
    assert not is_last_in_checkpoint(64, 64)
    assert first_in_checkpoint(63, 64) == 1
    assert first_in_checkpoint(127, 64) == 64
    assert list(checkpoints_in_range(1, 130, 64)) == [63, 127, 191]


def test_catchup_range_complete():
    r = calculate_catchup_range(1, CatchupConfiguration(100, 2**32 - 1), 64)
    assert not r.apply_buckets
    assert (r.replay_first, r.replay_last) == (2, 100)


def test_catchup_range_minimal():
    r = calculate_catchup_range(1, CatchupConfiguration(127, 0), 64)
    assert r.apply_buckets and r.apply_buckets_at == 127
    assert r.replay_count() == 0
    # mid-checkpoint target: buckets at the checkpoint below
    r = calculate_catchup_range(1, CatchupConfiguration(100, 0), 64)
    assert r.apply_buckets and r.apply_buckets_at == 63
    assert (r.replay_first, r.replay_last) == (64, 100)


def test_catchup_range_recent():
    r = calculate_catchup_range(1, CatchupConfiguration(127, 10), 64)
    assert r.apply_buckets and r.apply_buckets_at == 63
    assert (r.replay_first, r.replay_last) == (64, 127)
    # count covers the whole gap -> pure replay
    r = calculate_catchup_range(120, CatchupConfiguration(127, 10), 64)
    assert not r.apply_buckets
    assert (r.replay_first, r.replay_last) == (121, 127)


# ---------------------------------------------------------------- fixtures

def make_app(tmp_path, n, archive_root, writable=True, protocol=None):
    cfg = Config.test_config(n)
    if protocol is not None:
        cfg.LEDGER_PROTOCOL_VERSION = protocol
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.CHECKPOINT_FREQUENCY = FREQ
    arch = HistoryArchive.local_dir("test", str(archive_root))
    d = {"get": arch.get_tmpl, "mkdir": arch.mkdir_tmpl}
    if writable:
        d["put"] = arch.put_tmpl
    cfg.HISTORY = {"test": d}
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    app = Application(clock, cfg)
    app.enable_buckets(str(tmp_path / ("buckets-%d" % n)))
    app.start()
    return app


def close_ledgers_with_traffic(app, upto):
    """Manual-close ledgers with a payment in most of them."""
    adapter = AppLedgerAdapter(app)
    root = adapter.root_account()
    alice = root.create(10**10)
    while app.ledger_manager.last_closed_ledger_num() < upto:
        f = alice.tx([alice.op_payment(root.account_id, 1000)])
        app.submit_transaction(f)
        app.manual_close()
    return alice


def run_work(app, work, max_cranks=200000):
    for _ in range(max_cranks):
        if work.is_done():
            break
        app.crank(False)
    assert work.is_done(), "work did not finish"
    return work.state


@pytest.fixture
def publisher(tmp_path):
    archive_root = tmp_path / "archive"
    os.makedirs(archive_root, exist_ok=True)
    app = make_app(tmp_path, 0, archive_root)
    close_ledgers_with_traffic(app, 2 * FREQ + 3)   # past two checkpoints
    # let queued publishes run
    app.crank_until(lambda: app.history_manager.publish_queue() == [],
                    max_cranks=5000)
    assert app.history_manager.published_checkpoints >= 2
    return app, tmp_path, archive_root


# ---------------------------------------------------------------- publish

def test_publish_layout(publisher):
    app, tmp_path, archive_root = publisher
    c1 = FREQ - 1
    assert (archive_root / ".well-known" /
            "stellar-history.json").exists()
    h = "%08x" % c1
    sub = h[0:2] + "/" + h[2:4] + "/" + h[4:6]
    for cat in ("ledger", "transactions", "results", "scp"):
        assert (archive_root / cat / h[0:2] / h[2:4] / h[4:6] /
                ("%s-%s.xdr.gz" % (cat, h))).exists(), cat
    # HAS names real bucket files
    from stellar_core_tpu.history.archive_state import HistoryArchiveState
    has = HistoryArchiveState.from_json(
        (archive_root / ".well-known" / "stellar-history.json").read_text())
    assert has.current_ledger == 2 * FREQ - 1
    for hh in has.bucket_hashes():
        assert (archive_root / "bucket" / hh[0:2] / hh[2:4] / hh[4:6] /
                ("bucket-%s.xdr.gz" % hh)).exists()


# ---------------------------------------------------------------- catchup

def test_catchup_complete(publisher):
    app_a, tmp_path, archive_root = publisher
    app_b = make_app(tmp_path, 1, archive_root, writable=False)
    tip = 2 * FREQ - 1

    work = app_b.catchup_manager.start_catchup(
        CatchupConfiguration.complete())
    assert work is not None
    assert run_work(app_b, work) == State.SUCCESS

    lm_b = app_b.ledger_manager
    assert lm_b.last_closed_ledger_num() == tip
    # byte-identical chain
    row = app_a.database.execute(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?",
        (tip,)).fetchone()
    assert lm_b.lcl_hash.hex() == row[0]
    assert lm_b.is_synced()


def test_catchup_minimal_buckets(publisher):
    app_a, tmp_path, archive_root = publisher
    app_b = make_app(tmp_path, 2, archive_root, writable=False)
    tip = 2 * FREQ - 1

    work = app_b.catchup_manager.start_catchup(
        CatchupConfiguration.minimal())
    assert run_work(app_b, work) == State.SUCCESS

    lm_b = app_b.ledger_manager
    assert lm_b.last_closed_ledger_num() == tip
    row = app_a.database.execute(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?",
        (tip,)).fetchone()
    assert lm_b.lcl_hash.hex() == row[0]
    # bucket list restored bit-for-bit
    assert app_b.bucket_manager.get_hash() == \
        app_a.ledger_manager.lcl_header.bucketListHash or \
        app_b.bucket_manager.get_hash() == \
        lm_b.lcl_header.bucketListHash
    # state usable: root balance matches A's at that ledger
    root = app_b.network_root_key().public_key
    assert AppLedgerAdapter(app_b).balance(root) > 0


@pytest.mark.parametrize("mode,phases", [
    ("complete", ["get_has", "download_verify", "apply_txs"]),
    ("minimal", ["get_has", "get_apply_has", "download_verify", "buckets"]),
])
def test_catchup_records_one_span_per_phase(publisher, mode, phases):
    """catchup.phase.<name>: one completed span per phase as it ends, in
    order, end to start; a replay that applies no bucket records no
    `buckets` phase (and a bucket catchup to the tip no `apply_txs`)."""
    app_a, tmp_path, archive_root = publisher
    app_b = make_app(tmp_path, 7, archive_root, writable=False)
    app_b.tracer.enable()
    t0 = app_b.tracer.now()
    work = app_b.catchup_manager.start_catchup(
        getattr(CatchupConfiguration, mode)())
    assert run_work(app_b, work) == State.SUCCESS
    t1 = app_b.tracer.now()
    spans = [s for s in app_b.tracer.spans()
             if s.name.startswith("catchup.phase.")]
    assert [s.name for s in spans] == ["catchup.phase." + p for p in phases]
    assert all(s.parent == 0 and s.dur >= 0.0 for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a.t0 + a.dur <= b.t0 + 1e-6
    assert t0 <= spans[0].t0 and spans[-1].t0 + spans[-1].dur <= t1
    # with tracing off nothing is stamped
    app_c = make_app(tmp_path, 8, archive_root, writable=False)
    work = app_c.catchup_manager.start_catchup(
        getattr(CatchupConfiguration, mode)())
    assert run_work(app_c, work) == State.SUCCESS
    assert work._phase_t0 == 0.0 and app_c.tracer.spans() == []


def make_lcd_from_db(app_src, seq):
    """Rebuild the LedgerCloseData node A externalized for `seq`."""
    from stellar_core_tpu.herder.txset import TxSetFrame
    from stellar_core_tpu.transactions.transaction_frame import \
        TransactionFrame
    db = app_src.database
    hrow = db.execute(
        "SELECT data FROM ledgerheaders WHERE ledgerseq = ?",
        (seq,)).fetchone()
    header = LedgerHeader.from_xdr(hrow[0])
    frames = [
        TransactionFrame.make_from_wire(
            app_src.config.network_id, TransactionEnvelope.from_xdr(r[0]))
        for r in db.execute(
            "SELECT txbody FROM txhistory WHERE ledgerseq = ? "
            "ORDER BY txindex", (seq,)).fetchall()]
    ts = TxSetFrame(app_src.config.network_id,
                    header.previousLedgerHash, frames)
    return LedgerCloseData(seq, ts, header.scpValue)


def test_online_catchup_with_buffered_ledgers(publisher):
    """A node that falls behind buffers live ledgers, heals from the
    archive, then drains the buffer (reference CatchupManagerImpl)."""
    app_a, tmp_path, archive_root = publisher
    top = app_a.ledger_manager.last_closed_ledger_num()   # 2*FREQ+3
    tip = 2 * FREQ - 1                                    # archive tip

    app_b = make_app(tmp_path, 3, archive_root, writable=False)
    cm = app_b.catchup_manager
    lm_b = app_b.ledger_manager

    # live stream arrives with a gap: first seq far ahead of genesis
    for seq in range(tip + 1, top + 1):
        lm_b.value_externalized(make_lcd_from_db(app_a, seq))
    assert lm_b.state == LedgerManagerState.LM_CATCHING_UP_STATE
    assert cm.buffered_count() == top - tip
    assert cm.catchup_running()

    app_b.crank_until(lambda: not cm.catchup_running(), max_cranks=200000)
    # catchup hit the archive tip, then the buffer drained to `top`
    assert lm_b.last_closed_ledger_num() == top
    assert lm_b.is_synced()
    row = app_a.database.execute(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?",
        (top,)).fetchone()
    assert lm_b.lcl_hash.hex() == row[0]


def test_catchup_detects_corrupt_archive(publisher):
    """Flip a byte in a published ledger file: VerifyLedgerChainWork must
    fail the catchup (reference VerifyLedgerChainWork hash checks)."""
    app_a, tmp_path, archive_root = publisher
    import gzip
    c = "%08x" % (FREQ - 1)
    p = (archive_root / "ledger" / c[0:2] / c[2:4] / c[4:6] /
         ("ledger-%s.xdr.gz" % c))
    raw = bytearray(gzip.decompress(p.read_bytes()))
    raw[40] ^= 0xFF
    p.write_bytes(gzip.compress(bytes(raw)))

    app_b = make_app(tmp_path, 4, archive_root, writable=False)
    work = app_b.catchup_manager.start_catchup(
        CatchupConfiguration.complete())
    assert run_work(app_b, work) == State.FAILURE
    assert app_b.ledger_manager.last_closed_ledger_num() == 1


def test_trusted_anchor_rejects_wrong_chain(publisher):
    """A consensus-derived trusted hash that doesn't match the archive's
    chain must fail the catchup before any state is touched."""
    from stellar_core_tpu.catchup.catchup_work import CatchupWork
    app_a, tmp_path, archive_root = publisher
    tip = 2 * FREQ - 1
    app_b = make_app(tmp_path, 6, archive_root, writable=False)
    work = CatchupWork(app_b, CatchupConfiguration.complete(),
                       trusted_hash=(tip, b"\x13" * 32))
    app_b.work_scheduler.schedule_work(work)
    assert run_work(app_b, work) == State.FAILURE
    assert app_b.ledger_manager.last_closed_ledger_num() == 1

    # and the matching anchor passes
    row = app_a.database.execute(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?",
        (tip,)).fetchone()
    app_c = make_app(tmp_path, 7, archive_root, writable=False)
    work = CatchupWork(app_c, CatchupConfiguration.complete(),
                       trusted_hash=(tip, bytes.fromhex(row[0])))
    app_c.work_scheduler.schedule_work(work)
    assert run_work(app_c, work) == State.SUCCESS
    assert app_c.ledger_manager.last_closed_ledger_num() == tip


def test_prewarm_batches_checkpoint_sigs(publisher):
    """Catchup replay drains whole-checkpoint signature batches through
    the verifier (SURVEY.md §3.4 TPU batch site)."""
    app_a, tmp_path, archive_root = publisher

    from stellar_core_tpu.crypto.batch_verifier import (
        CpuSigVerifier, SigVerifier)

    class CountingVerifier(SigVerifier):
        def __init__(self):
            super().__init__(CpuSigVerifier(), max_pending=0)
            self.batches = []
            self.distinct = set()

        def prewarm_many(self, triples):
            self.batches.append(len(triples))
            self.distinct.update(triples)
            return super().prewarm_many(triples)

    app_b = make_app(tmp_path, 5, archive_root, writable=False)
    cv = CountingVerifier()
    app_b.sig_verifier = cv
    # the CPU-backend + native-apply combination skips the bulk
    # checkpoint drain entirely (the engine resolves signer sets in C
    # per tx, and batching buys nothing on a synchronous backend —
    # DownloadApplyTxsWork._prewarm_redundant); pin the Python apply
    # path, the consumer the whole-checkpoint prewarm exists to feed
    app_b.ledger_manager.use_native_apply = False

    # the prewarm must cache under the exact (key, sig, contents-hash)
    # the apply-time SignatureChecker looks up: after the per-checkpoint
    # prewarm dispatch, NO further raw verifies happen (regression: a
    # wrong message in the triples made every sig verify twice and, under
    # the TPU backend, dispatched a tiny device batch per tx)
    from stellar_core_tpu.crypto import keys as _keys
    _keys.flush_verify_cache()
    raw_calls = [0]
    orig_raw = _keys.raw_verify
    orig_batch = _keys.raw_verify_batch
    _keys.raw_verify = lambda k, s, m: (
        raw_calls.__setitem__(0, raw_calls[0] + 1) or orig_raw(k, s, m))

    def counting_batch(triples):
        # CpuSigVerifier.verify_many drains misses through the bulk
        # call: one native call without `cryptography`, a loop over the
        # (patched) raw_verify with it. Each triple counts ONCE either
        # way — the invariant chip_smoke.py asserts on the device.
        n0 = raw_calls[0]
        out = orig_batch(triples)
        raw_calls[0] = n0 + len(triples)
        return out

    _keys.raw_verify_batch = counting_batch
    try:
        work = app_b.catchup_manager.start_catchup(
            CatchupConfiguration.complete())
        assert run_work(app_b, work) == State.SUCCESS
    finally:
        _keys.raw_verify = orig_raw
        _keys.raw_verify_batch = orig_batch
    # one bulk batch per checkpoint covering many ledgers' signatures,
    # plus per-ledger incremental prewarms that are cache-covered no-ops
    assert len(cv.batches) >= 2
    assert max(cv.batches) > 1
    # every DISTINCT signature triple raw-verifies exactly once — the
    # apply path and the incremental prewarms all hit the cache
    assert raw_calls[0] == len(cv.distinct)


@pytest.mark.min_version(13)
def test_replay_history_containing_fee_bump(publisher):
    """A fee-bump envelope in published history replays byte-exactly
    (checkpoint prewarm collects outer fee-source + inner signatures)."""
    from stellar_core_tpu.transactions.transaction_frame import (
        FeeBumpTransactionFrame,
    )
    from stellar_core_tpu.xdr import (
        EnvelopeType, FeeBumpTransaction, FeeBumpTransactionEnvelope,
        TransactionEnvelope, _Ext,
    )
    from stellar_core_tpu.xdr.transaction import _InnerTxEnvelope

    app_a, tmp_path, archive_root = publisher
    ad = AppLedgerAdapter(app_a)
    root = ad.root_account()
    payer = root.create(10**9)
    sponsor = root.create(10**9)
    inner = payer.tx([payer.op_payment(root.account_id, 77)], fee=100)
    fb = FeeBumpTransaction(
        feeSource=sponsor.muxed, fee=1000,
        innerTx=_InnerTxEnvelope(EnvelopeType.ENVELOPE_TYPE_TX,
                                 inner.envelope.value),
        ext=_Ext.v0())
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP,
        FeeBumpTransactionEnvelope(tx=fb, signatures=[]))
    frame = FeeBumpTransactionFrame(app_a.config.network_id, env)
    frame.add_signature(sponsor.sk)
    assert app_a.submit_transaction(frame) == 0
    app_a.manual_close()
    # run to the next checkpoint boundary and publish it
    while (app_a.ledger_manager.last_closed_ledger_num() + 1) % FREQ:
        app_a.manual_close()
    app_a.crank_until(lambda: app_a.history_manager.publish_queue() == [],
                      max_cranks=5000)

    app_b = make_app(tmp_path, 9, archive_root, writable=False)
    work = app_b.catchup_manager.start_catchup(
        CatchupConfiguration.complete())
    assert run_work(app_b, work) == State.SUCCESS
    lm_b = app_b.ledger_manager
    assert lm_b.lcl_hash.hex() == app_a.database.execute(
        "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?",
        (lm_b.last_closed_ledger_num(),)).fetchone()[0]
    assert AppLedgerAdapter(app_b).balance(payer.account_id) == \
        ad.balance(payer.account_id)


def test_bucket_apply_resumes_pre12_shadowed_merges(tmp_path):
    """r5 regression: a bucket-apply catchup at protocol < 12 must resume
    the publisher's in-flight SHADOWED merges exactly — the HAS now
    serializes each level's next merge (output hash, or input+shadow
    hashes while in flight), and assume_state reconstructs it. Before the
    fix, restart_merges re-kicked pre-12 merges shadowless, the replayer's
    bucketListHash forked on its first own close, and the buffered drain
    rejected every later ledger ("txset based on wrong ledger")."""
    archive_root = tmp_path / "archive"
    os.makedirs(archive_root, exist_ok=True)
    app_a = make_app(tmp_path, 0, archive_root, protocol=9)
    close_ledgers_with_traffic(app_a, 2 * FREQ + 3)
    app_a.crank_until(lambda: app_a.history_manager.publish_queue() == [],
                      max_cranks=5000)
    assert app_a.ledger_manager.lcl_header.ledgerVersion == 9

    app_b = make_app(tmp_path, 3, archive_root, writable=False, protocol=9)
    top = app_a.ledger_manager.last_closed_ledger_num()
    tip = 2 * FREQ - 1
    for seq in range(tip + 1, top + 1):
        app_b.ledger_manager.value_externalized(make_lcd_from_db(app_a, seq))
    app_b.crank_until(
        lambda: not app_b.catchup_manager.catchup_running(),
        max_cranks=200000)
    assert app_b.ledger_manager.last_closed_ledger_num() == top
    assert app_b.ledger_manager.lcl_hash == app_a.ledger_manager.lcl_hash
