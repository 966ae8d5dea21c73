"""Apply-side history works: bucket-state restore and checkpoint replay.

Role parity: reference `src/catchup/ApplyBucketsWork.cpp` (stream a
downloaded bucket-list snapshot into the ledger, then adopt it as the
live BucketList), `src/catchup/ApplyCheckpointWork.cpp:79-244` (stream
headers+txsets of one checkpoint, closing one ledger per crank via
`ApplyLedgerWork` → `LedgerManager::closeLedger`), and
`src/catchup/DownloadApplyTxsWork.cpp:23-104` (a BatchWork that overlaps
checkpoint N+1's download with checkpoint N's apply).

TPU batch site (SURVEY.md §3.4): before replaying a checkpoint, every
(source-key, signature, payload) triple in its txsets is drained through
the verifier in padded device batches, pre-warming the verify cache so
the synchronous per-tx checks during apply all hit. On a device engine
the drain streams (`SigVerifier.open_drain`, ISSUE 30): chunks leave for
the device while the rest is still being collected, and a ledger closes
as soon as its own chunk has landed.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from ..crypto.hashing import sha256
from ..history.archive import HistoryArchive, category_path
from ..history.archive_state import HistoryArchiveState, has_level_dicts
from ..history.checkpoints import checkpoints_in_range, first_in_checkpoint
from ..util.log import get_logger
from ..util.xdrstream import XDRInputFileStream
from ..work.basic_work import (FAILURE, RETRY_NEVER, RUNNING, SUCCESS,
                               BasicWork, State)
from ..work.work import BatchWork, ConditionalWork, WorkSequence
from ..xdr import LedgerHeaderHistoryEntry, TransactionHistoryEntry
from .works import GetAndUnzipRemoteFileWork

log = get_logger("History")


class ApplyBucketsWork(BasicWork):
    """Load the bucket snapshot named by a HAS into ledger state and
    fast-forward the LCL to that checkpoint's header.

    Reference parity: `catchup/ApplyBucketsWork.cpp` + the LCL reset in
    `CatchupWork::applyBucketsAtLedger`. Divergence checks: the restored
    bucket list's hash must equal the downloaded header's bucketListHash,
    else the archive state is corrupt."""

    def __init__(self, app, has: HistoryArchiveState,
                 header_entry: LedgerHeaderHistoryEntry) -> None:
        super().__init__(app.clock, "apply-buckets@%d"
                         % header_entry.header.ledgerSeq, RETRY_NEVER)
        self.app = app
        self.has = has
        self.header_entry = header_entry

    def on_run(self) -> State:
        from ..bucket import K_NUM_LEVELS
        from ..bucket.applicator import apply_buckets
        from ..bucket.bucket import Bucket

        bm = self.app.bucket_manager
        lm = self.app.ledger_manager
        header = self.header_entry.header

        # order: level 0 curr, 0 snap, 1 curr, ... (newest first)
        ordered: List[Bucket] = []
        for lv in self.has.levels:
            for hh in (lv.curr, lv.snap):
                if hh == "0" * 64:
                    continue
                b = (bm.get_bucket_by_hash(bytes.fromhex(hh))
                     if bm is not None else None)
                if b is None:
                    log.warning("apply-buckets: missing bucket %s", hh[:8])
                    return FAILURE
                ordered.append(b)

        # validate BEFORE destroying local state: the snapshot's whole-list
        # hash must already match the header (pure computation over the
        # level hashes, no mutation)
        from ..crypto.hashing import SHA256
        whole = SHA256()
        for lv in self.has.levels:
            lh = SHA256()
            lh.add(bytes.fromhex(lv.curr))
            lh.add(bytes.fromhex(lv.snap))
            whole.add(lh.finish())
        if whole.finish() != header.bucketListHash:
            log.warning("snapshot bucket list hash mismatch at %d — "
                        "refusing to touch local state", header.ledgerSeq)
            return FAILURE

        # the snapshot IS the state: drop anything local first, else
        # entries deleted on-network during the gap would survive as
        # phantoms (reference resets ledger state before bucket apply);
        # the invalidated flag blocks direct closes until the LCL
        # fast-forward below lands (cleared in set_last_closed_ledger)
        lm.entries_invalidated = True
        lm.ltx_root().clear_entries()
        n = apply_buckets(lm.ltx_root(), ordered)
        log.info("applied %d bucket entries at ledger %d", n,
                 header.ledgerSeq)

        if bm is not None:
            bm.assume_state(has_level_dicts(self.has), header.ledgerSeq,
                            header.ledgerVersion)

        lm.set_last_closed_ledger(header, self.header_entry.hash)
        lm._store_local_has()   # restart between here and the next close
        # must re-adopt THIS bucket list, not the pre-catchup one
        return SUCCESS


def checkpoint_verify_triples(frames, ltx) -> List[Tuple]:
    """Collect (key32, sig, contents-HASH) triples for a batch of tx
    frames — the whole-ledger/checkpoint drain of SURVEY.md §2.2. The
    message is the tx contents hash, exactly what SignatureChecker later
    verifies over (reference signs/verifies sha256(networkID‖envType‖tx),
    SignatureUtils.cpp:27-36), so the prewarmed cache entries are the ones
    the apply path hits. Signer sets (master + account signers of every
    tx/op source) resolve through ledger state, so multisig txs prewarm
    too; signers added mid-checkpoint are caught by the per-ledger
    incremental prewarm (only signers added within the SAME ledger fall
    back to the sync path)."""
    from ..transactions.transaction_frame import frames_sig_triples
    return frames_sig_triples(ltx, frames)


class ApplyCheckpointWork(BasicWork):
    """Replay one checkpoint's ledgers through LedgerManager.close_ledger,
    one ledger per crank (reference ApplyCheckpointWork.cpp:244 →
    ApplyLedgerWork.cpp:22-24). First crank drains the checkpoint's
    signatures through the batch verifier. One pipeline, the
    boundary's DrainStream, overlaps that verification with the closes
    on both backends: a device engine's drain streams and every close
    is gated on its own chunk (a close never dispatches, and never
    falls back, for a signature the drain holds); on the cpu+native
    path the checkpoint-wide drain is replaced by an ungated prewarm on
    the same worker (ledger N+1 verifies while N applies, a miss
    verifies inline). A prewarm is cache-warming only: stale or extra
    triples can never change an accept/reject decision, the apply path
    re-derives candidates against live state."""

    def __init__(self, app, download_dir: str, checkpoint: int,
                 first_seq: int, last_seq: int) -> None:
        super().__init__(app.clock, "apply-checkpoint %08x" % checkpoint,
                         RETRY_NEVER)
        self.app = app
        self.download_dir = download_dir
        self.checkpoint = checkpoint
        self.first_seq = first_seq
        self.last_seq = last_seq
        self._loaded = False
        self._headers: Dict[int, LedgerHeaderHistoryEntry] = {}
        self._txsets: Dict[int, object] = {}
        self._frames: Dict[int, object] = {}   # seq -> TxSetFrame
        self._next: int = first_seq
        self._sig_state_dirty = False   # a signer set changed mid-checkpoint
        self._prefetch_summary: Optional[dict] = None
        self._pipeline = None   # the boundary's DrainStream, once opened
        # seq -> the streamed drain's position after that ledger's
        # triples: what its close waits for
        self._gate: Dict[int, int] = {}

    def on_reset(self) -> None:
        self._loaded = False
        self._headers.clear()
        self._txsets.clear()
        self._frames.clear()
        self._next = self.first_seq
        self._sig_state_dirty = False
        self._prefetch_summary = None
        self._gate.clear()
        self._close_pipeline()

    def _close_pipeline(self) -> None:
        if self._pipeline is not None:
            self._pipeline.close()
            self._pipeline = None

    def _finish(self, st: State) -> None:
        self._close_pipeline()
        super()._finish(st)

    # -- pipelined per-ledger prewarm ---------------------------------------
    def _pipeline_enabled(self) -> bool:
        """Per-ledger pipelining replaces the checkpoint-wide drain
        exactly when that drain is redundant (sync CPU backend + native
        engine): there the verify cost sits INSIDE each close, and the
        only way to take it off the replay clock is to overlap it with
        the previous ledger's apply."""
        if not self._prewarm_redundant():
            return False
        cfg = getattr(self.app, "config", None)
        if not getattr(cfg, "CATCHUP_PIPELINE", True):
            return False
        return getattr(self.app, "sig_verifier", None) is not None

    def _range_groups(self, first: int, last: int) -> List[Tuple]:
        """(seq, frames) of every parsed txset in a ledger range."""
        return [(seq, self._frames[seq].frames)
                for seq in range(first, last + 1) if seq in self._frames]

    def _range_triples(self, first: int, last: int):
        """Candidate triples for a ledger range, collected on the MAIN
        thread against current state (one ltx + one signer cache for
        the whole batch)."""
        frames = [f for _seq, fs in self._range_groups(first, last)
                  for f in fs]
        if not frames:
            return []
        from ..ledger.ledgertxn import LedgerTxn
        ltx = LedgerTxn(self.app.ledger_manager.ltx_root())
        try:
            return checkpoint_verify_triples(frames, ltx)
        finally:
            ltx.rollback()

    def _pipeline_submit(self, first: int, last: int) -> None:
        """Hand the range's signature verification to the pipeline
        worker; the closes that follow overlap it. A prewarm is
        opportunistic — whatever the worker hasn't finished when a
        close needs it, the engine verifies synchronously (sharded),
        so there is no join barrier anywhere. The
        `apply.pipeline-stall` fault degrades to sequential: the
        collection still happens, the verify runs inline right here."""
        from ..util.faults import check_faults
        metrics = getattr(self.app, "metrics", None)
        triples = self._range_triples(first, last)
        if not triples:
            return
        if check_faults(self.app, "apply.pipeline-stall"):
            if metrics is not None:
                metrics.new_meter("catchup.pipeline.stall").mark()
            self.app.sig_verifier.prewarm_many(triples)
            return
        if self._pipeline is None:
            self._pipeline = self.app.sig_verifier.open_drain()
        if metrics is not None:
            metrics.new_meter("catchup.pipeline.prewarm").mark()
        self._pipeline.submit(triples)

    def _load(self) -> bool:
        lpath = os.path.join(self.download_dir,
                             "ledger-%08x.xdr" % self.checkpoint)
        tpath = os.path.join(self.download_dir,
                             "transactions-%08x.xdr" % self.checkpoint)
        if not os.path.exists(lpath):
            return False
        with XDRInputFileStream(lpath) as ins:
            for e in ins.read_all(LedgerHeaderHistoryEntry):
                self._headers[e.header.ledgerSeq] = e
        if os.path.exists(tpath):
            with XDRInputFileStream(tpath) as ins:
                for t in ins.read_all(TransactionHistoryEntry):
                    self._txsets[t.ledgerSeq] = t.txSet
        return True

    def _prewarm_redundant(self) -> bool:
        """The checkpoint prewarm exists to batch crypto into one device
        dispatch AND to pre-resolve signer sets in Python. With the
        native apply engine active it resolves signer sets in C and
        feeds the verifier per tx, and on the synchronous CPU backend
        batching buys nothing — the whole Python collection pass is then
        pure overhead on the replay clock."""
        verifier = getattr(self.app, "sig_verifier", None)
        if getattr(verifier, "name", "") != "cpu":
            return False
        lm = self.app.ledger_manager
        if not getattr(lm, "use_native_apply", True):
            return False
        from ..native import apply_engine
        return apply_engine() is not None

    def _stream_for_feed(self):
        """The streamed drain a device engine's feed goes through, or
        None where this feed drains synchronously: another engine, or
        the `apply.pipeline-stall` fault (which degrades to the whole
        drain at once, before the first close)."""
        from ..util.faults import check_faults
        verifier = self.app.sig_verifier
        if not verifier.wants_prewarm:
            return None
        if self._pipeline is not None:
            # a re-collection probes keys an earlier feed may still have
            # in flight, and a key goes to the device once: land them
            self._pipeline.wait(self._pipeline.position, self._next)
        if check_faults(self.app, "apply.pipeline-stall"):
            metrics = getattr(self.app, "metrics", None)
            if metrics is not None:
                metrics.new_meter("catchup.pipeline.stall").mark()
            return None
        if self._pipeline is None:
            self._pipeline = verifier.open_drain()
        return self._pipeline

    def _prewarm_groups(self, groups) -> None:
        """Collect candidate triples against CURRENT ledger state,
        ledger by ledger, and drain them through the batch verifier
        (cached triples are skipped at the probe: a fully-covered feed
        dispatches nothing). On a device engine each ledger's triples
        are fed to the streamed drain as they are collected and the
        drain's position after them is that ledger's gate; elsewhere
        the whole collection drains in one prewarm_many."""
        from ..transactions.transaction_frame import iter_sig_triples
        from ..util.tracing import app_span
        verifier = getattr(self.app, "sig_verifier", None)
        n_frames = sum(len(fs) for _seq, fs in groups)
        if verifier is None or not n_frames or self._prewarm_redundant():
            return
        from ..ledger.ledgertxn import LedgerTxn
        stream = self._stream_for_feed()
        triples: List[Tuple] = []
        # sig-batch prep (triple collection + signer-set resolution) and
        # the verify drain trace separately: prep is host CPU, the drain
        # is the backend-attributed phase (a streamed feed's probe is
        # the prep span's child)
        with app_span(self.app, "catchup.sig_prep", cat="catchup",
                      frames=n_frames):
            ltx = LedgerTxn(self.app.ledger_manager.ltx_root())
            try:
                fresh_by_group = iter_sig_triples(
                    ltx, (fs for _seq, fs in groups))
                for (seq, _fs), fresh in zip(groups, fresh_by_group):
                    if stream is None:
                        triples.extend(fresh)
                    else:
                        self._gate[seq] = stream.feed(fresh)
                if stream is not None:
                    stream.end()
            finally:
                ltx.rollback()
        if triples:
            verifier.prewarm_many(triples)

    def _await_drain(self, seq: int) -> None:
        """The close gate: ledger `seq` waits until the streamed drain
        has landed through its own triples, and never longer."""
        position = self._gate.pop(seq, None)
        if position is None or self._pipeline is None:
            return
        waited_s = self._pipeline.wait(position, seq)
        metrics = getattr(self.app, "metrics", None)
        if metrics is not None:
            metrics.new_meter("catchup.drain.ledgers").mark()
            if waited_s:
                metrics.new_meter("catchup.drain.ledgers_gated").mark()
                metrics.new_histogram("catchup.drain.gate_wait_ms").update(
                    waited_s * 1e3)

    def _prewarm(self) -> None:
        """The whole checkpoint's signatures, batched for the device."""
        from ..herder.txset import TxSetFrame
        from ..util.tracing import app_span
        net = self.app.config.network_id
        frames = []
        with app_span(self.app, "catchup.txset_parse", cat="catchup",
                      checkpoint=self.checkpoint) as psp:
            for seq in range(self.first_seq, self.last_seq + 1):
                ts = self._txsets.get(seq)
                if ts is None:
                    continue
                fr = TxSetFrame.from_wire(net, ts)
                self._frames[seq] = fr       # reused at apply: parse once
                for f in fr.frames:          # history wire is immutable:
                    f.freeze_signatures()    # skip per-serialize fp checks
                frames.extend(fr.frames)
            psp.set_tag("txs", len(frames))
        self._prewarm_groups(self._range_groups(self.first_seq,
                                                self.last_seq))
        if self._pipeline_enabled():
            # cpu+native: the whole checkpoint's signature verification
            # rides the pipeline worker underneath the apply loop
            self._pipeline_submit(self.first_seq, self.last_seq)
        self._prefetch_checkpoint(frames)
        log.debug("prewarmed checkpoint %08x (%d txs)",
                  self.checkpoint, len(frames))

    def _prefetch_checkpoint(self, frames) -> None:
        """Bulk-warm the root entry cache with the whole checkpoint's
        statically-knowable touched keys (ISSUE 9 satellite: the
        prefetch() count finally lands somewhere — the
        ledger.apply.prefetch.* coverage metrics via LedgerTxnRoot)."""
        root = self.app.ledger_manager.ltx_root()
        if not frames or not hasattr(root, "prefetch"):
            return
        from ..ledger.apply_stats import txset_prefetch_keys
        keys = txset_prefetch_keys(frames)
        # prefetch() returns only NEWLY loaded keys; coverage (resident
        # after the pass / requested, already-warm included) comes from
        # the stats aggregates it feeds — delta around the call
        stats = getattr(self.app.ledger_manager, "apply_stats", None)
        before = stats.prefetch_totals() if stats is not None else None
        loaded = root.prefetch(keys)
        covered = len(keys)
        if before is not None:
            after = stats.prefetch_totals()
            covered = after["cached"] - before["cached"]
        self._prefetch_summary = {
            "keys": len(keys), "covered": covered, "loaded": loaded}

    def _log_checkpoint_summary(self) -> None:
        """One line per applied checkpoint: prefetch coverage + the
        cumulative getPrefetchHitRate-parity hit rate."""
        stats = getattr(self.app.ledger_manager, "apply_stats", None)
        ps = self._prefetch_summary
        if stats is None or ps is None:
            return
        log.info(
            "checkpoint %08x applied: prefetch coverage %d/%d keys "
            "(%d newly loaded; hit-rate %.1f%% cumulative)",
            self.checkpoint, ps["covered"], ps["keys"], ps["loaded"],
            100.0 * stats.prefetch_hit_rate())

    @staticmethod
    def _mutates_signers(txset) -> bool:
        """Does any op in the set ADD verification pairs? Only a
        SET_OPTIONS carrying a signer does (flags/threshold/home-domain
        changes and master-weight edits don't: the master key is always
        a candidate; creations/merges only add/remove master keys)."""
        from ..xdr import OperationType
        for f in txset.frames:
            tx = getattr(f, "tx", None) or f.inner.tx
            for op in tx.operations:
                if op.body.disc == OperationType.SET_OPTIONS and                         op.body.value.signer is not None:
                    return True
        return False

    def _prewarm_ledger(self, txset) -> None:
        """Re-prewarm after a signer-set mutation: the whole-checkpoint
        prewarm resolved signer sets at checkpoint start, so signatures
        from signers added mid-checkpoint missed it, and each miss would
        otherwise dispatch a tiny padded device batch from inside
        check_signature. When the dirty flag flips, ALL remaining
        checkpoint frames re-collect against current state in ONE batch
        and the flag clears (a later mutation re-arms it) — the common
        no-mutation case skips collection entirely."""
        del txset
        if not self._sig_state_dirty:
            return
        self._sig_state_dirty = False
        if self._pipeline_enabled():
            # re-collect the remaining range against post-mutation state
            self._pipeline_submit(self._next, self.last_seq)
            return
        self._prewarm_groups(self._range_groups(self._next, self.last_seq))

    def on_run(self) -> State:
        from ..herder.txset import TxSetFrame
        from ..ledger.ledger_manager import LedgerCloseData

        if not self._loaded:
            from ..util.tracing import app_span
            with app_span(self.app, "catchup.load_files", cat="catchup",
                          checkpoint=self.checkpoint):
                ok = self._load()
            if not ok:
                return FAILURE
            with self.app.ledger_manager.apply_stats.reading("prepare"):
                self._prewarm()
            self._loaded = True

        lm = self.app.ledger_manager
        if self._next > self.last_seq:
            self._log_checkpoint_summary()
            return SUCCESS
        seq = self._next
        if seq <= lm.last_closed_ledger_num():
            self._next += 1           # already applied (restart overlap)
            return RUNNING
        entry = self._headers.get(seq)
        if entry is None:
            log.warning("checkpoint %08x missing header %d",
                        self.checkpoint, seq)
            return FAILURE
        net = self.app.config.network_id
        txset = self._frames.get(seq)
        if txset is None:
            ts = self._txsets.get(seq)
            txset = (TxSetFrame.from_wire(net, ts) if ts is not None else
                     TxSetFrame(net, entry.header.previousLedgerHash, []))
        with lm.apply_stats.reading("prepare"):
            self._prewarm_ledger(txset)
        self._await_drain(seq)
        lcd = LedgerCloseData(seq, txset, entry.header.scpValue)
        from ..util.tracing import app_span
        with app_span(self.app, "catchup.apply_ledger", cat="catchup",
                      seq=seq, checkpoint=self.checkpoint):
            lm.close_ledger(lcd)
        if not self._sig_state_dirty and self._mutates_signers(txset):
            self._sig_state_dirty = True
        if lm.lcl_hash != entry.hash:
            log.error("replay diverged at ledger %d: %s != %s", seq,
                      lm.lcl_hash.hex()[:8], entry.hash.hex()[:8])
            return FAILURE
        self._next += 1
        if self._next > self.last_seq:
            self._log_checkpoint_summary()
            return SUCCESS
        return RUNNING


class DownloadApplyTxsWork(BatchWork):
    """Pipelines checkpoint downloads with strictly-ordered application
    (reference DownloadApplyTxsWork.cpp:35-104): up to `max_concurrent`
    checkpoints download in parallel while applies run in checkpoint
    order behind a ConditionalWork latch."""

    def __init__(self, app, archive: HistoryArchive, download_dir: str,
                 first_seq: int, last_seq: int,
                 max_concurrent: int = 4) -> None:
        super().__init__(app.clock, "download-apply-txs [%d..%d]"
                         % (first_seq, last_seq), max_concurrent)
        self.app = app
        self.archive = archive
        self.download_dir = download_dir
        self.first_seq = first_seq
        self.last_seq = last_seq
        freq = app.config.CHECKPOINT_FREQUENCY
        self._freq = freq
        self._checkpoints = list(checkpoints_in_range(first_seq, last_seq,
                                                      freq))
        self._idx = 0
        # apply gate: checkpoints apply strictly in order
        self._applied_up_to = first_seq - 1

    def do_reset(self) -> None:
        self._idx = 0
        self._applied_up_to = self.first_seq - 1

    def yield_more_work(self) -> Optional[BasicWork]:
        if self._idx >= len(self._checkpoints):
            return None
        c = self._checkpoints[self._idx]
        self._idx += 1
        lo = max(self.first_seq, first_in_checkpoint(c, self._freq))
        hi = min(self.last_seq, c)

        gets: List[BasicWork] = []
        for cat in ("ledger", "transactions"):
            local = os.path.join(self.download_dir,
                                 "%s-%08x.xdr" % (cat, c))
            if os.path.exists(local):
                continue              # verify phase already fetched it
            gets.append(GetAndUnzipRemoteFileWork(
                self.app, self.archive, category_path(cat, c, ".xdr.gz"),
                local))

        apply_work = ApplyCheckpointWork(self.app, self.download_dir, c,
                                         lo, hi)
        gate_lo = lo

        gated = ConditionalWork(
            self.clock, "apply-gate %08x" % c,
            lambda gate_lo=gate_lo: self._applied_up_to == gate_lo - 1,
            apply_work)

        apply_work.on_success = \
            lambda hi=hi: setattr(self, "_applied_up_to", hi)
        return WorkSequence(self.clock, "download-apply %08x" % c,
                            gets + [gated])
