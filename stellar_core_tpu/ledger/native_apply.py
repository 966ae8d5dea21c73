"""Close-time glue for the native transaction-apply fast path.

native/applyc.c implements the fee + apply phases of a ledger close for
every wire op type, plain v1 AND fee-bump envelopes, and muxed account
references (protocol >= 10). This module decides per close whether the
engine may run, feeds it (entry lookups, batched signature verifies,
order-book scans), chooses the execution mode (conflict-graph parallel
clusters vs serial), and installs its outputs so everything downstream
of the apply loop — result hash, bucket-list delta, tx/fee history rows,
close meta, invariants — runs unchanged Python over identical state.

The engine returns {"bail": reason} (or None) for ANY input outside its
subset before mutating shared state, so the Python apply path (the
differential-test oracle, tests/test_native_apply.py) remains the single
source of semantics. Residual bail reasons after full op coverage
(ISSUE 13): non-ed25519 signer keys (`signer-key-type`), >20-signer
shapes (`multisig-shape`), wire thresholds over 255 (`threshold-range`),
due inflation payouts pre-protocol-12 (`inflation-payout`), op shapes
whose Python apply raises (`op-shape`), and op-level auth failures whose
Python result mix is unserializable (`op-auth`). Every bail classifies
to `ledger.apply.native-bail.<reason>`.

Parallel close: the engine partitions the txset into clusters by
statically-touched entries and applies disjoint clusters on worker
threads with the GIL released; the differential oracle asserts
serial-equivalence for every schedule. `apply.cluster-fail`
(util.faults) degrades a would-be-parallel close to serial — the same
close, one thread. A close with any transaction whose key set depends
on the order book (offers, path payments, allow-trust revokes) is
`dynamic`: no clusters, one thread, the GIL held throughout
(`ledger.apply.cluster.dynamic-close`, beside `serial-close`, which
goes on counting it). Gate: SCT_NATIVE_APPLY=0 disables; Config
NATIVE_PARALLEL_APPLY / NATIVE_PARALLEL_WORKERS size the worker pool
(SCT_PARALLEL_APPLY=0 forces serial).
"""

from __future__ import annotations

import os
from typing import List, Optional


def _classify_engine_bail(reason: str) -> str:
    """Engine reason string -> metric-safe reason. `op-<n>` carries the
    numeric wire type; name it (`op-manage-sell-offer`) so operators
    read traffic, not enum values."""
    if reason.startswith("op-") and reason[3:].isdigit():
        try:
            from .apply_stats import op_type_name
            return "op-" + op_type_name(int(reason[3:]))
        except ValueError:
            return reason
    return reason


def _bail(stats, reason: str) -> bool:
    """Record one classified ineligibility/bailout; returns False so
    call sites read `return _bail(stats, "...")`."""
    if stats is not None:
        stats.record_bail(reason)
    return False


def parallel_workers(lm) -> int:
    """Effective worker count for the conflict-graph parallel close:
    Config NATIVE_PARALLEL_WORKERS when set (> 0), else cpu_count
    capped at 16 (measured on the bench host: wider pools keep enough
    workers scheduled under sandboxed kernels that park threads — 16
    beat 8 by 4x on the conflict-light gate leg). 1 disables
    parallelism."""
    if os.environ.get("SCT_PARALLEL_APPLY") == "0":
        return 1
    cfg = getattr(getattr(lm, "app", None), "config", None)
    if cfg is not None and not getattr(cfg, "NATIVE_PARALLEL_APPLY", True):
        return 1
    n = int(getattr(cfg, "NATIVE_PARALLEL_WORKERS", 0) or 0)
    if n > 0:
        return n
    return min(16, os.cpu_count() or 1)


def _book_loader(lm, stats, load, kind: str):
    """The engine's order-book callback `load` (`kind` "book": one whole
    book side; "account": one seller's offers) under a `close.book_load`
    span, counted into `ledger.apply.book.*`."""
    from ..util.tracing import app_span
    app = getattr(lm, "app", None)

    def loader(*key) -> list:
        with app_span(app, "close.book_load", cat="ledger",
                      kind=kind) as sp:
            rows = load(*key)
            if sp.live:
                sp.set_tag("rows", len(rows))
        if stats is not None:
            stats.record_book_load(len(rows))
        return rows

    return loader


def native_apply_txset(lm, ltx, frames, base_fee: Optional[int],
                       verifier, force_mode: Optional[str] = None) -> bool:
    """Run the whole txset's fee+apply phases natively. Returns False on
    any ineligibility/bailout with NO state mutated (the caller then runs
    the Python phases); True means ltx, the header fee pool + id pool,
    and every frame's result/meta are populated exactly as the Python
    path would have. Per-op attribution, bail classification, and
    cluster telemetry land in `lm.apply_stats` (ledger/apply_stats.py).

    `force_mode` ("serial"/"parallel") pins the execution mode — the
    differential oracle's forced-parallel-vs-serial equality leg."""
    stats = getattr(lm, "apply_stats", None)
    if not getattr(lm, "use_native_apply", True):
        return _bail(stats, "disabled")
    from ..native import apply_engine
    eng = apply_engine()
    if eng is None:
        return _bail(stats, "no-engine")
    from ..transactions.transaction_frame import (
        FeeBumpTransactionFrame, TransactionFrame,
    )
    if ltx._changes:
        return _bail(stats, "open-changes")
    header = ltx.load_header()
    if header.ledgerVersion < 10:
        return _bail(stats, "protocol-pre10")
    for f in frames:
        if type(f) is not TransactionFrame and \
                type(f) is not FeeBumpTransactionFrame:
            return _bail(stats, "frame-type")
    root = lm.root
    get_blob = getattr(root, "get_entry_blob", None)
    book = getattr(root, "offers_for_book_blobs", None)
    acct_offers = getattr(root, "offers_by_account_blobs", None)
    if get_blob is None or book is None or acct_offers is None:
        return _bail(stats, "no-blob-lookup")
    if verifier is None:
        from ..crypto.batch_verifier import CPU_VERIFIER
        verifier = CPU_VERIFIER
    params = {
        "ledgerVersion": header.ledgerVersion,
        "ledgerSeq": header.ledgerSeq,
        "closeTime": header.scpValue.closeTime,
        "baseFee": header.baseFee,
        "baseReserve": header.baseReserve,
        "effBaseFee": base_fee if base_fee is not None else header.baseFee,
        "feePool": header.feePool,
        "idPool": header.idPool,
        "inflationSeq": header.inflationSeq,
    }
    envs: List[bytes] = [f.envelope_bytes() for f in frames]
    # fee bumps carry outer||inner contents hashes (the engine verifies
    # outer signatures over the outer hash, inner over the inner)
    hashes: List[bytes] = [
        f.contents_hash() + f.inner.contents_hash()
        if hasattr(f, "inner") else f.contents_hash()
        for f in frames]
    # tests pin the schedule (forced-parallel vs serial equality leg)
    # either per call or per manager
    mode = force_mode or getattr(lm, "native_force_mode", None) or "auto"
    workers = parallel_workers(lm)
    if mode == "parallel" and workers < 2:
        workers = 2
    if mode == "auto" and workers > 1:
        # fault site: a parallel close degrades to the same close on one
        # thread (docs/robustness.md) — never to the Python path
        from ..util.faults import check_faults
        if check_faults(getattr(lm, "app", None), "apply.cluster-fail"):
            mode = "serial"
            if stats is not None:
                stats.record_cluster_degrade()
    opts = {"workers": workers, "mode": mode}
    out = eng.apply_close(params, envs, hashes, get_blob,
                          verifier.prewarm_many,
                          _book_loader(lm, stats, book, "book"),
                          _book_loader(lm, stats, acct_offers, "account"),
                          opts)
    if out is None:
        return _bail(stats, "engine-ineligible")
    if "bail" in out:
        return _bail(stats, _classify_engine_bail(out["bail"]))
    header.feePool = out["feePool"]
    header.idPool = out["idPool"]
    ltx.inject_native_changes(out["changes"])
    for f, rb, fcb, mb in zip(frames, out["results"], out["fee_changes"],
                              out["meta"]):
        f.set_native_apply_output(rb, fcb, mb)
    if stats is not None:
        if out.get("op_stats"):
            stats.record_native_op_table(out["op_stats"])
        cl = out.get("clusters")
        if cl:
            stats.record_clusters(cl["count"], cl["max_txs"],
                                  cl["workers"], bool(cl["parallel"]),
                                  apply_ns=cl.get("apply_ns", 0),
                                  dynamic=bool(cl.get("dynamic")))
        bk = out.get("book")
        if bk and bk["best_queries"]:
            stats.record_book_index(bk["best_queries"], bk["best_steps"])
    return True
