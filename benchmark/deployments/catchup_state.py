"""Deployment driver `catchup_state`: the `catchup` driver over a node
that restarts from its own disk (traffic/state_history.py). The window's
bookkeeping, the negative control and the device-path check are the
catchup driver's. Here every node of the window, the first too, starts
inside it by `Application.start()` from a copy of what the publisher's
disk held one checkpoint before the tip, and replays that checkpoint
over a large account state; the copy itself is made beside the running
replay on a thread of the driver's, so the window times the node's start
and its replay and not the benchmark's copying. A full replay is held
to the generator's model and to the agreement of the node's two stores.
"""

from __future__ import annotations

import gc
import os
import shutil
import threading
import time

from ..harness import annotate
from ..harness.runner import RunError
from ..traffic.state_history import StateHistory
from . import catchup


class Deployment(catchup.Deployment):
    def __init__(self, config: dict, workload: dict, seed: int,
                 workdir: str, trace: bool, node_hook=None) -> None:
        from stellar_core_tpu.ledger.apply_stats import ApplyStats
        if not hasattr(ApplyStats, "reading"):
            raise RunError("this program keeps no cold-read meters "
                           "(ledger.root.cold-read.*) and has no "
                           "node.restore span: the cell cannot be read "
                           "on it")
        super().__init__(config, workload, seed, workdir, trace,
                         node_hook=node_hook)
        self.hist = StateHistory(config, workload["traffic"], seed, workdir)
        self._prep = None       # the thread that makes the next node's dir
        self._dirs = {}         # node -> its directory, until deleted
        self._drop = []         # directories of nodes done with

    # -- node directories ----------------------------------------------------
    def _prepare(self, n: int, drop: list) -> None:
        for d in drop:
            shutil.rmtree(d, ignore_errors=True)
        self.hist.clone_snapshot(self.hist.node_dir(n))

    def _prepare_next(self) -> None:
        """Node n+1's directory, and the end of the directories of nodes
        already compared, on a thread beside whatever runs now."""
        drop, self._drop = self._drop, []
        self._prep = threading.Thread(
            target=self._prepare, args=(self.n_nodes + 1, drop),
            name="bench-node-dir")
        self._prep.start()

    def _new_node(self):
        if self._prep is not None:      # the warm-up node has no snapshot
            self._prep.join()
            self._prep = None
        app = super()._new_node()
        self._dirs[app] = self.hist.node_dir(self.n_nodes)
        return app

    def setup(self) -> dict:
        """The catchup driver's set-up: its first node has no state, warms
        the device shapes while the publisher, on this thread, loads the
        state and writes the archive, and replays nothing. Then the
        first replay's directory is made, and what set-up leaves
        resident (the publisher, the model, a million ids) goes out of
        the collector's reach, as in `catchup_dex`."""
        info = super().setup()
        info["load"] = self.hist.load_info
        info["lcl_at_snapshot"] = self.hist.lcl_at_snapshot
        info["snapshot_db_bytes"] = os.path.getsize(
            os.path.join(self.hist.snapshot_dir, "node.db"))
        self._prepare_next()
        self._prep.join()
        gc.collect()
        gc.freeze()
        return info

    # -- the measured window -------------------------------------------------
    def _replay(self, app, deadline: float, tick) -> None:
        lm = app.ledger_manager
        restored_at = lm.last_closed_ledger_num()
        backed_at_start = lm.root.bucket_backed()
        self._prepare_next()
        super()._replay(app, deadline, tick)
        rec = self.replays[-1]
        rec["restored_at"] = restored_at
        rec["detached"] = not (backed_at_start and lm.root.bucket_backed())
        rec["sql_fallbacks"] = app.bucket_manager.bucketdb.stats.sql_fallbacks
        cold = lm.apply_stats.to_json()["state_reads"]["cold_reads"]
        rec["cold"] = {phase: sum(by.values()) for phase, by in cold.items()}
        rec["cold_sql"] = sum(by["sql"] for by in cold.values())

    def window(self, seconds: float, tick) -> None:
        self.t_begin = time.perf_counter()
        deadline = self.t_begin + seconds
        while True:
            with annotate.span("bench.catchup.new_node"):
                app = self._new_node()
            self._replay(app, deadline, tick)
            rec = self.replays[-1]
            if rec["ok"]:
                if self.last_node is not None:
                    self.last_node.stop()
                    self._drop.append(self._dirs.pop(self.last_node))
                self.last_node = app
            else:
                app.stop()
                self._drop.append(self._dirs.pop(app))
            if time.perf_counter() >= deadline or self.cut or \
                    (rec["done"] and not rec["ok"]):
                break
        self.t_end = time.perf_counter()

    # -- results -------------------------------------------------------------
    def counts(self) -> dict:
        out = super().counts()
        for phase in ("prepare", "prefetch", "apply"):
            out["cold_" + phase] = sum(r["cold"][phase]
                                       for r in self.replays)
        out["cold_close"] = out["cold_prefetch"] + out["cold_apply"]
        return out

    def compare(self) -> dict:
        """The catchup driver's numbers (header chain, every source's
        balance and sequence number, signatures on the device, the
        negative control); then the rest of the generator's model (every
        destination, the fee pool), the agreement of the two stores on
        every account the replay touched, and what a restart and its
        closes themselves have to report."""
        out = super().compare()
        hist = self.hist
        mismatches = checked = store_mismatches = 0
        if self.last_node is not None:
            from stellar_core_tpu.crypto.strkey import encode_public_key
            from stellar_core_tpu.xdr import LedgerEntry, LedgerKey, PublicKey
            app = self.last_node
            bdb = app.bucket_manager.bucketdb
            for key in hist.model:
                row = app.database.execute(
                    "SELECT entry FROM accounts WHERE accountid=?",
                    (encode_public_key(key),)).fetchone()
                served, blob = bdb.lookup(
                    LedgerKey.account(PublicKey.ed25519(key)).to_xdr())
                checked += 1
                if row is None or not served or blob != row[0]:
                    store_mismatches += 1
                    continue
                acc = LedgerEntry.from_xdr(blob).data.value
                m = hist.model[key]
                mismatches += acc.balance != m["balance"] or \
                    acc.seqNum != m["seq"]
            checked += 1
            mismatches += \
                app.ledger_manager.lcl_header.feePool != hist.fee_pool
        out["state_mismatches"]["value"] += mismatches
        out["state_checked"]["value"] += checked
        out["state_checked"]["limit"] = \
            len(hist.sender_keys) + len(hist.model) + 1
        out["store_mismatches"] = {"value": store_mismatches, "limit": 0}
        full = [r for r in self.replays if r["ok"]]
        out["restarts_off_snapshot"] = {
            "value": sum(r["restored_at"] != hist.lcl_at_snapshot
                         for r in self.replays), "limit": 0}
        out["bucketdb_detached"] = {
            "value": sum(r["detached"] for r in self.replays), "limit": 0}
        out["sql_fallbacks"] = {
            "value": sum(r["sql_fallbacks"] + r["cold_sql"]
                         for r in self.replays), "limit": 0}
        out["replayed_ledgers_off"] = {
            "value": sum(abs(r["closed"] - hist.dense) for r in full),
            "limit": 0}
        out["python_closes"] = {
            "value": sum(r["python_closes"] for r in self.replays),
            "limit": 0}
        out["native_bails"] = {
            "value": sum(sum(r["native_bails"].values())
                         for r in self.replays), "limit": 0}
        return out

    def release(self) -> None:
        if self._prep is not None:
            self._prep.join()
        self.first.stop()
        super().release()
        gc.unfreeze()
