"""Deployment driver `catchup_dex`: the `catchup` driver over an archive
of offer management (traffic/dex_history.py). Set-up, the window, the
negative control and the device-path check are the catchup driver's;
this one holds a full replay to the generator's order-book model as
well, and reads the order-book counters that the native close keeps.
"""

from __future__ import annotations

import base64
import gc

from ..harness.runner import RunError
from ..traffic.dex_history import DexHistory
from . import catchup


class Deployment(catchup.Deployment):
    def __init__(self, config: dict, workload: dict, seed: int,
                 workdir: str, trace: bool, node_hook=None) -> None:
        from stellar_core_tpu.ledger.apply_stats import ApplyStats
        if not hasattr(ApplyStats, "record_book_load"):
            raise RunError("this program keeps no order-book counters "
                           "(ledger.apply.book.*): the cell cannot be "
                           "read on it")
        super().__init__(config, workload, seed, workdir, trace,
                         node_hook=node_hook)
        self.hist = DexHistory(config, workload["traffic"], seed, workdir)

    def setup(self) -> dict:
        """The catchup driver's set-up; then what it left resident goes
        out of the collector's reach. The publisher and the generator's
        model stay alive for the comparison, some three million objects
        that are the benchmark's and not the node's; a full collection
        walks the heap of the replaying nodes alone from here on, as it
        would in a node's own process."""
        info = super().setup()
        gc.collect()
        gc.freeze()
        return info

    def release(self) -> None:
        super().release()
        gc.unfreeze()

    def _replay(self, app, deadline: float, tick) -> None:
        super()._replay(app, deadline, tick)
        stats = app.ledger_manager.apply_stats
        rec = self.replays[-1]
        rec["book_rows"] = stats.book["rows"]
        rec["book_loads"] = stats.book["loads"]
        rec["dynamic_closes"] = stats.clusters["dynamic_closes"]
        rec["closes"] = sum(stats.closes.values())

    def counts(self) -> dict:
        out = super().counts()
        for k in ("book_rows", "book_loads", "dynamic_closes", "closes"):
            out[k] = sum(r[k] for r in self.replays)
        return out

    def compare(self) -> dict:
        """The catchup driver's numbers (header chain, every sender's
        native balance and sequence number, the fee pool, signatures on
        the device, the negative control), and the rest of the
        generator's model: issuers, trust lines, every book side offer
        by offer (id, amount, rung), the id pool; and what the closes
        themselves have to report."""
        out = super().compare()
        hist = self.hist
        mismatches = checked = 0
        if self.last_node is not None:
            from stellar_core_tpu.xdr import Asset, LedgerKey
            app = self.last_node
            root = app.ledger_manager.ltx_root()
            for key in hist.issuer_keys:
                e = root.get_entry(LedgerKey.account(key))
                m = hist.model[key.key_bytes]
                checked += 1
                mismatches += e is None or \
                    e.data.value.balance != m["balance"] or \
                    e.data.value.seqNum != m["seq"]
            for key in hist.sender_keys:
                for p, want in hist.model[key.key_bytes]["lines"].items():
                    e = root.get_entry(
                        LedgerKey.trustline(key, hist.assets[p]))
                    checked += 1
                    mismatches += e is None or e.data.value.balance != want

            def text(asset) -> str:
                return base64.b64encode(asset.to_xdr()).decode()

            native = Asset.native()
            for side, want in enumerate(hist.book_rows()):
                # every resting offer of the side, by id: its amount and
                # its rung (prices held equal by cross-multiplication)
                x = hist.assets[side // 2]
                sell, buy = (x, native) if side % 2 == 0 else (native, x)
                got = app.database.execute(
                    "SELECT offerid, amount, pricen, priced FROM offers "
                    "WHERE selling=? AND buying=?",
                    (text(sell), text(buy))).fetchall()
                checked += 1
                mismatches += len(got) != len(want) or any(
                    oid not in want or want[oid][0] != amount or
                    want[oid][1] * d != n * want[oid][2]
                    for oid, amount, n, d in got)
            checked += 1
            mismatches += \
                app.ledger_manager.lcl_header.idPool != hist.id_pool
        out["state_mismatches"]["value"] += mismatches
        out["state_checked"]["value"] += checked
        out["state_checked"]["limit"] = \
            len(hist.sender_keys) + len(hist.issuer_keys) + hist.n_sides
        full = [r for r in self.replays if r["ok"]]
        # every ledger after genesis is replayed; those that carry an
        # order-book operation are the generator's own count
        out["dynamic_close_mismatches"] = {
            "value": sum(abs(r["dynamic_closes"] - hist.book_ledgers) +
                         abs(r["closes"] - (hist.tip - 1)) for r in full),
            "limit": 0}
        out["python_closes"] = {
            "value": sum(r["python_closes"] for r in self.replays),
            "limit": 0}
        out["native_bails"] = {
            "value": sum(sum(r["native_bails"].values())
                         for r in self.replays), "limit": 0}
        return out
