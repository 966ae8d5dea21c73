"""Deployment driver `validator_dex`: the `validator` driver's network
(`topologies.core(n, threshold, OVER_PEERS)`, node 0 on the device
backend, the others on the cpu backend as the independent reference,
the clocks, the cranking and the admission dispatch), over order-book
traffic (traffic/dex_live.py): a closed loop of market makers, takers
and payers over deep books, every transaction submitted to node 0 and
flooded from there.

Set-up creates the accounts, trust lines and credit, then posts the
books through consensus, as live transactions. The plain reference is
the generator's order-book model, which applies every ledger node 0
closed, in the order it computes itself; after the drain every node is
held to it. `validator.Deployment` is not edited.
"""

from __future__ import annotations

import base64
import gc
import time

from ..harness import annotate
from ..harness.runner import RunError
from ..traffic.dex_live import DexLive
from . import validator


class Deployment(validator.Deployment):
    def __init__(self, config: dict, workload: dict, seed: int,
                 workdir: str, trace: bool, node_hook=None) -> None:
        from stellar_core_tpu.herder import herder
        if not hasattr(herder, "TXSET_CHECK_SITES"):
            # say so now, not after a set-up that cannot be read
            raise RunError("this program keeps no txset validation meter "
                           "(herder.txset.checks) nor span "
                           "(txset.check_valid): the cell cannot be read "
                           "on it")
        super().__init__(config, workload, seed, workdir, trace,
                         node_hook=node_hook)
        self.gen = DexLive(config, workload["traffic"], seed, workdir)
        self.absorbed = 1        # the last of node 0's ledgers the model has

    # -- set-up --------------------------------------------------------------
    def setup(self) -> dict:
        """The validator driver's set-up with no accounts of its own (the
        network, the warm-up, the first closes); then, with the clocks
        back in set-up mode, the accounts, trust lines, credit and books
        as live transactions; then the window's cadence."""
        from stellar_core_tpu.testing import AppLedgerAdapter
        self.n_accounts = 0
        info = super().setup()
        self.wall_t0 = None
        t0 = time.perf_counter()
        node0, g = self.node0, self.gen
        ledger = AppLedgerAdapter(node0)
        root = ledger.root_account()
        g.make_accounts(ledger)
        if len(g.accounts) != int(self.config["accounts"]):
            raise ValueError("accounts: %d in the state, %s in the file"
                             % (len(g.accounts), self.config["accounts"]))
        for txs in g.create_txs(root, ledger.seq_num(root.account_id)):
            self._through(txs, "account creation")
        self._through(g.trust_txs(), "trust lines")
        self._through(g.funding_txs(), "credit")
        info["accounts_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        for posts in g.book_rounds():
            self._through(g.post_txs(posts), "the books")
        whole = g.n_sides * g.per_side
        if sum(len(b) for b in g.books) != whole or g.id_pool != whole:
            raise RuntimeError("the books hold %d offers, not %d"
                               % (sum(len(b) for b in g.books), whole))
        info["books_s"] = time.perf_counter() - t0
        self.accounts = g.accounts
        # what set-up leaves resident goes out of the collector's reach,
        # as in the catchup cells: three nodes' books and state and the
        # model share this process, some 1.3 million objects that a full
        # pass walked in 0.8-1.0 s, three or four times in a 30 s window
        # (`cpu` backend); the window's own objects stay in reach
        gc.collect()
        gc.freeze()
        # from here the clocks follow the wall clock, with the room of
        # close time over clock whole (validator.Deployment._crank_once)
        t0 = time.perf_counter()
        apps = self.apps
        self.virtual_t0 = max(
            [a.clock.now() for a in apps] +
            [a.ledger_manager.lcl_header.scpValue.closeTime + 1.0
             for a in apps])
        self.virtual_elapsed = 0.0
        self.wall_t0 = self.wall_last = time.perf_counter()
        tip = max(a.ledger_manager.last_closed_ledger_num() for a in apps)
        self._crank_until(lambda: self._absorbed_min() >= tip + 2,
                          "two closes at the deployment's cadence", 60.0)
        info["cadence_s"] = time.perf_counter() - t0
        return info

    def _through(self, txs, what: str) -> None:
        """Submit `txs` to node 0 in chunks that the queue holds, each
        applied on every node before the next."""
        cap = self.node0.herder.tx_queue.pool_cap_ops()
        while txs:
            ops = 0
            n = 0
            while n < len(txs) and ops + _ops(txs[n]) <= cap:
                ops += _ops(txs[n])
                n += 1
            chunk, txs = txs[:max(n, 1)], txs[max(n, 1):]
            for tx in chunk:
                frame = tx.frame
                status = self.node0.submit_transaction(frame)
                if status != 0:
                    raise RuntimeError("set-up (%s): node 0 refused a "
                                       "transaction: %r %r"
                                       % (what, status, frame.result))
                self.gen.sent(tx, frame)
            self._crank_until(lambda: self._absorbed_min() >=
                              self.absorbed and not self.gen.pending, what)
            if self.gen.failed["setup"]:
                raise RuntimeError("set-up (%s): a transaction failed"
                                   % what)

    def _absorbed_min(self) -> int:
        self._note_closes()
        return self._lcl_min()

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tick) -> None:
        """The validator driver's window over the dex_live generator."""
        batch = int(self.workload["traffic"]["submit_batch"])
        self.gen.start(seconds)
        self.seconds = seconds
        self.slots0 = self._lcl_min()
        self.counters0 = self._counters()
        self.t_begin = time.perf_counter()
        cut = False
        while not cut:
            now = time.perf_counter()
            rel = now - self.t_begin
            if tick(now) or rel >= seconds:
                break
            for req in self.gen.take_due(rel, batch):
                now = time.perf_counter()
                cut = cut or tick(now)
                self._submit(req, now - self.t_begin)
            self._crank_once()
            self._note_closes()
        self.t_end = time.perf_counter()
        self.slots1 = self._lcl_min()
        self.counters1 = self._counters()
        self.applied_in_window = sum(
            n for n, at in self.applied_by_ledger.values()
            if at <= self.t_end - self.t_begin)

    def _submit(self, req, now_rel: float) -> None:
        from stellar_core_tpu.xdr import TransactionResultCode
        frame = req.tx.frame
        if req.corrupt:
            sig = frame.envelope.value.signatures[0]
            sig.signature = bytes([sig.signature[0] ^ 1]) + sig.signature[1:]
        t0 = time.perf_counter()
        with annotate.span("bench.submit"):
            status = self.node0.submit_transaction(frame)
        t1 = time.perf_counter()
        self.admit_s += t1 - t0
        self.admit_n += 1
        self.late_max = max(self.late_max, now_rel - req.due)
        if status != 0:
            # the right answer to a corrupted signature is a refusal with
            # txBAD_AUTH, and to any other no refusal at all
            req.refused = not req.corrupt or \
                frame.result.code == TransactionResultCode.txBAD_AUTH
            req.done = t1 - self.t_begin
            self.gen.withdraw(req.tx)
            self.gen.replied(req, req.done)
        else:
            # admitted, a corrupted one too (then wrongly): it applies
            req.refused = False
            req.txid = self.gen.sent(req.tx, frame)
            self.inflight[req.txid] = req

    def _note_closes(self) -> None:
        """The model applies every ledger node 0 closed since the last
        look; then the validator driver's bookkeeping of replies."""
        lcl0 = self.node0.ledger_manager.last_closed_ledger_num()
        if lcl0 > self.absorbed:
            self.close_times.append(time.perf_counter())
            with annotate.span("bench.read_closed"):
                db = self.node0.database
                for seq in range(self.absorbed + 1, lcl0 + 1):
                    txs = self.gen.absorb(seq, _previous_hash(db, seq),
                                          _applied(db, seq))
                    hit = []
                    for tx in txs:
                        req = tx.req
                        if req is not None and \
                                self.inflight.pop(req.txid, None):
                            req.ledger = seq
                            hit.append(req)
                    if hit:
                        self.waiting.append((seq, hit))
            self.absorbed = lcl0
        self.lcl0_seen = lcl0
        if self.waiting:
            super()._note_closes()

    # -- results -------------------------------------------------------------
    def _counters(self) -> dict:
        out = super()._counters()
        stats = self.node0.ledger_manager.apply_stats
        m = self.node0.metrics.to_json(prefix="herder.txset.checks")
        out["txset_checks"] = m.get("herder.txset.checks",
                                    {}).get("count", 0)
        out["closes"] = sum(stats.closes.values())
        out["dynamic_closes"] = stats.clusters["dynamic_closes"]
        return out

    def counts(self) -> dict:
        out = super().counts()
        c0, c1 = self.counters0, self.counters1
        for k in ("txset_checks", "closes", "dynamic_closes"):
            out[k] = c1[k] - c0[k]
        g = self.gen
        out["taker_deferrals"] = g.deferrals
        out["fills"] = dict(g.fills)
        out["book_size"] = sum(len(b) for b in g.books)
        return out

    def compare(self) -> dict:
        """The validator driver's numbers (header chains, the corrupted
        refused and the valid admitted, every admitted transaction
        applied, the device path), its state comparison now over the
        model's native balances and sequence numbers; and the rest of
        the model on every node: trust lines, the header's idPool and
        feePool, every resting offer of every side; and what node 0's
        closes have to report."""
        self._note_closes()
        g = self.gen
        self.balance = [g.model[a.account_id.key_bytes]["balance"]
                        for a in self.accounts]
        self.seq = [g.model[a.account_id.key_bytes]["seq"]
                    for a in self.accounts]
        out = super().compare()
        state = book = 0
        for app in self.apps:
            s, b = self._held_to_model(app)
            state += s
            book += b
        out["state_mismatches"]["value"] += state
        out["book_mismatches"] = {"value": book, "limit": 0}
        stats = self.node0.ledger_manager.apply_stats
        out["python_closes"] = {"value": stats.closes.get("python", 0),
                                "limit": 0}
        out["native_bails"] = {"value": sum(stats.bails.values()),
                               "limit": 0}
        # node 0 closed every ledger the model applied (none before the
        # accounts carries an order-book operation)
        out["dynamic_close_mismatches"] = {
            "value": abs(stats.clusters["dynamic_closes"] - g.book_ledgers),
            "limit": 0}
        out["requote_failures"] = {"value": g.failed["maker"], "limit": 0}
        out["failed_txs"] = {"value": sum(g.failed.values()), "limit": 0}
        out["model_faults"] = {"value": len(g.faults) + g.unknown_txs,
                               "limit": 0, "detail": g.faults[:4]}
        out["requotes_checked"] = {"value": g.requotes_checked,
                                   "limit": 1, "need": "min"}
        return out

    def release(self) -> None:
        super().release()
        gc.unfreeze()

    def _held_to_model(self, app) -> tuple:
        """(state mismatches, book mismatches) of one node against the
        model: every trust line, the header's pools; every side's resting
        offers by id (amount, and price by cross-multiplication)."""
        from stellar_core_tpu.xdr import Asset, LedgerKey
        g = self.gen
        root = app.ledger_manager.ltx_root()
        state = 0
        for key in g.sender_keys:
            for p, want in g.model[key.key_bytes]["lines"].items():
                e = root.get_entry(LedgerKey.trustline(key, g.assets[p]))
                state += e is None or e.data.value.balance != want
        header = app.ledger_manager.lcl_header
        state += header.idPool != g.id_pool
        state += header.feePool != g.fee_pool

        def text(asset) -> str:
            return base64.b64encode(asset.to_xdr()).decode()

        native = Asset.native()
        book = 0
        for side, want in enumerate(g.book_rows()):
            x = g.assets[side // 2]
            sell, buy = (x, native) if side % 2 == 0 else (native, x)
            got = app.database.execute(
                "SELECT offerid, amount, pricen, priced FROM offers "
                "WHERE selling=? AND buying=?",
                (text(sell), text(buy))).fetchall()
            book += abs(len(got) - len(want)) + sum(
                oid not in want or want[oid][0] != amount or
                want[oid][1] * d != n * want[oid][2]
                for oid, amount, n, d in got)
        return state, book


def _ops(tx) -> int:
    return len(tx.frame.envelope.value.tx.operations)


def _previous_hash(db, seq: int) -> bytes:
    row = db.execute("SELECT prevhash FROM ledgerheaders WHERE ledgerseq = ?",
                     (seq,)).fetchone()
    return bytes.fromhex(row[0])


def _applied(db, seq: int) -> list:
    """(txid, result code) of every transaction of ledger `seq`, as node
    0's txhistory lists them."""
    from stellar_core_tpu.xdr.transaction import TransactionResultPair
    return [(txid, TransactionResultPair.from_xdr(bytes(res)).result.code)
            for txid, res in db.execute(
                "SELECT txid, txresult FROM txhistory WHERE ledgerseq = ? "
                "ORDER BY txindex", (seq,)).fetchall()]
