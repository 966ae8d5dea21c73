"""The generator of a DEX archive: a cpu-backend publisher closes a
history of offer management over deep order books into a local file
archive, from a configuration's `state`, a workload's `traffic` and the
run's seed. It shares the archive, the node configuration and the
publishing loop's shape with `history.PublishedHistory`, which it
subclasses; that file is not edited.

State (configs/<config>.json "state"):
  pairs            credit assets, each from its own issuer, each traded
                   against native: two book sides a pair
  offers_per_side  resting offers a side at the first dense ledger
  levels           rungs of the price ladder a side
  makers, takers, payers   accounts (makers are split evenly over pairs)
  lot_scale        u: an ask lot is 100*u units of the credit asset X, a
                   bid lot 200*u native (about one X)
Traffic (workloads/<cell>.json "traffic"):
  maker_txs, taker_txs, payment_txs   transactions of each kind in every
                   dense ledger, one per account of that kind
  checkpoints      how many checkpoints to publish
What has one value everywhere is a constant below: a maker's update has
MAKER_OPS operations, every one a re-quote by offerID of one of its own
resting offers; an offer rests OFFER_LOTS lots; a taker's order is
TAKER_LOTS whole lots and one SUBLOTS-th of a lot, priced THROUGH rungs
through the best.

The ladder. An ask side sells X for native: rung k is the price
(200+k)/100 native a unit of X. A bid side sells native for X: rung k is
(503+k)/1000 X a native unit, 1.988 native a unit of X at rung 0 and
less further down, so no ask crosses a bid. What a side sells (its
wheat) comes in lots of the same size on every rung, so an order for a
whole number of sub-lots of wheat pays a whole number of stroops at
every rung it walks: every fill is exact in integers whatever the
exchange's rounding rules are, and the model below asserts it.

What a taker fills. An order is at most TAKER_LOTS[1] lots and a
sub-lot, less than two offers of OFFER_LOTS[0] lots: it fills the
part-filled offer at the head of the side, if there is one, and one or
two more, three at most, and because of its sub-lot (fewer orders cross
a side in a checkpoint than a lot has sub-lots) it ends inside an offer:
the last fill is in part. The model asserts both for every order. When
a ledger's orders on a side may reach the side's second rung, all of
them name what they receive (manage_buy_offer, strict receive), which
is exact at every rung; else they alternate with orders that name what
they pay (manage_sell_offer, strict send).

What the generator cannot know when it signs a ledger's transactions is
the order in which the node will apply them: the protocol's apply order
follows the hash of the transaction set. So a ledger is built to apply
in any order (no maker touches an offer that the ledger's takers may
reach, nor quotes into a rung they may reach), and once it is signed
the model computes that order as the protocol defines it, from hashlib
alone (`apply_order`), and applies the ledger in it: which taker meets
which offer, and which id a new offer gets, follow from it.

The plain reference (no program code): an integer order book per side,
ordered by price (cross-multiplied) and then offer id, balances, trust
lines, sequence numbers, the fee pool and the id pool, which numbers
every new offer.
"""

from __future__ import annotations

import hashlib
import os
from functools import partial
from typing import Dict, List, Optional

from .history import PublishedHistory, _sk

MAKER_NATIVE = 10 ** 12
TAKER_NATIVE = 10 ** 12
PAYER_NATIVE = 10 ** 10
ISSUER_NATIVE = 10 ** 10
CREDIT_FUNDING = 10 ** 11
TRUST_LIMIT = 2 ** 63 - 1
NATIVE = -1          # the model's index of the native asset
MAKER_OPS = 5
OFFER_LOTS = (4, 16)
TAKER_LOTS = (1, 7)
SUBLOTS = 1000
THROUGH = 5
assert TAKER_LOTS[1] < 2 * OFFER_LOTS[0]    # an order is under two offers


class _Offer:
    """A resting offer of the model: `amount` of the side's wheat (what
    it sells) at n/d sheep a unit."""
    __slots__ = ("id", "owner", "side", "level", "n", "d", "amount")

    def __init__(self, oid, owner, side, level, n, d, amount):
        self.id, self.owner, self.side, self.level = oid, owner, side, level
        self.n, self.d, self.amount = n, d, amount


def _in_priority(offers):
    """A side's offers, best price first (prices compared by
    cross-multiplication), then by offer id: price-time priority."""
    rest = list(offers)
    while rest:
        best = rest[0]
        for o in rest:
            if o.n * best.d < best.n * o.d:
                best = o
        yield from sorted((o for o in rest if o.n * best.d == best.n * o.d),
                          key=lambda o: o.id)
        rest = [o for o in rest if o.n * best.d != best.n * o.d]


def apply_order(previous_ledger_hash: bytes, txs: list) -> list:
    """The order in which a ledger applies `txs`, a list of (account key,
    sequence number, signed envelope bytes, ...) tuples, by this
    ledger's rule (the node's own is `TxSetFrame.sort_for_apply`; a test
    holds the two together): the set's hash is SHA-256 over the previous
    ledger's hash and the envelopes sorted by their own SHA-256; accounts
    go in the order of their key XOR that hash, one transaction of each
    in turn, an account's own by sequence number."""
    by_hash = sorted(txs, key=lambda t: hashlib.sha256(t[2]).digest())
    set_hash = hashlib.sha256(
        previous_ledger_hash + b"".join(t[2] for t in by_hash)).digest()
    chains: Dict[bytes, list] = {}
    for t in by_hash:
        chains.setdefault(t[0], []).append(t)
    for chain in chains.values():
        chain.sort(key=lambda t: t[1])
    order = sorted(chains, key=lambda k: bytes(
        a ^ b for a, b in zip(k, set_hash)))
    out = []
    while chains:
        for k in order:
            if k in chains:
                out.append(chains[k].pop(0))
                if not chains[k]:
                    del chains[k]
    return out


class DexHistory(PublishedHistory):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        super().__init__(config, traffic, seed, workdir)
        st = config["state"]
        self.n_pairs = int(st["pairs"])
        self.n_sides = 2 * self.n_pairs
        self.levels = int(st["levels"])
        self.per_side = int(st["offers_per_side"])
        self.u = int(st["lot_scale"])
        self.n_makers, self.n_takers, self.n_payers = (
            int(st["makers"]), int(st["takers"]), int(st["payers"]))
        if self.n_makers % self.n_pairs or \
                self.per_side % (self.n_makers // self.n_pairs) or \
                self.levels > 100 or self.n_payers % 2 or \
                self.u % (5 * SUBLOTS):
            raise ValueError("dex state: makers divide evenly over pairs "
                             "and a side's offers over its makers; at "
                             "most 100 rungs; payers come in pairs; a "
                             "sub-lot is a whole number on both sides")
        self.books: List[Dict[int, _Offer]] = [
            {} for _ in range(self.n_sides)]
        self.id_pool = 0
        self.book_ledgers = 0    # ledgers that carry an order-book op
        self.fills = {1: 0, 2: 0, 3: 0}   # orders by offers they filled
        self.walks = 0           # orders that filled on two rungs
        self.orders = [0] * self.n_sides
        self.issuer_keys: List = []
        self.assets: List = []

    # -- the ladder ----------------------------------------------------------
    def _price(self, side: int, level: int) -> tuple:
        """(n, d) sheep a unit of wheat of rung `level`."""
        return (200 + level, 100) if side % 2 == 0 else (503 + level, 1000)

    def _wheat_per_lot(self, side: int) -> int:
        return 100 * self.u if side % 2 == 0 else 200 * self.u

    def _sheep_per_lot(self, side: int, level: int) -> int:
        n, d = self._price(side, level)
        return self._wheat_per_lot(side) * n // d

    def _wheat_sheep(self, side: int) -> tuple:
        """Model asset indices (wheat, sheep) of a side."""
        p = side // 2
        return (p, NATIVE) if side % 2 == 0 else (NATIVE, p)

    # -- the plain model -----------------------------------------------------
    def _credit(self, key: bytes, asset: int, amount: int) -> None:
        m = self.model[key]
        if asset == NATIVE:
            m["balance"] += amount
        else:
            m["lines"][asset] += amount
        if (m["balance"] if asset == NATIVE else m["lines"][asset]) < 0:
            raise AssertionError("dex model: a balance went negative")

    def _take(self, side: int, taker: bytes, dest: bytes,
              wheat: Optional[int] = None,
              sheep: Optional[int] = None) -> None:
        """`taker` crosses `side` for exactly `wheat` of what it sells,
        or with exactly `sheep` of what it buys; `dest` receives the
        wheat. Price-time priority, every fill exact in integers; one to
        three offers filled, the last of them in part."""
        w_asset, s_asset = self._wheat_sheep(side)
        got = paid = 0
        filled = []
        for o in _in_priority(self.books[side].values()):
            if wheat is not None:
                x = min(o.amount, wheat - got)
            else:
                left = (sheep - paid) * o.d
                if left % o.n:
                    raise AssertionError("dex model: inexact fill")
                x = min(o.amount, left // o.n)
            if x == 0:
                break
            if (x * o.n) % o.d:
                raise AssertionError("dex model: inexact fill")
            pay = x * o.n // o.d
            o.amount -= x
            if o.amount == 0:
                del self.books[side][o.id]
            self._credit(o.owner, w_asset, -x)
            self._credit(o.owner, s_asset, pay)
            got += x
            paid += pay
            filled.append(o)
        if (wheat is not None and got != wheat) or \
                (sheep is not None and paid != sheep):
            raise AssertionError("dex model: an order was not filled whole")
        if not 1 <= len(filled) <= 3 or filled[-1].amount == 0:
            raise AssertionError(
                "dex model: an order filled %d offers, the last %s"
                % (len(filled), "whole" if filled else "none"))
        self.fills[len(filled)] += 1
        self.walks += filled[0].n * filled[-1].d != filled[-1].n * filled[0].d
        self._credit(taker, s_asset, -paid)
        self._credit(dest, w_asset, got)

    def _rest(self, oid: int, owner: bytes, side: int, level: int,
              lots: int) -> None:
        """Offer `oid` of `owner` now rests on rung `level` with `lots`
        (a new offer, or a re-quote that keeps its id)."""
        n, d = self._price(side, level)
        self.books[side][oid] = _Offer(
            oid, owner, side, level, n, d, lots * self._wheat_per_lot(side))

    def _post(self, owner: bytes, batch: list) -> None:
        """`owner`'s transaction of new offers applies: each takes the
        next id of the pool, in the order of its operations."""
        for side, level, lots in batch:
            self.id_pool += 1
            self._rest(self.id_pool, owner, side, level, lots)

    def _move(self, owner: bytes, moves: list) -> None:
        """`owner`'s re-quote applies: each offer has to rest still."""
        for oid, side, level, lots in moves:
            if self.books[side][oid].owner != owner:
                raise AssertionError("dex model: a re-quote of another's")
            self._rest(oid, owner, side, level, lots)

    # -- operations ----------------------------------------------------------
    def _quote_op(self, acct, side: int, level: int, lots: int,
                  offer_id: int = 0):
        """A maker's offer on `side`: asks as manage_sell_offer, bids as
        manage_buy_offer (which names the X it buys and X's price in
        native: the resting offer sells `lots` bid lots of native)."""
        from stellar_core_tpu.xdr import Asset
        x, native = self.assets[side // 2], Asset.native()
        if side % 2 == 0:
            return acct.op_manage_sell_offer(
                x, native, lots * self._wheat_per_lot(side),
                200 + level, 100, offer_id)
        return acct.op_manage_buy_offer(
            native, x, lots * self._sheep_per_lot(side, level),
            1000, 503 + level, offer_id)

    def _taker_op(self, acct, kind: str, side: int, best: int, sublots: int,
                  dest):
        """One taker operation for `sublots` sub-lots of `side`'s wheat,
        priced THROUGH rungs through the best; returns (op, wheat,
        sheep): which of the two the order names, and the model holds it
        to. What an order pays is reckoned at the best rung: the caller
        names it only where every fill of the ledger is on that rung."""
        from stellar_core_tpu.xdr import (
            Asset, OperationBody, OperationType,
        )
        from stellar_core_tpu.xdr.transaction import (
            PathPaymentStrictReceiveOp, PathPaymentStrictSendOp,
        )
        x, native = self.assets[side // 2], Asset.native()
        wheat = sublots * self._wheat_per_lot(side) // SUBLOTS
        sheep = sublots * self._sheep_per_lot(side, best) // SUBLOTS
        # the taker sells the side's sheep and buys its wheat
        sell, buy = (native, x) if side % 2 == 0 else (x, native)
        worst_n, worst_d = self._price(side, best + THROUGH)
        if kind == "sell":      # `sheep` of what it sells, whatever comes
            return acct.op_manage_sell_offer(
                sell, buy, sheep, worst_d, worst_n), None, sheep
        if kind == "buy":       # exactly `wheat` of what it buys
            return acct.op_manage_buy_offer(
                sell, buy, wheat, worst_n, worst_d), wheat, None
        if kind == "send":
            return acct.op(OperationBody(
                OperationType.PATH_PAYMENT_STRICT_SEND,
                PathPaymentStrictSendOp(
                    sendAsset=sell, sendAmount=sheep,
                    destination=dest.muxed, destAsset=buy, destMin=1,
                    path=[]))), None, sheep
        return acct.op(OperationBody(
            OperationType.PATH_PAYMENT_STRICT_RECEIVE,
            PathPaymentStrictReceiveOp(
                sendAsset=sell, sendMax=2 * sheep, destination=dest.muxed,
                destAsset=buy, destAmount=wheat, path=[]))), wheat, None

    # -- publishing ----------------------------------------------------------
    def publish(self) -> None:
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        from stellar_core_tpu.xdr import Asset
        from ..harness.stats import rng_for
        t = self.traffic
        rng = rng_for(self.seed, "dex-history")
        pub = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                          self.node_config(0, "cpu", writable=True))
        pub.enable_buckets(os.path.join(self.node_dir(0), "buckets"))
        pub.start()
        self.pub = pub
        adapter = AppLedgerAdapter(pub)
        root = adapter.root_account()
        ledger: list = []   # (account key, seq, envelope, frame, effect)

        def submit(frame, effect=None) -> None:
            status = pub.submit_transaction(frame)
            if status != 0:
                raise RuntimeError("publisher refused a transaction: %r %r"
                                   % (status, frame.result))
            body = frame.envelope.value
            self.sigs_issued += len(body.signatures)
            self.fee_pool += body.tx.fee
            ledger.append((frame.seq_account_id().key_bytes, body.tx.seqNum,
                           frame.envelope_bytes(), frame, effect))

        def close(book_ops: bool = False) -> None:
            """Close the ledger; then the model applies what it carried,
            in the protocol's order."""
            previous = pub.ledger_manager.lcl_hash
            pub.clock.set_virtual_time(pub.clock.now() + 1.0)
            pub.manual_close()
            self.book_ledgers += bool(book_ops)
            for _, _, _, frame, effect in apply_order(previous, ledger):
                if frame.result.code != 0:
                    raise RuntimeError(
                        "a transaction of the archive failed: %r"
                        % (frame.result,))
                if effect is not None:
                    effect()
            ledger.clear()

        def tx(acct, ops, effect=None):
            """One signed transaction of `acct`, charged in the model."""
            m = self.model[acct.account_id.key_bytes]
            m["seq"] += 1
            f = acct.tx(ops, seq=m["seq"])
            m["balance"] -= f.envelope.value.tx.fee
            submit(f, effect)
            return f

        # accounts: issuers, makers, takers, payers
        kinds = (("issuer", self.n_pairs, ISSUER_NATIVE),
                 ("maker", self.n_makers, MAKER_NATIVE),
                 ("taker", self.n_takers, TAKER_NATIVE),
                 ("payer", self.n_payers, PAYER_NATIVE))
        sks = [(_sk(self.seed, kind, i), start)
               for kind, n, start in kinds for i in range(n)]
        for lo in range(0, len(sks), 100):
            submit(root.tx([root.op_create_account(sk.public_key, start)
                            for sk, start in sks[lo:lo + 100]]))
            close()
            created_at = pub.ledger_manager.last_closed_ledger_num()
            for sk, start in sks[lo:lo + 100]:
                self.model[sk.public_key.key_bytes] = {
                    "balance": start, "seq": created_at << 32, "lines": {}}
        accounts = [TestAccount(adapter, sk) for sk, _ in sks]
        cut = [0]
        for _, n, _ in kinds:
            cut.append(cut[-1] + n)
        issuers, makers, takers, payers = (
            accounts[cut[i]:cut[i + 1]] for i in range(4))
        self.issuer_keys = [a.account_id for a in issuers]
        self.sender_keys = [a.account_id
                            for a in makers + takers + payers]
        self.assets = [Asset.credit("DX%d" % p, issuers[p].account_id)
                       for p in range(self.n_pairs)]
        per_pair = self.n_makers // self.n_pairs

        # trust lines: a maker's own pair, a taker's every asset
        for i, m in enumerate(makers):
            p = i // per_pair
            tx(m, [m.op_change_trust(self.assets[p], TRUST_LIMIT)])
            self.model[m.account_id.key_bytes]["lines"][p] = 0
        for a in takers:
            tx(a, [a.op_change_trust(x, TRUST_LIMIT) for x in self.assets])
            self.model[a.account_id.key_bytes]["lines"] = {
                p: 0 for p in range(self.n_pairs)}
        close()
        for p, iss in enumerate(issuers):
            holders = makers[p * per_pair:(p + 1) * per_pair] + takers
            for lo in range(0, len(holders), 100):
                tx(iss, [iss.op_payment(h.account_id, CREDIT_FUNDING,
                                        self.assets[p])
                         for h in holders[lo:lo + 100]])
            for h in holders:
                self.model[h.account_id.key_bytes]["lines"][p] = \
                    CREDIT_FUNDING
        close()

        # the books: every rung of a side holds the same number of
        # offers, the seed deals them to the side's makers
        specs: Dict[bytes, list] = {m.account_id.key_bytes: []
                                    for m in makers}
        for side in range(self.n_sides):
            p = side // 2
            rungs = [i % self.levels for i in range(self.per_side)]
            rng.shuffle(rungs)
            for i, level in enumerate(rungs):
                m = makers[p * per_pair + i % per_pair]
                specs[m.account_id.key_bytes].append(
                    (side, level, rng.randint(*OFFER_LOTS)))
        for mine in specs.values():
            rng.shuffle(mine)
        whole = self.n_sides * self.per_side
        while any(specs.values()):
            for m in makers:
                key = m.account_id.key_bytes
                batch, specs[key] = specs[key][:100], specs[key][100:]
                if batch:
                    tx(m, [self._quote_op(m, *s) for s in batch],
                       partial(self._post, key, batch))
            close(book_ops=True)
        if self.id_pool != whole or \
                sum(len(b) for b in self.books) != whole:
            raise RuntimeError("the books hold %d offers, not %d"
                               % (sum(len(b) for b in self.books), whole))

        # keep virtual time ahead of closeTime (history.py)
        pub.clock.set_virtual_time(
            pub.clock.now() + pub.ledger_manager.last_closed_ledger_num())
        hm = pub.history_manager
        target = hm.published_checkpoints + int(t["checkpoints"])
        n_m, n_t, n_p = (int(t["maker_txs"]), int(t["taker_txs"]),
                         int(t["payment_txs"]))
        if n_m > len(makers) or n_t > len(takers) or n_p > len(payers) \
                or n_p % 2:
            raise ValueError("dex traffic: more transactions of a kind "
                             "than accounts of it")
        dense = 0
        self.book_sizes = []     # resting offers after each dense ledger
        while hm.published_checkpoints < target:
            self._dense_ledger(rng, tx, makers[:n_m], takers[:n_t],
                               payers[:n_p])
            close(book_ops=True)
            dense += 1
            self.book_sizes.append(sum(len(b) for b in self.books))
            if abs(self.book_sizes[-1] - whole) * 10 > whole:
                raise RuntimeError("the books left 10%% of %d offers: %d"
                                   % (whole, self.book_sizes[-1]))
            pub.crank_until(lambda: hm.publish_queue() == [],
                            max_cranks=20000)
        lcl = pub.ledger_manager.last_closed_ledger_num()
        self.tip = ((lcl + 1) // self.freq) * self.freq - 1
        if lcl != self.tip:
            raise RuntimeError("publisher closed past the archive tip "
                               "(%d > %d): the model counts every ledger"
                               % (lcl, self.tip))
        if max(self.orders) >= SUBLOTS:
            raise RuntimeError("%d orders crossed one side: a sub-lot no "
                               "longer keeps the last fill in part"
                               % max(self.orders))
        self.dense = dense
        self.headers = dict(pub.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        self.pub_time = pub.clock.now()

    def _dense_ledger(self, rng, tx, makers, takers, payers) -> None:
        """Sign and submit one dense ledger. Each transaction carries its
        effect on the model, which `close` applies in the apply order."""
        # payments: disjoint pairs, as standard-mix
        for i, a in enumerate(payers):
            dest = payers[i + 1 if i % 2 == 0 else i - 1]
            amount = 1000 + rng.randrange(1000)
            tx(a, [a.op_payment(dest.account_id, amount)])
            self.model[a.account_id.key_bytes]["balance"] -= amount
            self.model[dest.account_id.key_bytes]["balance"] += amount

        # takers' orders, and with them how far down each side this
        # ledger's takers can reach in any order: the offers at risk
        orders = [(a, rng.randrange(self.n_sides),
                   rng.randint(*TAKER_LOTS) * SUBLOTS + 1) for a in takers]
        best, guard = [], []
        at_risk = set()
        for side, book in enumerate(self.books):
            reach = sum(n for _, s, n in orders if s == side) * \
                self._wheat_per_lot(side) // SUBLOTS
            queue = _in_priority(book.values())
            head = last = next(queue, None)
            if head is None:
                raise RuntimeError("a book side is empty")
            # the head is kept out of the makers' reach in every ledger:
            # it alone may be part-filled
            at_risk.add(head.id)
            reach -= head.amount
            while reach > 0:
                last = next(queue)
                at_risk.add(last.id)
                reach -= last.amount
            if last.level >= min(head.level + THROUGH, self.levels - 1):
                raise RuntimeError("a book side ran out of rungs")
            best.append(head.level)
            guard.append(last.level)

        # takers: the first half cross with an offer, the second with a
        # path payment to the next taker
        for i, (a, side, sublots) in enumerate(orders):
            path = i >= len(orders) // 2
            dest = takers[(i + 1) % len(takers)] if path else a
            pays = i % 2 == 0 and guard[side] == best[side]
            kind = (("buy", "sell"), ("receive", "send"))[path][pays]
            op, wheat, sheep = self._taker_op(a, kind, side, best[side],
                                              sublots, dest)
            self.orders[side] += 1
            tx(a, [op], partial(self._take, side, a.account_id.key_bytes,
                                dest.account_id.key_bytes, wheat=wheat,
                                sheep=sheep))

        # makers: MAKER_OPS of a maker's own offers that no taker of this
        # ledger can reach move to other rungs, below all they can reach
        mine: Dict[bytes, list] = {}
        for book in self.books:
            for o in book.values():
                if o.id not in at_risk:
                    mine.setdefault(o.owner, []).append(o)
        for m in makers:
            key = m.account_id.key_bytes
            moves = [(o.id, o.side,
                      rng.randrange(guard[o.side] + 1, self.levels),
                      rng.randint(*OFFER_LOTS))
                     for o in rng.sample(mine[key], MAKER_OPS)]
            tx(m, [self._quote_op(m, side, level, lots, oid)
                   for oid, side, level, lots in moves],
               partial(self._move, key, moves))

    # -- what a replay has to arrive at --------------------------------------
    def book_rows(self) -> list:
        """Per side, every resting offer: id -> (amount of what the side
        sells, price n, price d)."""
        return [{o.id: (o.amount, o.n, o.d) for o in b.values()}
                for b in self.books]
