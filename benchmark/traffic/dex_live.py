"""The generator of live order-book traffic, and its plain model.

A closed loop of clients on a live network: each sends its account's
next transaction the moment the previous one has its reply (applied on
every node, or refused). The accounts, the assets, the ladder, the book
and the operations are `dex_history.DexHistory`'s (this file subclasses
it and does not edit it): makers re-quote five of their own resting
offers by offerID, takers cross a side with an offer or a one-hop path
payment to another taker (1-7 lots and a thousandth of a lot: 1-3 offers
filled, the last in part), payers pay another payer in native.

Parameters (workloads/<cell>.json "traffic"):
  loop           "closed" (the only loop)
  clients        makers + takers + payers of the configuration's `state`:
                 every account of those kinds is one client
  corrupt_every  one submission in this many carries a signature with
                 one bit flipped; the right answer is a refusal
  submit_batch   at most this many submissions between two cranks

The one rule the live form needs. An archive's generator composes each
ledger, so it can build a ledger that applies in any order. Live, the
generator does not know which ledger a transaction lands in, nor what
lands beside it. So it keeps, per book side, the orders of its takers
still in flight and how deep into the side they can reach together in
any order (the guard rung), and the rungs its makers' re-quotes in
flight touch:
  - a maker moves only offers resting at least MAKER_MARGIN rungs behind
    the guard, to rungs as far behind; it asserts so for every re-quote;
  - a taker order goes to a side only where, with it, the guard stays in
    front of every rung a re-quote in flight touches, inside the price
    limit of every order in flight there, and, where an order in flight
    names what it pays, on the best rung (where that order is exact).
    Where no side takes it, the taker waits for the next look.
At apply time the model checks the rule against what happened: no
re-quote moves an offer that changed since it was signed, and no fill
takes an offer on a rung that a re-quote in flight touches. A break of
either is a model fault, as is any assertion of the order-book model.

The plain reference (no program code): for each ledger node 0 closed,
the transactions it applied are read from node 0's `txhistory`; the
model orders them itself (`apply_order`, hashlib alone, from the
ledger's previous hash) and applies them with integer-exact fills: fees,
sequence numbers, native balances, trust lines, every resting offer,
the id pool and the fee pool.
"""

from __future__ import annotations

from bisect import bisect_left, insort
from collections import deque
from functools import partial
from typing import Dict, List, Optional

from ..harness.stats import rng_for
from .dex_history import (
    CREDIT_FUNDING, ISSUER_NATIVE, MAKER_NATIVE, MAKER_OPS, OFFER_LOTS,
    PAYER_NATIVE, SUBLOTS, TAKER_LOTS, TAKER_NATIVE, THROUGH, TRUST_LIMIT,
    DexHistory, apply_order,
)
from .history import _sk

MAKER_MARGIN = 2     # rungs behind the guard a re-quote keeps to
SETUP_OPS_PER_TX = 100


class Tx:
    """One signed transaction the model waits to see applied. Its frame
    goes once it is applied or refused: the model keeps what it reads,
    and the window's collector walks no frame of the generator's."""
    __slots__ = ("frame", "key", "seq", "env", "fee", "kind", "book",
                 "effect", "release", "req")

    def __init__(self, frame, kind: str, effect=None, book: bool = False,
                 release=None) -> None:
        body = frame.envelope.value.tx
        self.frame = frame
        self.key = frame.seq_account_id().key_bytes
        self.seq, self.fee = body.seqNum, body.fee
        self.env = None          # the envelope as sent, at submission
        self.kind, self.book = kind, book
        self.effect, self.release = effect, release
        self.req = None


class Request:
    __slots__ = ("k", "client", "due", "corrupt", "tx", "done", "ledger",
                 "refused", "txid")

    def __init__(self, k: int, client: int, due: float, corrupt: bool,
                 tx: Tx) -> None:
        self.k, self.client, self.due, self.corrupt = k, client, due, corrupt
        self.tx = tx
        self.done: Optional[float] = None    # reply on every node
        self.ledger: Optional[int] = None
        self.refused: Optional[bool] = None
        self.txid: Optional[str] = None
        tx.req = self


class DexLive(DexHistory):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        # the archive fields of PublishedHistory go unused: nothing is
        # published
        super().__init__(dict(config, checkpoint_frequency=64), traffic,
                         seed, workdir)
        if traffic["loop"] != "closed" or int(traffic["clients"]) != \
                self.n_makers + self.n_takers + self.n_payers:
            raise ValueError("dex_live: a closed loop whose clients are "
                             "the makers, takers and payers")
        # a side's offers in priority: on this ladder a rung's price
        # grows with its index on both kinds of side, so price-time
        # priority (dex_history._in_priority) is (rung, offer id)
        self.queue: List[list] = [[] for _ in range(self.n_sides)]
        self.side_of: Dict[int, int] = {}
        self.owned: Dict[bytes, set] = {}
        self.corrupt_every = int(traffic["corrupt_every"])
        self.rng = rng_for(seed, "dex-live")
        self._crng = rng_for(seed, "dex-live-corrupt")
        self._corrupt_at = -1
        self.k = 0
        self.requests: List[Request] = []
        self.closed = True
        self.no_idle_account = 0
        self.seconds = 0.0
        self.ready: deque = deque()
        self.pending: Dict[str, Tx] = {}    # txid -> sent, not yet applied
        # per side: taker orders in flight [sublots, limit rung, pays],
        # and rung -> re-quote operations in flight that touch it
        self.flying: List[list] = [[] for _ in range(self.n_sides)]
        self.moving: List[Dict[int, int]] = [{} for _ in range(self.n_sides)]
        self.deferrals = 0       # looks at which a taker found no side
        self.requotes_checked = 0
        self.fills_checked = 0
        self.failed = {"setup": 0, "maker": 0, "taker": 0, "payer": 0}
        self.faults: List[str] = []
        self.unknown_txs = 0
        self.ledgers: List[tuple] = []   # (seq, txids in the model's order)
        self.at_ledger = 0

    # -- accounts ------------------------------------------------------------
    def make_accounts(self, adapter) -> None:
        from stellar_core_tpu.testing import TestAccount
        from stellar_core_tpu.xdr import Asset
        kinds = (("issuer", self.n_pairs, ISSUER_NATIVE),
                 ("maker", self.n_makers, MAKER_NATIVE),
                 ("taker", self.n_takers, TAKER_NATIVE),
                 ("payer", self.n_payers, PAYER_NATIVE))
        self.starts = []
        for kind, n, start in kinds:
            self.starts += [start] * n
        self.accounts = [TestAccount(adapter, _sk(self.seed, kind, i))
                         for kind, n, _ in kinds for i in range(n)]
        cut = [0]
        for _, n, _ in kinds:
            cut.append(cut[-1] + n)
        self.issuers, self.makers, self.takers, self.payers = (
            self.accounts[cut[i]:cut[i + 1]] for i in range(4))
        self.clients = self.makers + self.takers + self.payers
        self.issuer_keys = [a.account_id for a in self.issuers]
        self.sender_keys = [a.account_id for a in self.clients]
        self.assets = [Asset.credit("DX%d" % p, self.issuers[p].account_id)
                       for p in range(self.n_pairs)]

    def _created(self, accounts, starts) -> None:
        for a, start in zip(accounts, starts):
            self.model[a.account_id.key_bytes] = {
                "balance": start, "seq": self.at_ledger << 32, "lines": {}}

    def _next_seq(self, acct) -> int:
        return self.model[acct.account_id.key_bytes]["seq"] + 1

    # -- set-up: lists of transactions, each applied before the next --------
    def create_txs(self, root, root_seq: int) -> List[List[Tx]]:
        """The root's create transactions, 100 accounts each."""
        out = []
        for lo in range(0, len(self.accounts), SETUP_OPS_PER_TX):
            chunk = self.accounts[lo:lo + SETUP_OPS_PER_TX]
            starts = self.starts[lo:lo + SETUP_OPS_PER_TX]
            root_seq += 1
            f = root.tx([root.op_create_account(a.account_id, s)
                         for a, s in zip(chunk, starts)], seq=root_seq)
            out.append([Tx(f, "setup", partial(self._created, chunk,
                                               starts))])
        return out

    def trust_txs(self) -> List[Tx]:
        """Trust lines: a maker's own pair, a taker's every asset."""
        per_pair = self.n_makers // self.n_pairs
        out = []
        for i, m in enumerate(self.makers):
            p = i // per_pair
            out.append(Tx(m.tx([m.op_change_trust(self.assets[p],
                                                  TRUST_LIMIT)],
                               seq=self._next_seq(m)), "setup",
                          partial(self._lines, m, [p])))
        for a in self.takers:
            out.append(Tx(a.tx([a.op_change_trust(x, TRUST_LIMIT)
                                for x in self.assets],
                               seq=self._next_seq(a)), "setup",
                          partial(self._lines, a, range(self.n_pairs))))
        return out

    def _lines(self, acct, pairs) -> None:
        lines = self.model[acct.account_id.key_bytes]["lines"]
        for p in pairs:
            lines[p] = 0

    def funding_txs(self) -> List[Tx]:
        per_pair = self.n_makers // self.n_pairs
        out = []
        for p, iss in enumerate(self.issuers):
            holders = self.makers[p * per_pair:(p + 1) * per_pair] + \
                self.takers
            seq = self._next_seq(iss)
            for lo in range(0, len(holders), SETUP_OPS_PER_TX):
                chunk = holders[lo:lo + SETUP_OPS_PER_TX]
                out.append(Tx(iss.tx([iss.op_payment(
                    h.account_id, CREDIT_FUNDING, self.assets[p])
                    for h in chunk], seq=seq), "setup",
                    partial(self._funded, chunk, p)))
                seq += 1
        return out

    def _funded(self, holders, p: int) -> None:
        for h in holders:
            self.model[h.account_id.key_bytes]["lines"][p] += CREDIT_FUNDING

    def book_rounds(self) -> List[list]:
        """The books, as dex_history posts them: every rung of a side
        holds the same number of offers, the seed deals them to the
        side's makers; a maker posts up to 100 a transaction, one
        transaction a round."""
        rng = rng_for(self.seed, "dex-live-books")
        per_pair = self.n_makers // self.n_pairs
        specs: Dict[bytes, list] = {m.account_id.key_bytes: []
                                    for m in self.makers}
        for side in range(self.n_sides):
            p = side // 2
            rungs = [i % self.levels for i in range(self.per_side)]
            rng.shuffle(rungs)
            for i, level in enumerate(rungs):
                m = self.makers[p * per_pair + i % per_pair]
                specs[m.account_id.key_bytes].append(
                    (side, level, rng.randint(*OFFER_LOTS)))
        for mine in specs.values():
            rng.shuffle(mine)
        rounds = []
        r = 0
        while any(len(s) > r * SETUP_OPS_PER_TX for s in specs.values()):
            txs = []
            for m in self.makers:
                key = m.account_id.key_bytes
                batch = specs[key][r * SETUP_OPS_PER_TX:
                                   (r + 1) * SETUP_OPS_PER_TX]
                if batch:
                    txs.append((m, batch))
            rounds.append(txs)
            r += 1
        return rounds

    def post_txs(self, posts) -> List[Tx]:
        """One round of `book_rounds`, signed now (sequence numbers follow
        the previous round's)."""
        return [Tx(m.tx([self._quote_op(m, *s) for s in batch],
                        seq=self._next_seq(m)), "setup",
                   partial(self._post, m.account_id.key_bytes, batch),
                   book=True)
                for m, batch in posts]

    # -- the order book, indexed ---------------------------------------------
    def _rest(self, oid: int, owner: bytes, side: int, level: int,
              lots: int) -> None:
        old = self.books[side].get(oid)
        if old is not None:
            self._unqueue(old)
        super()._rest(oid, owner, side, level, lots)
        insort(self.queue[side], (level, oid))
        self.side_of[oid] = side
        self.owned.setdefault(owner, set()).add(oid)

    def _unqueue(self, o) -> None:
        q = self.queue[o.side]
        i = bisect_left(q, (o.level, o.id))
        if i == len(q) or q[i] != (o.level, o.id):
            raise AssertionError("dex model: offer %d is not queued" % o.id)
        del q[i]

    def _take(self, side: int, taker: bytes, dest: bytes,
              wheat: Optional[int] = None,
              sheep: Optional[int] = None) -> None:
        """DexHistory._take over the side's queue instead of a scan of
        the whole side (the same priority on this ladder); and no fill
        on a rung that a re-quote in flight touches."""
        w_asset, s_asset = self._wheat_sheep(side)
        book, q = self.books[side], self.queue[side]
        got = paid = 0
        filled = []
        for _level, oid in q[:4]:        # three offers at the most
            o = book[oid]
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
            if self.moving[side].get(o.level):
                raise AssertionError(
                    "dex model: a fill on rung %d of side %d, which a "
                    "re-quote in flight touches" % (o.level, side))
            pay = x * o.n // o.d
            o.amount -= x
            self._credit(o.owner, w_asset, -x)
            self._credit(o.owner, s_asset, pay)
            got += x
            paid += pay
            filled.append(o)
        for o in filled:
            if o.amount == 0:
                del book[o.id]
                self._unqueue(o)
                self.owned[o.owner].discard(o.id)
        self.fills_checked += len(filled)
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

    def _reach(self, side: int, sublots: int) -> Optional[tuple]:
        """(best rung, guard rung): the rung of the side's head, and the
        rung of the last offer that the orders in flight on the side,
        and `sublots` sub-lots more, can reach in any order; the head is
        always within reach (it alone may rest part-filled). None: they
        could empty the side."""
        n = sum(f[0] for f in self.flying[side]) + sublots
        reach = n * self._wheat_per_lot(side) // SUBLOTS
        book, q = self.books[side], self.queue[side]
        if not q:
            return None
        i = 0
        reach -= book[q[0][1]].amount
        while reach > 0:
            i += 1
            if i == len(q):
                return None
            reach -= book[q[i][1]].amount
        return q[0][0], q[i][0]

    # -- the closed loop -----------------------------------------------------
    def start(self, seconds: float) -> None:
        self.seconds = seconds
        order = list(range(len(self.clients)))
        self.rng.shuffle(order)
        self.ready = deque((c, 0.0) for c in order)
        self.sends = [0] * len(self.clients)

    def _is_corrupt(self, k: int) -> bool:
        if k % self.corrupt_every == 0:
            self._corrupt_at = k + self._crng.randrange(self.corrupt_every)
        return k == self._corrupt_at

    def take_due(self, now: float, limit: int) -> List[Request]:
        """The ready clients' next transactions, at most `limit`. Once
        the window has closed a closed loop sends no more."""
        out: List[Request] = []
        waits = []
        while now < self.seconds and self.ready and len(out) < limit:
            c, due = self.ready.popleft()
            req = self._make(c, due)
            if req is None:
                self.deferrals += 1
                waits.append((c, due))
            else:
                out.append(req)
        self.ready.extend(waits)
        return out

    def _make(self, c: int, due: float) -> Optional[Request]:
        acct = self.clients[c]
        if c < self.n_makers:
            tx = self._requote(acct)
        elif c < self.n_makers + self.n_takers:
            tx = self._order(c - self.n_makers, acct)
            if tx is None:
                return None
        else:
            tx = self._payment(c - self.n_makers - self.n_takers, acct)
        self.sends[c] += 1
        req = Request(self.k, c, due, self._is_corrupt(self.k), tx)
        self.k += 1
        self.requests.append(req)
        return req

    def _requote(self, m):
        """MAKER_OPS of the maker's own offers, each at least
        MAKER_MARGIN rungs behind its side's guard, move to rungs as far
        behind."""
        key = m.account_id.key_bytes
        floor = [self._reach(s, 0)[1] + MAKER_MARGIN
                 for s in range(self.n_sides)]
        mine = sorted(oid for oid in self.owned[key]
                      if self.books[self.side_of[oid]][oid].level >=
                      floor[self.side_of[oid]])
        if len(mine) < MAKER_OPS:
            raise RuntimeError("a maker has fewer than %d offers behind "
                               "the guard" % MAKER_OPS)
        moves = []
        for oid in self.rng.sample(mine, MAKER_OPS):
            side = self.side_of[oid]
            o = self.books[side][oid]
            dst = self.rng.randrange(floor[side], self.levels)
            if min(o.level, dst) <= floor[side] - MAKER_MARGIN:
                raise AssertionError("dex model: a re-quote within reach")
            moves.append((oid, side, dst, self.rng.randint(*OFFER_LOTS),
                          o.level, o.amount))
        for _oid, side, dst, _lots, src, _amount in moves:
            for rung in (src, dst):
                self.moving[side][rung] = self.moving[side].get(rung, 0) + 1
        frame = m.tx([self._quote_op(m, side, dst, lots, oid)
                      for oid, side, dst, lots, _src, _amount in moves],
                     seq=self._next_seq(m))
        return Tx(frame, "maker", partial(self._requoted, key, moves),
                  book=True, release=partial(self._unmove, moves))

    def _unmove(self, moves) -> None:
        for _oid, side, dst, _lots, src, _amount in moves:
            for rung in (src, dst):
                left = self.moving[side][rung] - 1
                if left:
                    self.moving[side][rung] = left
                else:
                    del self.moving[side][rung]

    def _requoted(self, key: bytes, moves) -> None:
        for oid, side, _dst, _lots, src, amount in moves:
            o = self.books[side].get(oid)
            self.requotes_checked += 1
            if o is None or o.level != src or o.amount != amount:
                raise AssertionError(
                    "dex model: offer %d changed between its re-quote's "
                    "signing and its application" % oid)
        self._move(key, [(oid, side, dst, lots)
                         for oid, side, dst, lots, _s, _a in moves])

    def _order(self, i: int, a):
        """A taker's order on the first side, in the seed's order, that
        takes it under the rule (module docstring); None: none does."""
        sublots = self.rng.randint(*TAKER_LOTS) * SUBLOTS + 1
        path = i >= self.n_takers // 2
        sides = list(range(self.n_sides))
        self.rng.shuffle(sides)
        for side in sides:
            got = self._reach(side, sublots)
            if got is None or self.orders[side] >= SUBLOTS - 1:
                continue
            best, guard = got
            flying = self.flying[side]
            limit = min([f[1] for f in flying] +
                        [best + THROUGH, self.levels - 1 - MAKER_MARGIN])
            if guard >= limit or (self.moving[side] and
                                  guard >= min(self.moving[side])):
                continue
            if guard != best and any(f[2] for f in flying):
                continue
            pays = self.sends[self.n_makers + i] % 2 == 0 and guard == best
            dest = self.takers[(i + 1) % self.n_takers] if path else a
            kind = (("buy", "sell"), ("receive", "send"))[path][pays]
            op, wheat, sheep = self._taker_op(a, kind, side, best, sublots,
                                              dest)
            entry = [sublots, best + THROUGH, pays]
            flying.append(entry)
            self.orders[side] += 1
            return Tx(
                a.tx([op], seq=self._next_seq(a)), "taker",
                partial(self._take, side, a.account_id.key_bytes,
                        dest.account_id.key_bytes, wheat=wheat,
                        sheep=sheep),
                book=True, release=partial(self._land, side, entry))
        return None

    def _land(self, side: int, entry) -> None:
        self.flying[side].remove(entry)

    def _payment(self, i: int, a):
        j = self.rng.randrange(self.n_payers - 1)
        dest = self.payers[j + (j >= i)]
        amount = 1000 + self.rng.randrange(1000)
        return Tx(a.tx([a.op_payment(dest.account_id, amount)],
                       seq=self._next_seq(a)), "payer", partial(
            self._paid, a.account_id.key_bytes, dest.account_id.key_bytes,
            amount))

    def _paid(self, src: bytes, dest: bytes, amount: int) -> None:
        self._credit(src, -1, -amount)
        self._credit(dest, -1, amount)

    def sent(self, tx: Tx, frame) -> str:
        """`tx` was admitted: the model waits for it in a ledger."""
        tx.env = frame.envelope_bytes()
        txid = frame.contents_hash().hex()
        self.pending[txid] = tx
        return txid

    def withdraw(self, tx: Tx) -> None:
        """`tx` was refused: what it held in flight is free again."""
        if tx.release is not None:
            tx.release()
        tx.frame = None

    def replied(self, req: Request, now: float) -> None:
        self.ready.append((req.client, now))

    # -- the plain reference -------------------------------------------------
    def absorb(self, seq: int, previous_hash: bytes, rows) -> List[Tx]:
        """Ledger `seq`, whose transactions node 0's txhistory lists as
        `rows` of (txid, result code): the model orders them by
        `apply_order` and applies them. Returns them in that order."""
        txs = []
        for txid, code in rows:
            tx = self.pending.pop(txid, None)
            if tx is None:
                self.unknown_txs += 1
            else:
                txs.append((tx.key, tx.seq, tx.env, tx, code, txid))
        order = apply_order(previous_hash, txs)
        self.at_ledger = seq
        for key, seqnum, _env, tx, code, _txid in order:
            tx.frame = None
            m = self.model.get(key)
            if m is not None:
                m["balance"] -= tx.fee
                m["seq"] = seqnum
            self.fee_pool += tx.fee
            if tx.release is not None:
                tx.release()
            if code != 0:
                self.failed[tx.kind] += 1
                continue
            if tx.effect is not None:
                try:
                    tx.effect()
                except (AssertionError, KeyError) as e:
                    self.faults.append("ledger %d: %s" % (seq, e))
        self.book_ledgers += any(t[3].book for t in order)
        self.ledgers.append((seq, [t[5] for t in order]))
        return [t[3] for t in order]
