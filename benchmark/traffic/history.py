"""The general generator of archive traffic: a cpu-backend publisher
closes a dense synthetic history into a local file archive, from the
parameters of a workload file and the run's seed. (Adapted copy of
bench.py's PublishedHistory and _StandardMix, PR 13/21; the originals
are listed in PERF.md for deletion.)

Parameters (workloads/<cell>.json "traffic"):
  txs_per_ledger, sigs_per_tx   every dense ledger's payments
  mix          "hub": every sender pays the root account
               "pairs": sender 2k pays 2k+1 and back (disjoint clusters)
  mixed_every  0, or N: every Nth dense ledger also carries the other op
               types from 20 role accounts (trust lines, offers, path
               payments, data, bump-sequence, merges, fee bumps, muxed)
  checkpoints  how many checkpoints to publish

The seed draws the account keys and every payment's amount; the counts
are the same for every seed. Beside the archive the generator keeps the
plain model of what a replay has to arrive at: per sender the balance
and sequence number, the fee pool, and the count of signatures issued.
"""

from __future__ import annotations

import hashlib
import os
from typing import Dict, List

START_BALANCE = 10 ** 10


def _sk(seed: int, what: str, i: int, j: int = 0):
    from stellar_core_tpu.crypto.keys import SecretKey
    return SecretKey.from_seed(hashlib.sha256(
        b"bench-history/%d/%s/%d/%d" % (seed, what.encode(), i, j)).digest())


class _RoleOps:
    """Every 4th-ledger traffic of the other wire op types, from
    dedicated role accounts, none of which touches a sender."""

    def __init__(self, app, adapter, root, roles, seed: int) -> None:
        self.app, self.adapter, self.root, self.roles = \
            app, adapter, root, roles
        self.issuer = roles[0]
        self.seed = seed
        self.merge_n = 0
        self.sigs = 0       # signatures this object put into the history

    def _sub(self, frame) -> None:
        status = self.app.submit_transaction(frame)
        if status != 0:
            raise RuntimeError("role transaction refused: %r %r"
                               % (status, frame.result))
        self.sigs += len(frame.envelope.value.signatures)

    def setup(self) -> None:
        from stellar_core_tpu.xdr import AccountFlags, Asset
        app, issuer = self.app, self.issuer
        self._sub(issuer.tx([issuer.op_set_options(
            set_flags=AccountFlags.AUTH_REQUIRED_FLAG |
            AccountFlags.AUTH_REVOCABLE_FLAG)]))
        app.manual_close()
        self.USD = Asset.credit("USD", issuer.account_id)
        lines = self.roles[1:9]
        for r in lines:
            self._sub(r.tx([r.op_change_trust(self.USD, 10 ** 12)]))
        app.manual_close()
        self._sub(issuer.tx([issuer.op_allow_trust(r.account_id, b"USD\x00")
                             for r in lines]))
        app.manual_close()
        self._sub(issuer.tx([issuer.op_payment(r.account_id, 10 ** 9,
                                               self.USD)
                             for r in lines[:4]]))
        app.manual_close()

    def submit(self, rnd: int) -> None:
        from stellar_core_tpu.testing import TestAccount
        from stellar_core_tpu.transactions.transaction_frame import (
            FeeBumpTransactionFrame,
        )
        from stellar_core_tpu.xdr import (
            Asset, EnvelopeType, FeeBumpTransaction,
            FeeBumpTransactionEnvelope, MuxedAccount, OperationBody,
            OperationType, PaymentOp, TransactionEnvelope, _Ext,
        )
        from stellar_core_tpu.xdr.basic import MuxedAccountMed25519
        from stellar_core_tpu.xdr.transaction import (
            BumpSequenceOp, PathPaymentStrictReceiveOp,
            PathPaymentStrictSendOp, _InnerTxEnvelope,
        )
        app, USD, r, sub = self.app, self.USD, self.roles, self._sub
        native = Asset.native()
        sub(r[9].tx([r[9].op_change_trust(USD, 10 ** 10 + rnd),
                     r[9].op_manage_data("bench-k", b"v%d" % rnd)]))
        sub(r[10].tx([r[10].op_manage_data("tmp%d" % (rnd % 3),
                                           b"x" if rnd % 2 else None)]))
        sub(r[11].tx([r[11].op(OperationBody(
            OperationType.BUMP_SEQUENCE,
            BumpSequenceOp(bumpTo=r[11].next_seq() + 3)))]))
        sub(r[1].tx([r[1].op_manage_sell_offer(USD, native, 500 + rnd,
                                               2, 1)]))
        sub(r[2].tx([r[2].op_manage_buy_offer(native, USD, 60 + rnd,
                                              1, 2)]))
        sub(r[3].tx([r[3].op(OperationBody(
            OperationType.PATH_PAYMENT_STRICT_RECEIVE,
            PathPaymentStrictReceiveOp(
                sendAsset=USD, sendMax=10 ** 8, destination=r[4].muxed,
                destAsset=native, destAmount=40 + rnd, path=[])))]))
        sub(r[4].tx([r[4].op(OperationBody(
            OperationType.PATH_PAYMENT_STRICT_SEND,
            PathPaymentStrictSendOp(
                sendAsset=USD, sendAmount=25 + rnd, destination=r[5].muxed,
                destAsset=native, destMin=1, path=[])))]))
        sub(self.issuer.tx([self.issuer.op_allow_trust(
            r[6].account_id, b"USD\x00", authorize=2 if rnd % 2 else 1)]))
        # account merge: fund a throwaway, merge it back next round
        if self.merge_n:
            prev = TestAccount(self.adapter,
                               _sk(self.seed, "fodder", self.merge_n))
            sub(prev.tx([prev.op(OperationBody(
                OperationType.ACCOUNT_MERGE,
                MuxedAccount.from_account_id(self.root.account_id)))]))
        self.merge_n += 1
        fodder = _sk(self.seed, "fodder", self.merge_n)
        sub(r[12].tx([r[12].op_create_account(fodder.public_key,
                                              3 * 10 ** 7)]))
        # (no INFLATION: retired at protocol 13, refused at admission)
        inner = r[15].tx([r[15].op_payment(self.root.account_id, 5)])
        fb = FeeBumpTransaction(
            feeSource=r[14].muxed, fee=2000,
            innerTx=_InnerTxEnvelope(EnvelopeType.ENVELOPE_TYPE_TX,
                                     inner.envelope.value),
            ext=_Ext.v0())
        env = TransactionEnvelope(
            EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP,
            FeeBumpTransactionEnvelope(tx=fb, signatures=[]))
        frame = FeeBumpTransactionFrame(app.config.network_id, env)
        frame.add_signature(r[14].sk)
        status = app.submit_transaction(frame)
        if status != 0:
            raise RuntimeError("fee bump refused: %r" % status)
        self.sigs += 2      # the inner signature and the sponsor's
        sub(r[16].tx([r[16].op(OperationBody(
            OperationType.PAYMENT,
            PaymentOp(destination=MuxedAccount(
                0x100, MuxedAccountMed25519(
                    id=7, ed25519=r[17].account_id.key_bytes)),
                asset=native, amount=9 + rnd)))]))


class PublishedHistory:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        self.config, self.traffic, self.seed = config, traffic, seed
        self.workdir = workdir
        self.archive_root = os.path.join(workdir, "archive")
        os.makedirs(self.archive_root, exist_ok=True)
        self.freq = int(config["checkpoint_frequency"])
        self.model: Dict[bytes, dict] = {}   # account key bytes -> state
        self.sender_keys: List = []
        self.sigs_issued = 0     # signatures in ledgers <= tip
        self.fee_pool = 0
        self.pub = None

    def node_config(self, n: int, backend: str, writable: bool = False):
        """The Config of a node over this archive, from the deployment's
        file: the same for the publisher and every replaying node but
        for the verify backend."""
        from stellar_core_tpu.history.archive import HistoryArchive
        from stellar_core_tpu.main.config import Config
        c = self.config
        cfg = Config.test_config(n)
        cfg.DATABASE = c["database"]
        cfg.CHECKPOINT_FREQUENCY = self.freq
        cfg.SIG_VERIFY_BACKEND = backend
        cfg.INVARIANT_CHECKS = list(c["invariant_checks"])
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = int(c["max_tx_set_ops"])
        arch = HistoryArchive.local_dir("bench", self.archive_root)
        d = {"get": arch.get_tmpl, "mkdir": arch.mkdir_tmpl}
        if writable:
            d["put"] = arch.put_tmpl
        cfg.HISTORY = {"bench": d}
        return cfg

    def node_dir(self, n: int) -> str:
        d = os.path.join(self.workdir, "node-%d" % n)
        os.makedirs(d, exist_ok=True)
        return d

    def publish(self) -> None:
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        from ..harness.stats import rng_for
        t = self.traffic
        n_tx, n_sig = int(t["txs_per_ledger"]), int(t["sigs_per_tx"])
        pairs = t["mix"] == "pairs"
        mixed_every = int(t.get("mixed_every", 0))
        rng = rng_for(self.seed, "history-amounts")
        pub = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                          self.node_config(0, "cpu", writable=True))
        pub.enable_buckets(os.path.join(self.node_dir(0), "buckets"))
        pub.start()
        self.pub = pub
        adapter = AppLedgerAdapter(pub)
        root = adapter.root_account()

        def submit(frame) -> None:
            status = pub.submit_transaction(frame)
            if status != 0:
                raise RuntimeError("publisher refused a transaction: %r %r"
                                   % (status, frame.result))
            self.sigs_issued += len(frame.envelope.value.signatures)
            self.fee_pool += frame.envelope.value.tx.fee

        n_roles = 20 if mixed_every else 0
        sks = [_sk(self.seed, "sender", i) for i in range(n_tx)] + \
            [_sk(self.seed, "role", i) for i in range(n_roles)]
        for lo in range(0, len(sks), 100):
            submit(root.tx([root.op_create_account(sk.public_key,
                                                   START_BALANCE)
                            for sk in sks[lo:lo + 100]]))
            pub.manual_close()
            created_at = pub.ledger_manager.last_closed_ledger_num()
            for sk in sks[lo:lo + 100]:
                self.model[sk.public_key.key_bytes] = {
                    "balance": START_BALANCE, "seq": created_at << 32}
        accounts = [TestAccount(adapter, sk) for sk in sks]
        senders, roles = accounts[:n_tx], accounts[n_tx:]
        self.sender_keys = [s.account_id for s in senders]
        seqs = [self.model[s.account_id.key_bytes]["seq"] for s in senders]

        def charge(i: int, frame, out: int = 0) -> None:
            m = self.model[senders[i].account_id.key_bytes]
            m["balance"] -= frame.envelope.value.tx.fee + out
            m["seq"] += 1

        extra: Dict[int, list] = {}
        if n_sig > 1:
            for i, s in enumerate(senders):
                ks = [_sk(self.seed, "signer", i, j)
                      for j in range(n_sig - 1)]
                ops = [s.op_add_signer(k.public_key.key_bytes) for k in ks]
                ops.append(s.op_set_options(med=n_sig))
                seqs[i] += 1
                f = s.tx(ops, seq=seqs[i])
                submit(f)
                charge(i, f)
                extra[i] = ks
            pub.manual_close()   # one ledger arms every sender
        mixer = None
        if mixed_every:
            mixer = _RoleOps(pub, adapter, root, roles, self.seed)
            mixer.setup()
        # keep virtual time ahead of closeTime (1 s per close; the herder
        # refuses values more than 60 s ahead of the local clock)
        pub.clock.set_virtual_time(
            pub.clock.now() + pub.ledger_manager.last_closed_ledger_num())
        hm = pub.history_manager
        target = hm.published_checkpoints + int(t["checkpoints"])
        dense = 0
        while hm.published_checkpoints < target:
            for i, snd in enumerate(senders):
                amount = 1000 + rng.randrange(1000)
                if pairs:
                    j = i + 1 if i % 2 == 0 else i - 1
                    dest = senders[j].account_id
                    self.model[dest.key_bytes]["balance"] += amount
                else:
                    dest = root.account_id
                seqs[i] += 1
                f = snd.tx([snd.op_payment(dest, amount)], seq=seqs[i],
                           extra_signers=extra.get(i))
                submit(f)
                charge(i, f, amount)
            if mixer is not None and dense % mixed_every == 1:
                mixer.submit(dense)
            pub.clock.set_virtual_time(pub.clock.now() + 1.0)
            pub.manual_close()
            dense += 1
            pub.crank_until(lambda: hm.publish_queue() == [],
                            max_cranks=20000)
        lcl = pub.ledger_manager.last_closed_ledger_num()
        self.tip = ((lcl + 1) // self.freq) * self.freq - 1
        if lcl != self.tip:
            raise RuntimeError("publisher closed past the archive tip "
                               "(%d > %d): the model counts every ledger"
                               % (lcl, self.tip))
        self.dense = dense
        if mixer is not None:
            self.sigs_issued += mixer.sigs
        self.headers = dict(pub.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        self.pub_time = pub.clock.now()

    def close(self) -> None:
        if self.pub is not None:
            self.pub.stop()
            self.pub = None
