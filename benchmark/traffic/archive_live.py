"""The generator of a full validator's state and traffic: the restart
state of `state_history` (a cpu-backend publisher loads a large account
state onto its own disk and publishes the first checkpoint of a local
file archive), stopped at that checkpoint's snapshot; and a closed loop
of payments over it, each from a keyed account not used before in the
run. Neither `state_history.py` nor `payments.py` is edited.

State (configs/<config>.json): `accounts` the loader installs, and
"state" as `state_history`'s without its `accounts`.
Traffic (workloads/<cell>.json "traffic"): as `payments`' closed loop
(clients, corrupt_every, submit_batch); besides
  sources       "without replacement from the signer accounts"
  destinations  "uniform with replacement over all accounts"

The publisher is node 0 of the network to come, before its network
formed: it signs with node 0's key on that network's passphrase, so the
first checkpoint's SCP messages are node 0's own and its ledgers are
those the three validators restart from. Its history is the genesis
ledger, state_history's role accounts created by an ordinary close, the
load between two closes, and empty ledgers to the first
checkpoint, which it publishes; then the snapshot (database, bucket
files, index sidecars) is taken, and the publisher stops.

The plain reference (no program code): each account's balance and
sequence number as the loaded state and the admitted payments leave
them; the fee pool, at 100 stroops a payment.
"""

from __future__ import annotations

import os
from typing import List

from .history import _sk
from .payments import Payments, Request
from .state_history import (
    N_ROLES, ROLE_BALANCE, StateHistory, account_ids, bulk_load,
)


class SnapshotHistory(StateHistory):
    def __init__(self, config: dict, seed: int, workdir: str,
                 node_seed, passphrase: str) -> None:
        super().__init__(config, {}, seed, workdir)
        self.node_seed, self.passphrase = node_seed, passphrase

    def node_config(self, n: int, backend: str, writable: bool = False):
        cfg = super().node_config(n, backend, writable)
        cfg.NODE_SEED = self.node_seed
        cfg.NETWORK_PASSPHRASE = self.passphrase
        cfg.QUORUM_SET = cfg.self_qset()
        return cfg

    def publish(self) -> None:
        import time
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.testing import AppLedgerAdapter
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        st = self.state
        pub = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                          self.node_config(0, "cpu", writable=True))
        pub.enable_buckets(os.path.join(self.node_dir(0), "buckets"))
        pub.start()
        self.pub = pub
        lm, hm = pub.ledger_manager, pub.history_manager
        # state_history's role accounts, by an ordinary close: the first
        # checkpoint holds a transaction set and its results
        root = AppLedgerAdapter(pub).root_account()
        f = root.tx([root.op_create_account(
            _sk(self.seed, "role", i).public_key, ROLE_BALANCE)
            for i in range(N_ROLES)])
        if pub.submit_transaction(f) != 0:
            raise RuntimeError("publisher refused the role accounts")
        pub.manual_close()
        n_signers = int(st["signer_accounts"])
        t0 = time.perf_counter()
        self.signer_sks = [_sk(self.seed, "state-signer", i)
                           for i in range(n_signers)]
        self.extra_sks = [_sk(self.seed, "state-signer", i, 1)
                          for i in range(n_signers)]
        self.ids = account_ids(self.seed, int(self.config["accounts"]),
                               self.signer_sks)
        keys_s = time.perf_counter() - t0
        self.start_balance = int(st["balance"])
        self.start_seq = lm.last_closed_ledger_num() << 32
        self.load_info = bulk_load(
            pub, self.ids,
            {sk.public_key.key_bytes: x.public_key.key_bytes
             for sk, x in zip(self.signer_sks, self.extra_sks)},
            self.start_balance, int(st["bucket_level"]))
        self.load_info["keys_s"] = keys_s
        while hm.published_checkpoints < 1:
            pub.clock.set_virtual_time(pub.clock.now() + 1.0)
            pub.manual_close()
            pub.crank_until(lambda: hm.publish_queue() == [],
                            max_cranks=20000)
        self.lcl_at_snapshot = lm.last_closed_ledger_num()
        if self.lcl_at_snapshot != self.freq - 1:
            raise RuntimeError("the publisher stopped at %d, not at the "
                               "first checkpoint" % self.lcl_at_snapshot)
        self.fee_pool = lm.lcl_header.feePool
        self._snapshot(pub)
        self.headers = dict(pub.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        self.pub_time = pub.clock.now()


class _Request(Request):
    __slots__ = ("client",)


class FreshSources(Payments):
    """`payments`' closed loop, where a request's `account` is the index
    of its source among the signer accounts, a new one each time, and
    its `dest` an index into all the state's ids, uniform with
    replacement, never the source's own; amounts are 1,000-1,999
    stroops. The loop's clients are what `ready` holds."""

    def __init__(self, traffic: dict, seed: int, signer_keys: List[bytes],
                 ids: List[bytes], seconds: float) -> None:
        if traffic["loop"] != "closed":
            raise ValueError("archive_live runs a closed loop")
        super().__init__(traffic, seed, len(ids), seconds)
        from ..harness.stats import rng_for
        self.signer_keys, self.ids = signer_keys, ids
        self.order = rng_for(seed, "archive-sources").sample(
            range(len(signer_keys)), len(signer_keys))
        self.used = 0

    def _make(self, client: int, due: float) -> Request:
        if self.used == len(self.order):
            raise RuntimeError("every signer account has sent: the "
                               "state holds too few for the run")
        k = self.k
        self.k += 1
        src = self.order[self.used]
        self.used += 1
        dest = self.rng.randrange(len(self.ids))
        while self.ids[dest] == self.signer_keys[src]:
            dest = self.rng.randrange(len(self.ids))
        req = _Request(k, src, due, self._is_corrupt(k),
                       1000 + self.rng.randrange(1000), dest)
        req.client = client
        self.requests.append(req)
        return req

    def replied(self, req: Request, now: float) -> None:
        self.ready.append((req.client, now))
