"""The generator of a restart archive: a cpu-backend publisher that holds
a large account state on its own disk closes a history of payments over
it into a local file archive and snapshots itself one checkpoint before
the tip, from a configuration's `state`, a workload's `traffic` and the
run's seed. It shares the archive, the role traffic and the publishing
loop's shape with `history.PublishedHistory`, which it subclasses; that
file is not edited.

State (configs/<config>.json "state"):
  accounts         accounts the bulk loader installs
  signer_accounts  of those, the ones with real ed25519 keys and one
                   extra signer of weight 1 under a medium threshold of
                   2: the only ones that can send
  balance          stroops each is installed with
  bucket_level     the bucket-list level whose `curr` the seeded bucket
                   becomes
Traffic (workloads/<cell>.json "traffic"):
  txs_per_ledger, sigs_per_tx   every dense ledger's payments
  mixed_every      every Nth dense ledger also carries the other op types
                   from 20 role accounts (history._RoleOps)
  checkpoints      2: the first ends the set-up ledgers and is where the
                   snapshot is taken, the second holds the dense ledgers

The history. Ordinary closes create the role accounts and set them up;
then `bulk_load` installs the state between two closes; empty ledgers
run on to the first checkpoint, whose last close commits to the seeded
bucket list; the publisher publishes it and snapshots itself (database,
bucket files, index sidecars). One checkpoint of dense ledgers follows:
each payment's source is drawn without replacement from the signer
accounts, so every source is read cold, and its destination uniformly,
with replacement, from all accounts (LoadGenerator's pickAccountPair).

The bulk loader writes one state into both of a node's stores: a bucket
(file, index sidecar, bloom filter; streamed, no entry object is ever
made) that it hands to the node's bucket manager by name, and the
`accounts` table. Every byte of an entry comes from the program's own
XDR of one template account with the key spliced in; the SQL row's blob
is the bucket record's body, so the two stores hold equal bytes.

The plain reference (no program code): for every account a payment
touched, its balance and sequence number; the fee pool, at 100 stroops
an operation and one more operation for a fee bump; the count of
signatures issued in the dense ledgers.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import sqlite3
from typing import Dict, List

from .history import PublishedHistory, _RoleOps, _sk

BASE_FEE = 100
ROLE_BALANCE = 10 ** 10
N_ROLES = 20
_MARK_A = b"\xa5" * 32      # the template account's id
_MARK_S = b"\x5a" * 32      # the template account's extra signer


def account_ids(seed: int, n: int, signer_sks: list) -> List[bytes]:
    """The ids of the state, sorted as a bucket sorts account entries
    (raw key order): the signer accounts' public keys and, for the rest,
    32-byte digests that only ever receive."""
    ids = [sk.public_key.key_bytes for sk in signer_sks]
    ids.extend(hashlib.sha256(b"bench-state/%d/%d" % (seed, i)).digest()
               for i in range(n - len(ids)))
    ids.sort()
    return ids


def _bloom_bits(key_xdrs: List[bytes], nbits: int, k: int) -> bytearray:
    """The bloom filter over `key_xdrs`, as `BloomFilter.add` of
    `key_fingerprint` sets it bit for bit (probe i is (h1 + i*h2) mod
    nbits, taken here mod nbits term by term), in bulk. A test holds the
    two together."""
    import numpy as np
    fp = np.frombuffer(b"".join(hashlib.sha256(kb).digest()[:16]
                                for kb in key_xdrs), dtype="<u8")
    m = np.uint64(nbits)
    h1 = fp[0::2] % m
    h2 = (fp[1::2] | np.uint64(1)) % m
    flags = np.zeros(nbits, dtype=bool)
    for i in range(k):
        flags[(h1 + np.uint64(i) * h2) % m] = True
    return bytearray(np.packbits(flags, bitorder="little").tobytes())


def bulk_load(app, ids: List[bytes], signers: Dict[bytes, bytes],
              balance: int, level: int) -> dict:
    """Install `ids` (sorted 32-byte account ids) on `app` between two
    closes: a bucket that becomes `curr` of `level`, with its sidecar
    index, and the rows of the `accounts` table. `signers` gives an
    account's extra signer key. The next close's header commits to the
    list; the node must not stop before it."""
    import time
    from stellar_core_tpu.bucket.bucket import entry_record
    from stellar_core_tpu.bucket.bucket_index import (
        BloomFilter, BucketIndex, sidecar_path,
    )
    from stellar_core_tpu.crypto.strkey import encode_public_key
    from stellar_core_tpu.transactions.account_helpers import (
        make_account_entry,
    )
    from stellar_core_tpu.xdr import (
        BucketEntry, LedgerKey, PublicKey, Signer, SignerKey,
    )
    t0 = time.perf_counter()
    lm, bm = app.ledger_manager, app.bucket_manager
    lcl = lm.last_closed_ledger_num()
    seq0 = lcl << 32
    lev = bm.bucket_list.levels[level]
    if lev.curr.get_hash() != b"\x00" * 32 or lev.next.is_live():
        raise RuntimeError("level %d of the bucket list is in use" % level)

    def template(signer: bool) -> list:
        e = make_account_entry(PublicKey.ed25519(_MARK_A), balance, seq0,
                               lcl)
        if signer:
            acc = e.data.value
            acc.numSubEntries = 1
            acc.thresholds = bytes([1, 0, 2, 0])
            acc.signers = [Signer(key=SignerKey.ed25519(_MARK_S),
                                  weight=1)]
        rec = entry_record(BucketEntry.live(e))
        parts = rec.split(_MARK_A)
        assert len(parts) == 2
        return parts[:1] + parts[1].split(_MARK_S)

    p0, p1 = template(False)
    s0, s1, s2 = template(True)
    k0, k1 = LedgerKey.account(PublicKey.ed25519(_MARK_A)).to_xdr() \
        .split(_MARK_A)
    meta = entry_record(BucketEntry.meta(lm.lcl_header.ledgerVersion))
    h = hashlib.sha256(meta)
    tmp_path = os.path.join(bm.bucket_dir, ".bulk-load.tmp")
    key_xdrs, offsets, lengths, rows = [], [], [], []
    off = len(meta)
    db = app.database
    insert = ("INSERT INTO accounts (accountid,balance,seqnum,"
              "numsubentries,flags,lastmodified,entry) "
              "VALUES (?,?,?,?,?,?,?)")
    with open(tmp_path, "wb") as fh:
        fh.write(meta)
        chunk = []
        for kb in ids:
            sx = signers.get(kb)
            rec = p0 + kb + p1 if sx is None else s0 + kb + s1 + sx + s2
            chunk.append(rec)
            key_xdrs.append(k0 + kb + k1)
            offsets.append(off + 8)     # record mark + union discriminant
            lengths.append(len(rec) - 8)
            off += len(rec)
            rows.append((encode_public_key(kb), balance, seq0,
                         0 if sx is None else 1, 0, lcl, rec[8:]))
            if len(chunk) == 8192:
                body = b"".join(chunk)
                fh.write(body)
                h.update(body)
                db.executemany(insert, rows)
                chunk, rows = [], []
        body = b"".join(chunk)
        fh.write(body)
        h.update(body)
        db.executemany(insert, rows)
    db.commit()
    bucket_hash = h.digest()
    path = bm.bucket_filename(bucket_hash)
    os.replace(tmp_path, path)
    t_file = time.perf_counter()
    bloom = BloomFilter.for_capacity(
        len(ids), app.config.BUCKETDB_BLOOM_BITS_PER_KEY)
    bloom.bits = _bloom_bits(key_xdrs, bloom.nbits, bloom.k)
    BucketIndex(bucket_hash, key_xdrs, list(range(1, len(ids) + 1)),
                offsets, lengths, bloom).save(sidecar_path(path))
    t_index = time.perf_counter()
    # by name, as a restart finds it: the file is hashed against its
    # name and the sidecar just written is what indexes it
    bucket = bm.get_bucket_by_hash(bucket_hash)
    if bucket is None:
        raise RuntimeError("the bucket manager refused the seeded bucket")
    lev.curr = bucket
    return {"accounts": len(ids), "bucket_bytes": off,
            "file_sql_s": t_file - t0, "index_s": t_index - t_file,
            "adopt_s": time.perf_counter() - t_index}


class _FeeCountingRoleOps(_RoleOps):
    """The role traffic, with the fees the model charges for it: 100
    stroops an operation, and a fee bump pays for one operation more
    than its inner transaction has."""

    def __init__(self, *a) -> None:
        super().__init__(*a)
        self.fees = 0

    def _sub(self, frame) -> None:
        super()._sub(frame)
        self.fees += BASE_FEE * len(frame.envelope.value.tx.operations)

    def submit(self, rnd: int) -> None:
        super().submit(rnd)
        self.fees += BASE_FEE * 2   # the one fee bump of a round: 1 op + 1


class StateHistory(PublishedHistory):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        super().__init__(config, traffic, seed, workdir)
        self.state = config["state"]
        self.snapshot_dir = os.path.join(workdir, "snapshot")
        self.load_info: dict = {}

    def node_config(self, n: int, backend: str, writable: bool = False):
        cfg = super().node_config(n, backend, writable)
        cfg.DATABASE = self.config["database"].replace(
            "<node dir>", self.node_dir(n))
        return cfg

    # -- the snapshot --------------------------------------------------------
    def _snapshot(self, pub) -> None:
        """The publisher's disk as it stands: its database through
        SQLite's backup of a second connection, its bucket files and
        their sidecars as hard links (buckets are immutable)."""
        os.makedirs(os.path.join(self.snapshot_dir, "buckets"))
        src = sqlite3.connect(pub.database.path)
        dst = sqlite3.connect(os.path.join(self.snapshot_dir, "node.db"))
        try:
            src.backup(dst)
        finally:
            dst.close()
            src.close()
        bdir = pub.bucket_manager.bucket_dir
        for name in os.listdir(bdir):
            if name.startswith("bucket-") and not name.endswith(".tmp"):
                os.link(os.path.join(bdir, name),
                        os.path.join(self.snapshot_dir, "buckets", name))

    def clone_snapshot(self, node_dir: str) -> None:
        """A node directory that holds what the publisher held at the
        snapshot: a copy of the database, links to the buckets."""
        os.makedirs(os.path.join(node_dir, "buckets"), exist_ok=True)
        shutil.copyfile(os.path.join(self.snapshot_dir, "node.db"),
                        os.path.join(node_dir, "node.db"))
        sdir = os.path.join(self.snapshot_dir, "buckets")
        for name in os.listdir(sdir):
            os.link(os.path.join(sdir, name),
                    os.path.join(node_dir, "buckets", name))

    # -- the history ---------------------------------------------------------
    def publish(self) -> None:
        import time
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        from stellar_core_tpu.xdr import PublicKey
        from ..harness.stats import rng_for
        t, st = self.traffic, self.state
        n_tx, n_sig = int(t["txs_per_ledger"]), int(t["sigs_per_tx"])
        mixed_every = int(t["mixed_every"])
        if n_sig != 2 or int(t["checkpoints"]) != 2:
            raise ValueError("state_history signs with the master key and "
                             "one extra signer, over two checkpoints")
        rng = rng_for(self.seed, "state-history")
        pub = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                          self.node_config(0, "cpu", writable=True))
        pub.enable_buckets(os.path.join(self.node_dir(0), "buckets"))
        pub.start()
        self.pub = pub
        lm, hm = pub.ledger_manager, pub.history_manager
        adapter = AppLedgerAdapter(pub)
        root = adapter.root_account()

        def close() -> None:
            pub.clock.set_virtual_time(pub.clock.now() + 1.0)
            pub.manual_close()
            pub.crank_until(lambda: hm.publish_queue() == [],
                            max_cranks=20000)

        # the role accounts, by ordinary closes
        role_sks = [_sk(self.seed, "role", i) for i in range(N_ROLES)]
        f = root.tx([root.op_create_account(sk.public_key, ROLE_BALANCE)
                     for sk in role_sks])
        if pub.submit_transaction(f) != 0:
            raise RuntimeError("publisher refused the role accounts")
        self.fee_pool += BASE_FEE * N_ROLES
        pub.manual_close()
        mixer = _FeeCountingRoleOps(
            pub, adapter, root, [TestAccount(adapter, sk)
                                 for sk in role_sks], self.seed)
        mixer.setup()
        pub.clock.set_virtual_time(
            pub.clock.now() + lm.last_closed_ledger_num())

        # the state, between two closes
        n_signers = int(st["signer_accounts"])
        t0 = time.perf_counter()
        signer_sks = [_sk(self.seed, "state-signer", i)
                      for i in range(n_signers)]
        extra_sks = [_sk(self.seed, "state-signer", i, 1)
                     for i in range(n_signers)]
        self.ids = account_ids(self.seed, int(st["accounts"]), signer_sks)
        keys_s = time.perf_counter() - t0
        self.start_balance = int(st["balance"])
        self.start_seq = lm.last_closed_ledger_num() << 32
        self.load_info = bulk_load(
            pub, self.ids,
            {sk.public_key.key_bytes: x.public_key.key_bytes
             for sk, x in zip(signer_sks, extra_sks)},
            self.start_balance, int(st["bucket_level"]))
        self.load_info["keys_s"] = keys_s

        # empty ledgers to the first checkpoint; the snapshot is taken
        # once it is published
        if lm.last_closed_ledger_num() >= self.freq - 1:
            raise RuntimeError("the set-up ledgers reach the first "
                               "checkpoint: nothing closes over the state")
        while hm.published_checkpoints < 1:
            close()
        self.lcl_at_snapshot = lm.last_closed_ledger_num()
        self._snapshot(pub)
        setup_sigs = mixer.sigs

        # one checkpoint of dense ledgers
        sources = rng.sample(range(n_signers), n_tx * self.freq)
        n_ids = len(self.ids)
        dense = 0
        while hm.published_checkpoints < 2:
            for i in sources[dense * n_tx:(dense + 1) * n_tx]:
                sk = signer_sks[i]
                src = sk.public_key.key_bytes
                dest = src
                while dest == src:
                    dest = self.ids[rng.randrange(n_ids)]
                amount = 1000 + rng.randrange(1000)
                snd = TestAccount(adapter, sk)
                f = snd.tx([snd.op_payment(PublicKey.ed25519(dest),
                                           amount)],
                           seq=self.start_seq + 1,
                           extra_signers=[extra_sks[i]])
                if pub.submit_transaction(f) != 0:
                    raise RuntimeError("publisher refused a payment: %r"
                                       % (f.result,))
                self.sigs_issued += n_sig
                self.fee_pool += BASE_FEE
                m = self._account(src)
                m["balance"] -= BASE_FEE + amount
                m["seq"] += 1
                self._account(dest)["balance"] += amount
                self.sender_keys.append(sk.public_key)
            if mixed_every and dense % mixed_every == 1:
                mixer.submit(dense)
            close()
            dense += 1
        self.tip = lm.last_closed_ledger_num()
        if self.tip != 2 * self.freq - 1 or dense != self.freq:
            raise RuntimeError("publisher closed past the archive tip "
                               "(%d): the model counts every ledger"
                               % self.tip)
        self.dense = dense
        self.sigs_issued += mixer.sigs - setup_sigs
        self.fee_pool += mixer.fees
        self.headers = dict(pub.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        self.pub_time = pub.clock.now()

    def _account(self, key: bytes) -> dict:
        m = self.model.get(key)
        if m is None:
            m = self.model[key] = {"balance": self.start_balance,
                                   "seq": self.start_seq}
        return m

    @property
    def touched(self) -> List[bytes]:
        """Every account a payment touched: the model's keys."""
        return list(self.model)
