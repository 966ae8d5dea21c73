"""The restart archive (ISSUE 33): benchmark/traffic/state_history.py's
generator at a small size (2,000 accounts, 256 of them signers,
checkpoint frequency 8, 20 payments a ledger), its bulk loader, and the
benchmark's own driver (benchmark/deployments/catchup_state.py) on the
`cpu` backend: a node restarted from the publisher's snapshot keeps
BucketDB attached and replays to the publisher's header chain and the
generator's plain model; a node that joins by applying the archive's
buckets arrives at the same rows; the two stores agree; the cold-read
meters and the restore spans say what they should; a bucket adopted
from its file is hashed, not parsed.
"""

import copy
import hashlib
import json
import os
import sqlite3
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control  # noqa: E402
from benchmark.deployments import catchup_state  # noqa: E402
from benchmark.harness import runner  # noqa: E402
from benchmark.traffic.state_history import (  # noqa: E402
    StateHistory, _bloom_bits,
)

STATE = {"accounts": 2000, "signer_accounts": 256}
PAYMENTS = 20
FREQ = 8
NEVER = float("inf")
PHASES = ("prepare", "prefetch", "apply")


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as fh:
        return json.load(fh)


def small(backend="cpu"):
    config = _load("configs", "catchup-state13")
    workload = _load("workloads", "catchup-state13.standard-mix-1m")
    config["checkpoint_frequency"] = FREQ
    config["backend_under_test"] = backend
    config["state"].update(STATE)
    workload["traffic"]["txs_per_ledger"] = PAYMENTS
    workload["negative_control_lanes"] = 64
    return config, workload


@pytest.fixture(scope="module")
def dep(tmp_path_factory):
    config, workload = small()
    d = catchup_state.Deployment(
        config, workload, 11, str(tmp_path_factory.mktemp("state")), False)
    d.setup()
    yield d
    d.release()


def replay(d, trace=False, hook=None):
    """One whole replay by a node restarted from the snapshot; returns
    (node, compared)."""
    def node_hook(app):
        if trace:
            app.tracer.enable(capacity=1 << 16)
        if hook is not None:
            hook(app)
    d.node_hook = node_hook
    if d.last_node is not None:
        d.last_node.stop()
        d.last_node = None
    d.replays, d.ledgers_closed = [], 0
    app = d._new_node()
    d._replay(app, NEVER, lambda now: False)
    d.last_node = app
    return app, {k: c["value"] for k, c in d.compare().items()}


def count(app, meter):
    return app.metrics.to_json().get(meter, {}).get("count", 0)


def account_rows(app):
    return app.database.execute(
        "SELECT accountid, balance, seqnum, numsubentries, lastmodified, "
        "entry FROM accounts ORDER BY accountid").fetchall()


def account_key_xdr(key32):
    from stellar_core_tpu.xdr import LedgerKey, PublicKey
    return LedgerKey.account(PublicKey.ed25519(key32)).to_xdr()


# -- the loader ---------------------------------------------------------------

def test_the_loader_leaves_both_stores_with_equal_blobs(dep):
    """Every seeded account: the SQL row the program finds by its own
    key, the BucketDB lookup and the root's point read give one blob."""
    from stellar_core_tpu.crypto.strkey import encode_public_key
    from stellar_core_tpu.xdr import LedgerEntry
    h, pub = dep.hist, dep.hist.pub
    snap = sqlite3.connect(os.path.join(h.snapshot_dir, "node.db"))
    rows = dict(snap.execute("SELECT accountid, entry FROM accounts"))
    snap.close()
    assert len(h.ids) == STATE["accounts"] == len(set(h.ids))
    assert h.ids == sorted(h.ids)
    bdb = pub.bucket_manager.bucketdb
    signers = 0
    for key in h.ids:
        row = rows[encode_public_key(key)]
        if key not in h.model:      # untouched since the load
            served, blob = bdb.lookup(account_key_xdr(key))
            assert served and blob == row
        acc = LedgerEntry.from_xdr(row).data.value
        assert acc.accountID.key_bytes == key
        assert acc.balance == h.start_balance and acc.seqNum == h.start_seq
        signers += len(acc.signers)
        if acc.signers:
            assert acc.numSubEntries == 1 and acc.thresholds[2] == 2
    assert signers == STATE["signer_accounts"]
    # the 20 role accounts and the root were created by closes
    assert len(rows) >= STATE["accounts"] + 21
    assert h.load_info["accounts"] == STATE["accounts"]


def test_the_next_header_commits_to_the_seeded_list(dep):
    from stellar_core_tpu.history.archive_state import HistoryArchiveState
    h, pub = dep.hist, dep.hist.pub
    level = dep.config["state"]["bucket_level"]
    seeded = pub.bucket_manager.bucket_list.levels[level].curr
    assert not seeded.resident and len(seeded) == STATE["accounts"] + 1
    snap = sqlite3.connect(os.path.join(h.snapshot_dir, "node.db"))
    header_hash, = snap.execute(
        "SELECT bucketlisthash FROM ledgerheaders WHERE ledgerseq=?",
        (h.lcl_at_snapshot,)).fetchone()
    has_json, = snap.execute(
        "SELECT state FROM storestate WHERE statename LIKE '%archive%'"
    ).fetchone()
    snap.close()
    has = HistoryArchiveState.from_json(has_json)
    assert has.levels[level].curr == seeded.get_hash().hex()
    assert has.current_ledger == h.lcl_at_snapshot == FREQ - 1
    # the archive's checkpoint names it too, and holds the file
    with open(os.path.join(h.archive_root, ".well-known",
                           "stellar-history.json")) as fh:
        assert seeded.get_hash().hex() in fh.read()
    # the list the header of the snapshot's ledger hashes over
    lh = [hashlib.sha256(bytes.fromhex(lv.curr) +
                         bytes.fromhex(lv.snap)).digest()
          for lv in has.levels]
    assert hashlib.sha256(b"".join(lh)).hexdigest() == header_hash


def test_the_bulk_bloom_is_the_programs_bit_for_bit():
    from stellar_core_tpu.bucket.bucket_index import (
        BloomFilter, key_fingerprint,
    )
    keys = [account_key_xdr(hashlib.sha256(b"%d" % i).digest())
            for i in range(3000)]
    ref = BloomFilter.for_capacity(len(keys), 10)
    for kb in keys:
        ref.add(key_fingerprint(kb))
    assert _bloom_bits(keys, ref.nbits, ref.k) == ref.bits


def test_the_archive_has_the_shape_the_cell_states(dep):
    h = dep.hist
    assert h.tip == 2 * FREQ - 1 and h.dense == FREQ
    assert len(h.sender_keys) == PAYMENTS * FREQ == \
        len({k.key_bytes for k in h.sender_keys})     # each source once
    mixed = len([d for d in range(FREQ) if d % 4 == 1])
    assert h.sigs_issued > 2 * PAYMENTS * FREQ + 10 * mixed
    assert len(h.touched) > len(h.sender_keys)
    # destinations are drawn over all accounts: most never send
    signer_ids = {k.key_bytes for k in h.sender_keys}
    assert sum(k not in signer_ids for k in h.touched) > PAYMENTS * FREQ // 2


# -- the restart --------------------------------------------------------------

def test_a_restarted_node_keeps_bucketdb_and_reaches_the_chain(dep):
    app, got = replay(dep)
    rec = dep.replays[-1]
    assert rec["ok"] and rec["restored_at"] == FREQ - 1
    assert rec["closed"] == FREQ and not rec["detached"]
    assert app.ledger_manager.root.bucket_backed()
    assert got["full_replays"] == 1 and got["failed_replays"] == 0
    for zero in ("header_mismatches", "state_mismatches", "store_mismatches",
                 "restarts_off_snapshot", "bucketdb_detached",
                 "sql_fallbacks", "replayed_ledgers_off", "python_closes",
                 "native_bails"):
        assert got[zero] == 0, zero
    assert rec["headers"] == dep.hist.headers
    # the deep bucket was adopted by name: hashed, never parsed
    level = dep.config["state"]["bucket_level"]
    deep = app.bucket_manager.bucket_list.levels[level].curr
    assert not deep.resident and len(deep) == STATE["accounts"] + 1
    assert deep.get_hash() == \
        dep.hist.pub.bucket_manager.bucket_list.levels[level].curr.get_hash()


def test_the_plain_model_equals_the_node_for_every_account_touched(dep):
    app, got = replay(dep)
    h = dep.hist
    assert got["state_checked"] == len(h.sender_keys) + len(h.touched) + 1
    assert got["state_mismatches"] == 0
    root = app.ledger_manager.ltx_root()
    from stellar_core_tpu.xdr import LedgerKey, PublicKey
    for key in h.touched:
        acc = root.get_entry(
            LedgerKey.account(PublicKey.ed25519(key))).data.value
        assert (acc.balance, acc.seqNum) == (h.model[key]["balance"],
                                             h.model[key]["seq"])
    assert app.ledger_manager.lcl_header.feePool == h.fee_pool
    sent = sum(h.start_balance - h.model[k.key_bytes]["balance"]
               for k in h.sender_keys)
    assert sent > 0


def test_a_node_that_joins_by_the_buckets_arrives_at_the_same_rows(
        dep, tmp_path):
    """Ties the snapshot to the join path: a fresh node applies the
    archive's buckets at the first checkpoint (`ApplyBucketsWork`) and
    replays the second; the restarted node came from the publisher's
    disk. Same header chain, same `accounts` table."""
    from stellar_core_tpu.catchup.catchup_work import CatchupConfiguration
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.work.basic_work import State
    restarted, _ = replay(dep)
    h = dep.hist
    cfg = h.node_config(0, "cpu")
    cfg.DATABASE = "sqlite3://%s" % (tmp_path / "join.db")
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.enable_buckets(str(tmp_path / "buckets"))
    app.start()
    try:
        app.clock.set_virtual_time(h.pub_time + 10.0)
        work = app.catchup_manager.start_catchup(
            CatchupConfiguration(h.tip, FREQ))
        assert app.crank_until(work.is_done, 200000)
        assert work.state == State.SUCCESS
        lm = app.ledger_manager
        assert lm.last_closed_ledger_num() == h.tip
        assert lm.lcl_hash == restarted.ledger_manager.lcl_hash
        assert lm.root.bucket_backed()
        # its own genesis, then headers from the bucket apply on: it
        # never closed the set-up ledgers
        mine = dict(app.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        assert sorted(mine) == [1] + list(range(FREQ - 1, h.tip + 1))
        assert all(h.headers[seq] == hh for seq, hh in mine.items())
        assert account_rows(app) == account_rows(restarted)
        assert len(account_rows(app)) > STATE["accounts"]
    finally:
        app.stop()


@pytest.mark.parametrize("reads", [True, False], ids=["bucketdb", "sql"])
def test_the_chain_is_the_same_with_bucketdb_reads_on_and_off(
        tmp_path, reads):
    config, workload = small()
    d = catchup_state.Deployment(config, workload, 11, str(tmp_path), False)
    node_config = d.hist.node_config

    def pinned(n, backend, writable=False):
        cfg = node_config(n, backend, writable)
        cfg.BUCKETDB_READS = reads
        return cfg
    d.hist.node_config = pinned
    try:
        d.setup()
        app, got = replay(d)
        assert got["full_replays"] == 1 and got["header_mismatches"] == 0
        assert got["state_mismatches"] == got["store_mismatches"] == 0
        assert app.ledger_manager.root.bucket_backed() is reads
        # the publisher of the module's fixture read through BucketDB
        assert d.hist.headers[d.hist.tip] == _chain_tip()
        cold = app.ledger_manager.apply_stats.to_json()[
            "state_reads"]["cold_reads"]
        served = "bucket" if reads else "sql"
        assert sum(by[served] for by in cold.values()) > 0
        assert sum(sum(by.values()) for by in cold.values()) == \
            sum(by[served] for by in cold.values())
        assert (got["sql_fallbacks"] == 0) is reads
    finally:
        d.release()


_TIP = {}


def _chain_tip():
    """The tip's header hash of seed 11's history, from a publisher that
    reads through BucketDB (made once)."""
    if not _TIP:
        import shutil
        import tempfile
        tmp = tempfile.mkdtemp(prefix="sct-state-tip-")
        config, workload = small()
        h = StateHistory(config, workload["traffic"], 11, tmp)
        try:
            h.publish()
            _TIP["hash"] = h.headers[h.tip]
        finally:
            h.close()
            shutil.rmtree(tmp, ignore_errors=True)
    return _TIP["hash"]


# -- what the program reports -------------------------------------------------

def test_cold_read_meters_repeat_and_sum_to_the_roots_misses(dep):
    seen = []
    for _ in range(2):
        app, _got = replay(dep)
        stats = app.ledger_manager.apply_stats
        blob = stats.to_json()["state_reads"]
        cold = blob["cold_reads"]
        by_phase = {p: count(app, "ledger.root.cold-read." + p)
                    for p in PHASES}
        assert by_phase == {p: sum(cold[p].values()) for p in PHASES}
        for p in PHASES:
            assert by_phase[p] == sum(
                count(app, "ledger.root.cold-read.%s.%s" % (p, s))
                for s in ("bucket", "sql"))
        # every cold read was served by something, and counted there
        assert sum(by_phase.values()) == blob["bucket_reads"] + \
            sum(blob["lookups"].values())
        assert blob["bucket_reads"] == count(
            app, "ledger.apply.state.bucket-read")
        # a point read that missed the cache is one of them
        assert blob["cache_misses"] <= sum(by_phase.values())
        assert by_phase["prepare"] >= len(dep.hist.sender_keys)
        assert dep.counts()["cold_prepare"] == by_phase["prepare"]
        assert dep.counts()["cold_close"] == \
            by_phase["prefetch"] + by_phase["apply"]
        seen.append(by_phase)
    assert seen[0] == seen[1]


def test_restore_spans_nest_and_say_what_was_restored(dep):
    app, _ = replay(dep, trace=True)
    spans = {s.sid: s for s in app.tracer.spans() if s.dur is not None}
    restore, = [s for s in spans.values() if s.name == "node.restore"]
    assume, = [s for s in spans.values() if s.name == "bucket.assume_state"]
    loads = [s for s in spans.values() if s.name == "bucketdb.index_load"]
    assert restore.tags == {"lcl": FREQ - 1, "bucket_backed": True}
    assert assume.parent == restore.sid
    # each sidecar loads under its bucket's adoption (PR 35)
    adopts = {s.sid for s in spans.values()
              if s.name == "bucket.adopt" and s.parent == assume.sid}
    assert loads and all(s.parent in adopts for s in loads)
    assert not any(spans[a].tags["wrote"] for a in adopts)
    assert assume.tags["buckets"] == len(loads)
    assert assume.tags["bytes"] > 100 * STATE["accounts"]
    assert max(s.tags["keys"] for s in loads) == STATE["accounts"]
    assert all(s.tags["seconds"] > 0 for s in loads)
    assert count(app, "bucketdb.index.loads") == len(loads)
    assert count(app, "bucketdb.index.builds") > 0      # the closes' own
    # a fresh node restores nothing and says so
    first = [s for s in dep.first.tracer.spans()]
    assert first == []      # its tracer was never on


def test_close_prefetch_says_how_many_keys_were_cold(dep):
    def small_cache(app):
        # a cache that the prepare's reads fill, as 4,096 is at size
        cache = app.ledger_manager.root._cache
        cache._max = 64
    app, got = replay(dep, trace=True, hook=small_cache)
    assert got["header_mismatches"] == got["state_mismatches"] == 0
    pre = [s for s in app.tracer.spans()
           if s.name == "close.prefetch" and s.dur is not None]
    assert len(pre) == FREQ
    assert all({"cached", "cold", "over_budget"} <= set(s.tags) for s in pre)
    assert all(s.tags["cold"] - s.tags["over_budget"] == s.tags["cached"]
               for s in pre)
    # the half-cache budget meets a full cache: cold keys go unloaded
    # and the engine reads them one by one
    assert sum(s.tags["over_budget"] for s in pre) > 0
    assert count(app, "ledger.root.cold-read.apply") >= \
        sum(s.tags["over_budget"] for s in pre)
    assert count(app, "ledger.apply.entry-cache.evicted") > 0


def test_a_fresh_node_restores_nothing(tmp_path):
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    cfg = Config.test_config(0)
    cfg.DATABASE = "sqlite3://%s" % (tmp_path / "node.db")
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.enable_buckets(str(tmp_path / "buckets"))
    app.tracer.enable()
    app.start()
    try:
        restore, = [s for s in app.tracer.spans()
                    if s.name == "node.restore"]
        assert restore.tags == {"lcl": 0, "bucket_backed": True}
        assert not [s for s in app.tracer.spans()
                    if s.name == "bucket.assume_state"]
        assert app.ledger_manager.last_closed_ledger_num() == 1
    finally:
        app.stop()


# -- what the comparison refuses ----------------------------------------------

def wrong_balance(h):
    h.model[h.touched[-1]]["balance"] += 1


def wrong_seq(h):
    h.model[h.sender_keys[0].key_bytes]["seq"] -= 1


def wrong_fee_pool(h):
    h.fee_pool += 100


@pytest.mark.parametrize("plant,n", [(wrong_balance, 1), (wrong_seq, 2),
                                     (wrong_fee_pool, 1)])
def test_one_wrong_number_in_the_model_is_a_state_mismatch(dep, plant, n):
    replay(dep)
    h = dep.hist
    kept = copy.deepcopy((h.model, h.fee_pool))
    try:
        plant(h)
        got = dep.compare()
    finally:
        h.model, h.fee_pool = kept
    # a source is checked by the catchup driver's pass and by this one
    assert got["state_mismatches"]["value"] == n
    assert got["store_mismatches"]["value"] == 0


def test_a_row_that_differs_from_its_bucket_is_a_store_mismatch(dep):
    from stellar_core_tpu.crypto.strkey import encode_public_key
    app, got = replay(dep)
    assert got["store_mismatches"] == 0
    key = encode_public_key(dep.hist.touched[0])
    blob, = app.database.execute(
        "SELECT entry FROM accounts WHERE accountid=?", (key,)).fetchone()
    app.database.execute("UPDATE accounts SET entry=? WHERE accountid=?",
                         (blob[:-1] + bytes([blob[-1] ^ 1]), key))
    assert dep.compare()["store_mismatches"]["value"] == 1


def test_a_torn_bucket_file_is_refused_and_the_replay_is_not_correct(dep):
    """The deep bucket's file, one byte off: it no longer hashes to its
    name, the restart adopts no list and detaches BucketDB, and the
    comparison says so."""
    level = dep.config["state"]["bucket_level"]
    name = "bucket-%s.xdr" % dep.hist.pub.bucket_manager.bucket_list \
        .levels[level].curr.get_hash().hex()
    dep._prep.join()
    path = os.path.join(dep.hist.node_dir(dep.n_nodes + 1), "buckets", name)
    with open(path, "rb") as fh:
        body = bytearray(fh.read())
    os.unlink(path)         # a hard link: the snapshot's file stays whole
    body[len(body) // 2] ^= 1
    with open(path, "wb") as fh:
        fh.write(bytes(body))
    app, got = replay(dep)
    assert not app.ledger_manager.root.bucket_backed()
    assert got["bucketdb_detached"] == 1 and got["sql_fallbacks"] > 0
    compared = dep.compare()
    assert not all(runner._holds(c) for c in compared.values())


@pytest.mark.parametrize("fault,number", [
    ("accept-all", "sigs_not_on_device"),
    ("accept-all", "verdict_mismatches"),
    ("half-batch", "sigs_not_on_device")])
def test_a_planted_verifier_fault_fails_the_comparison(
        tmp_path, fault, number):
    """On the `tpu` backend over jax-CPU with the 32-lane shape only, as
    the benchmark's rehearsal runs it."""
    def tiny_buckets(app):
        v = getattr(app.sig_verifier, "inner", app.sig_verifier)
        if hasattr(v, "BUCKETS"):
            v.BUCKETS = (32,)
    config, workload = small(backend="tpu")
    workload["warm_buckets"] = [32]
    d = catchup_state.Deployment(
        config, workload, 13, str(tmp_path), False,
        node_hook=control.chain(tiny_buckets,
                                control.CONTROLS[fault]["node_hook"]))
    hook = d.node_hook
    try:
        d.setup()
        replay(d, hook=hook)
        compared = d.compare()
    finally:
        d.release()
    failed = {k for k, c in compared.items() if not runner._holds(c)}
    assert number in failed
    # the state is right all the same: the fault is the verifier's
    assert compared["header_mismatches"]["value"] == 0
    assert compared["store_mismatches"]["value"] == 0


# -- a bucket adopted from its file -------------------------------------------

def test_a_bucket_from_its_file_is_hashed_not_parsed(dep, tmp_path):
    from stellar_core_tpu.bucket.bucket import Bucket
    pub = dep.hist.pub
    src = next(lev.curr for lev in pub.bucket_manager.bucket_list.levels
               if lev.curr.resident and len(lev.curr) > 3)
    path = str(tmp_path / "b.xdr")
    src.write_to(path)
    lazy = Bucket.from_file(path, src.get_hash())
    assert lazy is not None and not lazy.resident and not lazy.is_empty()
    assert lazy.get_version() == src.get_version() > 0
    assert list(lazy.record_bodies()) == list(src.record_bodies())
    assert not lazy.resident                    # still
    assert len(lazy) == len(src) and not lazy.resident
    lazy.count_hint(7)
    assert len(lazy) == 7
    # a merge or an apply asks for the entries, and gets them
    assert [e.to_xdr() for e in lazy.payload_entries()] == \
        [e.to_xdr() for e in src.payload_entries()]
    assert lazy.resident and len(lazy) == len(src)
    assert Bucket.from_file(path, b"\x01" * 32) is None


def test_the_commitment_root_of_a_file_backed_bucket_is_the_residents(
        dep, tmp_path):
    """The state commitment hashes a bucket's entries as they sit on
    disk: the same root whether the bucket is resident or not, and the
    one the publisher left in the bucket's root sidecar."""
    import shutil
    from stellar_core_tpu.bucket.bucket import Bucket, root_sidecar_path
    from stellar_core_tpu.ledger.state_commitment import (
        StateCommitmentEngine, load_root_sidecar,
    )
    pub = dep.hist.pub
    level = dep.config["state"]["bucket_level"]
    deep = pub.bucket_manager.bucket_list.levels[level].curr
    # a copy with no sidecar beside it: both engines hash
    path = str(tmp_path / os.path.basename(deep.path))
    shutil.copyfile(deep.path, path)
    lazy = Bucket.from_file(path, deep.get_hash())
    resident = Bucket.read_from(path)
    assert resident.get_hash() == deep.get_hash() and not lazy.resident

    class App:
        metrics = None
    a, b = StateCommitmentEngine(App()), StateCommitmentEngine(App())
    assert a.entry_root(lazy) == b.entry_root(resident)
    for eng in (a, b):
        assert (eng.roots_loaded, eng.roots_hashed, eng.entries_hashed) == \
            (0, 1, STATE["accounts"] + 1)
    assert not lazy.resident and not os.path.exists(root_sidecar_path(path))
    assert load_root_sidecar(root_sidecar_path(deep.path),
                             deep.get_hash()) == \
        (a.entry_root(lazy), STATE["accounts"] + 1)
    assert pub.state_commitment.root is not None


def test_a_restart_reads_the_seeded_buckets_root_off_its_disk(dep):
    """ISSUE 34: the publisher wrote the seeded bucket's entry root
    beside it at its first close over it, the snapshot carries the
    sidecar as it carries every `bucket-` file, and a restarted node's
    first close reads it where it hashed the bucket's entries again."""
    level = dep.config["state"]["bucket_level"]
    seeded = dep.hist.pub.bucket_manager.bucket_list.levels[level].curr
    assert "bucket-%s.xdr.root" % seeded.get_hash().hex() in os.listdir(
        os.path.join(dep.hist.snapshot_dir, "buckets"))
    seen = []
    for _ in range(2):
        app, got = replay(dep, trace=True)
        assert got["header_mismatches"] == got["state_mismatches"] == 0
        spans = [s for s in app.tracer.spans()
                 if s.name == "close.commitment" and s.dur is not None]
        assert len(spans) == FREQ
        first = spans[0].tags
        assert first["seq"] == FREQ and first["roots_loaded"] >= 1
        assert first["entries_hashed"] < STATE["accounts"]
        assert all(s.tags["roots_loaded"] == 0 for s in spans[1:])
        sce = app.state_commitment
        assert sce.root == dep.hist.pub.state_commitment.root
        assert count(app, "commitment.entry-root.rejected") == 0
        seen.append([(s.tags["roots_loaded"], s.tags["roots_hashed"],
                      s.tags["entries_hashed"]) for s in spans] +
                    [count(app, "commitment.entry-root." + m)
                     for m in ("loaded", "hashed", "persisted")])
    assert seen[0] == seen[1]


def test_strkey_checksum_is_crc16_xmodem():
    from stellar_core_tpu.crypto import strkey

    def bitwise(data):
        crc = 0
        for byte in data:
            crc ^= byte << 8
            for _ in range(8):
                crc = ((crc << 1) ^ 0x1021) if crc & 0x8000 else (crc << 1)
                crc &= 0xFFFF
        return crc
    for i in range(64):
        data = hashlib.sha256(b"%d" % i).digest() + bytes([i])
        assert strkey._crc16_xmodem(data) == bitwise(data)
    key = hashlib.sha256(b"k").digest()
    assert strkey.decode_public_key(strkey.encode_public_key(key)) == key
    assert strkey.encode_public_key(bytes(32)).startswith("GAAAA")
