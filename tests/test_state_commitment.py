"""State-commitment tests (ISSUE 12): Merkle helper algebra, the
incremental-vs-from-scratch differential oracle under randomized bucket
churn, the 30-ledger replay acceptance, proof round-trips including
tamper rejection, checkpoint cadence + the sign-fail fault, and the
admin `checkpoint` endpoint."""

import json
import os
from types import SimpleNamespace

import pytest

import stellar_core_tpu.xdr as X
from stellar_core_tpu.bucket.bucket_list import BucketList
from stellar_core_tpu.crypto.hashing import sha256
from stellar_core_tpu.ledger.state_commitment import (
    StateCommitmentEngine, checkpoint_sign_payload, light_client_verify,
    merkle_climb, merkle_path, merkle_root,
)
from stellar_core_tpu.transactions.account_helpers import make_account_entry
from stellar_core_tpu.util import rnd

PROTO = 13


def acct(i: int) -> X.LedgerEntry:
    key = X.PublicKey.ed25519(i.to_bytes(32, "big"))
    return make_account_entry(key, 10 ** 9 + i, 0, 1)


def acct_key(i: int) -> X.LedgerKey:
    return X.LedgerKey.account(X.PublicKey.ed25519(i.to_bytes(32, "big")))


def _engine() -> StateCommitmentEngine:
    return StateCommitmentEngine(SimpleNamespace(metrics=None,
                                                 config=None))


# --- merkle algebra ---------------------------------------------------------

def test_merkle_roundtrip_every_size_and_index():
    for n in (1, 2, 3, 4, 5, 7, 8, 22, 33):
        leaves = [sha256(bytes([i, n])) for i in range(n)]
        root = merkle_root(leaves)
        for i in range(n):
            path = merkle_path(leaves, i)
            assert merkle_climb(leaves[i], path) == root, (n, i)
            # a wrong sibling breaks the climb
            if path:
                bad = [dict(s) for s in path]
                bad[0]["h"] = sha256(b"evil").hex()
                assert merkle_climb(leaves[i], bad) != root


def test_merkle_empty_commits_to_zero():
    assert merkle_root([]) == b"\x00" * 32


# --- the differential oracle under randomized churn ------------------------

def test_incremental_root_matches_oracle_under_random_churn():
    """Seeded random init/live/dead batches through the real BucketList
    spill schedule: after EVERY add_batch the engine's incremental root
    (cached entry roots, cached leaves) must equal the from-scratch
    recompute."""
    rnd.reseed(0x5C7C)
    bl = BucketList()           # synchronous merges: deterministic
    eng = _engine()
    live_ids: set = set()
    next_id = 1
    for ledger in range(1, 41):
        inits, lives, deads = [], [], []
        batch_ids: set = set()
        for _ in range(rnd.rand_int(1, 3)):
            inits.append(acct(next_id))
            live_ids.add(next_id)
            batch_ids.add(next_id)
            next_id += 1
        for i in sorted(live_ids - batch_ids)[:2]:
            if rnd.rand_int(0, 1):
                lives.append(acct(i))
                batch_ids.add(i)
        if len(live_ids) > 4 and rnd.rand_int(0, 2) == 0:
            gone = sorted(live_ids)[0]
            if gone not in batch_ids:
                live_ids.discard(gone)
                deads.append(acct_key(gone))
        bl.add_batch(ledger, PROTO, inits, lives, deads)
        bl.resolve_all_futures()
        for lev in bl.levels:
            lev.commit()
        got = eng.update_root(bl)
        assert got == eng.from_scratch_root(bl), \
            "divergence at ledger %d" % ledger


def test_entry_root_cache_hits_on_unchanged_buckets():
    bl = BucketList()
    eng = _engine()
    bl.add_batch(1, PROTO, [acct(1)], [], [])
    eng.update_root(bl)
    misses_before = len(eng._entry_roots)
    eng.update_root(bl)      # nothing changed: no new cache entries
    assert len(eng._entry_roots) == misses_before


# --- the 30-ledger replay acceptance ---------------------------------------

@pytest.fixture()
def closing_app(tmp_path):
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    cfg = Config.test_config(92)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.STATE_CHECKPOINT_INTERVAL = 5
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.enable_buckets(str(tmp_path / "buckets"))
    app.start()
    yield app
    app.stop()


def test_thirty_ledger_replay_oracle_checkpoints_and_proofs(closing_app):
    """The ISSUE 12 acceptance in one run: 30 closes under load with
    the incremental root equal to the from-scratch oracle at every
    close; checkpoints on cadence; a light client verifies a membership
    proof against the served checkpoint in well under 10 ms without
    touching the ledger DB; tampered proofs and forged checkpoint
    signatures are rejected."""
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import real_perf_counter
    app = closing_app
    lg = LoadGenerator(app)
    lg.generate_accounts(10)
    app.manual_close()
    sce = app.state_commitment
    bl = app.bucket_manager.bucket_list
    for i in range(30):
        lg.generate_payments(4)
        app.clock.set_virtual_time(app.clock.now() + 1.0)
        app.manual_close()
        assert sce.root == sce.from_scratch_root(bl), \
            "incremental root diverged at close %d" % i
    cp = sce.checkpoint()
    assert cp is not None
    assert app.metrics.to_json()[
        "commitment.checkpoint.emitted"]["count"] >= 5
    # an exact-seq fetch returns the same blob
    assert sce.checkpoint(cp["ledger_seq"]) == cp

    key = X.LedgerKey.account(app.network_root_key().public_key)
    proof = sce.prove_entry(key)
    assert proof is not None
    net = app.config.network_id
    t0 = real_perf_counter()
    ok, reason = light_client_verify(proof, cp, net)
    dt_ms = (real_perf_counter() - t0) * 1e3
    assert ok, reason
    assert dt_ms < 10.0, "light-client verify took %.3f ms" % dt_ms

    # tampering: entry bytes, merkle path, root, signature
    bad = json.loads(json.dumps(proof))
    bad["entry"] = bad["entry"][:-2] + (
        "00" if bad["entry"][-2:] != "00" else "01")
    assert light_client_verify(bad, cp, net) == (False,
                                                 "merkle root mismatch")
    if proof["entry_path"]:
        bad2 = json.loads(json.dumps(proof))
        bad2["entry_path"][0]["h"] = "11" * 32
        assert not light_client_verify(bad2, cp, net)[0]
    forged = dict(cp)
    forged["signature"] = "00" * 64
    assert light_client_verify(proof, forged, net) == \
        (False, "checkpoint signature invalid")
    # wrong network id: the signature payload is network-bound
    assert not light_client_verify(proof, cp, b"\x42" * 32)[0]
    # a proof for an absent entry does not exist
    assert sce.prove_entry(acct_key(999999)) is None


def test_sign_fail_fault_skips_the_interval(closing_app):
    app = closing_app
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    app.faults.configure("commitment.sign-fail", probability=1.0,
                         count=1)
    lg = LoadGenerator(app)
    lg.generate_accounts(3)
    app.manual_close()
    sce = app.state_commitment
    for _ in range(12):
        lg.generate_payments(2)
        app.clock.set_virtual_time(app.clock.now() + 1.0)
        app.manual_close()
    m = app.metrics.to_json()
    assert m["commitment.sign-fail"]["count"] == 1
    assert m["fault.injected.commitment.sign-fail"]["count"] == 1
    # later intervals recovered: a checkpoint still exists
    assert sce.checkpoint() is not None


def test_checkpoint_admin_endpoint(closing_app):
    app = closing_app
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    lg = LoadGenerator(app)
    lg.generate_accounts(3)
    app.manual_close()
    for _ in range(6):
        lg.generate_payments(2)
        app.clock.set_virtual_time(app.clock.now() + 1.0)
        app.manual_close()
    key = X.LedgerKey.account(app.network_root_key().public_key)
    st, body = app.command_handler.handle_command(
        "checkpoint", {"entry": key.to_xdr().hex()})
    assert st == 200
    assert body["checkpoint"] is not None
    assert body["proof"] is not None
    ok, reason = light_client_verify(body["proof"], body["checkpoint"],
                                     app.config.network_id)
    assert ok, reason
    # malformed entry param is a 400, not a 500
    st, body = app.command_handler.handle_command(
        "checkpoint", {"entry": "zz"})
    assert st == 400
    # proofs pair only with the LATEST checkpoint: an entry proof
    # requested against an older ring seq is a 400, never a
    # (proof, checkpoint) pair that cannot verify
    seqs = sorted(app.state_commitment.checkpoints)
    if len(seqs) > 1:
        st, body = app.command_handler.handle_command(
            "checkpoint", {"seq": str(seqs[0]),
                           "entry": key.to_xdr().hex()})
        assert st == 400, body
    # the signed payload binds domain, network, seq, header, root
    p = checkpoint_sign_payload(b"n" * 32, 7, b"h" * 32, b"r" * 32)
    assert p != checkpoint_sign_payload(b"n" * 32, 8, b"h" * 32,
                                        b"r" * 32)


# --- root sidecars: the entry-root cache on disk (ISSUE 34) -----------------

CLOSES = 20         # level 0 spills every 2 ledgers, level 1 at 8 and 16


def _disk_node(node_dir):
    """A node whose database and buckets are files under `node_dir`;
    a second call over the same directory is a restart."""
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    os.makedirs(node_dir, exist_ok=True)
    cfg = Config.test_config(93)
    cfg.NODE_SEED = SecretKey.from_seed(sha256(b"root-sidecar-node"))
    cfg.QUORUM_SET = cfg.self_qset()
    cfg.DATABASE = "sqlite3://%s" % (node_dir / "node.db")
    cfg.STATE_CHECKPOINT_INTERVAL = 2
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.enable_buckets(str(node_dir / "buckets"))
    app.tracer.enable()
    app.start()
    return app


def _close_at(app, when, frames=()):
    for f in frames:
        assert app.submit_transaction(f) == 0
    app.clock.set_virtual_time(when)
    app.manual_close()


@pytest.fixture(scope="module")
def first_node(tmp_path_factory):
    """A node that has closed past level 1's second spill, a copy of its
    directory as it stood then, and what it closed next."""
    import shutil
    from stellar_core_tpu.testing import AppLedgerAdapter
    home = tmp_path_factory.mktemp("roots")
    app = _disk_node(home / "first")
    root = AppLedgerAdapter(app).root_account()
    bob = root.create(10 ** 9)          # never touched again: sinks
    alice = root.create(10 ** 10)
    t = app.clock.now() + 5
    while app.ledger_manager.last_closed_ledger_num() < CLOSES:
        t += 1.0
        _close_at(app, t, [alice.tx([alice.op_payment(root.account_id, 7)])])
    shutil.copytree(home / "first", home / "snapshot",
                    ignore=shutil.ignore_patterns("*.tmp"))
    nxt = alice.tx([alice.op_payment(root.account_id, 9)])
    _close_at(app, t + 1.0, [nxt])
    out = SimpleNamespace(
        snapshot=home / "snapshot", next_frame=nxt, next_time=t + 1.0,
        lcl=CLOSES + 1, lcl_hash=app.ledger_manager.lcl_hash,
        root=app.state_commitment.root, bob=bob.account_id)
    app.stop()
    return out


def _restart(first_node, tmp_path):
    import shutil
    shutil.copytree(first_node.snapshot, tmp_path / "node")
    return _disk_node(tmp_path / "node")


def _slot_buckets(app):
    """[(level, bucket)] of the live list's non-empty slots."""
    return [(lev.level, b)
            for lev in app.bucket_manager.bucket_list.levels
            for b in (lev.curr, lev.snap) if b.get_hash() != b"\x00" * 32]


def _root_files(app):
    d = app.bucket_manager.bucket_dir
    return {n for n in os.listdir(d) if n.endswith(".root")}


def _meter(app, name):
    return app.metrics.to_json().get(name, {}).get("count", 0)


def _commitment_spans(app):
    return [s for s in app.tracer.spans()
            if s.name == "close.commitment" and s.dur is not None]


def test_a_restarted_nodes_first_close_reads_its_roots(first_node,
                                                       tmp_path):
    """(a) and (d): the first close after a restart hashes only the
    buckets that have no sidecar, the two of level 0 and what this close
    merged; the root is the oracle's and the one the node that never
    stopped had at that ledger."""
    app = _restart(first_node, tmp_path)
    try:
        assert app.ledger_manager.last_closed_ledger_num() == CLOSES
        assert not _commitment_spans(app)
        had = _root_files(app)
        deep = {b.get_hash() for lvl, b in _slot_buckets(app) if lvl >= 1}
        assert deep and {"bucket-%s.xdr.root" % h.hex() for h in deep} <= had
        _close_at(app, first_node.next_time, [first_node.next_frame])
        sce = app.state_commitment
        bl = app.bucket_manager.bucket_list
        assert app.ledger_manager.lcl_hash == first_node.lcl_hash
        assert sce.root == first_node.root == sce.from_scratch_root(bl)
        by_hash = {b.get_hash(): (lvl, b) for lvl, b in _slot_buckets(app)}
        loaded = [h for h in by_hash if os.path.basename(
            by_hash[h][1].path) + ".root" in had]
        rest = [h for h in by_hash if h not in loaded]
        span, = _commitment_spans(app)
        assert span.tags["roots_loaded"] == len(loaded) >= 2
        assert span.tags["roots_hashed"] == len(rest)
        assert span.tags["entries_hashed"] == \
            sum(len(by_hash[h][1]) for h in rest)
        assert span.tags["entries_hashed"] < \
            sum(len(b) for _l, b in by_hash.values())
        assert _meter(app, "commitment.entry-root.loaded") == len(loaded)
        assert _meter(app, "commitment.entry-root.hashed") == len(rest)
        assert _meter(app, "commitment.entry-root.rejected") == 0
        # the rule: a root is written once its bucket sits below level 0
        now = _root_files(app)
        for h, (lvl, b) in by_hash.items():
            name = os.path.basename(b.path) + ".root"
            if lvl >= 1:
                assert name in now
            elif name not in had:
                assert name not in now, "a level-0 bucket got a sidecar"
        assert _meter(app, "commitment.entry-root.persisted") == \
            len(now - had)
        # the next close finds every unchanged slot in memory
        _close_at(app, first_node.next_time + 1.0)
        assert _commitment_spans(app)[-1].tags["roots_loaded"] == 0
        assert sce.root == sce.from_scratch_root(bl)
    finally:
        app.stop()


def _truncate(raw, _other):
    return raw[:-7]


def _flip(raw, _other):
    return raw[:50] + bytes([raw[50] ^ 0x10]) + raw[51:]


def _resum(body):
    return body + sha256(body)


def _other_bucket(raw, other):
    return _resum(raw[:12] + other + raw[44:-32])


def _other_version(raw, _other):
    return _resum(raw[:10] + bytes([raw[10] ^ 0x01]) + raw[11:-32])


@pytest.mark.parametrize("damage,rejected", [
    (_truncate, 1), (_flip, 1), (_other_bucket, 1), (_other_version, 1),
    (None, 0)], ids=["truncated", "flipped-byte", "another-bucket",
                     "another-version", "absent"])
def test_a_sidecar_that_cannot_be_trusted_is_rebuilt(first_node, tmp_path,
                                                     damage, rejected):
    """(b): whatever is wrong with a sidecar, the root is the oracle's
    and a valid sidecar stands there afterwards."""
    from stellar_core_tpu.bucket.bucket import root_sidecar_path
    from stellar_core_tpu.ledger.state_commitment import (
        RootSidecarError, load_root_sidecar,
    )
    app = _restart(first_node, tmp_path)
    try:
        lvl, victim = _slot_buckets(app)[-1]       # the deepest: it stays
        assert lvl >= 2
        side = root_sidecar_path(victim.path)
        with open(side, "rb") as fh:
            raw = fh.read()
        good = load_root_sidecar(side, victim.get_hash())
        assert good is not None and good[1] == len(victim)
        os.unlink(side)
        if damage is not None:
            with open(side, "wb") as fh:
                fh.write(damage(raw, sha256(b"another bucket")))
            with pytest.raises(RootSidecarError):
                load_root_sidecar(side, victim.get_hash())
        else:
            assert load_root_sidecar(side, victim.get_hash()) is None
        _close_at(app, first_node.next_time, [first_node.next_frame])
        sce = app.state_commitment
        assert victim.get_hash() in {
            b.get_hash() for _l, b in _slot_buckets(app)}
        assert sce.root == first_node.root == \
            sce.from_scratch_root(app.bucket_manager.bucket_list)
        assert _meter(app, "commitment.entry-root.rejected") == rejected
        span, = _commitment_spans(app)
        assert span.tags["entries_hashed"] >= len(victim)
        assert load_root_sidecar(side, victim.get_hash()) == good
        with open(side, "rb") as fh:
            assert fh.read() == raw
        assert not [n for n in os.listdir(os.path.dirname(side))
                    if n.endswith(".tmp")]
    finally:
        app.stop()


def test_forgetting_a_bucket_takes_its_root_with_its_index(first_node,
                                                           tmp_path):
    """(c): the directory does not grow by sidecars of buckets that
    have gone."""
    app = _restart(first_node, tmp_path)
    try:
        t = first_node.next_time
        _close_at(app, t, [first_node.next_frame])
        for i in range(1, 9):        # level 1 snaps again: buckets go
            _close_at(app, t + i)
        d = app.bucket_manager.bucket_dir
        before = set(os.listdir(d))
        dropped = app.bucket_manager.forget_unreferenced_buckets()
        after = set(os.listdir(d))
        gone = {n for n in before - after if n.endswith(".xdr")}
        assert dropped >= len(gone) > 0
        assert {n for n in before - after if n.endswith(".root")}
        for n in after:
            stem = n.split(".xdr")[0] + ".xdr"
            assert stem in after, "%s outlived its bucket" % n
        for n in gone:
            assert n + ".root" not in after and n + ".idx" not in after
        # what is left is what the list holds, roots and all
        live = {"bucket-%s.xdr.root" % b.get_hash().hex()
                for lvl, b in _slot_buckets(app) if lvl >= 1}
        assert live <= after
        sce = app.state_commitment
        _close_at(app, t + 9)
        assert sce.root == \
            sce.from_scratch_root(app.bucket_manager.bucket_list)
    finally:
        app.stop()


def test_a_root_whose_sidecar_has_gone_is_persisted_again(first_node,
                                                          tmp_path):
    """A sidecar goes with its bucket; where the same content comes back
    while the engine still holds its root, the root is written again
    (from memory: nothing is hashed for it)."""
    from stellar_core_tpu.bucket.bucket import root_sidecar_path
    from stellar_core_tpu.ledger.state_commitment import load_root_sidecar
    app = _restart(first_node, tmp_path)
    try:
        _close_at(app, first_node.next_time, [first_node.next_frame])
        sce = app.state_commitment
        _lvl, victim = _slot_buckets(app)[-1]
        side = root_sidecar_path(victim.path)
        good = load_root_sidecar(side, victim.get_hash())
        assert good is not None
        os.unlink(side)
        hashed = _meter(app, "commitment.entry-root.hashed")
        assert sce.entry_root(victim, persist=True) == good[0]
        assert load_root_sidecar(side, victim.get_hash()) == good
        assert _meter(app, "commitment.entry-root.hashed") == hashed
        os.unlink(side)
        assert sce.entry_root(victim) == good[0]     # level 0's rule
        assert not os.path.exists(side)
    finally:
        app.stop()


def test_a_proof_served_after_a_restart_verifies(first_node, tmp_path):
    """(e): the entry proven sits in a bucket whose root was read, not
    hashed; the light client climbs from the entry to the checkpoint's
    root through it."""
    app = _restart(first_node, tmp_path)
    try:
        sce = app.state_commitment
        _close_at(app, first_node.next_time, [first_node.next_frame])
        _close_at(app, first_node.next_time + 1.0)
        cp = sce.checkpoint()
        assert cp is not None and cp["ledger_seq"] > CLOSES
        proof = sce.prove_entry(X.LedgerKey.account(first_node.bob))
        assert proof is not None and proof["leaf_index"] >= 2
        assert _meter(app, "commitment.entry-root.hashed") < \
            len(_slot_buckets(app)) + 2
        name = "bucket-%s.xdr.root" % proof["bucket_hash"]
        assert name in os.listdir(first_node.snapshot / "buckets")
        ok, reason = light_client_verify(proof, cp, app.config.network_id)
        assert ok, reason
    finally:
        app.stop()


def test_a_bucket_without_a_file_has_no_sidecar_and_needs_none():
    """The engine over a plain bucket list (no directory): nothing is
    read or written, the counts say what was hashed."""
    bl = BucketList()
    eng = _engine()
    for ledger in range(1, 10):
        bl.add_batch(ledger, PROTO, [acct(ledger)], [], [])
        bl.resolve_all_futures()
        eng.update_root(bl)
        assert eng.roots_loaded == 0 and eng.roots_hashed >= 1
    assert eng.root == eng.from_scratch_root(bl)
