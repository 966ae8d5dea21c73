"""A full validator publishes under load, at a small size:
benchmark/deployments/validator_archive.py's network (three validators
restarted from one publisher's snapshot, node 0 writing a local
archive) with 1,000 accounts, 64 of them signers, checkpoint frequency
8, on the `cpu` backend. The network resumes consensus and closes past
a checkpoint under the payment loop; node 0's archive passes the plain
checker (benchmark/traffic/archive_check.py), which fails on each
planted fault; every account the loop touched is the model's on every
node, in both stores; a publish is one `history.publish` span over its
snapshot, gzips and puts, and its meters count what is on disk; a node
restarted from the snapshot catches up from node 0's archive to node
0's header.
"""

import glob
import gzip
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.deployments import validator_archive  # noqa: E402
from benchmark.traffic import archive_check  # noqa: E402

FREQ = 8


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def net(tmp_path_factory):
    config = _load("configs", "validator-archive3")
    workload = _load("workloads", "validator-archive3.payments-1m")
    config.update(accounts=1000, checkpoint_frequency=FREQ,
                  backend_under_test="cpu")
    config["state"]["signer_accounts"] = 64
    workload["opens_before_checkpoint"] = 2
    workload["traffic"].update(clients=8, corrupt_every=5)
    d = validator_archive.Deployment(
        config, workload, 5, str(tmp_path_factory.mktemp("archive")), True)
    d.setup()
    d.window(3.0, lambda now: False)
    d.drain()
    yield d
    d.release()


def _check(d, root=None):
    chains = [dict(a.database.execute(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        for a in d.apps]
    headers = {s: bytes(x) for s, x in d.node0.database.execute(
        "SELECT ledgerseq, data FROM ledgerheaders").fetchall()}
    return archive_check.check(root or d.hist.archive_root, FREQ, headers,
                               chains, d.keys[0].public_key.key_bytes)


def test_a_restarted_network_closes_past_a_checkpoint_under_load(net):
    got = net.compare()
    for name, c in got.items():
        if name in ("device_path_violations", "window_compiles"):
            continue        # the cpu backend: no device drains
        if c.get("need") == "min":
            assert c["value"] >= c["limit"], name
        else:
            assert c["value"] <= c["limit"], (name, c)
    assert net.restored_at == [FREQ - 1] * 3
    assert net._lcl_min() >= 2 * FREQ
    counts = net.counts()
    assert counts["publishes"] == 1 and counts["admitted"] > 0
    assert counts["applied_in_window"] > 0


def test_the_archive_passes_the_plain_checker(net):
    got = _check(net)
    assert got["mismatches"] == 0, got["detail"]
    assert got["checkpoints"] >= 2
    # the first checkpoint starts at the genesis ledger, 1
    assert got["ledgers"] == FREQ * got["checkpoints"] - 1


def test_every_touched_account_is_the_models_in_both_stores(net):
    from stellar_core_tpu.crypto.strkey import encode_public_key
    from stellar_core_tpu.xdr import LedgerEntry, LedgerKey, PublicKey
    model = net.hist.model
    assert len(model) > 2
    for app in net.apps:
        bdb = app.bucket_manager.bucketdb
        for key, m in model.items():
            row = app.database.execute(
                "SELECT entry FROM accounts WHERE accountid=?",
                (encode_public_key(key),)).fetchone()
            served, blob = bdb.lookup(
                LedgerKey.account(PublicKey.ed25519(key)).to_xdr())
            assert served and blob == row[0]
            acc = LedgerEntry.from_xdr(blob).data.value
            assert (acc.balance, acc.seqNum) == (m["balance"], m["seq"])


def _rewrite_gz(path, edit):
    with gzip.open(path, "rb") as fh:
        body = bytearray(fh.read())
    edit(body)
    with gzip.open(path, "wb") as fh:
        fh.write(bytes(body))


def _flip(at):
    def edit(body):
        body[at(len(body))] ^= 0x01
    return edit


def _other_bucket(root):
    """The HAS of the newest checkpoint names, for its level 0's curr,
    another bucket the archive holds."""
    has_path = sorted(glob.glob(os.path.join(
        root, "history", "*", "*", "*", "history-*.json")))[-1]
    with open(has_path) as fh:
        has = json.load(fh)
    named = {lv["curr"] for lv in has["currentBuckets"]}
    held = [os.path.basename(p)[len("bucket-"):-len(".xdr.gz")]
            for p in glob.glob(os.path.join(root, "bucket", "**", "*.gz"),
                               recursive=True)]
    has["currentBuckets"][0]["curr"] = sorted(set(held) - named)[0]
    with open(has_path, "w") as fh:
        json.dump(has, fh)


PLANTED = {
    "a bucket byte": lambda root: _rewrite_gz(sorted(glob.glob(
        os.path.join(root, "bucket", "**", "*.gz"), recursive=True))[-1],
        _flip(lambda n: n // 2)),
    "a header byte": lambda root: _rewrite_gz(sorted(glob.glob(
        os.path.join(root, "ledger", "**", "*.gz"), recursive=True))[-1],
        _flip(lambda n: n - 40)),
    "a missing file": lambda root: os.remove(sorted(glob.glob(
        os.path.join(root, "results", "**", "*.gz"), recursive=True))[-1]),
    "a HAS that names another bucket": _other_bucket,
}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_each_planted_fault_fails_the_checker(net, tmp_path, fault):
    root = str(tmp_path / "archive")
    shutil.copytree(net.hist.archive_root, root)
    assert _check(net, root)["mismatches"] == 0
    PLANTED[fault](root)
    assert _check(net, root)["mismatches"] > 0


def test_one_publish_span_a_checkpoint_over_its_parts(net):
    spans = net.node0.tracer.spans()
    by_sid = {s.sid: s for s in spans}
    pubs = [s for s in spans if s.name == "history.publish"]
    assert [s.tags["checkpoint"] for s in pubs] == \
        list(range(2 * FREQ - 1, net.node0.ledger_manager
                   .last_closed_ledger_num() + 1, FREQ))
    for pub in pubs:
        kids = [s for s in spans if s.parent == pub.sid]
        names = [s.name for s in kids]
        assert names.count("history.snapshot") == 1
        gz = [s for s in kids if s.name == "history.gzip"]
        puts = [s for s in kids if s.name == "history.put"]
        # the four streams and every bucket are gzipped; they and the
        # HAS (twice: its checkpoint's name and the well-known one) put
        assert len(puts) == len(gz) + 2 == pub.tags["files"]
        assert all(s.tags["rc"] == 0 for s in puts)
        assert pub.tags["buckets"] == sum(s.tags["kind"] == "bucket"
                                          for s in puts) > 0
        assert pub.tags["bytes_in"] == sum(s.tags["bytes_in"] for s in gz)
        assert pub.tags["bytes_out"] == sum(s.tags["bytes_out"] for s in gz)
        assert all(by_sid[s.parent] is pub for s in gz + puts)


def test_the_publish_meters_count_what_is_on_disk(net):
    root = net.hist.archive_root
    m = net.node0.metrics.to_json(prefix="history.publish")
    n_pub = m["history.publish.success"]["count"]
    files = buckets = size = 0
    for cp in range(2 * FREQ - 1, 2 * FREQ - 1 + FREQ * n_pub, FREQ):
        hx = "%08x" % cp
        sub = os.path.join(hx[0:2], hx[2:4], hx[4:6])
        paths = [os.path.join(root, cat, sub, "%s-%s.xdr.gz" % (cat, hx))
                 for cat in ("ledger", "transactions", "results", "scp")]
        paths = [p for p in paths if os.path.exists(p)]
        has = os.path.join(root, "history", sub, "history-%s.json" % hx)
        with open(has) as fh:
            named = [h for lv in json.load(fh)["currentBuckets"]
                     for h in [lv["curr"], lv["snap"]] + [
                         lv["next"].get(k) for k in ("output", "curr",
                                                     "snap")]
                     if h and h != "0" * 64]
        for h in named:
            paths.append(os.path.join(root, "bucket", h[0:2], h[2:4],
                                      h[4:6], "bucket-%s.xdr.gz" % h))
        paths += [has, has]     # and again as the well-known HAS
        files += len(paths)
        buckets += len(named)
        size += sum(os.path.getsize(p) for p in paths)
    assert n_pub >= 1
    assert m["history.publish.files"]["count"] == files
    assert m["history.publish.buckets"]["count"] == buckets
    assert m["history.publish.bytes"]["count"] == size


def test_a_node_restarted_from_the_snapshot_catches_up_from_node_0(net):
    from stellar_core_tpu.catchup.catchup_work import CatchupConfiguration
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.work.basic_work import State
    hist = net.hist
    node_dir = hist.node_dir(7)
    hist.clone_snapshot(node_dir)
    cfg = hist.node_config(7, "cpu")
    cfg.NODE_SEED = SecretKey.from_seed(b"a fresh node's seed, 32 bytes...")
    cfg.QUORUM_SET = cfg.self_qset()
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.enable_buckets(os.path.join(node_dir, "buckets"))
    app.start()
    try:
        assert app.ledger_manager.last_closed_ledger_num() == FREQ - 1
        app.clock.set_virtual_time(hist.pub_time + 10.0)
        work = app.catchup_manager.start_catchup(
            CatchupConfiguration.complete())
        assert app.crank_until(work.is_done, 200000)
        assert work.state == State.SUCCESS
        tip = app.ledger_manager.last_closed_ledger_num()
        assert tip >= 2 * FREQ - 1 and (tip + 1) % FREQ == 0
        assert app.ledger_manager.lcl_hash.hex() == \
            net.node0.database.execute(
                "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?",
                (tip,)).fetchone()[0]
    finally:
        app.stop()
