"""Order-book traffic on the live path: three `cpu` validators in one
`Simulation` over the real overlay (`topologies.core(3, 2,
OVER_PEERS)`), driven through the benchmark's own driver
(`benchmark/deployments/validator_dex.py`) with the generator and plain
order-book model of `benchmark/traffic/dex_live.py` at a small size (2
pairs, 50 offers a side, 12 clients): market makers re-quote by
offerID, takers cross the books with offers and path payments, payers
pay; every transaction is submitted to node 0, floods to the others,
is nominated, validated and closed by consensus. The model, which
orders each ledger itself from hashlib, has to equal every node; the
txset validation each slot asks for has to show as `txset.check_valid`
spans and `herder.txset.checks` meters, one each a walk, or as a mark
of `herder.txset.valid_cache.hits` where the node answers from its
verdicts at the LCL.
"""

import collections
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.deployments import validator_dex  # noqa: E402
from benchmark.traffic.dex_live import MAKER_MARGIN  # noqa: E402
from stellar_core_tpu.herder.herder import (  # noqa: E402
    TXSET_CHECK_SITES, Herder,
)
from stellar_core_tpu.herder.txset import TxSetFrame  # noqa: E402

STATE = {"pairs": 2, "offers_per_side": 50, "levels": 20, "makers": 4,
         "takers": 4, "payers": 4}
WINDOW_S = 4.0


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as fh:
        return json.load(fh)


def small():
    config = _load("configs", "validator-dex3")
    workload = _load("workloads", "validator-dex3.maker-taker")
    config["backend_under_test"] = "cpu"
    config["state"].update(STATE)
    config["accounts"] = 2 + 4 + 4 + 4
    workload["traffic"].update({"clients": 12, "corrupt_every": 5})
    return config, workload


@pytest.fixture(scope="module")
def dep(tmp_path_factory):
    config, workload = small()
    # every ask of Herder.check_txset, by node and site: each is a walk
    # or a hit of the node's verdict cache
    asks = collections.Counter()
    check = Herder.check_txset

    def counted(self, txset, site, *args, **kwargs):
        asks[self, site] += 1
        return check(self, txset, site, *args, **kwargs)
    mp = pytest.MonkeyPatch()
    mp.setattr(Herder, "check_txset", counted)
    d = validator_dex.Deployment(config, workload, 37,
                                 str(tmp_path_factory.mktemp("dexlive")),
                                 True)
    d.asks = asks
    try:
        d.setup()
        d.window(WINDOW_S, lambda now: False)
        d.drain()
        d.compared = {k: c["value"] for k, c in d.compare().items()}
        yield d
    finally:
        mp.undo()
        d.release()


def test_live_traffic_leaves_every_node_equal_to_the_model(dep):
    c = dep.compared
    for zero in ("state_mismatches", "header_mismatches", "book_mismatches",
                 "corrupt_not_refused", "valid_refused",
                 "admitted_never_applied", "python_closes", "native_bails",
                 "dynamic_close_mismatches", "requote_failures",
                 "failed_txs", "model_faults"):
        assert c[zero] == 0, (zero, c)
    counts = dep.counts()
    assert counts["ledgers"] >= 5
    assert counts["admitted"] > 30 and counts["applied_in_window"] > 0
    # takers filled 1-3 offers each, some more than one
    assert sum(counts["fills"].values()) > 0 and counts["fills"][2] > 0
    g = dep.gen
    assert g.book_ledgers > counts["ledgers"]      # the set-up's too
    # the model holds what every node holds: the 200 offers, less what
    # takers ate whole
    assert 0 < 200 - counts["book_size"] < 60
    lcl = {a.ledger_manager.last_closed_ledger_num() for a in dep.apps}
    assert c["heights_compared"] >= min(lcl) - 1 > 10


def test_the_model_orders_each_ledger_as_node_0_applied_it(dep):
    db = dep.node0.database
    busy = 0
    for seq, txids in dep.gen.ledgers:
        rows = db.execute("SELECT txid FROM txhistory WHERE ledgerseq = ? "
                          "ORDER BY txindex", (seq,)).fetchall()
        assert [r[0] for r in rows] == txids, seq
        busy += len(txids) > 1
    assert busy >= 5


def test_an_offer_altered_on_one_node_is_a_book_mismatch(dep):
    app = dep.apps[1]
    oid, amount = app.database.execute(
        "SELECT offerid, amount FROM offers ORDER BY offerid "
        "LIMIT 1").fetchone()
    app.database.execute("UPDATE offers SET amount = ? WHERE offerid = ?",
                         (amount + 1, oid))
    try:
        compared = dep.compare()
        assert compared["book_mismatches"]["value"] == 1
        assert compared["state_mismatches"]["value"] == 0
        assert dep._held_to_model(dep.apps[0]) == (0, 0)
    finally:
        app.database.execute(
            "UPDATE offers SET amount = ? WHERE offerid = ?", (amount, oid))
    assert dep.compare()["book_mismatches"]["value"] == 0


def _site_counts(app, prefix="herder.txset.checks"):
    spans = [s for s in app.tracer.spans() if s.name == "txset.check_valid"]
    meters = app.metrics.to_json(prefix=prefix)
    return spans, {k: v["count"] for k, v in meters.items()}


def _hit_counts(app):
    return _site_counts(app, "herder.txset.valid_cache.hits")[1]


def test_every_txset_walk_is_one_span_and_one_mark_of_its_site(dep):
    app = dep.node0
    spans, meters = _site_counts(app)
    assert app.tracer.dropped == 0
    by_site = collections.Counter(s.tags["site"] for s in spans)
    assert meters["herder.txset.checks"] == len(spans) == sum(
        by_site.values())
    for site, n in by_site.items():
        assert meters["herder.txset.checks." + site] == n
    # a validator's slot walks its own trimmed set and the leaders'
    # values it picks first; the values its quorum accepts it has picked
    # or walked at the LCL already
    assert {"trigger", "leader_pick"} <= set(by_site)
    # every ask, site by site, is a walk or a hit of the node's cache
    for a in dep.apps:
        _, walks = _site_counts(a)
        hits = _hit_counts(a)
        for site in TXSET_CHECK_SITES:
            assert dep.asks[a.herder, site] == walks.get(
                "herder.txset.checks." + site, 0) + hits.get(
                "herder.txset.valid_cache.hits." + site, 0), site
        assert sum(dep.asks[a.herder, site] for site in
                   TXSET_CHECK_SITES) == walks["herder.txset.checks"] + \
            hits["herder.txset.valid_cache.hits"]
        assert {"trigger", "leader_pick", "nominate_accept"} <= {
            site for site in TXSET_CHECK_SITES if dep.asks[a.herder, site]}
        # a set asked about again at one LCL is answered, not walked
        assert hits["herder.txset.valid_cache.hits"] > 0
    for s in spans:
        assert set(s.tags) == {"site", "txs", "ops", "removed"}
        assert s.tags["site"] in TXSET_CHECK_SITES and s.tags["removed"] == 0
        assert s.tags["ops"] >= s.tags["txs"] >= 0
    assert max(s.tags["ops"] for s in spans) > max(
        s.tags["txs"] for s in spans)      # re-quotes carry five ops
    # the cpu validators count their walks too (tracers off)
    for other in dep.apps[1:]:
        _, m = _site_counts(other)
        assert m["herder.txset.checks"] > 0


def test_a_validation_outside_nomination_is_site_other(dep):
    from stellar_core_tpu.scp.driver import ValidationLevel
    from stellar_core_tpu.xdr import StellarValue, StellarValueExt
    app = dep.node0
    h, lm = app.herder, app.ledger_manager
    g = dep.gen
    payer = g.payers[0]
    # a set no node has seen at this LCL: a payment nobody submitted
    fresh = payer.tx([payer.op_payment(g.payers[1].account_id, 1)],
                     seq=g.model[payer.account_id.key_bytes]["seq"] + 1)
    txset = TxSetFrame(app.config.network_id, lm.lcl_hash, [fresh])
    key = txset.get_contents_hash()
    h.pending.add_tx_set(key, txset)
    sv = StellarValue(txSetHash=key,
                      closeTime=lm.lcl_header.scpValue.closeTime + 1,
                      upgrades=[], ext=StellarValueExt(0, None))
    h.sign_stellar_value(sv)
    spans0, meters0 = _site_counts(app)
    hits0 = _hit_counts(app)
    for _ in range(2):
        assert h.scp_driver.validate_value(lm.lcl_header.ledgerSeq + 1,
                                           sv.to_xdr(), True) == \
            ValidationLevel.FULLY_VALIDATED
    spans1, meters1 = _site_counts(app)
    new, = spans1[len(spans0):]
    assert new.tags["site"] == "other" and new.tags["txs"] == 1
    assert meters1["herder.txset.checks.other"] == \
        meters0.get("herder.txset.checks.other", 0) + 1
    # the second ask is the node's cached verdict, marked at its site
    assert _hit_counts(app)["herder.txset.valid_cache.hits.other"] == \
        hits0.get("herder.txset.valid_cache.hits.other", 0) + 1


def test_a_trimming_walk_tags_what_it_removed(dep):
    app = dep.node0
    g = dep.gen
    payer = g.payers[0]
    stale = payer.tx([payer.op_payment(g.payers[1].account_id, 1)],
                     seq=g.model[payer.account_id.key_bytes]["seq"])
    txset = app.herder.tx_queue.to_txset(app.ledger_manager.lcl_hash,
                                         app.config.network_id)
    txset.frames.append(stale)
    ok, removed = app.herder.check_txset(txset, "trigger", trim=True)
    assert not ok and removed == [stale] and txset.frames == []
    spans, _ = _site_counts(app)
    assert spans[-1].tags == {"site": "trigger", "txs": 1, "ops": 1,
                              "removed": 1}


def test_no_requote_moves_an_offer_a_taker_can_reach(dep):
    g = dep.gen
    # checked at every application: each re-quote found its offers as
    # signed, no fill landed on a rung a re-quote in flight touched
    assert g.requotes_checked >= 5 * 10 and g.fills_checked > 0
    assert g.faults == []
    # and at signing: with an order in flight on a side, every re-quote
    # keeps MAKER_MARGIN rungs behind the deepest rung it can reach
    assert not any(g.flying) and not any(g.moving)
    side = 0
    maker = g.makers[0]               # a maker of pair 0: sides 0 and 1
    entry = [7 * 1000 + 1, g.levels, False]
    g.flying[side].append(entry)
    try:
        _best, guard = g._reach(side, 0)
        for _ in range(20):
            tx = g._requote(maker)
            try:
                for op in tx.frame.envelope.value.tx.operations:
                    body = op.body.value
                    o = g.books[side].get(body.offerID)
                    if o is None:
                        continue           # the other side of the pair
                    assert o.level >= guard + MAKER_MARGIN
                    for rung in g.moving[side]:
                        assert rung >= guard + MAKER_MARGIN
            finally:
                tx.release()
    finally:
        g.flying[side].remove(entry)
    assert not any(g.moving)


def test_a_taker_keeps_off_a_side_where_a_requote_is_in_flight(dep):
    g = dep.gen
    saved = copy.deepcopy(g.moving)
    # a re-quote in flight at every side's head rung: no order fits
    for side in range(g.n_sides):
        g.moving[side][g.queue[side][0][0]] = 1
    try:
        i = 0
        before = list(g.orders)
        assert g._order(i, g.takers[i]) is None
        assert g.orders == before and not any(g.flying)
    finally:
        g.moving[:] = saved
