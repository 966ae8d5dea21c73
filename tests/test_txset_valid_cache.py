"""A node walks a transaction set once per LCL.

Role parity: reference `src/herder/HerderSCPDriver.cpp` — its txset
validity cache, keyed by the LCL hash and the set's contents hash.
`Herder.check_txset` without `trim` answers a second ask at one LCL from
the node's own verdicts: no `txset.check_valid` span, no
`herder.txset.checks` mark, one mark of `herder.txset.valid_cache.hits`.
A close empties it; the trigger's trimming walk never reads or fills it;
no node sees another's verdicts.
"""

import pytest

from stellar_core_tpu.herder.herder import Herder, TXSET_VALID_CACHE_SIZE
from stellar_core_tpu.herder.txset import TxSetFrame
from stellar_core_tpu.scp.driver import ValidationLevel
from stellar_core_tpu.simulation import topologies
from stellar_core_tpu.testing import AppLedgerAdapter
from stellar_core_tpu.xdr import StellarValue, StellarValueExt


@pytest.fixture
def net():
    sim = topologies.core(3, 2)
    sim.start_all_nodes()
    assert sim.crank_until(lambda: sim.have_all_externalized(2), 20000)
    apps = [n.app for n in sim.nodes.values()]
    for app in apps:
        app.tracer.enable()
    return sim, apps


def _payments(app, n, seq_step=1):
    """A set of `n` root payments at the app's LCL; `seq_step` 0 makes
    the first one reuse the account's current sequence number."""
    ledger = AppLedgerAdapter(app)
    root = ledger.root_account()
    seq = ledger.seq_num(root.account_id)
    frames = [root.tx([root.op_payment(root.account_id, 1)],
                      seq=seq + seq_step + i) for i in range(n)]
    lm = app.ledger_manager
    return TxSetFrame(app.config.network_id, lm.lcl_hash, frames)


def _ask(app, txset):
    """A nomination-mode `validate_value` of a value naming `txset`."""
    h, lm = app.herder, app.ledger_manager
    key = txset.get_contents_hash()
    h.pending.add_tx_set(key, txset)
    sv = StellarValue(txSetHash=key,
                      closeTime=lm.lcl_header.scpValue.closeTime + 1,
                      upgrades=[], ext=StellarValueExt(0, None))
    if lm.lcl_header.ledgerVersion >= 11:
        h.sign_stellar_value(sv)
    return h.scp_driver.validate_value(lm.lcl_header.ledgerSeq + 1,
                                       sv.to_xdr(), True)


def _counts(app, site=None):
    """(walk spans, walk marks, cache hits) of `app` so far, of one
    `site` where it is given."""
    spans = sum(s.name == "txset.check_valid" and
                site in (None, s.tags["site"]) for s in app.tracer.spans())
    m = app.metrics.to_json(prefix="herder.txset.")
    tail = "" if site is None else "." + site

    def count(name):
        return m.get(name + tail, {}).get("count", 0)
    return (spans, count("herder.txset.checks"),
            count("herder.txset.valid_cache.hits"))


def test_a_second_ask_at_one_lcl_walks_nothing(net):
    _, apps = net
    app = apps[0]
    txset = _payments(app, 3)
    before = _counts(app)
    assert _ask(app, txset) == ValidationLevel.FULLY_VALIDATED
    walked = _counts(app)
    assert walked == (before[0] + 1, before[1] + 1, before[2])
    assert _ask(app, txset) == ValidationLevel.FULLY_VALIDATED
    assert _counts(app) == (walked[0], walked[1], walked[2] + 1)
    hits = app.metrics.to_json(prefix="herder.txset.valid_cache.hits.")
    assert hits["herder.txset.valid_cache.hits.other"]["count"] >= 1
    key = (app.ledger_manager.lcl_hash, txset.get_contents_hash())
    assert app.herder._txset_valid[key] is True


def test_a_close_walks_the_same_hash_again(net):
    sim, apps = net
    app = apps[0]
    txset = _payments(app, 2)
    h = txset.get_contents_hash()
    assert app.herder.check_txset(txset, "other", txset_hash=h)[0]
    assert app.herder.check_txset(txset, "other", txset_hash=h)[0]
    walked = _counts(app, "other")
    assert walked[2] >= 1
    lcl = app.ledger_manager.last_closed_ledger_num()
    assert sim.crank_until(lambda: sim.have_all_externalized(lcl + 1),
                           20000)
    now = app.ledger_manager.lcl_hash
    assert app.herder.check_txset(txset, "other", txset_hash=h)[0]
    assert _counts(app, "other") == (walked[0] + 1, walked[1] + 1,
                                     walked[2])
    # nothing of the old LCL is left
    assert {k[0] for k in app.herder._txset_valid} == {now}


def test_an_invalid_set_is_cached_as_a_fresh_walk_finds_it(net):
    _, apps = net
    app = apps[0]
    txset = _payments(app, 2, seq_step=0)        # a stale sequence number
    assert _ask(app, txset) == ValidationLevel.INVALID
    walked = _counts(app)
    assert _ask(app, txset) == ValidationLevel.INVALID
    assert _counts(app) == (walked[0], walked[1], walked[2] + 1)
    key = (app.ledger_manager.lcl_hash, txset.get_contents_hash())
    ok, removed = txset.check_or_trim(app.ledger_manager.ltx_root(),
                                      app.herder.verifier)
    assert app.herder._txset_valid[key] is ok is False
    assert len(removed) == 2                      # the chain breaks at 1


def test_a_trimming_walk_neither_reads_nor_fills_the_cache(net):
    _, apps = net
    app = apps[0]
    cache = app.herder._txset_valid
    txset = _payments(app, 2)
    key = (app.ledger_manager.lcl_hash, txset.get_contents_hash())
    assert app.herder.check_txset(txset, "trigger", trim=True) == (True, [])
    assert key not in cache
    assert _ask(app, txset) == ValidationLevel.FULLY_VALIDATED
    assert cache[key] is True
    before = _counts(app)
    assert app.herder.check_txset(txset, "trigger", trim=True) == (True, [])
    assert _counts(app) == (before[0] + 1, before[1] + 1, before[2])
    # a stale set trimmed is walked each time and never filed
    stale = _payments(app, 1, seq_step=0)
    stale_key = (key[0], stale.get_contents_hash())
    ok, removed = app.herder.check_txset(stale, "trigger", trim=True)
    assert not ok and len(removed) == 1 and stale.frames == []
    assert stale_key not in cache


def test_one_nodes_walk_gives_another_node_no_hit(net):
    _, apps = net
    a, b = apps[0], apps[1]
    assert a.herder._txset_valid is not b.herder._txset_valid
    txset = _payments(a, 2)
    assert _ask(a, txset) == ValidationLevel.FULLY_VALIDATED
    before = _counts(b)
    assert _ask(b, txset) == ValidationLevel.FULLY_VALIDATED
    assert _counts(b) == (before[0] + 1, before[1] + 1, before[2])
    assert _ask(b, txset) == ValidationLevel.FULLY_VALIDATED
    assert _counts(b)[2] == before[2] + 1


def test_the_cache_holds_a_bounded_number_of_sets(net):
    _, apps = net
    app = apps[0]
    cache = app.herder._txset_valid
    lcl = app.ledger_manager.lcl_hash
    empty = TxSetFrame(app.config.network_id, lcl, [])
    for i in range(TXSET_VALID_CACHE_SIZE + 5):
        app.herder.check_txset(empty, "other",
                               txset_hash=i.to_bytes(32, "big"))
    assert len(cache) == TXSET_VALID_CACHE_SIZE
    # the oldest went first
    assert (lcl, (0).to_bytes(32, "big")) not in cache
    assert (lcl, (TXSET_VALID_CACHE_SIZE + 4).to_bytes(32, "big")) in cache


def test_every_cached_verdict_equals_a_fresh_walk(net, monkeypatch):
    """Over a short consensus run with payments, every answer a node
    gives without trimming — walked or from its cache — equals an
    uncached walk of the same set at the same LCL."""
    sim, apps = net
    seen = {"asks": 0, "hits": 0}
    original = Herder.check_txset

    def checked(self, txset, site, trim=False, txset_hash=None):
        hits = self.app.metrics.new_meter(
            "herder.txset.valid_cache.hits").count
        ok, removed = original(self, txset, site, trim=trim,
                               txset_hash=txset_hash)
        if not trim:
            seen["asks"] += 1
            seen["hits"] += self.app.metrics.new_meter(
                "herder.txset.valid_cache.hits").count - hits
            fresh, _ = txset.check_or_trim(
                self.app.ledger_manager.ltx_root(), self.verifier)
            assert ok is fresh, (site, txset_hash)
        return ok, removed
    monkeypatch.setattr(Herder, "check_txset", checked)
    ledger = AppLedgerAdapter(apps[0])
    root = ledger.root_account()
    for _ in range(3):
        seq = ledger.seq_num(root.account_id)
        for i in range(3):
            frame = root.tx([root.op_payment(root.account_id, 1)],
                            seq=seq + 1 + i)
            assert apps[0].submit_transaction(frame) == 0
        assert sim.crank_until(
            lambda: all(AppLedgerAdapter(a).seq_num(root.account_id) ==
                        seq + 3 for a in apps), 20000)
    assert seen["asks"] > 0 and seen["hits"] > 0
