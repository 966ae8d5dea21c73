"""The DEX archive (ISSUE 31): benchmark/traffic/dex_history.py's
generator at a small size (2 pairs, 60 offers a side, checkpoint
frequency 8, 12 transactions a ledger: maker re-quotes, crossing offers
and path payments, native payments), published and replayed by catchup
on the `cpu` backend through the benchmark's own driver
(benchmark/deployments/catchup_dex.py), on the native engine and on the
Python apply oracle. Both have to reach the publisher's header chain
and the generator's plain order-book model; and the native close has to
say which closes the order book forced serial (`close.book_load`,
`ledger.apply.book.*`, `ledger.apply.cluster.dynamic-close`, the `mode`
tag of `close.apply`).
"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.deployments import catchup, catchup_dex  # noqa: E402
from benchmark.traffic.dex_history import (  # noqa: E402
    MAKER_OPS, SUBLOTS, DexHistory, _in_priority, _Offer, apply_order,
)

STATE = {"pairs": 2, "offers_per_side": 60, "levels": 20, "makers": 4,
         "takers": 4, "payers": 4}
TRAFFIC = {"maker_txs": 4, "taker_txs": 4, "payment_txs": 4,
           "checkpoints": 2}
ACCOUNTS, SIDES = 12 + 2, 4
NEVER = float("inf")


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as fh:
        return json.load(fh)


def small():
    config = _load("configs", "catchup-dex13")
    workload = _load("workloads", "catchup-dex13.maker-taker")
    config["checkpoint_frequency"] = 8
    config["backend_under_test"] = "cpu"
    config["state"].update(STATE)
    workload["traffic"].update(TRAFFIC)
    workload["negative_control_lanes"] = 64
    return config, workload


@pytest.fixture(scope="module")
def dep(tmp_path_factory):
    config, workload = small()
    d = catchup_dex.Deployment(config, workload, 11,
                               str(tmp_path_factory.mktemp("dex")), False)
    d.setup()
    yield d
    d.first.stop()
    d.release()


def replay(d, native=True, trace=False):
    """One whole replay by a fresh node; returns (node, compared)."""
    def hook(app):
        app.ledger_manager.use_native_apply = native
        if trace:
            app.tracer.enable(capacity=1 << 16)
    d.node_hook = hook
    if d.last_node is not None:
        d.last_node.stop()
        d.last_node = None
    d.replays, d.ledgers_closed = [], 0
    app = d._new_node()
    d._replay(app, NEVER, lambda now: False)
    assert d.replays[-1]["ok"]
    d.last_node = app
    return app, {k: c["value"] for k, c in d.compare().items()}


def count(app, meter):
    return app.metrics.to_json().get(meter, {}).get("count", 0)


def test_the_archive_has_the_shape_the_cell_states(dep):
    h = dep.hist
    assert h.tip == 15 and h.dense == 10
    # 3 ledgers of accounts, trust lines and funding carry no book op
    assert h.book_ledgers == h.dense + 1 == h.tip - 1 - 3
    whole = SIDES * STATE["offers_per_side"]
    assert all(abs(n - whole) <= whole // 10 for n in h.book_sizes)
    # every maker op is a re-quote by offerID: no offer is new after the
    # books were posted, and only takers take one away
    assert h.id_pool == whole and h.book_sizes[-1] < whole
    # every order filled one to three offers, the last in part (the model
    # raises otherwise), and some walked on to a side's second rung
    assert sum(h.fills.values()) == TRAFFIC["taker_txs"] * h.dense
    assert h.fills[2] > 0 and h.walks > 0
    # one signature a transaction; 12 transactions a dense ledger
    assert h.sigs_issued == 1 + 8 + 2 + 4 + 12 * h.dense


@pytest.mark.parametrize("native", [True, False],
                         ids=["native", "python-oracle"])
def test_a_replay_reaches_the_chain_and_the_model(dep, native):
    app, got = replay(dep, native=native)
    assert got["full_replays"] == 1 and got["failed_replays"] == 0
    assert got["header_mismatches"] == 0
    assert got["state_mismatches"] == 0
    assert got["state_checked"] >= ACCOUNTS + SIDES
    closes = dep.hist.tip - 1
    if native:
        assert got["python_closes"] == got["native_bails"] == 0
        assert got["dynamic_close_mismatches"] == 0
    else:
        assert got["python_closes"] == closes
        assert count(app, "ledger.apply.book.loads") == 0
    counts = dep.counts()
    assert counts["closes"] == counts["ledgers"] == closes


def wrong_line(h):
    key = h.sender_keys[STATE["makers"]].key_bytes      # a taker
    h.model[key]["lines"][0] += 1


def wrong_balance(h):
    h.model[h.sender_keys[0].key_bytes]["balance"] -= 1  # a maker


def wrong_book(h):
    next(iter(h.books[1].values())).amount += 100


def wrong_rung(h):
    # a re-quote the model left on its old rung: count and summed amount
    # of the side are as they were
    o = next(iter(h.books[2].values()))
    o.n += 1


def wrong_id(h):
    next(iter(h.books[3].values())).id = h.id_pool + 1


def wrong_id_pool(h):
    h.id_pool += 1


@pytest.mark.parametrize("plant", [wrong_line, wrong_balance, wrong_book,
                                   wrong_rung, wrong_id, wrong_id_pool])
def test_one_wrong_fill_in_the_model_is_a_state_mismatch(dep, plant):
    replay(dep)
    h = dep.hist
    kept = copy.deepcopy((h.model, h.books, h.id_pool))
    try:
        plant(h)
        got = dep.compare()
    finally:
        h.model, h.books, h.id_pool = kept
    assert got["state_mismatches"]["value"] == 1
    assert got["header_mismatches"]["value"] == 0


def test_book_load_spans_sum_to_the_meter(dep):
    app, _ = replay(dep, trace=True)
    spans = {s.sid: s for s in app.tracer.spans() if s.dur is not None}
    loads = [s for s in spans.values() if s.name == "close.book_load"]
    assert loads and {s.tags["kind"] for s in loads} == {"book"}
    assert all(spans[s.parent].name == "close.apply" for s in loads)
    assert len(loads) == count(app, "ledger.apply.book.loads")
    rows = sum(s.tags["rows"] for s in loads)
    assert rows == count(app, "ledger.apply.book.rows") > 0
    assert rows == dep.counts()["book_rows"]
    # a dense close loads each of the four sides once, whole
    last = max(loads, key=lambda s: s.t0).parent
    mine = [s.tags["rows"] for s in loads if s.parent == last]
    assert len(mine) == SIDES
    assert abs(sum(mine) - dep.hist.book_sizes[-1]) <= 2 * TRAFFIC["taker_txs"]
    modes = [s.tags.get("mode") for s in spans.values()
             if s.name == "close.apply"]
    assert modes.count("dynamic") == dep.hist.book_ledgers
    assert set(modes) <= {"dynamic", "serial", "parallel"}
    assert len(modes) == dep.hist.tip - 1


def test_no_span_and_the_same_counts_with_tracing_off(dep):
    app, _ = replay(dep, trace=False)
    assert not app.tracer.enabled and app.tracer.spans() == []
    assert count(app, "ledger.apply.book.rows") == dep.counts()["book_rows"]
    assert count(app, "ledger.apply.book.loads") > 0


def test_best_offer_counts_ride_the_apply_span_and_repeat(dep):
    """ISSUE 32: the engine's best-offer lookups and the index records
    they examined are exact counts: the `close.apply` tags of the
    dynamic closes sum to the meters, `GET applystats` says the same,
    a second replay (tracing off) counts the same, and a lookup reads
    the head of a side, not the side (60 offers here)."""
    app, _ = replay(dep, trace=True)
    queries = count(app, "ledger.apply.book.best_queries")
    steps = count(app, "ledger.apply.book.best_steps")
    applies = [s for s in app.tracer.spans()
               if s.name == "close.apply" and s.dur is not None]
    asked = [s for s in applies if "best_queries" in s.tags]
    assert len(asked) == dep.hist.book_ledgers
    assert all(s.tags["mode"] == "dynamic" for s in asked)
    assert sum(s.tags["best_queries"] for s in asked) == queries > 0
    assert sum(s.tags["best_steps"] for s in asked) == steps
    # every offer op and every hop of a path payment asks at least once
    ops = app.ledger_manager.apply_stats.to_json()["ops"]
    assert queries >= sum(d["count"] for n, d in ops.items()
                          if "offer" in n or "path" in n)
    assert queries <= steps < 2 * queries
    blob = app.ledger_manager.apply_stats.to_json()
    assert blob["book"]["best_queries"] == queries
    assert blob["book"]["best_steps"] == steps
    assert blob["last_close"]["book"] == {
        "best_queries": asked[-1].tags["best_queries"],
        "best_steps": asked[-1].tags["best_steps"]}
    again, _ = replay(dep, trace=False)
    assert not again.tracer.enabled
    assert count(again, "ledger.apply.book.best_queries") == queries
    assert count(again, "ledger.apply.book.best_steps") == steps


def test_the_python_oracle_asks_the_index_nothing(dep):
    app, _ = replay(dep, native=False)
    assert count(app, "ledger.apply.book.best_queries") == 0
    assert app.ledger_manager.apply_stats.to_json()["book"] == {
        "loads": 0, "rows": 0, "best_queries": 0, "best_steps": 0}


def test_every_ledger_with_a_book_op_is_a_dynamic_close(dep):
    app, _ = replay(dep)
    closes = dep.hist.tip - 1
    dynamic = count(app, "ledger.apply.cluster.dynamic-close")
    assert dynamic == dep.hist.book_ledgers == dep.counts()["dynamic_closes"]
    # serial-close goes on counting them
    assert count(app, "ledger.apply.cluster.serial-close") + \
        count(app, "ledger.apply.cluster.parallel-close") == closes
    assert count(app, "ledger.apply.cluster.serial-close") >= dynamic
    blob = app.ledger_manager.apply_stats.to_json()
    assert blob["clusters"]["dynamic_closes"] == dynamic
    assert blob["book"]["rows"] == count(app, "ledger.apply.book.rows")
    assert blob["last_close"]["mode"] == "dynamic"


def test_a_payments_only_archive_has_no_dynamic_close(tmp_path):
    config = _load("configs", "catchup-pubnet13")
    workload = _load("workloads", "catchup-pubnet13.standard-mix")
    config["checkpoint_frequency"] = 8
    config["backend_under_test"] = "cpu"
    workload["traffic"].update(txs_per_ledger=6, sigs_per_tx=1,
                               mixed_every=0)
    workload["negative_control_lanes"] = 64
    d = catchup.Deployment(config, workload, 5, str(tmp_path), False)
    try:
        d.setup()
        d._replay(d.first, NEVER, lambda now: False)
        assert d.replays[-1]["ok"]
        app = d.first
        assert count(app, "ledger.apply.cluster.dynamic-close") == 0
        assert count(app, "ledger.apply.book.loads") == 0
        assert count(app, "ledger.apply.book.best_queries") == 0
        assert count(app, "ledger.apply.cluster.serial-close") + \
            count(app, "ledger.apply.cluster.parallel-close") == d.hist.tip - 1
        assert app.ledger_manager.apply_stats.to_json()[
            "last_close"]["mode"] in ("serial", "parallel")
    finally:
        d.first.stop()
        d.release()


def test_counts_do_not_depend_on_the_seed(dep, tmp_path):
    config, workload = small()
    other = DexHistory(config, workload["traffic"], 2 ** 31 + 4242,
                       str(tmp_path))
    try:
        other.publish()
    finally:
        other.close()
    h = dep.hist
    for what in ("tip", "dense", "sigs_issued", "book_ledgers", "fee_pool"):
        assert getattr(other, what) == getattr(h, what), what
    assert other.headers != h.headers


def offer(oid, n, d, amount, owner=b"m"):
    return _Offer(oid, owner, 0, 0, n, d, amount)


def test_the_model_orders_by_price_then_id():
    # 201/100 < 403/200 < 203/100, compared without division
    book = [offer(9, 203, 100, 1), offer(4, 403, 200, 1),
            offer(7, 201, 100, 1), offer(2, 403, 200, 1),
            offer(5, 201, 100, 1)]
    assert [o.id for o in _in_priority(book)] == [5, 7, 2, 4, 9]


def model(tmp_path):
    config, workload = small()
    h = DexHistory(config, workload["traffic"], 1, str(tmp_path))
    for key in (b"maker", b"taker", b"dest"):
        h.model[key] = {"balance": 10 ** 9, "seq": 0, "lines": {0: 10 ** 9}}
    h.books[0] = {3: offer(3, 201, 100, 500, b"maker"),
                  8: offer(8, 201, 100, 300, b"maker"),
                  1: offer(1, 202, 100, 900, b"maker")}
    return h


def test_the_model_fills_in_priority_and_in_whole_numbers(tmp_path):
    h = model(tmp_path)
    # 603 native buys 300 of X at 2.01: offer 3 first, in part
    h._take(0, b"taker", b"dest", sheep=603)
    assert h.books[0][3].amount == 200 and h.books[0][8].amount == 300
    assert h.model[b"taker"]["balance"] == 10 ** 9 - 603
    assert h.model[b"dest"]["lines"][0] == 10 ** 9 + 300
    assert h.model[b"maker"]["balance"] == 10 ** 9 + 603
    assert h.model[b"maker"]["lines"][0] == 10 ** 9 - 300
    # exactly 400 of X: the rest of 3, gone, then 8 in part
    h._take(0, b"taker", b"taker", wheat=400)
    assert 3 not in h.books[0] and h.books[0][8].amount == 100
    assert h.fills == {1: 1, 2: 1, 3: 0} and h.walks == 0
    # 200 of X more: the rest of 8 at 2.01 and 100 of offer 1 at 2.02,
    # the side's second rung: 201 + 202 native
    h._take(0, b"taker", b"taker", wheat=200)
    assert h.book_rows()[0] == {1: (800, 202, 100)}
    assert h.model[b"maker"]["balance"] == 10 ** 9 + 603 + 804 + 403
    assert h.fills[2] == 2 and h.walks == 1


@pytest.mark.parametrize("order", [
    {"sheep": 100}, {"wheat": 150},     # 49.75 of X; 301.5 native
    {"wheat": 5000},                    # the book holds 1,700
    {"wheat": 500},                     # offer 3 whole, none in part
    {"wheat": 1000},                    # a fourth fill would be needed
])
def test_the_model_refuses_an_order_outside_its_bounds(tmp_path, order):
    h = model(tmp_path)
    if order == {"wheat": 1000}:
        h.books[0][2] = offer(2, 201, 100, 100, b"maker")
    with pytest.raises(AssertionError):
        h._take(0, b"taker", b"dest", **order)


def test_the_apply_order_is_the_protocols(dep):
    """`apply_order` (hashlib alone) against the node's own TxSetFrame,
    over accounts with one and with several transactions."""
    from stellar_core_tpu.herder.txset import TxSetFrame
    from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
    from benchmark.traffic.history import _sk
    app = dep.last_node or dep.first
    adapter = AppLedgerAdapter(app)
    frames = []
    for i in range(7):
        a = TestAccount(adapter, _sk(3, "order", i))
        for seq in range(1, 1 + i % 3 + 1):
            frames.append(a.tx([a.op_payment(a.account_id, 1)], seq=seq))
    previous = bytes(range(32))
    want = TxSetFrame(app.config.network_id, previous,
                      list(reversed(frames))).sort_for_apply()
    got = apply_order(previous, [
        (f.seq_account_id().key_bytes, f.envelope.value.tx.seqNum,
         f.envelope_bytes(), f) for f in frames])
    assert [t[3] for t in got] == want and len(want) == len(frames)


def test_a_ledgers_maker_ops_are_all_requotes(dep):
    """Every operation of a maker's update in a dense ledger names an
    offer id (ISSUE 31: offerID != 0), five a transaction."""
    from stellar_core_tpu.xdr import OperationType, TransactionEnvelope
    pub = dep.hist.pub
    rows = pub.database.execute(
        "SELECT txbody FROM txhistory WHERE ledgerseq = ?",
        (dep.hist.tip,)).fetchall()
    offers = (OperationType.MANAGE_SELL_OFFER, OperationType.MANAGE_BUY_OFFER)
    updates = 0
    for (body,) in rows:
        ops = TransactionEnvelope.from_xdr(bytes(body)).value.tx.operations
        if len(ops) == MAKER_OPS:
            updates += 1
            assert all(op.body.disc in offers and op.body.value.offerID != 0
                       for op in ops)
    assert updates == TRAFFIC["maker_txs"] and len(rows) == 12
    assert max(dep.hist.orders) < SUBLOTS
