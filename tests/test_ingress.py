"""TxIngress admission tier (ISSUE 18): unit semantics of the
token-bucket rate classes, the bounded async intake with
shed-lowest-class-first, the million-submitter bounded-memory soak, the
ingress fault sites (`ingress.admit-stall` / `ingress.shed-storm`) with
funnel outcomes + breaker-free recovery, and the per-class fairness
property on a live 3-node sim: an untrusted flooder at 10x the honest
rate cannot push priority latency past 2x the unloaded baseline or
starve a single priority tx.
"""

import pytest

from stellar_core_tpu.crypto.hashing import sha256
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.ingress import (
    ADMIT, PARKED, SHED, THROTTLE, TxIngress,
)
from stellar_core_tpu.main.application import Application
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.util.faults import FaultInjector
from stellar_core_tpu.util.timer import ClockMode, VirtualClock


def _acct(i: int) -> bytes:
    return i.to_bytes(4, "big") + b"\x00" * 28


def _ingress(**kw):
    """A TxIngress on a hand-cranked clock; returns (ingress, now)."""
    now = [0.0]
    kw.setdefault("now_fn", lambda: now[0])
    return TxIngress(**kw), now


# ------------------------------------------------------------ rate classes

def test_default_classes_are_pass_through():
    """Unconfigured nodes behave as if the tier were absent: the
    generous default classes admit a realistic burst untouched."""
    ing, _ = _ingress()
    for i in range(1000):
        decision, retry = ing.admit_source(_acct(i % 7))
        assert decision == ADMIT and retry is None
    assert ing.counters["default"]["admitted"] == 1000


def test_token_bucket_throttles_with_retry_hint():
    ing, now = _ingress(
        classes={"default": {"rate": 10.0, "burst": 5.0}})
    a = _acct(1)
    decisions = [ing.admit_source(a)[0] for _ in range(7)]
    assert decisions == [ADMIT] * 5 + [THROTTLE, THROTTLE]
    _, retry = ing.admit_source(a)
    # deficit of 1 token at 10/s -> 0.1 s hint
    assert retry == pytest.approx(0.1, abs=0.01)
    assert ing.last_retry_after == retry
    now[0] += 0.5  # refill 5 tokens
    assert ing.admit_source(a)[0] == ADMIT


def test_priority_rate_zero_is_unlimited():
    ing, _ = _ingress(priority=[_acct(9)])
    for _ in range(5000):
        assert ing.admit_source(_acct(9))[0] == ADMIT


def test_max_inflight_caps_per_close_window():
    ing, _ = _ingress(
        classes={"default": {"rate": 1000.0, "burst": 1000.0,
                             "max_inflight": 3}})
    a = _acct(2)
    assert [ing.admit_source(a)[0] for _ in range(5)] == \
        [ADMIT] * 3 + [THROTTLE, THROTTLE]
    ing.ledger_closed()   # the close window resets the inflight cap
    assert ing.admit_source(a)[0] == ADMIT


def test_class_table_overrides_and_bounds():
    ing, _ = _ingress(untrusted=[_acct(3)])
    assert ing.class_of(_acct(3)).name == "untrusted"
    assert ing.class_of(_acct(4)).name == "default"
    ing.set_class(_acct(3), "priority")
    assert ing.class_of(_acct(3)).name == "priority"
    ing.set_class(_acct(3), "default")   # removes the override
    assert len(ing._class_of) == 0
    with pytest.raises(ValueError, match="unknown ingress class"):
        ing.set_class(_acct(3), "vip")
    # the override map is bounded operator input
    for i in range(TxIngress.MAX_CLASS_OVERRIDES):
        ing.set_class(_acct(10 + i), "untrusted")
    with pytest.raises(ValueError, match="full"):
        ing.set_class(_acct(10**7), "untrusted")


def test_config_class_table_merges_over_defaults():
    ing, _ = _ingress(classes={"untrusted": {"rate": 0.25}})
    rc = ing.classes["untrusted"]
    assert rc.rate == 0.25
    # unspecified fields keep their defaults
    assert rc.burst == 200.0 and rc.max_inflight == 1000
    js = ing.to_json()
    assert js["classes"]["untrusted"]["rate"] == 0.25
    assert set(js["classes"]) == {"priority", "default", "untrusted"}


# ---------------------------------------------------- bounded async intake

def test_async_intake_parks_and_pumps_priority_first():
    sunk = []
    ing, _ = _ingress(async_intake=True, intake_depth=16,
                      sink=lambda f, h, fr: sunk.append(h),
                      priority=[_acct(0)],
                      classes={"default": {"rate": 0.0}})
    order = [(_acct(5), b"d1"), (_acct(6), b"d2"),
             (_acct(0), b"p1"), (_acct(5), b"d3"), (_acct(0), b"p2")]
    for acc, h in order:
        decision, _ = ing.admit_source(acc, frame=object(), tx_hash=h)
        assert decision == PARKED
    assert ing.intake_depth_now() == 5
    assert ing.pump() == 5
    # priority drains first, then default in FIFO order
    assert sunk == [b"p1", b"p2", b"d1", b"d2", b"d3"]
    assert ing.intake_depth_now() == 0
    assert ing.metrics.to_json()["herder.ingress.pumped"]["count"] == 5


def test_intake_full_sheds_lowest_class_first():
    shed_hashes = []
    ing, _ = _ingress(async_intake=True, intake_depth=3,
                      sink=lambda f, h, fr: None,
                      shed_cb=shed_hashes.append,
                      priority=[_acct(0)], untrusted=[_acct(8)],
                      classes={"default": {"rate": 0.0},
                               "untrusted": {"rate": 0.0}})
    for h in (b"u1", b"u2", b"u3"):
        assert ing.admit_source(_acct(8), frame=object(),
                                tx_hash=h)[0] == PARKED
    # a same-rank arrival cannot evict its own class: it sheds itself
    d, retry = ing.admit_source(_acct(8), frame=object(), tx_hash=b"u4")
    assert d == SHED and retry == TxIngress.DEFAULT_RETRY_AFTER
    assert shed_hashes == []
    # a priority arrival evicts the untrusted TAIL (newest) instead
    d, _ = ing.admit_source(_acct(0), frame=object(), tx_hash=b"p1")
    assert d == PARKED
    assert shed_hashes == [b"u3"]
    assert ing.intake_depth_now() == 3
    assert ing.counters["untrusted"]["shed"] == 2
    assert ing.counters["priority"]["admitted"] == 1


def test_pump_budget_and_sink_order_within_class():
    sunk = []
    ing, _ = _ingress(async_intake=True, intake_depth=8,
                      sink=lambda f, h, fr: sunk.append(h),
                      classes={"default": {"rate": 0.0}})
    for i in range(6):
        ing.admit_source(_acct(20), frame=object(),
                         tx_hash=b"h%d" % i)
    assert ing.pump(max_n=4) == 4
    assert sunk == [b"h0", b"h1", b"h2", b"h3"]
    assert ing.intake_depth_now() == 2


# ------------------------------------------------------------- fault sites

def test_fault_sites_drive_both_degraded_paths():
    """`ingress.shed-storm` forces SHED, `ingress.admit-stall` forces a
    THROTTLE that does NOT charge the source's bucket — after the fault
    clears, the source's full burst is still there."""
    faults = FaultInjector(seed=11)
    ing, _ = _ingress(faults=faults,
                      classes={"default": {"rate": 1.0, "burst": 2.0}})
    a = _acct(30)
    faults.configure("ingress.shed-storm", probability=1.0, count=2)
    assert ing.admit_source(a)[0] == SHED
    assert ing.admit_source(a)[0] == SHED
    faults.configure("ingress.admit-stall", probability=1.0, count=1)
    d, retry = ing.admit_source(a)
    assert d == THROTTLE and retry == TxIngress.DEFAULT_RETRY_AFTER
    # recovery: the un-charged burst admits immediately, no residue
    assert [ing.admit_source(a)[0] for _ in range(3)] == \
        [ADMIT, ADMIT, THROTTLE]
    assert ing.counters["default"] == \
        {"admitted": 2, "throttled": 2, "shed": 2}


# -------------------------------------------------- bounded-memory soak

def test_soak_million_distinct_submitters_bounded():
    """ISSUE 18 acceptance: 10^6 distinct submitter keys cost a
    fixed-size source map (RandomEvictionCache, seeded eviction), the
    intake never exceeds its depth, and admission stays O(1) — the run
    finishes in seconds, not minutes."""
    ing, now = _ingress(
        max_sources=65536, intake_depth=64, async_intake=True,
        sink=lambda f, h, fr: None,
        classes={"default": {"rate": 10.0, "burst": 2.0}})
    for i in range(1_000_000):
        ing.admit_source(_acct(i), frame=object(), tx_hash=None)
        if i % 4096 == 0:
            now[0] += 0.25
            ing.pump()
    assert len(ing._sources) <= 65536
    assert ing.intake_depth_now() <= 64
    js = ing.to_json()
    assert js["sources"]["tracked"] <= js["sources"]["cap"]
    assert js["sources"]["evictions"] > 0
    assert js["intake"]["depth"] <= js["intake"]["cap"]
    c = js["counters"]
    decided = sum(v for cl in c.values() for v in cl.values())
    assert decided == 1_000_000


def test_ledger_closed_reaps_refilled_sources():
    ing, now = _ingress(
        classes={"default": {"rate": 1.0, "burst": 2.0}})
    for i in range(50):
        ing.admit_source(_acct(i))
    assert len(ing._sources) == 50
    now[0] += 10.0   # every bucket fully refills
    ing.ledger_closed()
    assert len(ing._sources) == 0


# ----------------------------------------- live app: funnel + chaos leg

@pytest.fixture
def tight_app():
    cfg = Config.test_config(0)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.INGRESS_CLASSES = {"default": {"rate": 100.0, "burst": 2.0}}
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    a = Application(clock, cfg)
    a.start()
    yield a
    a.stop()


def test_throttle_lands_in_lifecycle_funnel(tight_app):
    """A throttled fresh tx gets exactly one funnel outcome
    (`herder.tx.outcome.throttled`) and recv_transaction answers
    TRY_AGAIN_LATER with a retry hint on the herder."""
    from stellar_core_tpu.testing import AppLedgerAdapter
    app = tight_app
    adapter = AppLedgerAdapter(app)
    root = adapter.root_account()
    alice = root.create(10**9)
    seq = alice.next_seq()
    statuses = [app.submit_transaction(
        alice.tx([alice.op_payment(root.account_id, 1 + i)],
                 seq=seq + i)) for i in range(4)]
    assert statuses == [0, 0, 3, 3]   # burst 2, then backpressure
    assert app.herder.last_retry_after is not None
    lc = app.herder.tx_lifecycle.to_json()
    assert lc["outcomes"]["throttled"] == 2
    m = app.metrics.to_json()
    assert m["herder.tx.outcome.throttled"]["count"] == 2
    assert m["herder.ingress.throttled"]["count"] == 2
    # a duplicate of a throttled tx is NOT a second funnel entry
    dup = alice.tx([alice.op_payment(root.account_id, 3)], seq=seq + 2)
    app.submit_transaction(dup)
    assert app.herder.tx_lifecycle.to_json()["outcomes"]["throttled"] == 3


def test_chaos_leg_funnel_outcomes_and_recovery(tight_app):
    """F1 chaos leg: arm both ingress fault sites against a live app,
    watch shed/throttled land in the funnel, then clear the faults and
    verify clean recovery — submissions flow again and the verify
    breaker never tripped."""
    from stellar_core_tpu.testing import AppLedgerAdapter
    app = tight_app
    adapter = AppLedgerAdapter(app)
    root = adapter.root_account()
    alice = root.create(10**9)
    app.faults.configure("ingress.shed-storm", probability=1.0, count=1)
    # shed-storm short-circuits admission, so admit-stall's first check
    # only happens once shed-storm is exhausted
    app.faults.configure("ingress.admit-stall", probability=1.0, count=1)
    seq = alice.next_seq()
    s1 = app.submit_transaction(
        alice.tx([alice.op_payment(root.account_id, 1)], seq=seq))
    s2 = app.submit_transaction(
        alice.tx([alice.op_payment(root.account_id, 2)], seq=seq))
    assert (s1, s2) == (3, 3)   # shed, then stalled
    lc = app.herder.tx_lifecycle.to_json()
    assert lc["outcomes"]["shed"] == 1
    assert lc["outcomes"]["throttled"] == 1
    m = app.metrics.to_json()
    assert m["fault.injected.ingress.shed-storm"]["count"] == 1
    assert m["fault.injected.ingress.admit-stall"]["count"] == 1
    # faults exhausted: the same chain admits cleanly (bucket uncharged
    # by the stall) and closes apply it — breaker-free recovery
    s3 = app.submit_transaction(
        alice.tx([alice.op_payment(root.account_id, 3)], seq=seq))
    assert s3 == 0
    app.manual_close()
    assert app.herder.tx_lifecycle.to_json()["outcomes"]["applied"] >= 1
    v = app.herder.tx_queue.verifier
    if v.breaker is not None:
        assert v.breaker.state == "closed"


# -------------------------------------------------- per-class fairness sim

def _fairness_leg(flood_on: bool) -> dict:
    """3-node loopback fleet, priority=root, one untrusted flooder at
    10x the priority rate through the sync admission path."""
    from stellar_core_tpu.crypto import strkey as _strkey
    from stellar_core_tpu.simulation.simulation import Simulation
    from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
    from stellar_core_tpu.util import rnd
    from stellar_core_tpu.xdr import SCPQuorumSet
    rnd.reseed(7)
    slots = 4
    keys = [SecretKey.from_seed(sha256(b"fair-%d" % i)) for i in range(3)]
    flooder_key = SecretKey.from_seed(sha256(b"fair-flooder"))
    qset = SCPQuorumSet(threshold=2,
                        validators=[k.public_key for k in keys],
                        innerSets=[])

    def tweak(cfg: Config) -> None:
        cfg.DATABASE = "sqlite3://:memory:"
        cfg.ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING = False
        cfg.EXPECTED_LEDGER_CLOSE_TIME = 1.0
        cfg.INGRESS_CLASSES = {
            "untrusted": {"rate": 1.0, "burst": 2.0, "max_inflight": 0}}
        cfg.INGRESS_PRIORITY_ACCOUNTS = [
            SecretKey.from_seed(sha256(cfg.network_id)).strkey_public()]
        cfg.INGRESS_UNTRUSTED_ACCOUNTS = [
            _strkey.encode_public_key(flooder_key.public_key.key_bytes)]

    sim = Simulation(Simulation.OVER_LOOPBACK)
    names = [sim.add_node(k, qset, name="f%d" % i, cfg_tweak=tweak).name
             for i, k in enumerate(keys)]
    for i in range(3):
        for j in range(i + 1, 3):
            sim.connect(names[i], names[j])
    sim.start_all_nodes()
    n0 = sim.nodes[names[0]].app
    assert sim.crank_until(lambda: sim.have_all_externalized(2), 40000)
    adapter = AppLedgerAdapter(n0)
    root = adapter.root_account()
    st = n0.submit_transaction(root.tx(
        [root.op_create_account(flooder_key.public_key, 10**10)]))
    assert st == 0
    assert sim.crank_until(
        lambda: adapter.account_exists(flooder_key.public_key), 40000)
    flooder = TestAccount(adapter, flooder_key)
    pri_hashes, submitted = set(), set()
    rseq, fseq = root.next_seq() - 1, flooder.next_seq() - 1
    base = n0.ledger_manager.last_closed_ledger_num()
    flood_stats = {"accepted": 0, "throttled": 0}
    for s in range(slots):
        if flood_on:
            for i in range(20):   # 10x the priority rate
                f = flooder.tx([flooder.op_payment(root.account_id,
                                                   1 + s * 20 + i)],
                               seq=fseq + 1, fee=100)
                submitted.add(f.full_hash())
                if n0.submit_transaction(f) == 0:
                    fseq += 1
                    flood_stats["accepted"] += 1
                else:
                    flood_stats["throttled"] += 1
        for i in range(2):
            rseq += 1
            f = root.tx([root.op_payment(root.account_id, 1 + i)],
                        seq=rseq, fee=100)
            submitted.add(f.full_hash())
            assert n0.submit_transaction(f) == 0, \
                "priority tx refused under flood"
            pri_hashes.add(f.contents_hash().hex())
        assert sim.crank_until(
            lambda: sim.have_all_externalized(base + s + 1), 200000)
    assert sim.crank_until(
        lambda: sim.have_all_externalized(base + slots + 2), 200000)
    applied = {row[0] for row in n0.database.execute(
        "SELECT txid FROM txhistory").fetchall()}
    lc = n0.herder.tx_lifecycle.to_json()
    sim.stop_all_nodes()
    return {"p95_ms": lc["total_ms"]["p95"],
            "pri_applied": len(pri_hashes & applied),
            "pri_submitted": len(pri_hashes),
            "lifecycle": lc, "submitted": submitted,
            "flood": flood_stats}


def test_fairness_flooder_cannot_starve_priority():
    """ISSUE 18 satellite: with an untrusted flooder at 10x, every
    priority tx still applies, applied-tx p95 stays within 2x the
    unloaded leg, the flooder is mostly throttled, and the funnel sum
    contract holds — every locally-tracked tx has exactly one outcome
    (or is still pending)."""
    quiet = _fairness_leg(flood_on=False)
    loud = _fairness_leg(flood_on=True)
    assert quiet["pri_applied"] == quiet["pri_submitted"]
    assert loud["pri_applied"] == loud["pri_submitted"], \
        "flooder starved priority traffic"
    assert loud["p95_ms"] <= 2.0 * max(quiet["p95_ms"], 1.0), \
        (loud["p95_ms"], quiet["p95_ms"])
    assert loud["flood"]["throttled"] > loud["flood"]["accepted"]
    lc = loud["lifecycle"]
    assert lc["outcomes"]["throttled"] > 0
    # sum contract: outcomes + still-pending == distinct local txs
    # (the create tx rides along with the payments)
    tracked = len(loud["submitted"]) + 1
    assert sum(lc["outcomes"].values()) + lc["pending_tracked"] == tracked
