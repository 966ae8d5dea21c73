"""Wire cockpit (ISSUE 10): OverlayStats + TxLifecycle.

Covers the tentpole acceptance criteria — floodgate dedup accounting
(duplicates counted, never re-verified; ChaosTransport `overlay.duplicate`
injection shows in the ratio without killing the link), the tx-lifecycle
sum contract over a multi-node simulation run, the `overlaystats`
endpoint, Prometheus round-trips incl. the `# HELP` satellite, and the
fleet/bench `overlay_breakdown` normalization.
"""

import pytest

from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.overlay.floodgate import Floodgate
from stellar_core_tpu.overlay.overlay_stats import (
    MSG_TYPE_NAMES, OverlayStats, msg_type_name,
)
from stellar_core_tpu.herder.tx_lifecycle import STAGES, TxLifecycle
from stellar_core_tpu.simulation.simulation import Simulation
from stellar_core_tpu.xdr import MessageType, SCPQuorumSet, StellarMessage


def _peer_sim(n, threshold, cfg_tweak=None, chaos=False):
    sim = Simulation(Simulation.OVER_PEERS)
    keys = [SecretKey.from_seed(bytes([50 + i]) * 32) for i in range(n)]
    qset = SCPQuorumSet(threshold=threshold,
                        validators=[k.public_key for k in keys],
                        innerSets=[])
    names = [sim.add_node(k, qset, name="w%d" % i,
                          cfg_tweak=cfg_tweak).name
             for i, k in enumerate(keys)]
    for i in range(n):
        for j in range(i + 1, n):
            sim.connect_peers(names[i], names[j], chaos=chaos)
    return sim, names


def _tweak(cfg):
    cfg.DATABASE = "sqlite3://:memory:"


# ---------------------------------------------------------------- unit layer

def test_msg_type_names_cover_the_wire():
    assert msg_type_name(MessageType.SCP_MESSAGE) == "scp-message"
    assert msg_type_name(None) == "malformed"
    assert len(MSG_TYPE_NAMES) >= 15


def test_floodgate_dedup_accounting_unit():
    """add_record: first sight counts unique, re-receipts count
    duplicates; the ratio is duplicates/unique."""
    fg = Floodgate()
    stats = OverlayStats()           # private registry, app-free
    fg.stats = stats
    msg = StellarMessage(MessageType.GET_SCP_STATE, 7)
    assert fg.add_record(msg, "peer-a", 1) is True
    assert fg.add_record(msg, "peer-b", 1) is False
    assert fg.add_record(msg, "peer-c", 1) is False
    blob = stats.to_json()["flood"]
    assert blob["unique"] == 1
    assert blob["duplicates"] == 2
    assert blob["duplication_ratio"] == 2.0
    m = stats.metrics.to_json()
    assert m["overlay.flood.unique"]["count"] == 1
    assert m["overlay.flood.duplicate"]["count"] == 2


def test_overlay_stats_per_type_and_per_peer():
    stats = OverlayStats()
    key = b"\x11" * 32
    stats.record_recv(MessageType.SCP_MESSAGE, 100, key)
    stats.record_recv(MessageType.SCP_MESSAGE, 300, key)
    stats.record_send(MessageType.TRANSACTION, 50, key)
    blob = stats.to_json()
    t = blob["by_type"]["scp-message"]
    assert t["recv_msgs"] == 2 and t["recv_bytes"] == 400
    assert blob["by_type"]["transaction"]["send_bytes"] == 50
    assert blob["totals"]["recv_bytes"] == 400
    assert blob["peers"]["tracked"] == 1
    top = blob["peers"]["top"][0]
    assert top["peer"] == key.hex()[:16]
    assert top["recv_bytes"] == 400 and top["send_bytes"] == 50
    m = stats.metrics.to_json()
    assert m["overlay.recv.scp-message.count"]["count"] == 2
    assert m["overlay.send.transaction.bytes"]["count"] == 1


def test_overlay_stats_reset_keeps_registry_monotonic():
    stats = OverlayStats()
    stats.record_recv(MessageType.TRANSACTION, 10, None)
    stats.record_flood(unique=True)
    stats.reset()
    assert stats.to_json()["totals"]["recv_msgs"] == 0
    # Prometheus counters must never go backwards
    m = stats.metrics.to_json()
    assert m["overlay.recv.transaction.count"]["count"] == 1
    assert m["overlay.flood.unique"]["count"] == 1


def test_tx_lifecycle_stage_sum_contract_per_tx():
    """Per-tx: the total histogram sample equals the sum of the four
    stage samples exactly (total is COMPUTED as that sum)."""
    now = {"t": 0.0}
    lc = TxLifecycle(now_fn=lambda: now["t"])
    h = b"\xaa" * 32
    lc.submit(h)
    now["t"] = 0.25
    lc.queued(h)
    now["t"] = 1.0
    lc.included([h])
    now["t"] = 3.5
    lc.externalized([h])
    now["t"] = 3.75
    assert lc.applied([h], slot=7) == 1
    j = lc.to_json()
    assert j["applied"] == 1
    stage = j["stage_seconds"]
    assert stage["submit-to-queue"] == 0.25
    assert stage["queue-to-include"] == 0.75
    assert stage["include-to-externalize"] == 2.5
    assert stage["externalize-to-apply"] == 0.25
    assert j["total_seconds"] == sum(stage.values()) == 3.75
    assert j["outcomes"] == {"applied": 1}
    assert j["last_slot"]["slot"] == 7


def test_tx_lifecycle_backfills_missed_stages():
    """A node that never nominated the winning txset still satisfies the
    sum contract: the include stage backfills zero-width."""
    now = {"t": 10.0}
    lc = TxLifecycle(now_fn=lambda: now["t"])
    h = b"\xbb" * 32
    lc.submit(h)
    now["t"] = 11.0
    lc.queued(h)
    now["t"] = 14.0            # include never stamped locally
    lc.externalized([h])
    now["t"] = 14.5
    lc.applied([h], slot=3)
    stage = lc.to_json()["stage_seconds"]
    assert stage["queue-to-include"] == 3.0
    assert stage["include-to-externalize"] == 0.0
    assert lc.to_json()["total_seconds"] == 4.5


def test_tx_lifecycle_outcomes_and_duplicate_submit():
    now = {"t": 0.0}
    lc = TxLifecycle(now_fn=lambda: now["t"])
    h = b"\xcc" * 32
    assert lc.submit(h) is True
    assert lc.submit(h) is False          # re-flood must not clobber
    assert lc.outcome(h, "evicted") is True
    assert lc.outcome(h, "evicted") is False   # already finalized
    assert lc.outcome(b"\xdd" * 32, "expired") is False  # never tracked
    j = lc.to_json()
    assert j["outcomes"] == {"evicted": 1}
    assert lc.metrics.to_json()["herder.tx.outcome.evicted"]["count"] == 1


# ------------------------------------------------------------ endpoint layer

@pytest.fixture
def app():
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    cfg = Config.test_config(0)
    a = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    a.start()
    yield a
    a.stop()


def _cmd(app, name, **params):
    return app.command_handler.handle_command(
        name, {k: str(v) for k, v in params.items()})


def test_overlaystats_endpoint_round_trip(app):
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    lg = LoadGenerator(app)
    lg.generate_accounts(2)
    app.manual_close()
    lg.generate_payments(3)
    app.clock.set_virtual_time(app.clock.now() + 1.0)
    app.manual_close()

    st, body = _cmd(app, "overlaystats")
    assert st == 200
    lc = body["tx_lifecycle"]
    assert lc["applied"] >= 3
    assert lc["outcomes"]["applied"] == lc["applied"]
    assert abs(sum(lc["stage_seconds"].values()) -
               lc["total_seconds"]) < 1e-6
    assert set(lc["stage_seconds"]) == set(STAGES)
    assert body["overlay"]["send_queue"]["bytes"] == 0
    # the compact fleet shape rides along for util/fleet.py add_http
    assert set(body["fleet"]) == {"overlay", "tx"}
    assert body["fleet"]["tx"]["count"] == lc["applied"]

    st, body = _cmd(app, "overlaystats", action="reset")
    assert st == 200 and body["status"] == "reset"
    assert body["tx_lifecycle"]["applied"] == 0
    st, body = _cmd(app, "overlaystats", action="bogus")
    assert st == 400 and "action" in body["error"]


def test_prometheus_help_lines(app):
    app.manual_close()    # registers the ledger.ledger.close timer
    st, text = _cmd(app, "metrics", format="prometheus")
    assert st == 200 and isinstance(text, str)
    lines = text.splitlines()
    # every TYPE line is preceded by a HELP line for the same series
    for i, line in enumerate(lines):
        if line.startswith("# TYPE "):
            series = line.split()[2]
            assert lines[i - 1].startswith("# HELP %s " % series), line
    # catalog-sourced text for a documented metric...
    assert any(l.startswith("# HELP sct_ledger_ledger_close_count") or
               l.startswith("# HELP sct_ledger_ledger_close ") and
               "Wall time" in l for l in lines)
    help_close = [l for l in lines
                  if l.startswith("# HELP sct_ledger_ledger_close ")]
    assert help_close and "Wall time" in help_close[0]
    # ...and dynamic-prefix resolution for a per-site name
    dyn = [l for l in lines if l.startswith("# HELP sct_overlay_recv_")]
    assert dyn, "overlay cockpit series missing from the scrape"


def test_prometheus_help_fallback_is_the_metric_name():
    from stellar_core_tpu.util.metrics import HelpCatalog, render_prometheus
    out = render_prometheus({"totally.undocumented": {"type": "gauge",
                                                      "value": 1.0}},
                            help_catalog=HelpCatalog({}, []))
    assert "# HELP sct_totally_undocumented totally.undocumented" in out


def test_help_catalog_parses_docs_tables():
    from stellar_core_tpu.util.metrics import load_help_catalog
    cat = load_help_catalog()
    assert "Wall time" in cat.lookup("ledger.ledger.close")
    # dynamic prefix: fault.injected.<site>
    assert cat.lookup("fault.injected.device.dispatch") is not None
    assert cat.lookup("no.such.metric") is None


# ------------------------------------------------------- simulation layer

def test_multi_node_sum_contract_and_wire_accounting():
    """Tier-1 acceptance: over a 3-node OVER_PEERS run with real
    payments, every node's tx-lifecycle stage histograms sum to total,
    and the wire cockpit attributed bandwidth + flood dedup +
    envelope-pipeline latency."""
    sim, names = _peer_sim(3, 2, cfg_tweak=_tweak)
    sim.start_all_nodes()
    apps = [sim.nodes[n].app for n in names]
    assert sim.crank_until(lambda: sim.have_all_externalized(2), 40000)

    from stellar_core_tpu.testing import AppLedgerAdapter
    ad = AppLedgerAdapter(apps[0])
    root = ad.root_account()
    base_seq = ad.seq_num(root.account_id)
    for i in range(3):
        st = apps[0].submit_transaction(root.tx(
            [root.op_payment(root.account_id, 1 + i)],
            seq=base_seq + 1 + i))
        assert st == 0

    # a node's virtual clock is its own and moves only where the node
    # idles; one that follows the others' nomination through a ledger
    # never does, and would read 0 s from receipt to apply. So: every
    # node holds the three, then each clock moves on by the wait in the
    # queue that a shared clock would have shown
    def all_received():
        return all(a.herder.tx_lifecycle.to_json()["pending_tracked"] >= 3
                   for a in apps)
    assert sim.crank_until(all_received, 100)
    for a in apps:
        a.clock.set_virtual_time(a.clock.now() + 0.001)

    def all_applied():
        return all(a.herder.tx_lifecycle.to_json()["applied"] >= 3
                   for a in apps)
    assert sim.crank_until(all_applied, 200000)

    for a in apps:
        j = a.herder.tx_lifecycle.to_json()
        # the sum contract: stages sum to total (by construction)
        assert abs(sum(j["stage_seconds"].values()) -
                   j["total_seconds"]) < 1e-6
        assert j["total_seconds"] > 0.0
        m = a.metrics.to_json()
        total = m["herder.tx.latency.total"]
        assert total["count"] >= 3
        for s in STAGES:
            assert m["herder.tx.latency.%s" % s]["count"] == \
                total["count"]
        # wire accounting: both directions attributed by type + peer
        ov = a.overlay_manager.stats.to_json()
        assert ov["totals"]["recv_bytes"] > 0
        assert ov["totals"]["send_bytes"] > 0
        assert ov["by_type"]["scp-message"]["recv_msgs"] > 0
        assert ov["peers"]["tracked"] >= 2
        assert ov["peers"]["top"]
        # envelope pipeline attributed to the verify backend
        env = ov["envelope"]
        assert env["count"] > 0
        backend = a.sig_verifier.name
        assert env["by_backend"][backend]["count"] == env["count"]
        assert m["overlay.envelope.verify-latency"]["count"] == \
            env["count"]
    # a full mesh floods every message to everyone: duplicates exist
    assert any(a.overlay_manager.stats.to_json()["flood"]["duplicates"]
               > 0 for a in apps)
    # per-slot bandwidth attribution landed
    assert any(a.overlay_manager.stats.fleet_json()["per_slot"]
               for a in apps)

    # fleet aggregate + breakdown schema-validate
    agg = sim.fleet()
    ob = agg.overlay_breakdown()
    assert ob is not None
    assert ob["recv_bytes"] > 0 and ob["tx_latency_ms"]["count"] >= 9
    assert ob["flood"]["duplication_ratio"] > 0
    import sys, os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.bench_compare import (
        overlay_breakdown_records, validate_overlay_breakdown,
    )
    assert validate_overlay_breakdown(ob, "test") == []
    recs = overlay_breakdown_records(ob, "test-plat", "test")
    by_metric = {r["metric"]: r for r in recs}
    assert by_metric["flood_duplication_ratio"]["direction"] == "lower"
    assert by_metric["tx_latency_total_p95_ms"]["direction"] == "lower"
    assert by_metric["tx_latency_total_p95_ms"]["value"] >= \
        by_metric["tx_latency_total_p50_ms"]["value"]
    # fleet summary carries the bandwidth + latency headline numbers
    stats = agg.fleet_stats()
    assert stats["summary"]["recv_bytes_total"] == ob["recv_bytes"]
    assert stats["summary"]["tx_latency_p95_ms"] == \
        ob["tx_latency_ms"]["p95"]
    assert any("bandwidth" in e for e in stats["slots"].values())
    sim.stop_all_nodes()


def test_duplicate_envelope_not_reverified():
    """A re-flooded SCP envelope increments the duplication counters but
    never reaches the verifier again (PendingEnvelopes dedup)."""
    sim, names = _peer_sim(2, 1, cfg_tweak=_tweak)
    sim.start_all_nodes()
    a = sim.nodes[names[0]].app
    assert sim.crank_until(lambda: sim.have_all_externalized(2), 40000)

    calls = {"n": 0}
    orig = a.sig_verifier.enqueue

    def counting_enqueue(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)
    a.sig_verifier.enqueue = counting_enqueue

    # a fresh envelope from the peer, fed twice (a duplicate flood copy)
    b = sim.nodes[names[1]].app
    envs = b.herder.scp.get_latest_messages_send(b.herder.current_slot())
    if not envs:
        envs = b.herder.scp.get_latest_messages_send(
            b.herder.current_slot() - 1)
    assert envs
    env = envs[0]
    a.herder.recv_scp_envelope(env)
    first = calls["n"]
    st = a.herder.recv_scp_envelope(env)
    from stellar_core_tpu.scp.scp import SCP
    assert st == SCP.EnvelopeState.INVALID
    assert calls["n"] == first, "duplicate envelope was re-verified"
    sim.stop_all_nodes()


def test_chaos_duplicate_injection_shows_in_ratio():
    """ChaosTransport `overlay.duplicate` duplicates frames on the wire;
    the receiver detects them at the MAC layer, counts them into the
    duplication ratio, and keeps the link (consensus continues)."""
    sim, names = _peer_sim(2, 1, cfg_tweak=_tweak, chaos=True)
    sim.start_all_nodes()
    a = sim.nodes[names[0]].app
    b = sim.nodes[names[1]].app
    assert sim.crank_until(lambda: sim.have_all_externalized(2), 40000)

    a.faults.configure("overlay.duplicate", probability=1.0)
    tip = b.ledger_manager.last_closed_ledger_num()
    assert sim.crank_until(lambda: sim.have_all_externalized(tip + 3),
                           120000)
    m = b.metrics.to_json()
    assert m["overlay.recv.duplicate-frame"]["count"] > 0, \
        "injected duplicates were not detected"
    ov = b.overlay_manager.stats.to_json()["flood"]
    assert ov["duplicates"] > 0
    assert ov["duplication_ratio"] > 0
    # the link survived: the peer is still authenticated on both sides
    assert b.overlay_manager.get_peer(
        a.config.node_id().to_xdr()) is not None
    assert a.overlay_manager.get_peer(
        b.config.node_id().to_xdr()) is not None
    sim.stop_all_nodes()


def test_load_manager_counts_both_directions():
    """ISSUE 10 satellite: sent bytes are recorded per peer too, and the
    survey stats / fleet aggregate surface both totals."""
    sim, names = _peer_sim(2, 1, cfg_tweak=_tweak)
    sim.start_all_nodes()
    a = sim.nodes[names[0]].app
    assert sim.crank_until(lambda: sim.have_all_externalized(3), 60000)
    lm = a.overlay_manager.load_manager
    totals = lm.totals()
    assert totals["bytes_send"] > 0 and totals["bytes_recv"] > 0
    assert totals["msgs_send"] > 0 and totals["msgs_recv"] > 0
    costs = lm.get_json_info()
    assert any(c["bytes_send"] > 0 for c in costs.values())
    stats = a.overlay_manager.survey_manager.get_stats()
    assert stats["bytes_send"] == totals["bytes_send"]
    assert stats["bytes_recv"] == totals["bytes_recv"]
    # the fleet aggregate's survey block carries the same totals
    agg = sim.fleet()
    surveys = agg.fleet_stats()["survey"]
    assert any(s["bytes_send"] > 0 for s in surveys.values())
    sim.stop_all_nodes()
