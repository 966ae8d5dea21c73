"""Fleet observability tests (ISSUE 4 acceptance): 3-node simulation →
merged Chrome trace with one lane per node, per-slot fleet stats with
finite externalize skew and attributed flood latency, the Prometheus
exposition round-trip, and the bench.py multi-node `fleet` block.
"""

import json
import math
import re

import pytest

from stellar_core_tpu.simulation import topologies
from stellar_core_tpu.util.fleet import FleetAggregator
from stellar_core_tpu.util.metrics import (
    MetricsRegistry, prometheus_name, render_prometheus,
)

FIRST_SLOT, LAST_SLOT = 2, 11     # genesis is seq 1; 10 consensus closes


@pytest.fixture(scope="module")
def fleet_sim():
    sim = topologies.core(
        3, 2, cfg_tweak=lambda c: setattr(c, "TRACE_ENABLED", True))
    sim.start_all_nodes()
    ok = sim.crank_until(
        lambda: sim.have_all_externalized(LAST_SLOT), 200000)
    assert ok, {n: v.app.ledger_manager.last_closed_ledger_num()
                for n, v in sim.nodes.items()}
    yield sim
    sim.stop_all_nodes()


# ------------------------------------------------------- merged Chrome trace

def test_merged_trace_one_lane_per_node_externalize_clock_ordered(
        fleet_sim):
    """Acceptance (a): a merged Chrome trace with one process lane per
    node in which every node's externalize event for each slot is
    present and clock-ordered."""
    trace = fleet_sim.merged_chrome_trace()
    events = trace["traceEvents"]
    lanes = {ev["pid"]: ev["args"]["name"] for ev in events
             if ev.get("ph") == "M" and ev["name"] == "process_name"}
    assert len(lanes) == 3
    assert set(lanes.values()) == set(fleet_sim.nodes)
    for pid, name in lanes.items():
        exts = [ev for ev in events
                if ev["pid"] == pid and
                ev["name"] == "timeline.externalize"]
        by_slot = {ev["args"]["slot"]: ev["ts"] for ev in exts}
        for slot in range(FIRST_SLOT, LAST_SLOT + 1):
            assert slot in by_slot, (name, sorted(by_slot))
        ordered = [by_slot[s] for s in range(FIRST_SLOT, LAST_SLOT + 1)]
        assert ordered == sorted(ordered), name
        # the lane also carries the node's span ring (tracer was on)
        assert any(ev["pid"] == pid and ev["name"] == "ledger.close"
                   for ev in events), name
    json.dumps(trace)   # artifact must serialize


# ------------------------------------------------------------- fleet stats

def test_fleet_stats_skew_finite_and_flood_attributed(fleet_sim):
    """Acceptance (b): per-slot fleet stats where externalize skew is
    finite and flood-latency attribution names a sender."""
    stats = fleet_sim.fleet_stats()
    names = set(stats["nodes"])
    for slot in range(FIRST_SLOT, LAST_SLOT + 1):
        entry = stats["slots"][str(slot)]
        ext = entry["externalize"]
        assert ext["nodes"] == 3
        assert math.isfinite(ext["skew_s"]) and ext["skew_s"] >= 0.0
        assert ext["first"] in names and ext["straggler"] in names
        flood = entry["flood"]
        assert flood["first_sender"] in names     # attribution by name
        assert flood["latency_s"] >= 0.0
        assert entry["slot_latency_s"] >= ext["skew_s"]
    summary = stats["summary"]
    assert summary["slot_count"] >= 10
    assert 0.0 <= summary["slot_latency_p50_s"] \
        <= summary["slot_latency_p95_s"]
    assert math.isfinite(summary["externalize_skew_max_s"])
    assert sum(summary["stragglers"].values()) >= 10


def test_fleet_aggregator_resolves_sender_ids(fleet_sim):
    agg = fleet_sim.fleet()
    some_app = next(iter(fleet_sim.nodes.values())).app
    hexid = some_app.config.node_id().key_bytes.hex()
    assert agg.resolve(hexid) == some_app.config.node_name()
    assert agg.resolve(None) == "?"
    assert agg.resolve("ff" * 32) == "ff" * 4   # unknown -> hex prefix


def test_rebase_on_externalize_aligns_offset_node(fleet_sim):
    """Shifting one node's pc epoch (a different-host scrape) and
    rebasing recovers skew in the same order of magnitude."""
    agg = fleet_sim.fleet()
    before = agg.fleet_stats()["summary"]["externalize_skew_max_s"]
    # knock one node's clock 100s off
    victim = agg.nodes[0]
    for evs in victim["timeline"]["slots"].values():
        for ev in evs:
            ev["pc"] += 100.0
    skew_broken = agg.fleet_stats()["summary"]["externalize_skew_max_s"]
    assert skew_broken > 50.0
    assert agg.rebase_on_externalize()
    after = agg.fleet_stats()["summary"]["externalize_skew_max_s"]
    assert after < 1.0 and abs(after - before) < 1.0
    # aggregator with no common slot refuses
    empty = FleetAggregator()
    assert not empty.rebase_on_externalize()


def test_fleet_aggregator_against_live_http_node():
    """The aggregator also feeds from a live admin API (`add_http`):
    same node shape as `add_app`, so real deployments get the merged
    view without the simulation layer."""
    import threading

    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    cfg = Config.test_config(0)
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.TRACE_ENABLED = True
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()
    app.manual_close()
    port = app.command_handler.start_http(port=0)
    agg = FleetAggregator()
    done = []

    def fetch():
        agg.add_http("http://127.0.0.1:%d" % port)
        done.append(1)

    t = threading.Thread(target=fetch)
    t.start()
    app.crank_until(lambda: bool(done), max_cranks=500000)
    t.join(timeout=10)
    app.command_handler.stop_http()
    app.stop()
    assert done
    node = agg.nodes[0]
    assert node["name"] == app.config.node_name()
    assert node["node_id"] == app.config.node_id().key_bytes.hex()
    assert {"2", "3"} <= set(node["timeline"]["slots"])
    # survey stats arrive in the SAME compact shape add_app stores, so
    # fleet_stats()['survey'] consumers work against live nodes too
    # (+ the both-direction LoadManager bandwidth totals, ISSUE 10)
    assert set(node["survey"]) == {"running", "surveyed", "results",
                                   "backlog", "bad_responses",
                                   "bytes_send", "bytes_recv",
                                   "msgs_send", "msgs_recv"}
    trace = agg.merged_chrome_trace()
    assert any(ev["name"] == "timeline.externalize"
               for ev in trace["traceEvents"])
    stats = agg.fleet_stats()
    assert stats["slots"]["2"]["externalize"]["nodes"] == 1


# ------------------------------------------------------ prometheus round-trip

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$')


def parse_exposition(text):
    """Minimal Prometheus text-format parser: returns
    ({series_name: [(labels, value)]}, {series_name: type})."""
    samples, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            m = re.match(r"^# TYPE (\S+) (\S+)$", line)
            if m:
                assert m.group(1) not in types, \
                    "duplicate TYPE for %s" % m.group(1)
                types[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, "unparseable sample line: %r" % line
        labels = {}
        if m.group("labels"):
            for part in m.group("labels").split(","):
                k, v = part.split("=", 1)
                assert v.startswith('"') and v.endswith('"'), line
                labels[k] = v[1:-1]
        samples.setdefault(m.group("name"), []).append(
            (labels, float(m.group("value"))))
    return samples, types


def _clock():
    t = [0.0]

    def now():
        return t[0]
    now.advance = lambda dt: t.__setitem__(0, t[0] + dt)
    return now


def test_prometheus_round_trips_through_exposition_parser():
    clk = _clock()
    reg = MetricsRegistry(now_fn=clk)
    reg.new_counter("ledger.ledger.num").set_count(42)
    m = reg.new_meter("scp.envelope.receive")
    m.mark(7)
    t = reg.new_timer("ledger.ledger.close")
    for v in (0.1, 0.2, 0.3, 0.4):
        t.update(v)
    js = reg.to_json()
    text = render_prometheus(js)
    samples, types = parse_exposition(text)

    # every registry name surfaces under its mangled name
    assert samples[prometheus_name("ledger.ledger.num")][0][1] == 42.0
    assert types[prometheus_name("ledger.ledger.num")] == "gauge"

    meter = prometheus_name("scp.envelope.receive")
    assert samples[meter + "_total"][0][1] == 7.0
    assert types[meter + "_total"] == "counter"
    windows = {lbl["window"] for lbl, _ in samples[meter + "_rate"]}
    assert windows == {"1m", "5m", "15m"}

    timer = prometheus_name("ledger.ledger.close")
    assert types[timer] == "summary"
    by_q = {lbl["quantile"]: v for lbl, v in samples[timer]}
    assert set(by_q) == {"0.5", "0.75", "0.95", "0.99"}
    assert by_q["0.5"] == js["ledger.ledger.close"]["median"]
    assert by_q["0.95"] == js["ledger.ledger.close"]["p95"]
    assert samples[timer + "_count"][0][1] == 4.0
    assert samples[timer + "_sum"][0][1] == pytest.approx(1.0)
    assert samples[timer + "_min"][0][1] == pytest.approx(0.1)
    assert samples[timer + "_max"][0][1] == pytest.approx(0.4)


def test_prometheus_endpoint_serves_whole_registry(fleet_sim):
    """`metrics?format=prometheus` renders everything the JSON endpoint
    knows — registry AND the merged crypto-boundary extras — and
    round-trips through the parser (acceptance)."""
    app = next(iter(fleet_sim.nodes.values())).app
    import gc
    was = gc.isenabled()
    gc.disable()    # `runtime.gc.*` count the process's collections
    try:
        st, body = app.command_handler.handle_command(
            "metrics", {"format": "prometheus"})
        st2, js = app.command_handler.handle_command("metrics", {})
    finally:
        if was:
            gc.enable()
    assert st == 200 and st2 == 200 and isinstance(body, str)
    samples, types = parse_exposition(body)
    for name, m in js.items():
        base = prometheus_name(name)
        if m.get("type") == "meter":
            assert any((lbl == {} and v == float(m["count"]))
                       for lbl, v in samples[base + "_total"]), name
        elif m.get("type") in ("timer", "histogram"):
            assert samples[base + "_count"][0][1] == float(m["count"])
        elif m.get("type") == "gauge":
            # ISSUE 6: gauges (verifier cockpit) expose their value
            assert samples[base][0][1] == float(m["value"]), name
        else:
            assert samples[base][0][1] == float(m["count"]), name
    # filter + format compose
    st, crypto_only = app.command_handler.handle_command(
        "metrics", {"format": "prometheus", "filter": "crypto."})
    assert st == 200
    s2, _ = parse_exposition(crypto_only)
    assert all(n.startswith("sct_crypto_") for n in s2)


def test_prometheus_exposition_is_fully_typed_and_helped(fleet_sim):
    """0.0.4 compliance satellite (ISSUE 17): every emitted series
    carries a `# TYPE` line with a `# HELP` line for the same series —
    no orphan samples — and the propagation cockpit's dynamic
    `overlay.prop.*` names ride along like every eagerly-registered
    metric."""
    app = next(iter(fleet_sim.nodes.values())).app
    st, body = app.command_handler.handle_command(
        "metrics", {"format": "prometheus"})
    assert st == 200
    lines = body.splitlines()
    helped = {l.split()[2] for l in lines if l.startswith("# HELP ")}
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE ")}
    assert typed == helped, typed ^ helped
    samples, types = parse_exposition(body)
    for name in samples:
        if name in types:
            assert types[name] in ("counter", "gauge", "summary"), name
            continue
        # _count/_sum are implicit members of their summary family
        base = next((name[:-len(s)] for s in ("_count", "_sum")
                     if name.endswith(s)), name)
        assert types.get(base) == "summary", \
            "sample series %s has no # TYPE" % name
    # counters end in _total per the exposition-format convention
    for name, t in types.items():
        if t == "counter":
            assert name.endswith("_total"), name
    prop = {n for n in samples if n.startswith("sct_overlay_prop_")}
    assert {"sct_overlay_prop_edge_first_total",
            "sct_overlay_prop_edge_duplicate_total",
            "sct_overlay_prop_wasted_bytes",
            "sct_overlay_prop_pruned_total",
            "sct_overlay_prop_hashes",
            "sct_overlay_prop_usefulness_worst"} <= prop


def test_prometheus_name_mangling_rules():
    assert prometheus_name("ledger.ledger.close") == \
        "sct_ledger_ledger_close"
    assert prometheus_name("herder.pending-ops.count") == \
        "sct_herder_pending_ops_count"
    assert prometheus_name("UPPER.Case") == "sct_upper_case"
    assert prometheus_name("9lives") == "sct__9lives"
    out = render_prometheus({"a.b": {"count": 1}, "a-b": {"count": 2}})
    assert out.count("# TYPE sct_a_b gauge") == 1
    assert "# collision:" in out


# --------------------------------------------------------- bench fleet block

def test_bench_multi_node_leg_emits_fleet_block():
    """Acceptance: the bench.py multi-node leg emits the `fleet` block
    with slot-latency p50/p95."""
    import bench
    out = bench.fleet_bench(n_nodes=3, n_ledgers=10)
    assert out["converged"] and out["ledgers_closed"] >= 10
    fleet = out["fleet"]
    assert fleet["slot_count"] >= 10
    for k in ("slot_latency_p50_ms", "slot_latency_p95_ms",
              "externalize_skew_p50_ms", "externalize_skew_max_ms"):
        assert math.isfinite(fleet[k]) and fleet[k] >= 0.0
    assert fleet["slot_latency_p50_ms"] <= fleet["slot_latency_p95_ms"]
    json.dumps(out)   # BENCH artifact line must serialize
