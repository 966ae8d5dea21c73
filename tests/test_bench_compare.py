"""Perf-regression ledger tests (ISSUE 6): schema validation of every
committed bench artifact, deterministic ingest into bench/history.jsonl,
the direction-aware comparator, and the `bench.py --compare` gate driven
end to end with a tiny deterministic CPU replay leg against synthetic
baselines (the acceptance criterion: nonzero on an injected regression,
zero on a clean run).
"""

import copy
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import bench_compare as bc          # noqa: E402

HISTORY = os.path.join(REPO, "bench", "history.jsonl")


# ------------------------------------------------------------ committed set

def test_committed_artifacts_pass_schema_check():
    """tools/bench_compare.py --check over every committed BENCH_*.json,
    MULTICHIP_*.json and bench/history.jsonl: malformed bench artifacts
    must fail fast instead of silently dropping out of the trajectory."""
    paths = bc.default_artifacts()
    assert len(paths) >= 19, paths          # 15 BENCH + 4 MULTICHIP
    errors = []
    for p in paths + [HISTORY]:
        errors.extend(bc.check_artifact(p))
    assert not errors, errors
    # the CLI agrees (the tier-1 invocation named in ISSUE 6)
    assert bc.main(["--check"]) == 0


def test_history_matches_fresh_reingest():
    """bench/history.jsonl is exactly what ingest produces from the
    committed artifacts — the committed ledger can never drift from its
    sources."""
    fresh = bc.ingest(bc.default_artifacts())
    committed = bc.load_history(HISTORY)
    assert fresh == committed


def test_history_covers_the_headline_metrics():
    best = bc.best_baselines(bc.load_history(HISTORY))
    # device verify headline (129k sigs/s, BENCH_r05 cached block)
    dev = best[("ed25519_verifies_per_sec_per_chip", "tpu")]
    assert dev["value"] > 100_000
    assert best[("replay_ledgers_per_sec", "tpu")]["value"] > 0
    assert best[("native_apply_speedup", "cpu")]["value"] > 4
    assert best[("multichip_devices", "cpu-virtual")]["value"] >= 8
    # direction-aware best: the lowest committed warm-compile time wins
    warm = best[("device_compile_warm_s", "tpu")]
    assert warm["direction"] == "lower"


def test_malformed_artifacts_fail_check(tmp_path):
    bad_json = tmp_path / "BENCH_r99.json"
    bad_json.write_text("{not json")
    assert bc.check_artifact(str(bad_json))

    bad_payload = tmp_path / "BENCH_r98.json"
    bad_payload.write_text(json.dumps(
        {"metric": 5, "unit": "sigs/s", "value": "fast"}))
    errs = bc.check_artifact(str(bad_payload))
    assert any("'metric'" in e for e in errs)
    assert any("'value'" in e for e in errs)

    bad_multichip = tmp_path / "MULTICHIP_r99.json"
    bad_multichip.write_text(json.dumps({"n_devices": "eight", "rc": 0,
                                         "ok": True, "skipped": False}))
    assert any("n_devices" in e
               for e in bc.check_artifact(str(bad_multichip)))

    # rc=0 wrapper with no parsed payload is malformed; rc!=0 is a
    # valid record of a failed run
    wrapper = {"n": 1, "cmd": "x", "rc": 0, "tail": ""}
    w = tmp_path / "BENCH_r97.json"
    w.write_text(json.dumps(wrapper))
    assert bc.check_artifact(str(w))
    wrapper["rc"] = 124
    w.write_text(json.dumps(wrapper))
    assert not bc.check_artifact(str(w))

    bad_hist = tmp_path / "history.jsonl"
    bad_hist.write_text(json.dumps({"metric": "m", "unit": "u",
                                    "value": 1.0, "platform": "p",
                                    "direction": "sideways",
                                    "source": "s"}) + "\n{oops\n")
    errs = bc.check_artifact(str(bad_hist))
    assert any("direction" in e for e in errs)
    assert any("bad JSON" in e for e in errs)


# --------------------------------------------------- overlay_breakdown

def _good_overlay_breakdown():
    return {
        "recv_bytes": 1000, "send_bytes": 900,
        "recv_msgs": 10, "send_msgs": 9,
        "flood": {"unique": 10, "duplicates": 5,
                  "duplication_ratio": 0.5},
        "tx_latency_ms": {"count": 3, "p50": 100.0, "p95": 200.0},
        "stage_seconds": {"submit-to-queue": 0.1,
                          "queue-to-include": 0.2,
                          "include-to-externalize": 0.3,
                          "externalize-to-apply": 0.4},
        "total_seconds": 1.0,
        "outcomes": {"applied": 3},
    }


def test_overlay_breakdown_validates_and_normalizes():
    ob = _good_overlay_breakdown()
    assert bc.validate_overlay_breakdown(ob, "t") == []
    recs = bc.overlay_breakdown_records(ob, "scenario-flood", "src")
    by = {r["metric"]: r for r in recs}
    assert by["flood_duplication_ratio"]["value"] == 0.5
    assert by["flood_duplication_ratio"]["direction"] == "lower"
    assert by["tx_latency_total_p95_ms"]["value"] == 200.0
    assert by["tx_latency_total_p95_ms"]["direction"] == "lower"
    for r in recs:
        assert bc.validate_record(r, "t") == []


def test_overlay_breakdown_idle_run_emits_no_latency_records():
    """A 0-count run must never commit a 0-valued latency baseline (any
    later real latency would then gate as a regression forever)."""
    ob = _good_overlay_breakdown()
    ob["tx_latency_ms"] = {"count": 0, "p50": 0.0, "p95": 0.0}
    ob["flood"] = {"unique": 0, "duplicates": 0,
                   "duplication_ratio": 0.0}
    assert bc.validate_overlay_breakdown(ob, "t") == []
    assert bc.overlay_breakdown_records(ob, "p", "src") == []


def test_fleet_payload_overlay_breakdown_normalizes():
    """A `bench.py --fleet` payload carries its overlay_breakdown at
    the payload level (no embedded records list) — records_from_bench
    must derive the wire-cockpit records under the payload's stable
    platform key."""
    blob = {"metric": "fleet_slot_latency", "unit": "ms",
            "platform": "fleet-sim", "nodes": 3,
            "overlay_breakdown": _good_overlay_breakdown()}
    recs = bc.records_from_bench(blob, "BENCH_r99.json")
    by = {r["metric"]: r for r in recs}
    assert by["flood_duplication_ratio"]["platform"] == "fleet-sim"
    assert by["tx_latency_total_p95_ms"]["platform"] == "fleet-sim"
    assert all(r["direction"] == "lower" for r in recs)


# --------------------------------------------------- fleet_verify

def _good_fleet_verify():
    return {
        "1": {"devices": 1, "fleet_sigs_per_s": 480.0,
              "per_device_sigs_per_s": 480.0, "warm_restart_s": 2.5},
        "4": {"devices": 4, "fleet_sigs_per_s": 1000.0,
              "per_device_sigs_per_s": 250.0, "warm_restart_s": 3.1},
    }


def test_fleet_verify_validates_and_normalizes():
    fv = _good_fleet_verify()
    assert bc.validate_fleet_verify(fv, "t") == []
    recs = bc.fleet_verify_records(fv, "src")
    by = {(r["metric"], r["platform"]): r for r in recs}
    assert by[("fleet_sigs_per_s", "verify-fleet-cpu4")]["value"] == 1000.0
    assert by[("fleet_sigs_per_s", "verify-fleet-cpu4")]["direction"] == \
        "higher"
    assert by[("per_device_sigs_per_s", "verify-fleet-cpu1")]["value"] == \
        480.0
    assert by[("warm_restart_s", "verify-fleet-cpu4")]["direction"] == \
        "lower"
    assert len(recs) == 6
    for r in recs:
        assert bc.validate_record(r, "t") == []


def test_fleet_verify_schema_violations_fail_check():
    fv = _good_fleet_verify()
    fv["4"]["per_device_sigs_per_s"] = 900.0     # != fleet/devices
    errs = bc.validate_fleet_verify(fv, "t")
    assert any("inconsistent" in e for e in errs)
    fv = _good_fleet_verify()
    fv["4"]["devices"] = 2                       # key/devices mismatch
    assert any("matching its key" in e
               for e in bc.validate_fleet_verify(fv, "t"))
    fv = _good_fleet_verify()
    fv["1"]["warm_restart_s"] = -1
    assert any("warm_restart_s" in e
               for e in bc.validate_fleet_verify(fv, "t"))
    fv = _good_fleet_verify()
    fv["1"]["fleet_sigs_per_s"] = 0
    assert any("fleet_sigs_per_s" in e
               for e in bc.validate_fleet_verify(fv, "t"))


def test_fleet_verify_payload_normalizes_and_checks(tmp_path):
    """A `bench.py --fleet-verify` artifact (payload-level fleet_verify
    block + fleet_speedup) derives per-device-count records through
    records_from_bench, and check_artifact enforces the block schema."""
    import json
    blob = {"metric": "fleet_verify_sigs_per_s", "unit": "sigs/s",
            "value": 1000.0, "platform": "verify-fleet-cpu",
            "fleet_speedup": 2.08, "fleet_verify": _good_fleet_verify()}
    recs = bc.records_from_bench(blob, "BENCH_r99.json")
    by = {(r["metric"], r["platform"]): r for r in recs}
    assert ("fleet_sigs_per_s", "verify-fleet-cpu1") in by
    assert by[("fleet_verify_speedup", "verify-fleet-cpu")]["value"] == \
        2.08
    p = tmp_path / "BENCH_r99.json"
    p.write_text(json.dumps(blob))
    assert bc.check_artifact(str(p)) == []
    blob["fleet_verify"]["4"]["fleet_sigs_per_s"] = None
    p.write_text(json.dumps(blob))
    assert any("fleet_sigs_per_s" in e for e in bc.check_artifact(str(p)))


def test_overlay_breakdown_sum_contract_enforced(tmp_path):
    ob = _good_overlay_breakdown()
    ob["stage_seconds"]["queue-to-include"] = 5.0    # no longer sums
    errs = bc.validate_overlay_breakdown(ob, "t")
    assert any("no longer accounts" in e for e in errs)
    # ratio inconsistency is caught too
    ob2 = _good_overlay_breakdown()
    ob2["flood"]["duplication_ratio"] = 0.9
    assert any("inconsistent" in e
               for e in bc.validate_overlay_breakdown(ob2, "t"))
    # and the walk finds a breakdown nested inside a scenario artifact
    bad = tmp_path / "BENCH_r96.json"
    bad.write_text(json.dumps({"metric": "m", "unit": "u", "value": 1.0,
                               "scenarios": {"flood": {
                                   "overlay_breakdown": ob}}}))
    assert any("no longer accounts" in e
               for e in bc.check_artifact(str(bad)))


def _good_bucketdb():
    return {
        "small": {"accounts": 10**4, "close_ms_p50": 50.0,
                  "close_ms_mean": 52.0},
        "large": {"accounts": 10**6, "close_ms_p50": 55.0,
                  "close_ms_mean": 57.0},
        "latency_ratio": 1.1,
        "prefetch_hit_rate_pct": 99.5,
        "bloom_fp_pct": 1.2,
        "sql_point_lookups": 0,
    }


def test_bucketdb_block_normalizes_and_checks(tmp_path):
    """A `bench.py --bucketdb` artifact (ISSUE 14) derives the
    direction-aware flatness/hit-rate/FP records, and check_artifact
    enforces the block's own acceptance gates."""
    import json
    blob = {"metric": "bucketdb_latency_ratio", "unit": "x",
            "value": 1.1, "platform": "bucketdb-cpu",
            "bucketdb_bench": _good_bucketdb()}
    recs = bc.records_from_bench(blob, "BENCH_r98.json")
    by = {r["metric"]: r for r in recs}
    assert by["bucketdb_latency_ratio"]["direction"] == "lower"
    assert by["bucketdb_prefetch_hit_rate_pct"]["direction"] == "higher"
    assert by["bucketdb_bloom_fp_pct"]["direction"] == "lower"
    assert by["bucketdb_close_large_p50_ms"]["value"] == 55.0
    p = tmp_path / "BENCH_r98.json"
    p.write_text(json.dumps(blob))
    assert bc.check_artifact(str(p)) == []


def test_validate_bucketdb_enforces_the_gates():
    # ratio must match the legs AND stay under the 1.25x gate
    bd = _good_bucketdb()
    bd["latency_ratio"] = 0.5
    assert any("!= large/small" in e for e in bc.validate_bucketdb(bd, "t"))
    bd = _good_bucketdb()
    bd["large"]["close_ms_p50"] = 100.0
    bd["latency_ratio"] = 2.0
    assert any("1.25x" in e for e in bc.validate_bucketdb(bd, "t"))
    # the zero-SQL gate: a leaked point lookup fails the artifact
    bd = _good_bucketdb()
    bd["sql_point_lookups"] = 3
    assert any("sql_point_lookups" in e
               for e in bc.validate_bucketdb(bd, "t"))
    # prefetch hit-rate and bloom FP bands
    bd = _good_bucketdb()
    bd["prefetch_hit_rate_pct"] = 80.0
    assert any("prefetch_hit_rate_pct" in e
               for e in bc.validate_bucketdb(bd, "t"))
    bd = _good_bucketdb()
    bd["bloom_fp_pct"] = 9.0
    assert any("bloom_fp_pct" in e for e in bc.validate_bucketdb(bd, "t"))
    # scale ordering
    bd = _good_bucketdb()
    bd["large"]["accounts"] = 10**3
    assert any("must exceed" in e for e in bc.validate_bucketdb(bd, "t"))
    assert bc.validate_bucketdb(_good_bucketdb(), "t") == []


def test_committed_bucketdb_artifact_meets_its_gates():
    """The committed BENCH_r13 artifact must pass its own acceptance
    gates (validate_bucketdb runs in check over every committed
    artifact; this pins the r13 headline numbers directly)."""
    import json
    import os
    path = os.path.join(os.path.dirname(bc.__file__), os.pardir,
                        "BENCH_r13_bucketdb.json")
    blob = json.load(open(path))
    bd = blob["bucketdb_bench"]
    assert bc.validate_bucketdb(bd, "r13") == []
    assert bd["latency_ratio"] <= 1.25
    assert bd["prefetch_hit_rate_pct"] >= 95.0
    assert bd["sql_point_lookups"] == 0
    assert bd["large"]["accounts"] == 10**6


# ------------------------------------------------------------- ingress

def _good_ingress():
    return {
        "oversubscription": 6.9,
        "decided": 800, "admitted": 160, "throttled": 520, "shed": 120,
        "shed_ratio": 120 / 800,
        "priority": {"submitted": 48, "applied": 46,
                     "goodput": 46 / 48},
        "intake": {"depth": 3, "cap": 24},
        "sources": {"tracked": 512, "cap": 4096},
        "outcomes": {"applied": 50, "rejected": 10,
                     "shed": 120, "throttled": 520},
        "tx_latency_p95_ms": 4000.0, "unloaded_p95_ms": 6000.0,
        "p95_ratio": 4000.0 / 6000.0,
    }


def test_ingress_block_validates_and_normalizes():
    """An `overload` scenario ingress block (ISSUE 18) passes the
    schema gate and derives the four direction-aware records."""
    ib = _good_ingress()
    assert bc.validate_ingress(ib, "t") == []
    recs = bc.ingress_records(ib, "scenario-overload", "src")
    by = {r["metric"]: r for r in recs}
    assert by["ingress_priority_goodput"]["direction"] == "higher"
    assert by["ingress_priority_goodput"]["value"] == pytest.approx(46 / 48)
    assert by["ingress_shed_ratio"]["direction"] == "higher"
    assert by["ingress_tx_latency_p95_ms"]["direction"] == "lower"
    assert by["ingress_p95_vs_unloaded_ratio"]["direction"] == "lower"
    assert by["ingress_p95_vs_unloaded_ratio"]["value"] == \
        pytest.approx(2 / 3)
    for r in recs:
        assert bc.validate_record(r, "t") == []
    # an idle/empty block emits nothing (never commit a 0-baseline)
    assert bc.ingress_records({"decided": 0}, "p", "s") == []


def test_validate_ingress_enforces_the_gates():
    # decision counters must reconcile
    ib = _good_ingress()
    ib["admitted"] = 200
    assert any("admitted+throttled+shed" in e
               for e in bc.validate_ingress(ib, "t"))
    # shed_ratio must be shed/decided
    ib = _good_ingress()
    ib["shed_ratio"] = 0.5
    assert any("shed/decided" in e for e in bc.validate_ingress(ib, "t"))
    # goodput must be applied/submitted, applied <= submitted
    ib = _good_ingress()
    ib["priority"]["goodput"] = 0.1
    assert any("applied/submitted" in e
               for e in bc.validate_ingress(ib, "t"))
    ib = _good_ingress()
    ib["priority"]["applied"] = 99
    assert any("applied <= submitted" in e
               for e in bc.validate_ingress(ib, "t"))
    # p95 ratio must be its own numerator/denominator
    ib = _good_ingress()
    ib["p95_ratio"] = 3.0
    assert any("p95/unloaded" in e for e in bc.validate_ingress(ib, "t"))
    # the bounded-memory gate travels with the artifact
    ib = _good_ingress()
    ib["intake"]["depth"] = 100
    assert any("exceeds its cap" in e for e in bc.validate_ingress(ib, "t"))
    ib = _good_ingress()
    ib["sources"]["tracked"] = 10**6
    assert any("exceeds its cap" in e for e in bc.validate_ingress(ib, "t"))
    # the funnel can never report more sheds than the tier decided
    ib = _good_ingress()
    ib["outcomes"]["shed"] = 10**6
    assert any("exceeds the ingress" in e
               for e in bc.validate_ingress(ib, "t"))
    assert bc.validate_ingress(_good_ingress(), "t") == []


def test_check_artifact_walks_ingress_blocks(tmp_path):
    """`check` rejects a committed artifact whose ingress block violates
    the boundedness gate — the schema travels with the file."""
    blob = {"metric": "scenario_overload", "unit": "count", "value": 1.0,
            "platform": "scenario-overload", "ingress": _good_ingress()}
    p = tmp_path / "BENCH_r97.json"
    p.write_text(json.dumps(blob))
    assert bc.check_artifact(str(p)) == []
    blob["ingress"]["intake"]["depth"] = 999
    p.write_text(json.dumps(blob))
    assert any("exceeds its cap" in e for e in bc.check_artifact(str(p)))


# ------------------------------------------------------------ comparator

def _rec(metric, value, platform="p", direction="higher", **kw):
    return bc.make_record(metric, "u", value, platform, direction,
                          "test", **kw)


def test_compare_is_direction_aware():
    history = [_rec("rate", 100.0), _rec("rate", 80.0),
               _rec("lat", 10.0, direction="lower"),
               _rec("lat", 25.0, direction="lower")]
    # best = rate 100 (higher), lat 10 (lower)
    current = [_rec("rate", 95.0), _rec("lat", 10.5)]
    current[1]["direction"] = "lower"
    report = bc.compare(current, history, tolerance=0.1)
    assert not report["regressions"]
    assert len(report["ok"]) == 2

    report = bc.compare([_rec("rate", 89.0)], history, tolerance=0.1)
    assert len(report["regressions"]) == 1
    assert report["regressions"][0]["best"] == 100.0

    bad_lat = _rec("lat", 11.5, direction="lower")
    report = bc.compare([bad_lat], history, tolerance=0.1)
    assert len(report["regressions"]) == 1

    # a better-than-best run is an improvement, never a regression
    report = bc.compare([_rec("rate", 140.0)], history, tolerance=0.1)
    assert report["improvements"] and not report["regressions"]

    # unknown (metric, platform) pairs never gate
    report = bc.compare([_rec("rate", 1.0, platform="other")], history)
    assert report["new"] and not report["regressions"]


def test_compare_platform_keys_baselines_apart():
    history = [_rec("replay_ledgers_per_sec", 3.34, platform="tpu")]
    tiny = _rec("replay_ledgers_per_sec", 90.0, platform="cpu-tiny")
    report = bc.compare([tiny], history)
    assert report["new"] and not report["regressions"]


# --------------------------------------------- end-to-end gate (acceptance)

@pytest.fixture(scope="module")
def tiny_leg_records():
    """ONE tiny deterministic CPU replay leg, shared by the gate tests
    below (seeded content; seconds, not minutes)."""
    import bench
    return bench.compare_leg()


def test_tiny_leg_records_validate(tiny_leg_records):
    # 5 classic records + the close-cockpit apply records (ISSUE 9):
    # apply_wall_s, one apply_op_<type>_ms per op type seen, apply_other_ms
    assert len(tiny_leg_records) >= 8
    for rec in tiny_leg_records:
        assert not bc.validate_record(rec), rec
    assert {r["platform"] for r in tiny_leg_records} == \
        {"cpu-tiny", "openssl-cpu-tiny"}
    by_metric = {r["metric"]: r for r in tiny_leg_records}
    assert by_metric["replay_ledgers_per_sec"]["value"] > 0
    assert by_metric["replay_wall_s"]["direction"] == "lower"
    assert by_metric["apply_wall_s"]["direction"] == "lower"
    assert by_metric["apply_op_payment_ms"]["value"] > 0
    assert by_metric["apply_other_ms"]["platform"] == "cpu-tiny"


def _write_history(path, records):
    with open(path, "w") as fh:
        for rec in records:
            fh.write(json.dumps(rec, sort_keys=True) + "\n")


def _synthetic_baseline(records, regress=False):
    """Baselines from the measured tiny-leg values: equal to current for
    a clean run; absurdly better than current (x100 / /100) to inject a
    synthetic regression no real container could beat."""
    base = copy.deepcopy(records)
    for rec in base:
        rec["source"] = "synthetic-baseline"
        if regress:
            rec["value"] = (rec["value"] * 100.0
                            if rec["direction"] == "higher"
                            else rec["value"] / 100.0)
    return base


def test_compare_gate_clean_and_regressed_inprocess(
        tiny_leg_records, tmp_path, capsys):
    import bench
    cur = tmp_path / "current.json"
    cur.write_text(json.dumps({"records": tiny_leg_records}))

    n = len(tiny_leg_records)
    clean = tmp_path / "clean.jsonl"
    _write_history(str(clean), _synthetic_baseline(tiny_leg_records))
    rc = bench.compare_main(["--compare", "--input", str(cur),
                             "--history", str(clean)])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0, report
    assert not report["regressions"]
    assert len(report["ok"]) + len(report["improvements"]) == n

    regressed = tmp_path / "regressed.jsonl"
    _write_history(str(regressed),
                   _synthetic_baseline(tiny_leg_records, regress=True))
    rc = bench.compare_main(["--compare", "--input", str(cur),
                             "--history", str(regressed)])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    # every nonzero-valued record loses to its absurd synthetic best
    # (a zero-valued per-op total cannot regress against base 0)
    want = sum(1 for r in tiny_leg_records if r["value"] > 0)
    assert len(report["regressions"]) == want
    # every regression names the synthetic best it lost to
    assert all(r["best_source"] == "synthetic-baseline"
               for r in report["regressions"])


def test_compare_gate_record_appends_stamped_records(
        tiny_leg_records, tmp_path, capsys):
    import bench
    cur = tmp_path / "current.json"
    cur.write_text(json.dumps({"records": tiny_leg_records}))
    hist = tmp_path / "history.jsonl"
    _write_history(str(hist), _synthetic_baseline(tiny_leg_records))
    rc = bench.compare_main(["--compare", "--record",
                             "--input", str(cur),
                             "--history", str(hist)])
    capsys.readouterr()
    assert rc == 0
    n = len(tiny_leg_records)
    recs = bc.load_history(str(hist))
    assert len(recs) == 2 * n
    appended = recs[n:]
    for rec in appended:
        assert not bc.validate_record(rec), rec
        assert rec["at_unix"] is not None
    # the recorded run is now the baseline the next run gates against
    best = bc.best_baselines(recs)
    assert best[("replay_ledgers_per_sec", "cpu-tiny")]["value"] == \
        next(r["value"] for r in tiny_leg_records
             if r["metric"] == "replay_ledgers_per_sec")


def test_compare_gate_cli_exit_codes(tiny_leg_records, tmp_path):
    """The real `bench.py --compare` CLI exits 0 on a clean run and
    nonzero on an injected synthetic regression (acceptance
    criterion), via actual subprocess exit codes."""
    cur = tmp_path / "current.json"
    cur.write_text(json.dumps({"records": tiny_leg_records}))
    clean = tmp_path / "clean.jsonl"
    _write_history(str(clean), _synthetic_baseline(tiny_leg_records))
    regressed = tmp_path / "regressed.jsonl"
    _write_history(str(regressed),
                   _synthetic_baseline(tiny_leg_records, regress=True))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for hist, want_rc in ((clean, 0), (regressed, 1)):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--compare",
             "--input", str(cur), "--history", str(hist)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=240)
        assert proc.returncode == want_rc, \
            (hist, proc.returncode, proc.stdout[-500:],
             proc.stderr[-500:])
        report = json.loads(proc.stdout)
        assert ("regressions" in report and
                bool(report["regressions"]) == bool(want_rc))
