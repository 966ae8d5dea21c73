"""The CPU rehearsal of `catchup-state13.standard-mix-1m`, beside
test_rehearsal_dex.py and through the same `rehearse` (runner.run_cell at
a tiny size, the line through emit's validator): control flow only, no
number from here is ever printed under a device metric's name.
"""

import importlib

import pytest

from benchmark import control
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest

from test_rehearsal import SLICE, rehearse

CELL = "catchup-state13.standard-mix-1m"
TINY = {"config": {"checkpoint_frequency": 8,
                   "state": {"accounts": 2000, "signer_accounts": 256}},
        "workload": {"traffic": {"txs_per_ledger": 20},
                     "negative_control_lanes": 64,
                     "warm_buckets": [32], "trace_slice": SLICE}}
ZERO = ("window_compiles", "header_mismatches", "state_mismatches",
        "store_mismatches", "sigs_not_on_device", "python_closes",
        "native_bails", "restarts_off_snapshot", "bucketdb_detached",
        "sql_fallbacks", "replayed_ledgers_off", "device_path_violations")


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = rehearse(CELL, trace, 6.0 if trace else 4.0, overrides=TINY)
    ln = res["line"]
    assert res["problems"] == []
    assert ln["correct"], ln["compared"]
    compared = {k: c["value"] for k, c in ln["compared"].items()}
    assert compared["full_replays"] >= 1
    for zero in ZERO:
        assert compared[zero] == 0, zero
    # 160 sources twice (the catchup driver's pass and this one's), the
    # destinations, the fee pool
    assert compared["state_checked"] > 2 * 160
    assert ln["attempted"] > 0 and ln["failed"] == 0
    counts = res["counts"]
    assert set(counts["buckets"]) == {"32"}
    assert counts["cold_prepare"] >= 160 * counts["replays_full"]
    assert counts["cold_close"] == \
        counts["cold_prefetch"] + counts["cold_apply"]
    if trace:
        assert 0 < ln["device"]["busy_s"] <= ln["device"]["window_s"]
        m = Manifest()
        assert set(ln["metrics"]) == set(m.expected_metrics(CELL, True))
        mine = {e["name"] for e in m.per_layer(CELL)}
        assert len(mine) == 16
        # at this size the prepare's reads leave nothing cold for the
        # closes, and the gate may never wait
        for name in mine - {"state.close_cold_reads_per_ledger.state",
                            "verify.device_wait_ms_per_ledger.state",
                            "verify.pad_share_pct.state"}:
            assert ln["metrics"][name]["value"] > 0.0, name
        assert ln["metrics"]["state.prepare_cold_reads_per_ledger.state"][
            "value"] == pytest.approx(
                counts["cold_prepare"] / counts["ledgers"])
        # a restart loads its sidecars inside its node.restore
        assert ln["metrics"]["bucketdb.index_load_ms.state"]["value"] < \
            ln["metrics"]["node.restore_ms.state"]["value"]


def test_every_metric_file_of_the_cell_names_a_reader_that_exists():
    m = Manifest()
    mine = m.per_layer(CELL)
    assert len(mine) == 16
    for e in mine:
        spec = m.metric_params(e["name"])
        assert spec["workloads"] == [CELL]
        assert spec["moves"] == "replay_ledgers_per_s"
        assert spec["layer"] == e["layer"] and spec["unit"] == e["unit"]
        mod = importlib.import_module("benchmark.readers." + spec["reader"])
        assert callable(mod.read)
    assert CELL in next(e for e in m.doc["end_to_end"]
                        if e["name"] == "replay_ledgers_per_s")["workloads"]


def test_a_big_seed_is_a_seed():
    res = rehearse(CELL, False, 4.0, seed=2 ** 31 + 98765, overrides=TINY)
    assert res["problems"] == [] and res["line"]["correct"]


def failed_numbers(res) -> set:
    return {k for k, c in res["line"]["compared"].items()
            if not runner._holds(c)}


def test_control_on_the_cpu_backend_is_not_correct():
    res = rehearse(CELL, False, 2.0, overrides=TINY,
                   **control.CONTROLS["cpu-backend"])
    assert not res["line"]["correct"]
    assert "device_path_violations" in failed_numbers(res)


@pytest.mark.parametrize("fault", ["accept-all", "half-batch"])
def test_planted_fault_is_not_correct(fault):
    res = rehearse(CELL, False, 4.0, overrides=TINY,
                   **dict(control.CONTROLS[fault]))
    assert not res["line"]["correct"]
    assert "sigs_not_on_device" in failed_numbers(res)


def test_a_tampered_archive_is_not_correct():
    res = rehearse(CELL, False, 6.0, overrides=TINY,
                   **control.CONTROLS["tampered-archive"])
    assert not res["line"]["correct"]
    assert "failed_replays" in failed_numbers(res)


def test_a_program_without_the_meters_fails_at_once(monkeypatch):
    from stellar_core_tpu.ledger.apply_stats import ApplyStats
    monkeypatch.delattr(ApplyStats, "reading")
    with pytest.raises(runner.RunError):
        rehearse(CELL, False, 1.0, overrides=TINY)
