"""The CPU rehearsal of `catchup-dex13.maker-taker`, beside
test_rehearsal.py and through the same `rehearse` (runner.run_cell at a
tiny size, the line through emit's validator): control flow only, no
number from here is ever printed under a device metric's name.
"""

import importlib

import pytest

from benchmark import control
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest

from test_rehearsal import SLICE, rehearse

CELL = "catchup-dex13.maker-taker"
TINY = {"config": {"checkpoint_frequency": 8,
                   "state": {"pairs": 2, "offers_per_side": 60,
                             "levels": 20, "makers": 4, "takers": 4,
                             "payers": 4}},
        "workload": {"traffic": {"maker_txs": 4, "taker_txs": 4,
                                 "payment_txs": 4, "checkpoints": 2},
                     "negative_control_lanes": 64,
                     "warm_buckets": [32], "trace_slice": SLICE}}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = rehearse(CELL, trace, 6.0 if trace else 2.0, overrides=TINY)
    ln = res["line"]
    assert res["problems"] == []
    assert ln["correct"], ln["compared"]
    compared = {k: c["value"] for k, c in ln["compared"].items()}
    assert compared["full_replays"] >= 1
    for zero in ("window_compiles", "header_mismatches", "state_mismatches",
                 "sigs_not_on_device", "python_closes", "native_bails",
                 "dynamic_close_mismatches", "device_path_violations"):
        assert compared[zero] == 0, zero
    # 12 accounts of the model, 2 issuers, 4 book sides at the least
    assert compared["state_checked"] >= 12 + 2 + 4
    assert ln["attempted"] > 0 and ln["failed"] == 0
    counts = res["counts"]
    assert set(counts["buckets"]) == {"32"}
    assert counts["book_rows"] > 0 and counts["book_loads"] > 0
    assert 0 < counts["dynamic_closes"] < counts["closes"]
    if trace:
        assert 0 < ln["device"]["busy_s"] <= ln["device"]["window_s"]
        m = Manifest()
        assert set(ln["metrics"]) == set(m.expected_metrics(CELL, True))
        mine = {e["name"] for e in m.per_layer(CELL)}
        assert len(mine) == 16
        for name in mine - {"verify.device_ahead_ms_per_ledger.dex",
                            "verify.device_wait_ms_per_ledger.dex",
                            "verify.pad_share_pct.dex"}:
            assert ln["metrics"][name]["value"] > 0.0, name
        share = ln["metrics"]["close.dynamic_share_pct.dex"]["value"]
        assert share == pytest.approx(
            100.0 * counts["dynamic_closes"] / counts["closes"])
        # the 4 sides of 60 offers, loaded whole by every dense close
        assert 150 < ln["metrics"][
            "close.book_rows_per_ledger.dex"]["value"] <= 240


def test_every_metric_file_of_the_cell_names_a_reader_that_exists():
    m = Manifest()
    mine = m.per_layer(CELL)
    assert len(mine) == 16
    for e in mine:
        spec = m.metric_params(e["name"])
        assert spec["workloads"] == [CELL]
        assert spec["moves"] == "replay_ledgers_per_s"
        mod = importlib.import_module("benchmark.readers." + spec["reader"])
        assert callable(mod.read)


def test_a_big_seed_is_a_seed():
    res = rehearse(CELL, False, 2.0, seed=2 ** 31 + 98765, overrides=TINY)
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
    res = rehearse(CELL, False, 2.0, overrides=TINY,
                   **dict(control.CONTROLS[fault]))
    assert not res["line"]["correct"]
    assert "sigs_not_on_device" in failed_numbers(res)


def test_a_program_without_the_counters_fails_at_once(monkeypatch):
    from stellar_core_tpu.ledger.apply_stats import ApplyStats
    monkeypatch.delattr(ApplyStats, "record_book_load")
    with pytest.raises(runner.RunError):
        rehearse(CELL, False, 1.0, overrides=TINY)
