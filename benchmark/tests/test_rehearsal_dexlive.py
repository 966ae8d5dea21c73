"""The CPU rehearsal of `validator-dex3.maker-taker`, beside
test_rehearsal.py and through the same `rehearse` (runner.run_cell at a
tiny size, the line through emit's validator): control flow only, no
number from here is ever printed under a device metric's name.
"""

import glob
import importlib
import os

import pytest

from benchmark import control
from benchmark.harness import runner
from benchmark.harness.manifest import BENCH_DIR, Manifest

from test_rehearsal import SLICE, rehearse

CELL = "validator-dex3.maker-taker"
TINY = {"config": {"accounts": 14,
                   "state": {"pairs": 2, "offers_per_side": 50,
                             "levels": 20, "makers": 4, "takers": 4,
                             "payers": 4}},
        "workload": {"traffic": {"clients": 12, "corrupt_every": 5},
                     "warm_buckets": [32], "trace_slice": SLICE}}


def mine() -> set:
    """The cell's per-layer metrics, as its metric files name them."""
    return {os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(BENCH_DIR, "metrics", "*.dexlive.json"))}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = rehearse(CELL, trace, 6.0 if trace else 3.0, overrides=TINY)
    ln = res["line"]
    assert res["problems"] == []
    assert ln["correct"], ln["compared"]
    compared = {k: c["value"] for k, c in ln["compared"].items()}
    for zero in ("window_compiles", "header_mismatches", "state_mismatches",
                 "book_mismatches", "python_closes", "native_bails",
                 "dynamic_close_mismatches", "requote_failures",
                 "failed_txs", "model_faults", "device_path_violations",
                 "corrupt_not_refused", "valid_refused",
                 "admitted_never_applied"):
        assert compared[zero] == 0, zero
    assert compared["requotes_checked"] > 0
    assert ln["attempted"] > 0 and ln["failed"] == 0
    counts = res["counts"]
    assert set(counts["buckets"]) == {"32"}
    assert counts["txset_checks"] > counts["ledgers"] > 0
    assert 0 < counts["dynamic_closes"] <= counts["closes"]
    if trace:
        assert 0 < ln["device"]["busy_s"] <= ln["device"]["window_s"]
        m = Manifest()
        assert set(ln["metrics"]) == set(m.expected_metrics(CELL, True))
        assert {e["name"] for e in m.per_layer(CELL)} == mine()
        for name in mine():
            assert ln["metrics"][name]["value"] > 0.0, name
        share = ln["metrics"]["close.dynamic_share_pct.dexlive"]["value"]
        assert share == pytest.approx(
            100.0 * counts["dynamic_closes"] / counts["closes"])
        per_slot = ln["metrics"]["herder.txset_checks_per_slot.dexlive"]
        assert per_slot["value"] == pytest.approx(
            counts["txset_checks"] / counts["ledgers"])


def test_every_metric_file_of_the_cell_names_a_reader_that_exists():
    m = Manifest()
    assert {e["name"] for e in m.per_layer(CELL)} == mine()
    for name in mine():
        spec = m.metric_params(name)
        assert spec["workloads"] == [CELL]
        assert spec["moves"] == "applied_tx_per_s"
        mod = importlib.import_module("benchmark.readers." + spec["reader"])
        assert callable(mod.read)


def test_a_big_seed_is_a_seed():
    res = rehearse(CELL, False, 2.0, seed=2 ** 31 + 24680, overrides=TINY)
    assert res["problems"] == [] and res["line"]["correct"]


def failed_numbers(res) -> set:
    return {k for k, c in res["line"]["compared"].items()
            if not runner._holds(c)}


def test_control_on_the_cpu_backend_is_not_correct():
    res = rehearse(CELL, False, 2.0, overrides=TINY,
                   **control.CONTROLS["cpu-backend"])
    assert not res["line"]["correct"]
    assert "device_path_violations" in failed_numbers(res)


def test_planted_fault_is_not_correct():
    res = rehearse(CELL, False, 2.0, overrides=TINY,
                   **dict(control.CONTROLS["accept-all"]))
    assert not res["line"]["correct"]
    assert "corrupt_not_refused" in failed_numbers(res)


def test_a_program_without_the_txset_meter_fails_at_once(monkeypatch):
    from stellar_core_tpu.herder import herder
    monkeypatch.delattr(herder, "TXSET_CHECK_SITES")
    with pytest.raises(runner.RunError):
        rehearse(CELL, False, 1.0, overrides=TINY)
