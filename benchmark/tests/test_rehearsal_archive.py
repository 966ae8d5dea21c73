"""The CPU rehearsal of `validator-archive3.payments-1m`, beside
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

CELL = "validator-archive3.payments-1m"
TINY = {"config": {"accounts": 1000, "checkpoint_frequency": 16,
                   "state": {"signer_accounts": 256}},
        "workload": {"traffic": {"clients": 12, "corrupt_every": 5},
                     "opens_before_checkpoint": 2,
                     "warm_buckets": [32], "trace_slice": SLICE}}


def mine() -> set:
    """The cell's per-layer metrics, as its metric files name them."""
    return {os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(BENCH_DIR, "metrics", "*.archive.json"))}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = rehearse(CELL, trace, 10.0 if trace else 8.0, overrides=TINY)
    ln = res["line"]
    assert res["problems"] == []
    assert ln["correct"], ln["compared"]
    compared = {k: c["value"] for k, c in ln["compared"].items()}
    for zero in ("window_compiles", "header_mismatches", "state_mismatches",
                 "store_mismatches", "archive_mismatches",
                 "checkpoints_unpublished", "publishes_past_one",
                 "restarts_off_snapshot", "bucketdb_detached",
                 "sql_fallbacks", "python_closes", "native_bails",
                 "device_path_violations", "corrupt_not_refused",
                 "valid_refused", "admitted_never_applied"):
        assert compared[zero] == 0, zero
    assert compared["publishes_in_window"] == 1
    assert ln["attempted"] > 0 and ln["failed"] == 0
    counts = res["counts"]
    assert set(counts["buckets"]) == {"32"}
    assert counts["publishes"] == 1 and counts["publish_buckets"] > 0
    assert counts["cold_admit"] > 0 and counts["cold_close"] > 0
    if trace:
        assert 0 < ln["device"]["busy_s"] <= ln["device"]["window_s"]
        m = Manifest()
        assert set(ln["metrics"]) == set(m.expected_metrics(CELL, True))
        assert {e["name"] for e in m.per_layer(CELL)} == mine()
        for name in mine():
            assert ln["metrics"][name]["value"] > 0.0, name
        mb = ln["metrics"]["history.publish_mb.archive"]["value"]
        assert mb == pytest.approx(counts["publish_bytes"] / 1e6)


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
    res = rehearse(CELL, False, 8.0, seed=2 ** 31 + 24680, overrides=TINY)
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


def test_a_tampered_archive_is_not_correct():
    res = rehearse(CELL, False, 3.0, overrides=TINY,
                   **control.CONTROLS["tampered-archive"])
    assert not res["line"]["correct"]
    assert "archive_mismatches" in failed_numbers(res)


def test_a_program_without_the_publish_meters_fails_at_once(monkeypatch):
    from stellar_core_tpu.history import history_manager
    monkeypatch.delattr(history_manager, "PUBLISH_METERS")
    with pytest.raises(runner.RunError):
        rehearse(CELL, False, 1.0, overrides=TINY)
