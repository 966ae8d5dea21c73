"""The CPU rehearsal, for control flow only: each cell's set-up, window
and drain at a tiny size, through the same code as run.py
(harness/runner.py::run_cell), with and without the trace slice, and the
would-be line through emit's validator for shape. No number from here is
ever printed under a device metric's name: run.py has no CPU mode.

And the controls and planted faults of benchmark/control.py: each drives
the rest of a run with the timed path broken underneath, and `correct`
has to come out false.

The first test of a process pays the jax-CPU compile or cache load of
the 32-lane verify executable (half a minute to a minute).
"""

import time

import pytest

from benchmark import control
from benchmark.harness import line as L
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest

SLICE = {"length_s": 3.0, "max_dispatches": 6}
TINY = {
    "catchup-pubnet13.multisig-20": {
        "config": {"checkpoint_frequency": 8},
        "workload": {"traffic": {"txs_per_ledger": 4, "sigs_per_tx": 3},
                     "negative_control_lanes": 64,
                     "warm_buckets": [32], "trace_slice": SLICE}},
    "catchup-pubnet13.standard-mix": {
        "config": {"checkpoint_frequency": 8},
        "workload": {"traffic": {"txs_per_ledger": 6},
                     "negative_control_lanes": 64,
                     "warm_buckets": [32], "trace_slice": SLICE}},
    "validator-core3.payments-flood": {
        "config": {"accounts": 200},
        "workload": {"traffic": {"clients": 12, "corrupt_every": 5},
                     "warm_buckets": [32], "trace_slice": SLICE}},
}
# the open loop has no cell yet (PERF.md, open questions): its generator
# is rehearsed under the flood cell's name, whose metric it also reports
OPEN_LOOP = {
    "config": {"accounts": 200},
    "workload": {"traffic": {"loop": "open", "rate_per_s": 2.0,
                             "corrupt_every": 3},
                 "warm_buckets": [32], "trace_slice": SLICE}}


def tiny_buckets(app) -> None:
    """jax-CPU compiles the 32-lane shape only (tests/ does the same)."""
    v = getattr(app.sig_verifier, "inner", app.sig_verifier)
    if hasattr(v, "BUCKETS"):
        v.BUCKETS = (32,)


def rehearse(cell: str, trace: bool, seconds: float, seed: int = 7,
             overrides=None, **planted) -> dict:
    hook = control.chain(tiny_buckets, planted.pop("node_hook", None))
    res = runner.run_cell(Manifest(), cell, seed, seconds, trace,
                          time.perf_counter(), node_hook=hook,
                          overrides=overrides or TINY[cell], **planted)
    ln = res["line"]
    assert ln["device"]["platform"] == "cpu"
    # jax-CPU reports no memory statistics; the validator wants a number
    ln["device"]["memory_peak_bytes"] = ln["device"]["memory_peak_bytes"] or 1
    res["problems"] = L.validate(ln, res["expected"], trace)
    return res


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_rehearsal(cell, trace):
    res = rehearse(cell, trace, 6.0 if trace else 2.0)
    ln = res["line"]
    assert res["problems"] == []
    assert ln["correct"], ln["compared"]
    assert ln["compared"]["window_compiles"]["value"] == 0
    assert ln["attempted"] > 0 and ln["failed"] == 0
    # the warm-up plan: which verify buckets the traffic hit
    assert set(res["counts"]["buckets"]) == {"32"}
    if trace:
        assert 0 < ln["device"]["busy_s"] <= ln["device"]["window_s"]
        assert ln["breakdown"]["device_ops"]
        assert set(ln["metrics"]) == set(
            Manifest().expected_metrics(cell, True))


@pytest.mark.parametrize("trace", [False, True])
def test_open_loop_rehearsal(trace):
    res = rehearse("validator-core3.payments-flood", trace, 6.0,
                   overrides=OPEN_LOOP)
    ln = res["line"]
    assert res["problems"] == [] and ln["correct"], ln["compared"]
    assert ln["attempted"] == 12 and ln["failed"] == 0
    assert res["counts"]["unsent"] == 0


def test_a_big_seed_is_a_seed():
    res = rehearse("validator-core3.payments-flood", False, 1.0,
                   seed=2 ** 31 + 12345)
    assert res["problems"] == [] and res["line"]["correct"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_on_the_cpu_backend_is_not_correct(cell):
    res = rehearse(cell, False, 2.0, **control.CONTROLS["cpu-backend"])
    failed = {k for k, c in res["line"]["compared"].items()
              if not runner._holds(c)}
    assert not res["line"]["correct"]
    assert "device_path_violations" in failed


@pytest.mark.parametrize("cell,fault,number", [
    ("validator-core3.payments-flood", "accept-all", "corrupt_not_refused"),
    ("catchup-pubnet13.multisig-20", "accept-all", "sigs_not_on_device"),
    ("catchup-pubnet13.multisig-20", "accept-all", "verdict_mismatches"),
    ("catchup-pubnet13.multisig-20", "half-batch", "sigs_not_on_device"),
    ("catchup-pubnet13.standard-mix", "tampered-archive", "failed_replays"),
])
def test_planted_fault_is_not_correct(cell, fault, number):
    res = rehearse(cell, False, 2.0, **dict(control.CONTROLS[fault]))
    c = res["line"]["compared"][number]
    assert not res["line"]["correct"]
    assert not runner._holds(c), (number, c)
