"""The CPU rehearsal of `watcher-core3.payments-homed`, beside
test_rehearsal.py and through the same `rehearse` (runner.run_cell at a
tiny size, the line through emit's validator): control flow only, no
number from here is ever printed under a device metric's name.
"""

import pytest

from benchmark import control
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest

from test_rehearsal import SLICE, rehearse

CELL = "watcher-core3.payments-homed"
TINY = {"config": {"accounts": 200},
        "workload": {"traffic": {"clients": 12, "corrupt_every": 5},
                     "negative_control_lanes": 64,
                     "warm_buckets": [32], "trace_slice": SLICE}}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    # a jax-CPU dispatch takes a quarter of a second: the slice opens
    # once the first burst of twelve is through
    res = rehearse(CELL, trace, 12.0 if trace else 2.0, overrides=TINY)
    ln = res["line"]
    assert res["problems"] == []
    assert ln["correct"], ln["compared"]
    compared = ln["compared"]
    assert compared["window_compiles"]["value"] == 0
    assert compared["watcher_envelopes_emitted"]["value"] == 0
    assert compared["verdicts_compared"]["value"] == 64
    assert compared["heights_compared"]["value"] >= 2
    assert ln["attempted"] > 0 and ln["failed"] == 0
    counts = res["counts"]
    assert set(counts["buckets"]) == {"32"}
    # a quarter of the clients are homed on the watcher
    assert counts["admissions_local"] > 0
    assert counts["admissions_flood"] > counts["admissions_local"]
    assert counts["scp_envelopes_received"] == counts["scp_envelopes"] > 0
    if trace:
        assert 0 < ln["device"]["busy_s"] <= ln["device"]["window_s"]
        m = Manifest()
        assert set(ln["metrics"]) == set(m.expected_metrics(CELL, True))
        mine = {e["name"] for e in m.per_layer(CELL)}
        assert len(mine) == 11
        for name in mine:
            assert ln["metrics"][name]["value"] > 0.0, name
        assert 50.0 < ln["metrics"][
            "overlay.flood_share_pct.watcher"]["value"] < 100.0


def test_a_big_seed_is_a_seed():
    res = rehearse(CELL, False, 1.0, seed=2 ** 31 + 54321, overrides=TINY)
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
    assert "verdict_mismatches" in failed_numbers(res)
    # the fault is on the watcher alone: the chains still agree
    assert res["line"]["compared"]["header_mismatches"]["value"] == 0
