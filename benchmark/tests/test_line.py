"""The validator in front of the last line: a good line of each kind of
run passes; each shape that a refused line can have is refused."""

import copy
import io
import json
import math

import pytest

from benchmark.harness import line as L
from benchmark.harness.manifest import Manifest

CELL = "validator-core3.payments-flood"


def good(trace: bool) -> tuple:
    m = Manifest()
    expected = m.expected_metrics(CELL, trace)
    ln = L.build(True, 400, 0, {k: 1.5 for k in expected}, expected,
                 {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 224000000},
                 {"state_mismatches": {"value": 0, "limit": 0}},
                 {"device_ops": [["fusion.1", 0.5]],
                  "idle_gaps": [["bench.crank", 1.0]]} if trace else None)
    if trace:
        ln["device"]["window_s"] = 2.0
        ln["device"]["busy_s"] = 0.25
    return ln, expected


@pytest.mark.parametrize("trace", [False, True])
def test_good_line_is_printed(trace):
    ln, expected = good(trace)
    assert L.validate(ln, expected, trace) == []
    out = io.StringIO()
    L.emit(ln, expected, trace, out=out)
    text = out.getvalue()
    assert text.endswith("\n") and text.count("\n") == 1
    back = json.loads(text)
    assert list(back)[-1] == "compared"
    assert set(back["metrics"]) == set(expected)


def test_traced_line_carries_every_metric_of_the_cell():
    m = Manifest()
    traced = m.expected_metrics(CELL, True)
    assert set(m.expected_metrics(CELL, False)) < set(traced)
    assert {x["name"] for x in m.per_layer(CELL)} <= set(traced)


def _refused(ln, expected, trace) -> str:
    out = io.StringIO()
    with pytest.raises(L.LineError) as e:
        L.emit(ln, expected, trace, out=out)
    assert out.getvalue() == ""     # nothing was printed
    return str(e.value)


def test_a_traceback_is_not_a_line():
    _ln, expected = good(True)
    assert "not an object" in _refused(
        "Traceback (most recent call last):", expected, True)


@pytest.mark.parametrize("value", [float("nan"), float("inf"), None, "1.5",
                                   True])
def test_a_value_that_is_no_finite_number(value):
    ln, expected = good(True)
    ln["metrics"]["applied_tx_per_s"]["value"] = value
    assert "applied_tx_per_s" in _refused(ln, expected, True)


def test_nan_never_reaches_json():
    ln, expected = good(False)
    ln["compared"]["x"] = {"value": math.nan, "limit": 0}
    assert "not serialisable" in _refused(ln, expected, False)


@pytest.mark.parametrize("busy,window", [(0.0, 2.0), (None, 2.0),
                                         (2.5, 2.0), (-1.0, 2.0),
                                         (0.5, None), (float("nan"), 2.0)])
def test_busy_outside_the_window(busy, window):
    ln, expected = good(True)
    for k, v in (("busy_s", busy), ("window_s", window)):
        if v is None:
            del ln["device"][k]
        else:
            ln["device"][k] = v
    assert "busy_s" in _refused(ln, expected, True)


@pytest.mark.parametrize("trace", [False, True])
def test_a_manifest_metric_missing_from_the_line(trace):
    ln, expected = good(trace)
    name = sorted(expected)[-1]
    del ln["metrics"][name]
    assert name in _refused(ln, expected, trace)


def test_wrong_unit_unknown_metric_and_stray_key():
    ln, expected = good(False)
    bad = copy.deepcopy(ln)
    bad["metrics"]["setup_s"]["unit"] = "ms"
    assert "unit" in _refused(bad, expected, False)
    bad = copy.deepcopy(ln)
    bad["metrics"]["made_up"] = {"value": 1, "unit": "s"}
    assert "made_up" in _refused(bad, expected, False)
    bad = copy.deepcopy(ln)
    bad["notes"] = "x"
    assert "notes" in _refused(bad, expected, False)
    bad = copy.deepcopy(ln)
    del bad["device"]["memory_peak_bytes"]
    assert "memory_peak_bytes" in _refused(bad, expected, False)
