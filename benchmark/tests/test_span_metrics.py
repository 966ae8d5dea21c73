"""The span readers on hand-made span lists, and the CPU rehearsal of
each cell, traced, reporting the per-layer metrics that read the
program's own spans (control flow only: no number from here is ever
printed under a metric's name)."""

import pytest

from benchmark.harness.manifest import Manifest
from benchmark.readers import span_count_per_ledger, span_mean

from test_rehearsal import TINY, rehearse

# (name, t0, dur, sid, parent), as the deployments hand spans over
ADMIT = [
    ("herder.admit", 0.0, 0.010, 1, 0),
    ("txqueue.try_add", 0.001, 0.008, 2, 1),
    ("crypto.prewarm", 0.002, 0.005, 3, 2),
    ("tx.check_valid", 0.007, 0.001, 4, 2),
    ("herder.admit", 1.0, 0.020, 5, 0),
    ("txqueue.try_add", 1.001, 0.016, 6, 5),
    ("crypto.prewarm", 1.002, 0.010, 7, 6),
    ("tx.check_valid", 1.012, 0.002, 8, 6),
    # a completed span (tracer.record) has no parent and takes nothing
    # from the self time of the span it was recorded under
    ("crypto.queue_wait.scp", 1.0, 0.004, 9, 0),
]


def ctx(*span_lists, **counts):
    return {"spans": list(span_lists), "counts": counts}


def test_mean_whole():
    v = span_mean.read(ctx(ADMIT), {"spans": ["herder.admit"],
                                    "exclusive": False, "scale": 1000})
    assert v == pytest.approx(15.0)


def test_mean_exclusive_per_another_span():
    # self times: admit 2 + 4, try_add 2 + 4, check_valid 1 + 2 = 15 ms
    v = span_mean.read(ctx(ADMIT), {
        "spans": ["herder.admit", "txqueue.try_add", "tx.check_valid"],
        "exclusive": True, "scale": 1000, "per": "herder.admit"})
    assert v == pytest.approx(7.5)


def test_mean_over_several_span_lists_and_a_count():
    a = [("crypto.device_wait", 0.0, 0.030, 1, 0)]
    b = [("crypto.device_wait", 0.0, 0.010, 1, 0),
         ("ledger.close", 0.1, 0.5, 2, 0)]
    args = {"spans": ["crypto.device_wait"], "exclusive": False,
            "scale": 1000}
    assert span_mean.read(ctx(a, b), args) == pytest.approx(20.0)
    assert span_mean.read(ctx(a, b, ledgers=8),
                          dict(args, per_count="ledgers")) \
        == pytest.approx(5.0)


@pytest.mark.parametrize("args", [
    {"spans": ["no.such.span"], "exclusive": False},
    {"spans": ["herder.admit"], "exclusive": True, "per": "no.such.span"},
    {"spans": ["herder.admit"], "exclusive": False, "per_count": "ledgers"},
])
def test_mean_reads_zero_where_the_program_has_no_such_span(args):
    """The parent of a PR that adds a span runs with that PR's readers:
    runner.run_cell prints no line for a reader that returns nothing."""
    assert span_mean.read(ctx(ADMIT, ledgers=0), args) == 0.0


def test_count_per_ledger_and_its_zero():
    fired = [("scp.timer.fired", 0.5, 0.0, 1, 0),
             ("scp.timer.fired", 0.9, 0.0, 2, 0),
             ("scp.slot", 0.0, 1.0, 3, 0)]
    args = {"spans": ["scp.timer.fired"]}
    assert span_count_per_ledger.read(ctx(fired, ledgers=8), args) == 0.25
    v = span_count_per_ledger.read(ctx(ADMIT, ledgers=8), args)
    assert v == 0.0 and v is not None
    assert span_count_per_ledger.read(ctx(fired, ledgers=0), args) is None


SPAN_METRICS = {
    e["name"] for e in Manifest().doc["per_layer"]
    if Manifest().metric_params(e["name"])["reader"]
    in ("span_mean", "span_count_per_ledger")
} | {"verify.host_ms_per_ledger.catchup"}


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_rehearsal_reports_every_span_metric(cell):
    res = rehearse(cell, True, 6.0, seed=11)
    ln = res["line"]
    assert res["problems"] == [] and ln["correct"], ln["compared"]
    m = Manifest()
    mine = {e["name"] for e in m.per_layer(cell)} & SPAN_METRICS
    assert len(SPAN_METRICS) == 10 and mine
    for name in mine:
        v = ln["metrics"][name]["value"]
        if name == "scp.timeouts_per_slot.flood":
            assert v >= 0.0     # a run without a timeout reads 0.0
        else:
            assert v > 0.0, name
    if "admission.span_ms_per_tx.flood" in mine:
        # the inside of the outside clock
        assert ln["metrics"]["admission.span_ms_per_tx.flood"]["value"] \
            <= ln["metrics"]["admission.ms_per_tx.flood"]["value"]
        assert ln["metrics"]["admission.python_ms_per_tx.flood"]["value"] \
            < ln["metrics"]["admission.span_ms_per_tx.flood"]["value"]
