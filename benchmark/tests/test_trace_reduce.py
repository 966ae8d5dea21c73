"""harness/trace_reduce.py against a trace recorded on the chip (TPU v5
lite, PR 24; benchmark/tests/record_trace.py): three calls of the
128-lane verify executable, 5 ms apart, each under a `bench.step`
annotation, inside one slice annotation. The file keeps every line the
reduction reads; of the 212,028 per-op events of the full trace it keeps
the first 400. The numbers below were worked out by hand from the
file's events (nanoseconds):

  slice           43838543 .. 70189669               26351126
  executable runs 44925615 +1324881, 53523910 +1326086, 62205787 +1325609
  bench.step      43843473 +3091380, 52659952 +2975769, 61342090 +2909200
  busy            1324881 + 1326086 + 1325609       = 3976576
  gaps            43838543..44925615 = 1087072  (midpoint inside step 1)
                  46250496..53523910 = 7273414  (between steps)
                  54849996..62205787 = 7355791  (between steps)
                  63531396..70189669 = 6658273  (after step 3)
"""

import os

import pytest

from benchmark.harness import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_verify128_x3.xplane.pb")


def test_recorded_trace():
    red = T.reduce_trace(T.load(DATA), "tpu", {"ed25519": ("jit_verify",)})
    assert red["device"] == "/device:TPU:0" and red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(26351126e-9, abs=1e-12)
    assert red["busy_s"] == pytest.approx(3976576e-9, abs=1e-12)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["modules_s"] == {
        "jit_verify_batch_jit": pytest.approx(3976576e-9, abs=1e-12)}
    assert red["kernels_s"]["ed25519"] == pytest.approx(3976576e-9,
                                                        abs=1e-12)
    assert red["kernel_runs"] == 3
    assert red["longest_gap_s"] == pytest.approx(7355791e-9, abs=1e-12)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["bench.step"] == pytest.approx(1087072e-9, abs=1e-12)
    assert gaps["host.unannotated"] == pytest.approx(
        (7273414 + 7355791 + 6658273) * 1e-9, abs=1e-12)
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(
        red["window_s"], abs=1e-12)
    ops = red["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= 10 and all(s > 0 for _n, s in ops)
    assert all(len(n) <= 80 and " = " not in n for n, _s in ops)


def test_a_kernel_that_did_not_run_reads_nothing():
    red = T.reduce_trace(T.load(DATA), "tpu", {"sha256": ("jit_sha",)})
    assert red["kernels_s"] == {"sha256": 0.0} and red["kernel_runs"] == 0


def _space(text: str):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def _plane(pid, name, lines):
    metas, body, names = [], [], {}
    for lid, (lname, events) in enumerate(lines, 1):
        rows = []
        for ename, start_ns, dur_ns in events:
            mid = names.setdefault(ename, len(names) + 1)
            rows.append("events { metadata_id: %d offset_ps: %d "
                        "duration_ps: %d }" % (mid, start_ns * 1000,
                                               dur_ns * 1000))
        body.append('lines { id: %d name: "%s" timestamp_ns: 0 %s }'
                    % (lid, lname, " ".join(rows)))
    for n, i in names.items():
        metas.append('event_metadata { key: %d value { id: %d name: "%s" } }'
                     % (i, i, n))
    return 'planes { id: %d name: "%s" %s %s }' % (
        pid, name, " ".join(metas), " ".join(body))


def test_clipping_overlap_and_the_fullest_device():
    pd = _space("\n".join([
        _plane(1, "/device:TPU:0", [("XLA Modules", [
            ("jit_a(1)", 50, 100),      # starts before the slice: 50 kept
            ("jit_a(1)", 300, 100),
            ("jit_b(2)", 350, 100),     # overlaps the run before: +50
            ("jit_a(1)", 950, 100),     # runs past the slice: 50 kept
        ])]),
        _plane(2, "/device:TPU:1", [("XLA Modules", [
            ("jit_a(1)", 200, 100)])]),
        _plane(3, "/host:CPU", [("python3", [
            (T.SLICE, 100, 900), ("bench.outer", 100, 700),
            ("bench.inner", 500, 200)])]),
    ]))
    red = T.reduce_trace(pd, "tpu", {"a": ("jit_a",)})
    assert red["device"] == "/device:TPU:0" and red["n_devices"] == 2
    assert red["window_s"] == pytest.approx(900e-9)
    assert red["busy_s"] == pytest.approx((50 + 150 + 50) * 1e-9)
    assert red["kernels_s"]["a"] == pytest.approx(200e-9)
    assert red["kernel_runs"] == 3
    gaps = dict(red["breakdown"]["idle_gaps"])
    # 150..300 (midpoint 225: outer), 450..950 (midpoint 700: the inner
    # annotation, 500..700, ends there; outer covers it)
    assert gaps == {"bench.outer": pytest.approx(650e-9)}


def test_what_cannot_be_reduced_is_an_error():
    no_slice = _space(_plane(1, "/device:TPU:0",
                             [("XLA Modules", [("jit_a(1)", 10, 10)])]))
    with pytest.raises(ValueError, match="annotation"):
        T.reduce_trace(no_slice, "tpu")
    no_device = _space(_plane(3, "/host:CPU",
                              [("python3", [(T.SLICE, 100, 900)])]))
    with pytest.raises(ValueError, match="no device plane"):
        T.reduce_trace(no_device, "tpu")


def test_interval_arithmetic():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert T.clip([(0, 10), (20, 30), (40, 50)], 5, 25) == [(5, 10),
                                                            (20, 25)]
    assert T.total([(1, 4), (5, 8)]) == 6
    assert T.module_name("jit_verify_batch_jit(4970295796)") == \
        "jit_verify_batch_jit"
