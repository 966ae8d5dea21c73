"""tools/idle_by_span.py on hand-made intervals: the gap table's total is
the slice's idle time whatever the rule, a gap goes to the innermost
program span (else the benchmark's annotation, else nobody), and a
device run is matched to the wait that contains it."""

import pytest

from tools import idle_by_span as I

MS = 1e6    # the trace's clock is in nanoseconds


def test_gaps_are_the_complement_of_busy_in_the_slice():
    busy = [(10 * MS, 20 * MS), (50 * MS, 60 * MS)]
    gaps = I.gaps_of(busy, 0.0, 100 * MS)
    assert gaps == [(0.0, 10 * MS), (20 * MS, 50 * MS), (60 * MS, 100 * MS)]
    assert I.gaps_of([], 0.0, 5 * MS) == [(0.0, 5 * MS)]
    assert I.gaps_of([(0.0, 5 * MS)], 0.0, 5 * MS) == []


NOTES = [
    ("bench.trace_slice", 0.0, 100 * MS),
    ("bench.submit", 0.0, 40 * MS),
    ("herder.admit", 1 * MS, 39 * MS),
    ("txqueue.try_add", 2 * MS, 38 * MS),
    ("crypto.stage", 4 * MS, 10 * MS),
    ("bench.crank", 60 * MS, 90 * MS),
    ("PjitFunction(verify_batch)", 0.0, 100 * MS),   # not the program's
]


@pytest.mark.parametrize("split", [False, True])
def test_every_gap_is_charged_and_the_total_is_the_idle_time(split):
    gaps = [(0.0, 10 * MS), (20 * MS, 50 * MS), (60 * MS, 100 * MS)]
    idle = I.charge(gaps, NOTES, split)
    assert sum(idle.values()) == pytest.approx(80 * MS)
    assert "bench.trace_slice" not in idle
    assert not [k for k in idle if k.startswith("Pjit")]
    if not split:
        # whole gaps by their midpoints: 5 ms → crypto.stage, 35 ms →
        # try_add, 80 ms → bench.crank (no program span covers it)
        assert idle == {"crypto.stage": 10 * MS, "txqueue.try_add": 30 * MS,
                        "bench.crank": 40 * MS}
    else:
        assert idle == pytest.approx({
            "bench.submit": 1 * MS + 1 * MS,        # 0–1, 39–40
            "herder.admit": 1 * MS + 1 * MS,        # 1–2, 38–39
            "txqueue.try_add": 2 * MS + 18 * MS,    # 2–4, 20–38
            "crypto.stage": 6 * MS,                 # 4–10
            "host.unannotated": 10 * MS + 10 * MS,  # 40–50, 90–100
            "bench.crank": 30 * MS})                # 60–90


def test_a_device_run_is_matched_to_the_wait_around_it():
    notes = [("crypto.launch", 10.0 * MS, 10.2 * MS),
             ("crypto.device_wait", 10.2 * MS, 13.0 * MS),
             ("crypto.launch", 20.0 * MS, 20.2 * MS),
             ("crypto.device_wait", 20.2 * MS, 23.5 * MS),
             ("crypto.device_wait", 1.0 * MS, 2.0 * MS)]    # no launch
    modules = [("jit_verify_batch_jit(7)", 5.0 * MS, 6.0 * MS),  # warm-up
               ("jit_verify_batch_jit(7)", 10.1 * MS, 11.5 * MS),
               # the device's clock a little ahead of the host's
               ("jit_verify_batch_jit(7)", 19.9 * MS, 21.3 * MS),
               ("jit_sha256(3)", 20.5 * MS, 23.0 * MS)]
    off = I.wait_offsets(notes, modules, 0.0, 100 * MS)
    assert off == pytest.approx([(0.1 * MS, 1.4 * MS, 1.5 * MS),
                                 (-0.1 * MS, 1.4 * MS, 2.2 * MS)])
    # a wait that straddles the slice's end is left out
    assert len(I.wait_offsets(notes, modules, 0.0, 22 * MS)) == 1


# a restart, a close with a full pass of the collector inside its apply
# and a merge on a worker beside it (ISSUE 35): every name below lacked
# its prefix before, and its gap fell through to the benchmark's wrapper
RUNTIME_NOTES = [
    ("bench.trace_slice", 0.0, 100 * MS),
    ("bench.catchup.new_node", 0.0, 20 * MS),
    ("node.restore", 2 * MS, 18 * MS),
    ("bucketdb.index_load", 4 * MS, 12 * MS),
    ("bench.catchup.crank", 20 * MS, 100 * MS),
    ("ledger.close", 30 * MS, 90 * MS),
    ("close.apply", 32 * MS, 70 * MS),
    ("runtime.gc.full", 40 * MS, 60 * MS),
    ("close.bucket_add", 70 * MS, 88 * MS),
    ("bucket.merge_wait", 72 * MS, 80 * MS),
    ("bucket.merge", 73 * MS, 79 * MS),     # the worker's, beside it
]


def test_a_gap_under_a_full_pass_is_the_collectors_and_a_restarts_its_own():
    idle = I.charge([(0.0, 100 * MS)], RUNTIME_NOTES, split=True)
    assert sum(idle.values()) == pytest.approx(100 * MS)
    assert idle == pytest.approx({
        "bench.catchup.new_node": 2 * MS + 2 * MS,      # 0–2, 18–20
        "node.restore": 2 * MS + 6 * MS,                # 2–4, 12–18
        "bucketdb.index_load": 8 * MS,                  # 4–12
        "bench.catchup.crank": 10 * MS + 10 * MS,       # 20–30, 90–100
        "ledger.close": 2 * MS + 2 * MS,                # 30–32, 88–90
        "close.apply": 8 * MS + 10 * MS,                # 32–40, 60–70
        "runtime.gc.full": 20 * MS,                     # 40–60
        "close.bucket_add": 2 * MS + 8 * MS,            # 70–72, 80–88
        "bucket.merge_wait": 1 * MS + 1 * MS,           # 72–73, 79–80
        "bucket.merge": 6 * MS})                        # 73–79: the shortest
    for name in ("runtime.gc.full", "node.restore", "bucket.merge",
                 "bucketdb.index_load", "overlay.recv_tx", "scp.slot"):
        assert name.startswith(I.PROGRAM_PREFIXES)


def test_a_workers_merge_is_measured_against_the_other_threads_closes():
    threads = {
        "main": [("ledger.close", 30 * MS, 90 * MS),
                 ("close.bucket_add", 70 * MS, 88 * MS)],
        "bucket-merge_0": [("bucket.merge", 71 * MS, 79 * MS),
                           ("bucket.merge", 85 * MS, 95 * MS)],
    }
    rows = I.beside_closes(threads)
    n, ns, under = rows["bucket-merge_0"]["bucket.merge"]
    assert (n, ns, under) == (2, pytest.approx(18 * MS),
                              pytest.approx(8 * MS + 5 * MS))
    # the closing thread's own spans are under nobody else's close
    assert rows["main"]["ledger.close"] == (1, pytest.approx(60 * MS), 0.0)
