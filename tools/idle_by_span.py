#!/usr/bin/env python3
"""Who held the device idle: the traced slice's idle gaps charged to the
program's own spans.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace DIR
    python3 tools/idle_by_span.py DIR [--platform tpu] [--ops 12]

While its tracer is on, the program mirrors every span into the
profiler's trace (util/tracing.py), so its spans sit on the host plane
beside the benchmark's `bench.*` annotations, on the device trace's
clock. This tool charges each idle gap of the slice to the innermost
*program* span that covers the gap's midpoint (falling back to the
innermost `bench.*` annotation, then to `host.unannotated`), with the
same arithmetic as benchmark/harness/trace_reduce.py, whose helpers it
uses: the table's total is the run line's `window_s - busy_s`. With
--split a gap is first cut at every span boundary inside it, so a gap of
a second between two drains is shared out among the spans it crosses.

With --threads it prints the program's spans of the slice by host
thread, and how much of each thread's span time lies under a `close.*`
/ `ledger.close` span of ANOTHER thread (a `bucket.merge` on a worker
beside the closes it shares the interpreter with).

It also prints, for the verify executable, how far each device run sits
inside its `crypto.device_wait`: launch latency (`crypto.launch` start →
module start on the device) and readback latency (module end →
`crypto.device_wait` end); and with --ops N the N longest device ops
with the `op_name` of their HLO metadata (the `jax.named_scope`s of
ops/ed25519.py show there for an executable that was compiled with op
trace marks: the served verify executables are not,
parallel/device.py::verify_compile_options; the six-argument
`verify_batch_jit` is).
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce as T   # noqa: E402

# every span name the program opens with `with` (record()ed spans and
# instants are ring-only and never reach the profiler's trace, but for
# the collector's `runtime.gc.*`, whose hook enters its annotation in
# real time)
PROGRAM_PREFIXES = ("bucket.", "bucketdb.", "catchup.", "close.", "crypto.",
                    "herder.", "ledger.", "node.", "overlay.", "runtime.",
                    "scp.", "tx.", "txqueue.")
VERIFY_MODULE = "jit_verify_batch"

Note = Tuple[str, float, float]     # (name, start_ns, end_ns)


def slice_and_busy(pd, platform: str):
    """(lo, hi, busy intervals of the fullest device, its record), as
    trace_reduce.reduce_trace finds them."""
    slices = [n for n in T.host_annotations(pd) if n[0] == T.SLICE]
    if len(slices) != 1:
        raise ValueError("expected one %r annotation, found %d"
                         % (T.SLICE, len(slices)))
    _n, lo, hi = slices[0]
    devices = T._device_lines(pd, platform)
    if not devices:
        raise ValueError("no device plane in the trace (platform %r)"
                         % platform)
    per_device = {
        name: T.union(T.clip([(a, b) for _n, a, b in
                              rec["modules"] or rec["ops"]], lo, hi))
        for name, rec in devices.items()}
    fullest = max(per_device, key=lambda d: T.total(per_device[d]))
    return lo, hi, per_device[fullest], devices[fullest]


def gaps_of(busy: List[T.Interval], lo: float, hi: float
            ) -> List[T.Interval]:
    gaps, edge = [], lo
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = b
    if hi > edge:
        gaps.append((edge, hi))
    return gaps


def innermost(notes: List[Note], t: float):
    cover = [n for n in notes if n[1] <= t < n[2]]
    return min(cover, key=lambda n: n[2] - n[1])[0] if cover else None


def charge(gaps: List[T.Interval], notes: List[Note],
           split: bool = False) -> Dict[str, float]:
    """{span name: idle ns}: each gap to the innermost program span over
    its midpoint, else the innermost bench.* annotation, else nobody.
    That is the benchmark's own rule, and it hands a gap of a second to
    whatever span its middle falls in; with `split` a gap is cut at every
    span boundary inside it and each piece is charged by the same rule."""
    program = [n for n in notes if n[0].startswith(PROGRAM_PREFIXES)]
    bench = [n for n in notes
             if n[0].startswith("bench.") and n[0] != T.SLICE]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        cuts = [a, b]
        if split:
            cuts += [t for n in program + bench for t in n[1:] if a < t < b]
            cuts.sort()
        near = [n for n in program if n[2] > a and n[1] < b]
        near_bench = [n for n in bench if n[2] > a and n[1] < b]
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2.0
            who = innermost(near, mid) or innermost(near_bench, mid) \
                or "host.unannotated"
            idle[who] = idle.get(who, 0.0) + (hi - lo)
    return idle


def by_thread(pd, lo: float, hi: float) -> Dict[str, List[Note]]:
    """{host thread: its program spans, the part inside the slice}. A
    thread is a line of the host plane; Python's all carry the process's
    name, so the key is the name and the line's place in the plane."""
    out: Dict[str, List[Note]] = {}
    for plane in pd.planes:
        if plane.name != T.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            mine = [(e.name, max(e.start_ns, lo),
                     min(e.start_ns + e.duration_ns, hi))
                    for e in line.events
                    if e.name.startswith(PROGRAM_PREFIXES)
                    and e.start_ns < hi and e.start_ns + e.duration_ns > lo]
            if mine:
                out["%s#%d" % (line.name, i)] = mine
    return out


def beside_closes(threads: Dict[str, List[Note]]
                  ) -> Dict[str, Dict[str, Tuple[int, float, float]]]:
    """{thread: {span name: (spans, their ns, the ns of them under a
    close.* / ledger.close span of another thread)}}."""
    out = {}
    for thread, notes in threads.items():
        cover = T.union([(a, b) for other, theirs in threads.items()
                         if other != thread for name, a, b in theirs
                         if name.startswith("close.")
                         or name == "ledger.close"])
        rows: Dict[str, Tuple[int, float, float]] = {}
        for name, a, b in notes:
            n, ns, under = rows.get(name, (0, 0.0, 0.0))
            rows[name] = (n + 1, ns + (b - a),
                          under + T.total(T.clip(cover, a, b)))
        out[thread] = rows
    return out


def wait_offsets(notes: List[Note], modules: List[Note], lo: float,
                 hi: float) -> List[Tuple[float, float, float]]:
    """[(launch latency, device run, readback latency)] in ns: each
    `crypto.device_wait` of the slice with the `crypto.launch` that ends
    where it starts, and the last verify run on the device that lies
    between that launch's start and the wait's end (half a millisecond
    of room either side: the two planes' clocks agree only so far, and a
    latency below zero here is their disagreement)."""
    room = 0.5e6
    launches = sorted(n for n in notes if n[0] == "crypto.launch")
    waits = sorted((n for n in notes if n[0] == "crypto.device_wait"
                    and lo <= n[1] and n[2] <= hi), key=lambda n: n[1])
    runs = [m for m in modules
            if T.module_name(m[0]).startswith(VERIFY_MODULE)]
    out = []
    for _w, w0, w1 in waits:
        before = [x for x in launches if x[2] <= w0]
        if not before:
            continue
        l0 = before[-1][1]
        inside = [m for m in runs
                  if m[1] >= l0 - room and m[2] <= w1 + room]
        if inside:
            _m, m0, m1 = max(inside, key=lambda m: m[2])
            out.append((m0 - l0, m1 - m0, w1 - m1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="the DIR given to --keep-trace")
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--ops", type=int, default=0, metavar="N",
                    help="also print the N longest device ops")
    ap.add_argument("--split", action="store_true",
                    help="cut each gap at the span boundaries inside it")
    ap.add_argument("--threads", action="store_true",
                    help="also print the program's spans by host thread")
    args = ap.parse_args(argv)
    pd = T.load(T.find_xplane(args.trace_dir))
    lo, hi, busy, rec = slice_and_busy(pd, args.platform)
    notes = T.host_annotations(pd, prefix="")
    idle = charge(gaps_of(busy, lo, hi), notes, args.split)
    window_s, busy_s = (hi - lo) / 1e9, T.total(busy) / 1e9
    total_s = sum(idle.values()) / 1e9
    print("window_s %.9f busy_s %.9f idle_s %.9f (gap table total %.9f)"
          % (window_s, busy_s, window_s - busy_s, total_s))
    seen = sorted({n[0] for n in notes if n[0].startswith(PROGRAM_PREFIXES)})
    print("program spans on the host plane: %s" % (", ".join(seen) or "none"))
    print("%-28s %12s %7s" % ("idle charged to%s" % (
        " (split)" if args.split else ""), "seconds", "share"))
    for name, ns in sorted(idle.items(), key=lambda kv: -kv[1]):
        print("%-28s %12.6f %6.1f%%" % (name, ns / 1e9,
                                        100.0 * ns / 1e9 / total_s))
    off = wait_offsets(notes, rec["modules"], lo, hi)
    if off:
        med = [statistics.median(c) / 1e6 for c in zip(*off)]
        print("crypto.device_wait against %s on the device, %d matched: "
              "median launch latency %.3f ms, device run %.3f ms, "
              "readback latency %.3f ms"
              % (VERIFY_MODULE, len(off), med[0], med[1], med[2]))
    if args.threads:
        for thread, rows in sorted(beside_closes(
                by_thread(pd, lo, hi)).items()):
            print("thread %s" % thread)
            for name, (n, ns, under) in sorted(rows.items(),
                                               key=lambda kv: -kv[1][1]):
                print("  %-26s %6d spans %10.6f s, %10.6f s under another "
                      "thread's close" % (name, n, ns / 1e9, under / 1e9))
    if args.ops:
        print_ops(pd, args.platform, lo, hi, args.ops)
    return 0


def print_ops(pd, platform: str, lo: float, hi: float, n: int) -> None:
    """The n device ops with the most time in the slice. An op event's
    name is its whole HLO line: the `op_name` in its metadata is where a
    `jax.named_scope` shows (`.../ed25519.varbase/while`)."""
    acc: Dict[str, float] = {}
    line_of: Dict[str, str] = {}
    for plane in pd.planes:
        if not (platform == "tpu" and
                plane.name.startswith(T.DEVICE_PREFIX)):
            continue
        for line in plane.lines:
            if line.name not in T.OPS_LINES:
                continue
            for i, e in enumerate(line.events):
                if i >= T.OPS_READ:
                    break
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if b <= lo or a >= hi:
                    continue
                key = e.name.split(" = ")[0][:80]
                acc[key] = acc.get(key, 0.0) + (min(b, hi) - max(a, lo))
                line_of.setdefault(key, e.name)
        break       # one device's ops are enough for names
    for key, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:n]:
        hlo = line_of[key]
        i = hlo.find("op_name=")
        scope = hlo[i:i + 200].split('"')[1] if i >= 0 \
            and hlo[i:i + 200].count('"') >= 2 else "(no op_name) " + \
            hlo[len(key):len(key) + 120]
        print("op %-40s %.6f s  %s" % (key, ns / 1e9, scope))


if __name__ == "__main__":
    sys.exit(main())
