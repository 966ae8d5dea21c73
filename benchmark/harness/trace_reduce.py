"""From a profiler trace (.xplane.pb) to busy_s, per-kernel device time
and the idle gaps attributed to what the host was doing.

The traced slice is bounded by the host annotation SLICE that the
runner opens right after the profiler starts and closes with the
measured window: window_s is its length, and every device interval is
clipped to it. busy_s is the union of the intervals in which an
executable ran on the fullest device ("XLA Modules"). Host spans are `jax.profiler.TraceAnnotation`s named
`bench.*`, written from benchmark/ files.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

SLICE = "bench.trace_slice"
OPS_LINES = ("XLA Ops",)            # per-HLO-op device intervals
OPS_READ = 200000                   # of which the table reads the first
MODULE_LINES = ("XLA Modules",)     # one interval per executable run
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]      # nanoseconds


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def module_name(event_name: str) -> str:
    """`jit_verify_batch(1234567)` -> `jit_verify_batch`."""
    i = event_name.find("(")
    return event_name[:i] if i > 0 else event_name


def host_annotations(pd, prefix: str = "bench.") -> List[tuple]:
    """[(name, start_ns, end_ns)] of the benchmark's own host spans."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _device_lines(pd, platform: str) -> Dict[str, dict]:
    """{device plane name: {"ops": [(name, a, b)], "modules": [...]}}.

    On a TPU the device planes are /device:TPU:<i>. On the CPU platform
    (the control-flow rehearsal only; no CPU number is ever reported)
    XLA's ops run on host threads, and the events that carry an
    `hlo_module` stat stand in for both lines."""
    out: Dict[str, dict] = {}
    for plane in pd.planes:
        if platform == "tpu" and plane.name.startswith(DEVICE_PREFIX):
            rec = out.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = "ops" if line.name in OPS_LINES else \
                    "modules" if line.name in MODULE_LINES else None
                if key is None:
                    continue
                for i, e in enumerate(line.events):
                    if key == "ops" and i >= OPS_READ:
                        break
                    rec[key].append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
        elif platform == "cpu" and plane.name == HOST_PLANE:
            rec = out.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    mod = dict(e.stats).get("hlo_module")
                    if mod is not None:
                        iv = (e.start_ns, e.start_ns + e.duration_ns)
                        rec["ops"].append((e.name,) + iv)
                        rec["modules"].append((str(mod),) + iv)
    return out


def reduce_trace(pd, platform: str,
                 kernel_prefixes: Optional[Dict[str, tuple]] = None) -> dict:
    """Returns window_s, busy_s (fullest device), per-module device
    seconds, the op and gap tables of `breakdown`, and `kernels`:
    {label: seconds} for each label of `kernel_prefixes`, summing the
    module intervals whose name starts with one of its prefixes."""
    notes = host_annotations(pd)
    slices = [n for n in notes if n[0] == SLICE]
    if len(slices) != 1:
        raise ValueError("expected one %r annotation in the trace, found "
                         "%d" % (SLICE, len(slices)))
    _n, lo, hi = slices[0]
    if hi <= lo:
        raise ValueError("the traced slice is empty")
    devices = _device_lines(pd, platform)
    if not devices:
        raise ValueError("no device plane in the trace (platform %r; "
                         "planes: %s)" % (platform, ", ".join(
                             p.name for p in pd.planes)))
    per_device = {}
    for name, rec in devices.items():
        # an executable's interval on the module line is the device at
        # work on it from first op to last (three calls on the chip: the
        # per-op union read 0.3% less); the per-op line, some 70,000
        # events a call, is read only for the breakdown's table
        src = rec["modules"] or rec["ops"]
        per_device[name] = union(clip([(a, b) for _n, a, b in src], lo, hi))
    fullest = max(per_device, key=lambda d: total(per_device[d]))
    busy = per_device[fullest]
    rec = devices[fullest]

    def by_name(rows, key=lambda s: s) -> Dict[str, float]:
        acc: Dict[str, float] = {}
        for name, a, b in rows:
            c = clip([(a, b)], lo, hi)
            if c:
                acc[key(name)] = acc.get(key(name), 0.0) + total(c)
        return acc

    modules = by_name(rec["modules"], module_name)
    ops = by_name(rec["ops"] or rec["modules"])
    kernels = {}
    kernel_runs = 0
    for label, prefixes in (kernel_prefixes or {}).items():
        kernels[label] = sum(s for m, s in modules.items()
                             if m.startswith(tuple(prefixes))) / 1e9
        kernel_runs += sum(
            1 for name, a, b in rec["modules"]
            if module_name(name).startswith(tuple(prefixes))
            and clip([(a, b)], lo, hi))

    # idle gaps inside the slice, each charged to the innermost bench.*
    # annotation that covers its midpoint
    gaps = []
    edge = lo
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = b
    if hi > edge:
        gaps.append((edge, hi))
    inner = [n for n in notes if n[0] != SLICE]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2.0
        cover = [n for n in inner if n[1] <= mid < n[2]]
        who = min(cover, key=lambda n: n[2] - n[1])[0] if cover \
            else "host.unannotated"
        idle[who] = idle.get(who, 0.0) + (b - a)

    def top(d: Dict[str, float]) -> list:
        # an op's name is its whole HLO line: keep the assigned name
        return [[k.split(" = ")[0][:80], v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": total(busy) / 1e9,
            "device": fullest, "n_devices": len(devices),
            "modules_s": {k: v / 1e9 for k, v in modules.items()},
            "kernels_s": kernels, "kernel_runs": kernel_runs,
            "longest_gap_s": max((b - a for a, b in gaps),
                                 default=0.0) / 1e9,
            "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)}}
