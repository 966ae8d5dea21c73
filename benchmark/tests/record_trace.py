#!/usr/bin/env python3
"""Records the small trace that tests/test_trace_reduce.py reads, on the
chip: `chiprun -- python3 benchmark/tests/record_trace.py`.

Three calls of the 128-lane verify executable, 5 ms apart, each under a
`bench.step` annotation, inside one SLICE annotation. A full trace holds
some 70,000 per-op device events for every call of the executable (tens
of MB for three calls), so what is kept is cut down: every line of the
trace that the reduction reads, with the "XLA Ops" line cut to its first
OPS_KEPT events, rewritten through ProfileData's text-proto reader into
chiprun_out/trace_small.xplane.pb. The script also prints, for each
profiler option it knows, what a trace costs (seconds to stop, bytes),
which set the cells' slice lengths (PERF.md)."""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OPS_KEPT = 400


def _q(s: str) -> str:
    return '"%s"' % s.replace("\\", "\\\\").replace('"', '\\"')


def shrink(pd, keep_planes=("/device:TPU:0", "/host:CPU")) -> bytes:
    """The planes and lines the reduction reads, as a serialized XSpace;
    of the per-op line only its first OPS_KEPT events."""
    from jax.profiler import ProfileData
    out = []
    for pid, plane in enumerate(pd.planes):
        if plane.name not in keep_planes:
            continue
        meta, lines = {}, []
        for lid, line in enumerate(plane.lines):
            evs = list(line.events)
            if plane.name == "/host:CPU":
                evs = [e for e in evs if e.name.startswith("bench.")]
            elif line.name not in ("XLA Modules", "XLA Ops"):
                continue
            if line.name == "XLA Ops":
                evs = evs[:OPS_KEPT]
            if not evs:
                continue
            rows = []
            for e in evs:
                mid = meta.setdefault(e.name, len(meta) + 1)
                rows.append("events { metadata_id: %d offset_ps: %d "
                            "duration_ps: %d }" % (
                                mid, int(round(e.start_ns * 1000)),
                                int(round(e.duration_ns * 1000))))
            lines.append("lines { id: %d name: %s timestamp_ns: 0 %s }" % (
                lid + 1, _q(line.name), " ".join(rows)))
        metas = " ".join(
            "event_metadata { key: %d value { id: %d name: %s } }" % (
                i, i, _q(n)) for n, i in meta.items())
        out.append("planes { id: %d name: %s %s %s }" % (
            pid + 1, _q(plane.name), metas, " ".join(lines)))
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def main() -> int:
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py needs a TPU", file=sys.stderr)
        return 2
    from stellar_core_tpu.parallel.device import configure_compile_cache
    configure_compile_cache()
    from stellar_core_tpu.ops.ed25519 import verify_batch_jit
    from benchmark.harness import trace_reduce

    b = 128
    args = (np.zeros((b, 20), np.int32), np.zeros((b,), np.int32),
            np.zeros((b, 20), np.int32), np.zeros((b,), np.int32),
            np.zeros((b, 64), np.int32), np.zeros((b, 64), np.int32))
    t = time.perf_counter()
    np.asarray(verify_batch_jit(*args))
    print("first call (compile or load): %.1f s" % (time.perf_counter() - t))

    def trace(out: str, calls: int, gap_s: float, advanced=None):
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        if advanced:
            opts.advanced_configuration = advanced
        t0 = time.perf_counter()
        jax.profiler.start_trace(out, profiler_options=opts)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SLICE):
            for _ in range(calls):
                with jax.profiler.TraceAnnotation("bench.step"):
                    np.asarray(verify_batch_jit(*args))
                time.sleep(gap_s)
        t2 = time.perf_counter()
        jax.profiler.stop_trace()
        t3 = time.perf_counter()
        path = trace_reduce.find_xplane(out)
        pd = trace_reduce.load(path)
        counts = {"%s|%s" % (p.name, ln.name): len(list(ln.events))
                  for p in pd.planes if p.name.startswith("/device")
                  for ln in p.lines}
        print("%s, %d calls: start_trace %.3f s, body %.3f s, stop_trace "
              "%.3f s, %d bytes, device lines %r" % (
                  advanced, calls, t1 - t0, t2 - t1, t3 - t2,
                  os.path.getsize(path), counts), flush=True)
        return path, pd

    os.makedirs("chiprun_out", exist_ok=True)
    tmp = "chiprun_out/trace_tmp"
    for mode in ("TRACE_ONLY_HOST", "TRACE_ONLY_XLA", "TRACE_COMPUTE",
                 "TRACE_COMPUTE_AND_SYNC"):
        try:
            trace(tmp, 3, 0.005, {"tpu_trace_mode": mode})
        except Exception as e:
            print("mode %s: %s: %s" % (mode, type(e).__name__, e))
    path, pd = trace(tmp, 3, 0.005)
    small = shrink(pd)
    with open("chiprun_out/trace_small.xplane.pb", "wb") as fh:
        fh.write(small)
    print("kept %d bytes" % len(small))
    red = trace_reduce.reduce_trace(pd, "tpu", {"ed25519": ("jit_verify",)})
    red["breakdown"]["device_ops"] = [[n[:40], s] for n, s in
                                      red["breakdown"]["device_ops"]]
    print("REDUCED full", red)
    from jax.profiler import ProfileData
    red = trace_reduce.reduce_trace(
        ProfileData.from_serialized_xspace(small), "tpu",
        {"ed25519": ("jit_verify",)})
    red["breakdown"]["device_ops"] = [[n[:40], s] for n, s in
                                      red["breakdown"]["device_ops"]]
    print("REDUCED small", red)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
