"""One run of one cell: set up, warm, measure for `seconds`, drain,
compare, and build the line. run.py and the CPU rehearsal in
benchmark/tests both go through `run_cell`; only run.py prints."""

from __future__ import annotations

import importlib
import os
import shutil
import sys
import tempfile
import threading
import time

from . import annotate, trace_reduce
from .line import build
from .manifest import Manifest

_compiles = {"n": 0}
_compile_lock = threading.Lock()
_listening = False


def _on_duration(event: str, _secs: float, **_kw) -> None:
    # every backend compile or persistent-cache load, on any thread
    if event == "/jax/core/compile/backend_compile_duration":
        with _compile_lock:
            _compiles["n"] += 1


def _listen_for_compiles() -> None:
    global _listening
    if not _listening:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def say(msg: str) -> None:
    print("[bench] " + msg, flush=True)


class RunError(RuntimeError):
    """The run cannot report: no line is printed."""


class TraceSlice:
    """Profiles the end of the measured window, Python-level host tracing
    off: the slice opens `length_s` seconds before the window is due to
    close (never in its first second) and closes the window itself, as
    soon as the program's own counter says `max_dispatches` dispatches
    have run in it, or when the window is due, whichever comes first. So
    a traced run's window is up to `length_s` shorter than --seconds. The
    profiler is stopped the moment the window has closed, before the
    drain.

    Why so: on this chip every call of the verify executable leaves some
    70,000 per-op device events (10 MB) in the trace whatever
    `tpu_trace_mode` says; the device's trace buffer holds about thirty
    calls; stopping costs 9 s plus 0.9 s a call, and 165-180 s once the
    buffer has filled, also from a thread of its own while the window
    goes on (my chip runs, PR 24). So no call may run while the profiler
    is on except those of the slice, which therefore has to end the
    window; `length_s` is longer than the longest pause between two
    dispatches of the cell's traffic (a live network admits in bursts,
    and a tenth of a second caught none in three traced runs of five),
    and `max_dispatches` is well under thirty. The slice is bounded by one
    SLICE annotation, and the deployment's device counters are read at
    both of its ends. Requests in flight at the close wait for the stop;
    a traced run's end-to-end numbers are not the untraced run's."""

    def __init__(self, trace_dir: str, t_begin: float, seconds: float,
                 spec: dict, counters) -> None:
        self.dir, self.counters = trace_dir, counters
        self.t_on = t_begin + max(1.0, seconds - float(spec["length_s"]))
        self.max_dispatches = int(spec["max_dispatches"])
        self.state = 0
        self.c0 = self.c1 = None
        self.stop_cost_s = 0.0

    def tick(self, now: float) -> bool:
        """True: the slice is full, close the window now."""
        if self.state == 1:
            return self.counters()["dispatches"] - \
                self.c0["dispatches"] >= self.max_dispatches
        if self.state == 0 and now >= self.t_on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.note = jax.profiler.TraceAnnotation(trace_reduce.SLICE)
            self.note.__enter__()
            self.c0 = self.counters()
            self.state = 1
        return False

    def stop(self) -> None:
        """The window has closed."""
        if self.state != 1:
            raise RunError("the window closed before the traced slice "
                           "opened; give the run more seconds")
        import jax
        self.c1 = self.counters()
        self.note.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_cost_s = time.perf_counter() - t
        self.state = 2


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats is not None:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def read_metric(manifest: Manifest, name: str, ctx: dict):
    """A per-layer metric is a file that picks a reader and gives its
    arguments; the reader is a module found by name."""
    spec = manifest.metric_params(name)
    mod = importlib.import_module("benchmark.readers." + spec["reader"])
    return mod.read(ctx, spec.get("args", {}))


def run_cell(manifest: Manifest, cell_name: str, seed: int, seconds: float,
             trace: bool, t_process: float, node_hook=None,
             overrides: dict | None = None, keep_trace: str | None = None,
             backend_under_test: str | None = None,
             after_setup=None) -> dict:
    """Returns {"line": ..., "expected": ..., "info": ...}. Raises
    RunError where the run cannot report. `overrides` (sizes), and
    `node_hook` are for the CPU rehearsal; `backend_under_test`,
    `node_hook` and `after_setup` are how benchmark/control.py plants
    the control and the faults, which have to come out as not correct."""
    import jax
    from stellar_core_tpu import native
    from stellar_core_tpu.parallel.device import device_info

    cell = manifest.cell(cell_name)
    config = manifest.config_params(cell_name)
    workload = manifest.workload_params(cell_name)
    for section, changes in (overrides or {}).items():
        target = config if section == "config" else workload
        for k, v in changes.items():
            if isinstance(v, dict) and isinstance(target.get(k), dict):
                target[k].update(v)
            else:
                target[k] = v
    if backend_under_test is not None:
        config["backend_under_test"] = backend_under_test
    dev = device_info()
    _listen_for_compiles()
    missing = {k: v for k, v in native.engine_status().items() if v}
    if missing:
        raise RunError("native engines missing: %r" % missing)
    annotate.enable(trace)
    driver = importlib.import_module(
        "benchmark.deployments." + config["driver"])
    workdir = tempfile.mkdtemp(prefix="sct-bench-")
    trace_dir = keep_trace or tempfile.mkdtemp(prefix="sct-bench-trace-")
    # the program's flight recorder writes where this says (else /tmp)
    flight_dir = os.path.join(workdir, "flight")
    os.makedirs(flight_dir)
    os.environ["SCT_FLIGHT_DIR"] = flight_dir
    dep = None
    try:
        dep = driver.Deployment(config, workload, seed, workdir, trace,
                                node_hook=node_hook)
        info = dep.setup()
        say("set-up: %r" % (info,))
        if after_setup is not None:
            after_setup(dep)
        compiles_before = _compiles["n"]
        t_begin = time.perf_counter()
        setup_s = t_begin - t_process
        slicer = None
        tick = _no_tick
        if trace:
            slicer = TraceSlice(trace_dir, t_begin, seconds,
                                workload["trace_slice"],
                                dep.device_counters)
            tick = slicer.tick
        dep.window(seconds, tick)
        if slicer is not None:
            slicer.stop()
        window_compiles = _compiles["n"] - compiles_before
        dep.drain()
        peak = memory_peak_bytes()
        counts = dep.counts()
        values = dep.end_to_end()
        values["setup_s"] = setup_s
        spans = counts.pop("spans", [])
        say("window: %r" % ({k: v for k, v in counts.items()},))
        say("end to end: %r" % (values,))

        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["count"], "memory_peak_bytes": peak}
        breakdown = None
        if trace:
            path = trace_reduce.find_xplane(trace_dir)
            pd = trace_reduce.load(path)
            red = trace_reduce.reduce_trace(
                pd, dev["platform"],
                {"ed25519": tuple(config["verify_modules"])})
            in_slice = {k: slicer.c1[k] - slicer.c0[k] for k in slicer.c0}
            say("trace: %d bytes; the program counted %r in the slice, "
                "the trace holds %d runs of the verify executable there"
                % (os.path.getsize(path), in_slice, red["kernel_runs"]))
            # a device trace buffer that overflowed drops events in
            # silence: the trace has to hold the dispatches that the
            # program counted (one either way may straddle an end)
            counted = in_slice["dispatches"] + in_slice["warm_runs"]
            if abs(red["kernel_runs"] - counted) > 2 \
                    and dev["platform"] == "tpu":
                raise RunError(
                    "the trace holds %d runs of the verify executable in "
                    "the slice and the program counted %d (dispatches and "
                    "warm-up runs): the trace is not whole"
                    % (red["kernel_runs"], counted))
            say("trace: window %.6f s busy %.6f s on %s; modules %r; "
                "stopping the profiler took %.3f s" % (
                    red["window_s"], red["busy_s"], red["device"],
                    red["modules_s"], slicer.stop_cost_s))
            device["window_s"] = red["window_s"]
            device["busy_s"] = red["busy_s"]
            breakdown = red["breakdown"]
            ctx = {"counts": counts, "spans": spans, "trace": red,
                   "slice_counts": in_slice,
                   "device_kind": dev["device_kind"],
                   "platform": dev["platform"]}
            for m in manifest.per_layer(cell_name):
                v = read_metric(manifest, m["name"], ctx)
                if v is None:
                    raise RunError(
                        "per-layer metric %r found nothing to read in "
                        "this run (its reader returned nothing)"
                        % m["name"])
                values[m["name"]] = v

        compared = dep.compare()
        compared["window_compiles"] = {"value": window_compiles, "limit": 0}
        correct = all(_holds(c) for c in compared.values())
        attempted, failed = dep.attempted_failed()
        expected = manifest.expected_metrics(cell_name, trace)
        missing = [k for k in expected if k not in values]
        if missing:
            raise RunError("no value for %s" % ", ".join(missing))
        extra = {k: v for k, v in values.items() if k not in expected}
        if extra:
            say("beside the manifest's metrics: %r" % (extra,))
        line = build(correct, attempted, failed,
                     {k: values[k] for k in expected}, expected, device,
                     compared, breakdown)
        return {"line": line, "expected": expected, "info": info,
                "counts": counts, "chips": cell["chips"]}
    finally:
        if dep is not None:
            try:
                dep.release()
            except Exception as e:     # the result stands without it
                print("[bench] release failed: %r" % (e,), file=sys.stderr)
        shutil.rmtree(workdir, ignore_errors=True)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _no_tick(_now: float) -> bool:
    return False


def _holds(c: dict) -> bool:
    if c.get("need") == "min":
        return c["value"] >= c["limit"]
    return c["value"] <= c["limit"]


def print_compared(compared: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, c in compared.items():
        print("compared %s = %r (%s %r)%s" % (
            name, c["value"], "at least" if c.get("need") == "min"
            else "at most", c["limit"],
            "" if _holds(c) else "  <-- NOT MET"),
            file=sys.stderr, flush=True)
