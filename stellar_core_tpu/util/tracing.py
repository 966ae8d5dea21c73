"""Span tracer + flight recorder: the close path explains itself.

Role parity: the reference leans on medida timers plus hand-run `perf`
for latency attribution; DSig-style pipelines (PAPERS.md) show why a
replicated signature pipeline needs per-stage spans instead — the
headline numbers (batch-verify throughput, replay speedup) are only
auditable when every BENCH artifact carries a machine-generated phase
breakdown. This module provides:

- `Tracer`: nested spans with tags, recorded into a bounded ring buffer.
  Disabled (the default) it is one attribute check per span — cheap
  enough to leave the instrumentation permanently in the hot paths
  (tests/test_tracing.py pins the disabled-overhead guard).
- Chrome-trace-event export (`to_chrome_trace`) for chrome://tracing /
  Perfetto, served by the admin `trace` endpoint.
- `phase_breakdown`: exclusive (self-time) per-phase totals computed
  from real spans — what bench.py embeds in BENCH_*.json so device vs
  fallback verify attribution is structural, not prose.
- `FlightRecorder`: snapshots the last N spans + the metrics registry to
  a JSON file on unhandled close exceptions and on SCP-stall /
  slow-close watchdog triggers, so a wedged or stalled node leaves a
  black box behind instead of a mystery.

Threading: span stacks are thread-local (worker-thread dispatches nest
correctly); the ring buffer append is a deque op under a lock only on
the multi-producer paths' writes — GIL-atomic deque.append keeps the
single-threaded hot path lock-free.

Across threads a span names its `cause`: the span (usually on another
thread) that handed the work over. `parent` stays "the enclosing span on
this thread" and is all that self-time arithmetic reads — a worker's
span runs concurrently with its cause and must never be subtracted from
it. `Tracer.record` writes a completed span for an interval measured
where it happened (a queue wait, a consensus slot). While enabled, every
`with` span is mirrored into the JAX profiler's trace as a
`TraceAnnotation` of the same name, so program spans sit on the device
trace's clock — only when `jax` is already loaded: cpu-backend nodes
never import it because of tracing.

Beneath every span runs the interpreter, and CPython's collector stops
all of its threads at once. `GcHook` (ONE `gc.callbacks` entry a
process, installed when the first `Tracer` is built) keeps the process's
totals always and, while a tracer is enabled, writes each collection as
a `runtime.gc.young` / `runtime.gc.full` span into it.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
import traceback
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional

from .log import get_logger
from .metrics import Histogram

log = get_logger("Perf")

DEFAULT_CAPACITY = 16384


class Span:
    """One completed (or in-flight) traced region."""

    __slots__ = ("name", "cat", "t0", "dur", "tags", "tid", "sid",
                 "parent", "cause", "_tracer", "_mirror")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 tags: Optional[dict], cause: int = 0) -> None:
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.tags: Optional[dict] = tags
        self.tid = threading.get_ident()
        self.sid = 0
        self.parent = 0    # the enclosing span on THIS thread
        self.cause = cause  # the span, on any thread, that led to this one
        self.t0 = 0.0
        self.dur: Optional[float] = None   # None while open
        self._mirror = None

    live = True

    def set_tag(self, key: str, value) -> "Span":
        if self.tags is None:
            self.tags = {}
        self.tags[key] = value
        return self

    def __enter__(self) -> "Span":
        note = _annotation(self.name)
        if note is not None:
            note.__enter__()
            self._mirror = note
        self._tracer._push(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.set_tag("error", exc_type.__name__)
        self._tracer._pop(self)
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
            self._mirror = None
        return False

    def to_dict(self) -> dict:
        d = {"name": self.name, "cat": self.cat, "ts": self.t0,
             "dur": self.dur, "tid": self.tid, "sid": self.sid,
             "parent": self.parent}
        if self.cause:
            d["cause"] = self.cause
        if self.tags:
            d["tags"] = dict(self.tags)
        return d


def _annotation(name: str):
    """The profiler's host-plane twin of a span, or None where `jax` is
    not loaded (or is still being imported by another thread). Outside a
    profiler session a TraceMe is a flag check."""
    prof = getattr(sys.modules.get("jax"), "profiler", None)
    cls = getattr(prof, "TraceAnnotation", None)
    return cls(name) if cls is not None else None


class _NoopSpan:
    """Shared do-nothing span handed out while tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_tag(self, key: str, value) -> "_NoopSpan":
        return self

    sid = 0         # a disabled span causes nothing
    live = False    # `if sp.live:` guards a tag that costs work to compute


_NOOP = _NoopSpan()


def tracer_span(tracer, name: str, cat: str = "core", cause: int = 0,
                **tags):
    """The single tracer-guard: a span against a possibly-absent,
    possibly-disabled tracer. Every instrumentation site goes through
    this (or the wrappers below) so the enable semantics live in one
    place."""
    if tracer is None or not tracer.enabled:
        return _NOOP
    return tracer.span(name, cat, cause=cause, **tags)


def tracer_instant(tracer, name: str, cat: str = "core", **tags) -> None:
    if tracer is not None and tracer.enabled:
        tracer.instant(name, cat, **tags)


def app_span(app, name: str, cat: str = "core", **tags):
    """Span against `app.tracer`, tolerating apps (test doubles, partial
    wirings) that have no tracer at all — the instrumentation sites must
    never require one."""
    return tracer_span(getattr(app, "tracer", None), name, cat, **tags)


def enabled_tracer(tracer):
    """`tracer` while it is enabled, else None: the guard of a site that
    stamps the tracer's clock or writes a completed span (`record`)."""
    return tracer if tracer is not None and tracer.enabled else None


def app_tracer(app):
    """`app.tracer` while it is enabled, else None (apps without a
    tracer included)."""
    return enabled_tracer(getattr(app, "tracer", None))


class GcHook:
    """CPython's cyclic collector, as the process and its tracers see it.

    One instance a process (`GC_HOOK`), one `gc.callbacks` entry however
    many nodes the process builds. A collection stops every thread, so
    its time is inside whatever span was open, on every thread: the hook
    is what gives it a name.

    Always on: totals by generation in plain ints and floats (a few item
    writes a collection), read by `util/footprint.py::process_stats()`
    (`GET footprint`), by `GET metrics` (`runtime.gc.pause`,
    `runtime.gc.full.pause`: built from these totals at the scrape, no
    registry is written at a collection) and by `ledger.close`.

    While a tracer is enabled (a tracer enrols in `enable()` and leaves
    in `disable()`; held weakly, a dead one drops out): "start" enters
    the profiler's annotation on the collecting thread, "stop" leaves it
    and `record()`s one completed span into every enabled tracer. The
    span's `parent` is 0, so no other span's self time changes; `under`
    names the span it interrupted.

    The callback takes no lock and calls nothing that does: a collection
    can start wherever a thread allocates, under any lock of the
    program's. `_tracers` is a tuple that `enrol` / `leave` replace
    whole, under a lock of their own."""

    RECENT = 1028       # pauses kept for the timers' quantiles

    def __init__(self) -> None:
        self.collections = [0, 0, 0]        # by generation
        self.pause_s = [0.0, 0.0, 0.0]
        self.max_pause_s = [0.0, 0.0, 0.0]
        self.collected = [0, 0, 0]
        self.uncollectable = [0, 0, 0]
        self.pause_total_s = 0.0            # all generations
        self._recent: deque = deque(maxlen=self.RECENT)
        self._recent_full: deque = deque(maxlen=self.RECENT)
        self._tracers: tuple = ()           # weakrefs to enabled tracers
        self._lock = threading.Lock()       # writers of `_tracers`
        self._t0 = 0.0                      # 0.0: no collection running
        self._note = None

    # -- enrolment -----------------------------------------------------------
    def install(self) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)

    def enrol(self, tracer: "Tracer") -> None:
        self._replace(tracer, (weakref.ref(tracer),))

    def leave(self, tracer: "Tracer") -> None:
        self._replace(tracer, ())

    def _replace(self, tracer: "Tracer", refs: tuple) -> None:
        with self._lock:
            self._tracers = tuple(
                r for r in self._tracers
                if r() is not None and r() is not tracer) + refs

    def enrolled(self) -> List["Tracer"]:
        return [t for t in (r() for r in self._tracers) if t is not None]

    # -- the callback --------------------------------------------------------
    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if self._tracers:
                note = _annotation(_gc_span_name(info["generation"]))
                if note is not None:
                    note.__enter__()
                    self._note = note
            self._t0 = time.perf_counter()
            return
        if not self._t0:        # installed inside a collection: no start
            return
        dur = time.perf_counter() - self._t0
        self._t0 = 0.0
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        g = info["generation"]
        self.collections[g] += 1
        self.pause_s[g] += dur
        if dur > self.max_pause_s[g]:
            self.max_pause_s[g] = dur
        self.collected[g] += info["collected"]
        self.uncollectable[g] += info["uncollectable"]
        self.pause_total_s += dur
        self._recent.append(dur)
        if g == 2:
            self._recent_full.append(dur)
        for ref in self._tracers:
            tr = ref()
            if tr is not None and tr.enabled:
                st = tr._stack()    # the collecting thread's
                tr.record(_gc_span_name(g), "runtime", tr.now() - dur, dur,
                          generation=g, collected=info["collected"],
                          uncollectable=info["uncollectable"],
                          under=st[-1].name if st else "")

    # -- exports -------------------------------------------------------------
    def stats(self) -> dict:
        """The `gc` object of `process_stats()`."""
        return {"collections": sum(self.collections),
                "pause_s": round(self.pause_total_s, 6),
                "generations": [
                    {"collections": self.collections[g],
                     "pause_s": round(self.pause_s[g], 6),
                     "max_pause_s": round(self.max_pause_s[g], 6),
                     "collected": self.collected[g],
                     "uncollectable": self.uncollectable[g]}
                    for g in range(3)]}

    def timers(self) -> dict:
        """`runtime.gc.pause` / `runtime.gc.full.pause` in the shape a
        registry timer exports: count, mean and max from the totals,
        min and the quantiles from the last `RECENT` pauses."""
        return {
            "runtime.gc.pause": _timer_json(
                sum(self.collections), self.pause_total_s,
                max(self.max_pause_s), list(self._recent)),
            "runtime.gc.full.pause": _timer_json(
                self.collections[2], self.pause_s[2],
                self.max_pause_s[2], list(self._recent_full))}


def _gc_span_name(generation: int) -> str:
    return "runtime.gc.full" if generation == 2 else "runtime.gc.young"


def _timer_json(count: int, total: float, mx: float,
                recent: List[float]) -> dict:
    s = sorted(recent)
    pick = Histogram._pick
    return {"type": "timer", "count": count,
            "mean": total / count if count else 0.0,
            "min": s[0] if s else 0.0, "max": mx,
            "median": pick(s, 0.5), "p75": pick(s, 0.75),
            "p95": pick(s, 0.95), "p99": pick(s, 0.99)}


GC_HOOK = GcHook()


class Tracer:
    """Bounded-ring span recorder; see module docstring."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 now_fn: Callable[[], float] = time.perf_counter) -> None:
        self.enabled = False
        self._now = now_fn
        self._buf: deque = deque(maxlen=capacity)
        self._tls = threading.local()
        # lock-free (the collector's hook records from wherever a
        # collection starts, perhaps under a lock of the caller's)
        self._sids = itertools.count(1)
        self.dropped = 0   # spans evicted from the ring since enable()
        GC_HOOK.install()

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    # -- lifecycle -----------------------------------------------------------
    def enable(self, capacity: Optional[int] = None) -> None:
        if capacity is not None and capacity != self._buf.maxlen:
            self._buf = deque(self._buf, maxlen=capacity)
        self.dropped = 0
        self.enabled = True
        GC_HOOK.enrol(self)

    def disable(self) -> None:
        self.enabled = False
        GC_HOOK.leave(self)

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0

    # -- recording -----------------------------------------------------------
    def now(self) -> float:
        """The tracer's clock, for a `t0` handed to `record` later. Sites
        stamp it only when enabled."""
        return self._now()

    def current_sid(self) -> int:
        """The innermost span open on the calling thread (0: none, or
        disabled): capture it before handing work to a thread or to
        `post_to_main`, and pass it on as that work's `cause`."""
        if not self.enabled:
            return 0
        st = self._stack()
        return st[-1].sid if st else 0

    def span(self, name: str, cat: str = "core", cause: int = 0, **tags):
        """`with tracer.span("close.apply", seq=7):` — returns a shared
        no-op when disabled; tag values must be JSON-serializable.
        `cause` is the sid of the span that led to this one from another
        thread (`current_sid()` there)."""
        if not self.enabled:
            return _NOOP
        return Span(self, name, cat, tags or None, cause)

    def record(self, name: str, cat: str, t0: float, dur: float,
               cause: int = 0, **tags) -> None:
        """A completed span for an interval that was measured where it
        happened (a queue wait, a slot, a timer wait); `t0` is on this
        tracer's clock (`now()`). Its `parent` is 0 whatever is open on
        the thread, so it never changes another span's self time. It is
        ring-only: the profiler's trace cannot be backdated (the
        collector's hook enters its annotation itself, in real time)."""
        if not self.enabled:
            return
        s = Span(self, name, cat, tags or None, cause)
        s.t0 = t0
        s.dur = max(0.0, dur)
        s.sid = self._new_sid()
        self._record(s)

    def instant(self, name: str, cat: str = "core", **tags) -> None:
        """Zero-duration marker event (Chrome 'i' phase)."""
        if not self.enabled:
            return
        s = Span(self, name, cat, tags or None)
        s.t0 = self._now()
        s.dur = 0.0
        s.sid = self._new_sid()
        s.parent = self._stack()[-1].sid if self._stack() else 0
        self._record(s)

    def _new_sid(self) -> int:
        return next(self._sids)

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, span: Span) -> None:
        st = self._stack()
        span.sid = self._new_sid()
        span.parent = st[-1].sid if st else 0
        st.append(span)
        span.t0 = self._now()

    def _pop(self, span: Span) -> None:
        span.dur = self._now() - span.t0
        st = self._stack()
        # tolerate mismatched exits (a span leaked across an exception):
        # unwind to and including this span
        while st:
            top = st.pop()
            if top is span:
                break
        self._record(span)

    def _record(self, span: Span) -> None:
        if len(self._buf) == self._buf.maxlen:
            self.dropped += 1
        self._buf.append(span)

    # -- inspection ----------------------------------------------------------
    def spans(self, last_n: Optional[int] = None) -> List[Span]:
        out = list(self._buf)
        if last_n is not None:
            # guard last_n=0: out[-0:] would be the WHOLE list
            out = out[-last_n:] if last_n > 0 else []
        return out

    def open_spans(self) -> List[Span]:
        """In-flight spans on the CALLING thread (flight-recorder dumps
        run on the thread that hit the trigger, which is the interesting
        stack)."""
        return list(self._stack())

    def to_chrome_trace(self, last_n: Optional[int] = None) -> dict:
        """Chrome trace-event JSON (chrome://tracing, Perfetto): complete
        ('X') events with microsecond timestamps, tags under args."""
        events = []
        for s in self.spans(last_n):
            ev = {"name": s.name, "cat": s.cat,
                  "ph": "X" if s.dur else "i",
                  "ts": round(s.t0 * 1e6, 1),
                  "dur": round((s.dur or 0.0) * 1e6, 1),
                  "pid": os.getpid(), "tid": s.tid}
            if s.tags or s.cause:
                ev["args"] = dict(s.tags or {})
                if s.cause:
                    ev["args"]["cause"] = s.cause
            if ev["ph"] == "i":
                ev["s"] = "t"
                del ev["dur"]
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "dropped_spans": self.dropped}

    # -- phase attribution ---------------------------------------------------
    def phase_breakdown(self, wall_s: Optional[float] = None,
                        phase_of: Optional[Callable[[Span],
                                                    Optional[str]]] = None,
                        ) -> dict:
        """Exclusive per-phase totals from the recorded spans.

        Self-time = span duration minus its direct children's durations,
        so nested spans (verify drains inside an apply span) never double
        count. Default phase key is the span name with a `backend` tag
        appended (`crypto.verify_many:tpu` vs `:cpu`) — the device-vs-
        fallback attribution the r5 postmortem demanded. With `wall_s`,
        adds an `untraced` phase (wall minus the dominant thread's root
        spans) so the totals sum to the measured wall exactly on
        single-threaded runs; concurrent worker-thread spans (tpu-async
        dispatches) still report their own self-time, so accounted_s may
        legitimately exceed wall then.
        """
        spans = [s for s in self._buf if s.dur is not None]
        child_time: Dict[int, float] = {}
        for s in spans:
            if s.parent:
                child_time[s.parent] = child_time.get(s.parent, 0.0) + s.dur
        phases: Dict[str, dict] = {}
        root_by_tid: Dict[int, float] = {}
        for s in spans:
            if phase_of is not None:
                key = phase_of(s)
                if key is None:
                    continue
            else:
                key = s.name
                if s.tags and "backend" in s.tags:
                    key = "%s:%s" % (key, s.tags["backend"])
                    # actual backing platform when it differs from the
                    # configured backend — a jax-on-CPU "tpu" drain keys
                    # as crypto.verify_many:tpu@cpu, not as device time
                    plat = s.tags.get("platform")
                    if plat and plat != s.tags["backend"]:
                        key = "%s@%s" % (key, plat)
            self_s = max(0.0, s.dur - child_time.get(s.sid, 0.0))
            p = phases.setdefault(key, {"total_s": 0.0, "count": 0})
            p["total_s"] += self_s
            p["count"] += 1
            if not s.parent:
                root_by_tid[s.tid] = root_by_tid.get(s.tid, 0.0) + s.dur
        out = {"phases": phases, "dropped_spans": self.dropped}
        if wall_s:
            # wall is covered by the DOMINANT thread's roots (the main
            # loop); worker-thread roots run concurrently with it and
            # must not deflate `untraced` (an async-backend dispatch span
            # overlaps a close span — summing both would clamp untraced
            # to 0 and push pct_of_wall past 100)
            root_total = max(root_by_tid.values(), default=0.0)
            untraced = max(0.0, wall_s - root_total)
            phases["untraced"] = {"total_s": untraced, "count": 1}
            out["wall_s"] = wall_s
        total = sum(p["total_s"] for p in phases.values())
        out["accounted_s"] = round(total, 6)
        for p in phases.values():
            p["total_s"] = round(p["total_s"], 6)
            if wall_s:
                p["pct_of_wall"] = round(100.0 * p["total_s"] / wall_s, 2)
        return out


class FlightRecorder:
    """Black box: on a trigger, snapshot the tracer ring + open spans +
    metrics registry to
    `<dir>/sct-flight[-<node>]-<reason>-<t>-<seq>.json` (node name +
    zero-padded app-clock stamp + per-recorder sequence: concurrent
    multi-node chaos runs sharing a directory — and repeat dumps at an
    unchanged virtual clock — never overwrite each other's evidence).
    Dump failures are logged, never raised — the recorder must not turn
    a stall into a crash."""

    def __init__(self, tracer: Tracer, metrics=None,
                 out_dir: Optional[str] = None,
                 max_spans: int = 512,
                 min_interval_s: float = 60.0,
                 node_name: str = "",
                 now_fn: Optional[Callable[[], float]] = None) -> None:
        import tempfile
        self.tracer = tracer
        self.metrics = metrics
        self.out_dir = (out_dir or os.environ.get("SCT_FLIGHT_DIR")
                        or tempfile.gettempdir())
        # node name + app-clock stamp go into every dump filename so
        # concurrent multi-node chaos runs sharing one directory never
        # overwrite each other's incident evidence
        self.node_name = node_name
        self._now = now_fn or time.monotonic
        self.max_spans = max_spans
        # per-reason cooldown: a sustained burst of triggers (every slow
        # close in a slow patch) must not re-serialize the registry on
        # each close nor overwrite the FIRST incident's evidence — the
        # first dump in a burst is the interesting one
        self.min_interval_s = min_interval_s
        self._last_dump_at: Dict[str, float] = {}
        self.dumps = 0
        self.suppressed = 0
        self.last_path: Optional[str] = None

    def dump(self, reason: str, exc: Optional[BaseException] = None,
             extra: Optional[dict] = None,
             force: bool = False) -> Optional[str]:
        try:
            now = time.monotonic()
            last = self._last_dump_at.get(reason)
            if not force and last is not None and \
                    now - last < self.min_interval_s:
                self.suppressed += 1
                return None
            self._last_dump_at[reason] = now
            blob = {
                "reason": reason,
                "at_unix": int(time.time()),
                "pid": os.getpid(),
                "spans": [s.to_dict()
                          for s in self.tracer.spans(self.max_spans)],
                "open_spans": [s.to_dict()
                               for s in self.tracer.open_spans()],
                "dropped_spans": self.tracer.dropped,
                "tracing_enabled": self.tracer.enabled,
            }
            if exc is not None:
                blob["exception"] = {
                    "type": type(exc).__name__,
                    "message": str(exc),
                    "traceback": traceback.format_exception(
                        type(exc), exc, exc.__traceback__),
                }
            if self.metrics is not None:
                blob["metrics"] = self.metrics.to_json()
            if extra:
                blob["extra"] = extra
            def _safe(s: str) -> str:
                return "".join(c if c.isalnum() or c in "-_" else "-"
                               for c in s)
            parts = ["sct-flight"]
            if self.node_name:
                parts.append(_safe(self.node_name))
            parts.append(_safe(reason))
            # app-clock stamp + per-recorder sequence: two forced dumps
            # at an UNCHANGED virtual clock must still get distinct
            # paths, or the second overwrites the first's evidence
            parts.append("%012.3f" % max(0.0, self._now()))
            parts.append("%03d" % self.dumps)
            path = os.path.join(self.out_dir,
                                "-".join(parts) + ".json")
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(blob, fh, indent=1, default=repr)
            os.replace(tmp, path)
            self.dumps += 1
            self.last_path = path
            log.warning("flight recorder dumped %r to %s", reason, path)
            return path
        except Exception as e:   # noqa: BLE001 - recorder never raises
            log.error("flight recorder dump failed: %s", e)
            return None
