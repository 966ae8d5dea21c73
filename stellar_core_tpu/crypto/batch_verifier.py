"""SigVerifier: the config-gated crypto backend boundary.

North-star parity (BASELINE.json / SURVEY.md intro): the reference calls
libsodium synchronously one signature at a time
(/root/reference/src/crypto/SecretKey.cpp:310-337). Here the boundary is a
batch-oriented service from day one:

    enqueue(key, sig, msg) -> VerifyFuture     (accumulate)
    flush()                                    (dispatch one device batch)
    prewarm_many(triples) -> [bool]            (whole-ledger/checkpoint drain)
    verify_many(triples) -> [bool]             (the same, past the cache)

One boundary over plain engines (drawn in
docs/architecture.md#verifier-boundary):

- SigVerifier owns every decision that is not an engine's: the verdict
  cache in front (hits never enqueue; `_cache_probe` / `_cache_store`
  are the only code here that touches it), the one pending queue, where
  a flush runs (inline, or on the `crypto.verify-dispatch` worker with
  futures completing on the VirtualClock main loop, keeping the
  single-threaded consensus invariant, docs/architecture.md:23-26), and
  the circuit breaker between the engine and a CPU fallback: N
  consecutive dispatch failures trip to the fallback for a cooldown
  window with periodic reprobe, so a lost TPU degrades throughput
  instead of killing a ledger close (docs/robustness.md; DSig-style
  degraded operating mode).
- CpuSigVerifier: synchronous OpenSSL engine (reference's libsodium
  role).
- TpuSigVerifier: ships the triples it is given to the JAX ed25519
  kernel in padded, fixed-shape device calls (no recompiles); scales
  from a few envelopes (live SCP) to whole checkpoints (catchup replay).
- VerifierContext: what the three share by reference (verdict cache,
  tracer, metrics, fault injector, VerifierStats cockpit, flight
  recorder), handed to each at construction.

Clock/threading audit (ISSUE 5 satellite; the touch points):
1. CircuitBreaker.now_fn: injected app clock (make_verifier passes
   clock.now); default is util.timer.real_monotonic for direct
   constructions. Cooldown/reprobe advance deterministically under a
   virtual clock.
2-4. SigVerifier enqueue/dispatch/complete stamps (a boundary built
   with a clock): all three read the injected app clock, so the
   queue-wait gauges and the crypto.verify.latency timer are
   virtual-clock-deterministic in chaos soaks (module-level `time` is
   gone from this file; the D1 static rule keeps it out). The
   crypto.queue_wait.<class> spans are stamped on the tracer's own
   clock, and only while tracing is on.
5. SigVerifier._lock: TrackedLock("crypto.threaded-pending") over the
   pending queue, watched by the lock-order checker (util/threads.py).
6. SigVerifier dispatch worker ("crypto.verify-dispatch", only where
   the boundary has a clock): dispatch off-main; futures complete via
   clock.post_to_main only (single-threaded consensus).
7. TpuSigVerifier._warmup_thread: startup-only, touches JAX state, no
   ledger/consensus objects.
8. keys._cache_lock: TrackedLock shared with the worker thread.
9. SigVerifier breaker callbacks (_on_trip/_on_recover): run on
   whichever thread dispatched (the worker under tpu-async): they
   touch only metrics/tracer/flight-recorder, which are thread-safe.
10. VerifierStats (the ISSUE 6 cockpit): event stamps read the
    injected app clock (now_fn), compile DURATIONS read
    util.timer.real_monotonic (sanctioned: an XLA compile takes real
    time under a frozen virtual clock); recorded from the main loop,
    the dispatch worker, the staging worker and the warmup thread under
    its own TrackedLock("crypto.verifier-stats").
11. _StagingJob worker ("crypto.verify-staging", ISSUE 11): packs and
    device_puts the next drain chunk while the fleet executes the
    current one; touches only host numpy buffers, JAX transfer APIs and
    VerifierStats (thread-safe), never ledger/consensus objects.
    Overlap DURATIONS read util.timer.real_monotonic (sanctioned: the
    host/device overlap being measured is real elapsed time).
12. DeviceFleetHealth per-device breakers: same injected app clock as
    the boundary's breaker (make_verifier passes clock.now), so
    per-chip cooldown/reprobe advance deterministically under a
    virtual clock; callbacks touch only metrics/tracer/flight-recorder.

All three crypto workers (dispatch, staging, warmup) spawn through
util.threads.spawn_worker under names registered in
WORKER_THREAD_REGISTRY; the static T1 rule follows spawn_worker targets
like any Thread(target=...) site.
"""

from __future__ import annotations

import bisect
import threading
from typing import Callable, List, Optional, Sequence, Tuple

from ..util.log import get_logger
from ..util.metrics import MetricsRegistry
from ..util.threads import TrackedLock, spawn_worker
from ..util.timer import real_monotonic
from ..util.tracing import enabled_tracer, tracer_instant, tracer_span
from ..xdr import PublicKey
from . import keys as _keys

log = get_logger("Perf")

Triple = Tuple[bytes, bytes, bytes]  # (key32, sig, msg)


class VerifierStats:
    """Cockpit aggregation for the batch-verify boundary (ISSUE 6
    tentpole; docs/observability.md#device-cockpit).

    One instance per make_verifier() call, in the VerifierContext the
    boundary, the engine and the CPU fallback share, so drains are
    attributed to the backend that actually SERVED them
    (a fallback drain while the breaker is open counts against "cpu",
    never against the device). The same aggregate objects feed three
    consumers:

    - the admin `verifier` endpoint (`to_json`): per-bucket occupancy /
      pad-waste histograms, warmup + compile-cache status, queue depth;
    - the metrics registry (`verifier.*` names) — which makes the whole
      cockpit scrapeable via `metrics?format=prometheus`;
    - the tracer: `verifier.warmup.*` instants, so compile/warmup
      progress appears in Chrome traces and flight dumps.

    Clocks: event STAMPS (`t` fields) read the injected app clock
    (`now_fn` = clock.now via make_verifier), so chaos soaks under a
    virtual clock stay deterministic; compile DURATIONS are real
    elapsed seconds via util.timer.real_monotonic — an XLA compile
    takes real time even while the app clock is frozen. Recording
    happens on the main loop, the threaded dispatch worker and the
    warmup thread; aggregate mutation is under `_lock`, registry
    metric objects are individually thread-safe."""

    def __init__(self, metrics=None, tracer=None, now_fn=None,
                 flight_recorder=None) -> None:
        self._now = now_fn or real_monotonic
        # a private registry when none is injected keeps direct
        # constructions (tests, bench children) app-registry-free while
        # letting every registration below use the new_* idiom the M1
        # metric-catalog scanner keys on
        self.metrics = metrics if metrics is not None \
            else MetricsRegistry(now_fn=self._now)
        self.tracer = tracer
        self.flight_recorder = flight_recorder
        self._lock = TrackedLock("crypto.verifier-stats")
        self.backends: dict = {}      # name -> {drains, sigs, pad_total}
        self.buckets: dict = {}       # bucket -> counts + histograms
        # per-device fleet attribution (ISSUE 11): device index ->
        # {drains, sigs, pad_total, inflight} for every padded dispatch
        # the device participated in
        self.devices: dict = {}
        # non-bucketed (CPU-path) drain sizes, power-of-two quantized so
        # the dict stays bounded: the raw material bucket_traffic() maps
        # onto the candidate ladder for cockpit-driven warm start
        self.drain_sizes: dict = {}   # backend -> {quantized_n: drains}
        # double-buffer staging aggregate (host pack/device_put overlap
        # with device execution, ISSUE 11 tentpole)
        self.staging = {"chunks": 0, "staged_s": 0.0, "overlap_s": 0.0,
                        "last_overlap_pct": None, "stalls": 0}
        self.queue = {"depth": 0, "inflight": 0,
                      "wait_last_mean_ms": None, "wait_last_max_ms": None}
        # streamed checkpoint drains (DrainStream): chunks handed to the
        # worker, chunks landed in the verdict cache, closes that found
        # their chunk not yet landed
        self.drain_stream = {"chunks": 0, "landed": 0, "gated": 0}
        self.warmup = {"state": "idle", "planned": [], "source": None,
                       "begun_t": None, "done_t": None, "error": None,
                       "buckets": {}}
        self.compile_cache = {"enabled": None, "dir": None, "hits": 0,
                              "misses": 0, "unknown": 0, "error": None}
        # fixed-name registry metrics, created eagerly so the Prometheus
        # export carries the full cockpit shape from the first scrape
        m = self.metrics
        self._h_batch = m.new_histogram("verifier.drain.batch-size")
        self._h_pad = m.new_histogram("verifier.drain.pad-waste")
        self._h_occ = m.new_histogram("verifier.drain.occupancy-pct")
        self._h_splits = m.new_histogram("verifier.drain.splits")
        self._h_wsec = m.new_histogram("verifier.warmup.bucket-seconds")
        self._t_wait = m.new_timer("verifier.queue.wait")
        self._g_depth = m.new_gauge("verifier.queue.depth")
        self._g_inflight = m.new_gauge("verifier.queue.inflight")
        self._g_overlap = m.new_gauge("verifier.staging.overlap-pct")
        self._g_wstate = m.new_gauge("verifier.warmup.state")
        self._g_wdone = m.new_gauge("verifier.warmup.buckets-done")
        self._g_wsource = m.new_gauge("verifier.warmup.source")
        self._g_cc = m.new_gauge("verifier.compile-cache.enabled")
        self._c_h2d = m.new_counter("verifier.h2d.bytes")
        self._c_hit = m.new_counter("verifier.compile-cache.hit")
        self._c_miss = m.new_counter("verifier.compile-cache.miss")

    # -- drains --------------------------------------------------------------
    def record_drain(self, backend: str, n: int, pad: int = 0,
                     splits: int = 1, bucketed: bool = False) -> None:
        """One verify_many drain, attributed to the backend that served
        it. `pad` is the total padding-lane waste (0 on unpadded CPU
        drains — which still count, so bucket-selection analysis sees
        ALL traffic, not just the device path). `bucketed=True` means the
        drain's traffic already landed in the exact per-bucket dispatch
        stats (record_bucket_dispatch) — unbucketed drains additionally
        feed `drain_sizes`, the CPU-side half of bucket_traffic()."""
        occ = 100.0 * n / (n + pad) if (n + pad) else 100.0
        with self._lock:
            d = self.backends.setdefault(
                backend, {"drains": 0, "sigs": 0, "pad_total": 0})
            d["drains"] += 1
            d["sigs"] += n
            d["pad_total"] += pad
            if not bucketed and n > 0:
                q = 1 << (n - 1).bit_length()   # next power of two
                sizes = self.drain_sizes.setdefault(backend, {})
                sizes[q] = sizes.get(q, 0) + 1
        self._h_batch.update(n)
        self._h_pad.update(pad)
        self._h_occ.update(occ)
        self._h_splits.update(splits)
        self.metrics.new_meter("verifier.drains.%s" % backend).mark()

    def record_bucket_dispatch(self, bucket: int, n: int,
                               pad: int) -> None:
        """One padded device dispatch into a fixed bucket shape (the
        device path only — buckets come from TpuSigVerifier.BUCKETS, so
        the dynamic `verifier.bucket.<b>.*` name space stays bounded)."""
        occ = 100.0 * n / bucket if bucket else 100.0
        with self._lock:
            b = self.buckets.get(bucket)
            if b is None:
                b = self.buckets[bucket] = {
                    "drains": 0, "sigs": 0, "pad_total": 0,
                    "_occ": self.metrics.new_histogram(
                        "verifier.bucket.%d.occupancy-pct" % bucket),
                    "_pad": self.metrics.new_histogram(
                        "verifier.bucket.%d.pad-waste" % bucket),
                    "_m": self.metrics.new_meter(
                        "verifier.bucket.%d.drains" % bucket)}
            b["drains"] += 1
            b["sigs"] += n
            b["pad_total"] += pad
        b["_occ"].update(occ)
        b["_pad"].update(pad)
        b["_m"].mark()

    def record_h2d(self, nbytes: int) -> None:
        """One dispatch's host→device input: 128 bytes a lane of the
        bucket (ops/ed25519.py's packed array)."""
        with self._lock:    # dispatches run on the main and worker threads
            self._c_h2d.inc(nbytes)

    @property
    def h2d_bytes(self) -> int:
        """Bytes handed to the device, summed over dispatches."""
        return self._c_h2d.count

    # -- fleet: per-device attribution (ISSUE 11) ----------------------------
    def record_device_dispatch(self, idx: int, n: int, pad: int) -> None:
        """One device's share of a padded dispatch (its lanes on a
        sharded mesh drain, or the whole bucket on a single-device
        dispatch): per-device throughput attribution for the admin
        `verifier` endpoint's fleet rows."""
        with self._lock:
            d = self.devices.setdefault(
                idx, {"drains": 0, "sigs": 0, "pad_total": 0,
                      "inflight": 0})
            d["drains"] += 1
            d["sigs"] += n
            d["pad_total"] += pad
        self.metrics.new_meter("verifier.device.%d.drains" % idx).mark()

    def set_device_inflight(self, idx: int, inflight: bool) -> None:
        with self._lock:
            d = self.devices.setdefault(
                idx, {"drains": 0, "sigs": 0, "pad_total": 0,
                      "inflight": 0})
            d["inflight"] = int(inflight)
        self.metrics.new_gauge(
            "verifier.device.%d.inflight" % idx).set(int(inflight))

    def set_device_breaker(self, idx: int, code: int) -> None:
        self.metrics.new_gauge("verifier.device.%d.breaker" % idx).set(code)

    def device_trip(self, idx: int, breaker_json: dict) -> None:
        self.metrics.new_meter("verifier.device.trip").mark()
        tracer_instant(self.tracer, "verifier.device.trip", cat="crypto",
                       device=idx)
        if self.flight_recorder is not None:
            self.flight_recorder.dump(
                "verify-device-trip",
                extra={"device": idx, "breaker": breaker_json})

    def device_recover(self, idx: int) -> None:
        self.metrics.new_meter("verifier.device.recover").mark()
        tracer_instant(self.tracer, "verifier.device.recover",
                       cat="crypto", device=idx)

    # -- fleet: double-buffer staging ----------------------------------------
    def record_staging(self, staged_s: float, overlap_s: float,
                       chunks: int) -> None:
        """One drain's staging totals: `staged_s` of host pack +
        host→device transfer ran on the staging worker, `overlap_s` of
        it concurrent with device execution of the previous chunk. The
        overlap-pct gauge is the headline: near 100 means the device
        never idles on host marshalling."""
        pct = round(100.0 * overlap_s / staged_s, 1) if staged_s > 0 \
            else 100.0
        with self._lock:
            s = self.staging
            s["chunks"] += chunks
            s["staged_s"] = round(s["staged_s"] + staged_s, 6)
            s["overlap_s"] = round(s["overlap_s"] + overlap_s, 6)
            s["last_overlap_pct"] = pct
        self._g_overlap.set(pct)

    def record_staging_stall(self) -> None:
        """The staging worker failed (or the verify.staging-stall fault
        fired): the chunk re-staged synchronously on the dispatch
        thread — the drain completed, but the device idled."""
        with self._lock:
            self.staging["stalls"] += 1
        self.metrics.new_meter("verifier.staging.stall").mark()
        tracer_instant(self.tracer, "verifier.staging.stall", cat="crypto")

    def record_drain_stream(self, event: str) -> None:
        """One `chunks` / `landed` / `gated` event of a streamed drain."""
        with self._lock:
            self.drain_stream[event] += 1

    # -- cockpit-driven bucket selection -------------------------------------
    def bucket_traffic(self, candidates) -> dict:
        """Observed drain traffic mapped onto a candidate bucket ladder:
        exact per-bucket device dispatch counts plus every non-bucketed
        (CPU-path) drain size mapped to the smallest candidate that
        holds it. This is the evidence warmup_plan() ranks — CPU drains
        included, so bucket selection sees ALL traffic."""
        cands = sorted(candidates)

        def fit(n: int) -> int:
            for c in cands:
                if n <= c:
                    return c
            return cands[-1]

        out: dict = {}
        with self._lock:
            for b, d in self.buckets.items():
                out[fit(b)] = out.get(fit(b), 0) + d["drains"]
            for sizes in self.drain_sizes.values():
                for n, drains in sizes.items():
                    out[fit(n)] = out.get(fit(n), 0) + drains
        return out

    def bucket_occupancy_p50(self) -> dict:
        """Median occupancy-% per device bucket (None until sampled) —
        the pad-waste signal warmup_plan() uses to pre-warm the next
        smaller shape under a mostly-padding bucket."""
        out = {}
        with self._lock:
            for b, d in self.buckets.items():
                snap = d["_occ"].snapshot()
                out[b] = snap["median"] if snap["count"] else None
        return out

    # -- queue ---------------------------------------------------------------
    def set_queue_depth(self, depth: int) -> None:
        self.queue["depth"] = depth
        self._g_depth.set(depth)

    def set_inflight(self, inflight: bool) -> None:
        self.queue["inflight"] = int(inflight)
        self._g_inflight.set(int(inflight))

    def record_queue_wait(self, mean_s: float, max_s: float) -> None:
        self.queue["wait_last_mean_ms"] = round(mean_s * 1e3, 3)
        self.queue["wait_last_max_ms"] = round(max_s * 1e3, 3)
        self._t_wait.update(mean_s)

    # -- compile cache + warmup ---------------------------------------------
    def set_compile_cache_dir(self, path: Optional[str]) -> None:
        """Where JAX keeps its persistent compile cache, as warmup found
        it (parallel.device.compile_cache_dir); None = JAX has none."""
        if not path:
            self.compile_cache_error(
                "JAX has no persistent compile cache directory")
            return
        self.compile_cache.update(
            {"enabled": True, "dir": path, "error": None})
        self._g_cc.set(1)

    def compile_cache_error(self, err: str) -> None:
        """The node runs without a persistent compile cache: a meter, a
        tracer instant and a flight dump, because a node silently paying
        cold compiles on every restart is exactly the regression the
        cockpit exists to catch."""
        self.compile_cache.update({"enabled": False, "error": err})
        self._g_cc.set(0)
        self.metrics.new_meter("verifier.compile-cache.unavailable").mark()
        tracer_instant(self.tracer, "verifier.compile-cache.unavailable",
                       cat="crypto", error=err)
        if self.flight_recorder is not None:
            self.flight_recorder.dump("compile-cache-unavailable",
                                      extra={"error": err})

    WARMUP_STATE_CODE = {"idle": 0, "running": 1, "done": 2, "failed": 3}
    # where the warm-start bucket set came from: the hardcoded default
    # ladder, or the cockpit-derived plan persisted with the node's state
    WARMUP_SOURCE_CODE = {"default": 0, "cockpit": 1}

    def warmup_begin(self, buckets, source: str = "default") -> None:
        with self._lock:
            self.warmup.update({"state": "running", "begun_t": self._now(),
                                "done_t": None, "error": None,
                                "source": source,
                                "planned": list(buckets)})
        self._g_wstate.set(self.WARMUP_STATE_CODE["running"])
        self._g_wsource.set(self.WARMUP_SOURCE_CODE.get(source, 0))
        tracer_instant(self.tracer, "verifier.warmup.begin", cat="crypto",
                       buckets=list(buckets), source=source)

    def warmup_bucket_done(self, bucket: int, seconds: float,
                           cache_hit) -> None:
        """One bucket shape compiled (or loaded). `cache_hit` is
        parallel.device.cache_hit's reading of JAX's own cache events:
        True loaded, False compiled and written, None neither."""
        cache = ("hit" if cache_hit is True else
                 "miss" if cache_hit is False else "unknown")
        with self._lock:
            self.warmup["buckets"][str(bucket)] = {
                "seconds": round(seconds, 3), "cache": cache,
                "t": self._now()}
            done = len(self.warmup["buckets"])
            self.compile_cache[
                {"hit": "hits", "miss": "misses",
                 "unknown": "unknown"}[cache]] += 1
        self._h_wsec.update(seconds)
        self._g_wdone.set(done)
        if cache_hit is True:
            self._c_hit.inc()
        elif cache_hit is False:
            self._c_miss.inc()
        tracer_instant(self.tracer, "verifier.warmup.bucket", cat="crypto",
                       bucket=bucket, seconds=round(seconds, 3),
                       cache=cache)

    def warmup_done(self) -> None:
        with self._lock:
            self.warmup.update({"state": "done", "done_t": self._now()})
            total = sum(b["seconds"]
                        for b in self.warmup["buckets"].values())
            n = len(self.warmup["buckets"])
        self._g_wstate.set(self.WARMUP_STATE_CODE["done"])
        tracer_instant(self.tracer, "verifier.warmup.end", cat="crypto",
                       buckets=n, total_s=round(total, 3))

    def warmup_failed(self, err: str) -> None:
        with self._lock:
            self.warmup.update({"state": "failed", "done_t": self._now(),
                                "error": err})
        self._g_wstate.set(self.WARMUP_STATE_CODE["failed"])
        self.metrics.new_meter("verifier.warmup.failure").mark()
        tracer_instant(self.tracer, "verifier.warmup.failed", cat="crypto",
                       error=err)
        if self.flight_recorder is not None:
            self.flight_recorder.dump(
                "verify-warmup-failed",
                extra={"error": err, "warmup": self.warmup_json()})

    # -- export --------------------------------------------------------------
    def warmup_json(self) -> dict:
        with self._lock:
            w = dict(self.warmup)
            w["buckets"] = {k: dict(v)
                            for k, v in self.warmup["buckets"].items()}
        return w

    def to_json(self) -> dict:
        """The cockpit blob served by the admin `verifier` endpoint."""
        with self._lock:
            backends = {k: dict(v) for k, v in self.backends.items()}
            buckets = {
                str(b): {"drains": d["drains"], "sigs": d["sigs"],
                         "pad_waste_total": d["pad_total"],
                         "occupancy_pct": d["_occ"].snapshot(),
                         "pad_waste": d["_pad"].snapshot()}
                for b, d in sorted(self.buckets.items())}
            devices = {str(i): dict(d)
                       for i, d in sorted(self.devices.items())}
            staging = dict(self.staging)
            drain_stream = dict(self.drain_stream)
            queue = dict(self.queue)
            cc = dict(self.compile_cache)
        return {
            "drains": {"by_backend": backends,
                       "batch_size": self._h_batch.snapshot(),
                       "pad_waste": self._h_pad.snapshot(),
                       "occupancy_pct": self._h_occ.snapshot(),
                       "splits": self._h_splits.snapshot()},
            "buckets": buckets,
            "devices": devices,
            "staging": staging,
            "drain_stream": drain_stream,
            "warmup": self.warmup_json(),
            "compile_cache": cc,
            "queue": queue,
        }


def warmup_plan(stats, candidates):
    """Cockpit-driven warm-start bucket selection (ISSUE 11 tentpole):
    derive the AOT warmup set from the `verifier.bucket.<b>.drains` /
    `pad-waste` histograms the cockpit aggregates — CPU drains included
    via `drain_sizes`, so selection sees ALL traffic.

    Rules, in order:
    - only candidate shapes with observed traffic are warmed, hottest
      (most drains) first, so the first compile serves the most load;
    - a device bucket whose median occupancy is below 50% mostly pays
      padding: the next smaller candidate is appended too, so the
      dispatcher can split down without a cold compile;
    - no cockpit evidence at all (fresh node, stats=None) falls back to
      the full candidate ladder.

    Returns (buckets, info) where info carries `source`
    ("cockpit"/"default") and the evidence the choice was made from —
    persisted by save_warmup_plan() so a warm restart compiles only the
    shapes real traffic uses."""
    cands = sorted(candidates)
    if stats is None:
        return list(cands), {"source": "default",
                             "reason": "no cockpit stats"}
    traffic = stats.bucket_traffic(cands)
    if not traffic:
        return list(cands), {"source": "default",
                             "reason": "no recorded drains"}
    chosen = sorted(traffic, key=lambda b: (-traffic[b], b))
    extra = []
    for b, occ_p50 in sorted(stats.bucket_occupancy_p50().items()):
        if occ_p50 is None or occ_p50 >= 50.0 or b not in cands:
            continue
        i = cands.index(b)
        if i > 0 and cands[i - 1] not in chosen and \
                cands[i - 1] not in extra:
            extra.append(cands[i - 1])
    return chosen + extra, {"source": "cockpit", "traffic": traffic,
                            "low_occupancy_extra": extra}


class VerifyFuture:
    """Completion handle for one enqueued verify."""

    __slots__ = ("_done", "_result", "_callbacks")

    def __init__(self) -> None:
        self._done = False
        self._result = False
        self._callbacks: List[Callable[[bool], None]] = []

    def done(self) -> bool:
        return self._done

    def result(self) -> bool:
        assert self._done, "verify future not completed; call flush()"
        return self._result

    def add_done_callback(self, cb: Callable[[bool], None]) -> None:
        if self._done:
            cb(self._result)
        else:
            self._callbacks.append(cb)

    def _complete(self, ok: bool) -> None:
        self._done = True
        self._result = ok
        cbs, self._callbacks = self._callbacks, []
        for cb in cbs:
            cb(ok)


class VerifierContext:
    """What the boundary and its engines share, by reference: built once
    (make_verifier) and handed to each at construction, so no layer can
    be left verifying against another cache or recording nowhere. Every
    field is optional; the default context is silent (tests, the callers
    handed no verifier) and sits behind the process-wide verdict cache
    (keys.PROCESS_CACHE; a node's own keys.VerdictCache where
    Config.VERIFY_CACHE_SCOPE is "node")."""

    __slots__ = ("cache", "tracer", "metrics", "faults", "stats",
                 "flight_recorder", "ahead")

    # what a span is called on a streamed drain's worker thread
    # (DrainStream): its stage and its wait for the device run beside
    # the thread that replays, not in its way, and a reader of spans
    # sees names, not threads
    AHEAD_NAMES = {"crypto.stage": "crypto.stage_ahead",
                   "crypto.device_wait": "crypto.device_wait_ahead"}

    def __init__(self, cache=None, tracer=None, metrics=None, faults=None,
                 stats=None, flight_recorder=None) -> None:
        self.cache = cache or _keys.PROCESS_CACHE
        self.tracer = tracer                    # util/tracing.py
        self.metrics = metrics
        self.faults = faults                    # util/faults.py
        self.stats = stats                      # the VerifierStats cockpit
        self.flight_recorder = flight_recorder
        # `.cause` on a streamed drain's worker thread: the span that
        # handed the chunk over
        self.ahead = threading.local()

    def span(self, name: str, **tags):
        if name in self.AHEAD_NAMES and \
                enabled_tracer(self.tracer) is not None:
            cause = getattr(self.ahead, "cause", None)
            if cause is not None:
                return tracer_span(self.tracer, self.AHEAD_NAMES[name],
                                   cat="crypto", cause=cause, **tags)
        return tracer_span(self.tracer, name, cat="crypto", **tags)


class CpuSigVerifier:
    """Synchronous OpenSSL engine (libsodium role). An engine is plain:
    `verify_many` verifies the triples it is given, all of them, at the
    call; the verdict cache, the pending queue and the futures are
    SigVerifier's."""

    name = "cpu"
    # True for engines where one big device dispatch beats many small
    # ones: TxSetFrame.check_or_trim prewarms the whole set's signatures
    # through prewarm_many before walking txs (two-phase validation).
    wants_prewarm = False
    # `GET verifier`'s device counters: nothing is dispatched to a device
    batches_dispatched = 0
    sigs_verified = 0

    def __init__(self, ctx: Optional[VerifierContext] = None) -> None:
        self.ctx = ctx if ctx is not None else VerifierContext()

    def verify_many(self, triples: Sequence[Triple]) -> List[bool]:
        # CPU drains carry the same batch-shape tags as device drains
        # (pad_waste is structurally 0: no padding on the synchronous
        # path) so bucket-selection analysis sees ALL traffic, not just
        # what happened to reach the device
        with self.ctx.span("crypto.verify_many", backend=self.name,
                           n=len(triples), batches=1, pad_waste=0,
                           occupancy_pct=100.0):
            out = _keys.raw_verify_batch(triples)
            # recorded only after the verify returns: a raising drain is
            # re-run (and counted once) by the boundary's _flush_fallback
            if self.ctx.stats is not None:
                self.ctx.stats.record_drain(self.name, len(triples))
            return out


class TpuSigVerifier:
    """JAX/TPU batched backend with a device-fleet shard scheduler
    (ISSUE 11 tentpole).

    Batches are padded up to fixed bucket sizes so the kernel compiles
    once per bucket; oversized batches are split. Correctness contract:
    identical accept/reject decisions to CpuSigVerifier (RFC 8032
    cofactorless).

    Fleet dispatch: a drain is split into bucket-shaped sub-batches;
    sub-batches at or above SHARD_MIN_BATCH shard pure-data-parallel
    over the healthy devices' mesh (one compiled executable per
    (bucket, mesh) — XLA's SPMD runtime drives every chip in parallel),
    while straggler tails keep their own smaller bucket on one device
    instead of padding the whole mesh up. Host→device staging is
    double-buffered: while the fleet verifies chunk K, chunk K+1 is
    packed and device_put on the `crypto.verify-staging` worker, so the
    device never idles on host marshalling (`verifier.staging.
    overlap-pct`). Per-device health is a ring of circuit breakers
    (DeviceFleetHealth): a sick chip drops out of the mesh and the
    drain continues on N-1 devices — the all-or-nothing CPU fallback is
    the boundary's (SigVerifier), reserved for whole-engine failures.
    """

    name = "tpu"
    wants_prewarm = True
    BUCKETS = (128, 512, 2048, 8192)

    # batches below this size stay on one device: sharding a handful of
    # sigs over a pod slice buys nothing and costs a sharded compile
    SHARD_MIN_BATCH = 1024

    # device drains between cockpit-plan autosaves (save_warmup_plan)
    PLAN_AUTOSAVE_DRAINS = 32
    PLAN_BASENAME = "warmup_buckets.json"

    def __init__(self, ctx: Optional[VerifierContext] = None,
                 shard_threshold: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 now_fn: Optional[Callable[[], float]] = None,
                 device_breaker_threshold: int = 3,
                 device_breaker_cooldown: float = 30.0) -> None:
        self.ctx = ctx if ctx is not None else VerifierContext()
        self.batches_dispatched = 0
        self.sigs_verified = 0
        # where the cockpit-derived warmup plan persists; None (direct
        # constructions, nodes without a bucket directory) keeps the
        # default ladder and saves nothing. Application.enable_buckets
        # points it beside the node's bucket directory.
        self.warmup_plan_path: Optional[str] = None
        self._warmed = False
        self._warmup_thread: Optional[threading.Thread] = None
        self._warmup_error: Optional[BaseException] = None
        self._sharded_fn = None  # full-mesh dp fn (set on first build)
        self._platform: Optional[str] = None  # actual jax platform, lazy
        self._devices_override = devices
        self._devices: Optional[list] = None  # resolved on first jax use
        self._now = now_fn
        self._dev_threshold = device_breaker_threshold
        self._dev_cooldown = device_breaker_cooldown
        self._fleet_health: Optional[DeviceFleetHealth] = None
        self._mesh_fns: dict = {}   # tuple(device idxs) -> (fn, mesh)
        self._drains_since_plan_save = 0
        if shard_threshold is not None:
            self.SHARD_MIN_BATCH = shard_threshold

    # -- fleet topology ------------------------------------------------------
    def _fleet(self):
        """(devices, health), resolved lazily on first jax touch."""
        if self._devices is None:
            import jax
            self._devices = list(self._devices_override
                                 if self._devices_override is not None
                                 else jax.devices())
            self._fleet_health = DeviceFleetHealth(
                len(self._devices), threshold=self._dev_threshold,
                cooldown_s=self._dev_cooldown, now_fn=self._now,
                owner=self)
        return self._devices, self._fleet_health

    @property
    def fleet_health(self) -> "DeviceFleetHealth":
        return self._fleet()[1]

    def _mesh_fn(self, idxs: tuple):
        """dp-sharded verify fn over the devices at `idxs` — one
        compiled executable per (bucket shape, mesh membership). A mesh
        rebuild after a breaker trip/recover is a real recompile on new
        shapes; it is counted so degraded-fleet compile cost is never
        invisible."""
        got = self._mesh_fns.get(idxs)
        if got is None:
            from ..parallel.mesh import make_mesh, sharded_verify_fn
            devs, _health = self._fleet()
            mesh = make_mesh([devs[i] for i in idxs])
            got = (sharded_verify_fn(mesh), mesh)
            if self._mesh_fns and self.ctx.metrics is not None:
                self.ctx.metrics.new_meter("verifier.fleet.mesh-rebuild").mark()
            self._mesh_fns[idxs] = got
            if len(idxs) == len(devs):
                self._sharded_fn = got[0]   # full-mesh alias
        return got

    def _single_fn(self):
        from ..ops.ed25519 import verify_batch_packed
        return verify_batch_packed

    def _route(self, n: int):
        """(fn, padded bucket, device idxs) for an n-sig sub-batch.

        Mesh membership is the healthy device set at route time; the
        verify.device-lost fault point simulates losing the first
        healthy device for this dispatch (its breaker counts the
        failure, so repeated fires trip it and the fleet degrades to
        N-1)."""
        devs, health = self._fleet()
        idxs = health.healthy() if len(devs) > 1 else [0]
        if len(idxs) > 1 and self.ctx.faults is not None and \
                self.ctx.faults.should_fire("verify.device-lost"):
            lost = idxs[0]
            health.record_failure(lost)
            idxs = [i for i in idxs if i != lost]
        if not idxs:
            idxs = list(range(len(devs)))
        if len(idxs) > 1 and n >= self.SHARD_MIN_BATCH:
            fn, _mesh = self._mesh_fn(tuple(idxs))
            ndev = len(idxs)
        else:
            # sub-batch bucketing: a straggler tail keeps its own small
            # bucket on ONE device instead of serializing (and padding)
            # the whole mesh — the first HEALTHY device, so a tripped
            # device 0 doesn't keep eating every small live-SCP batch
            # (the per-device compile a non-default device costs only
            # happens in that degraded state)
            fn = self._single_fn()
            idxs = idxs[:1]
            ndev = 1
        b = -(-self._bucket(n) // ndev) * ndev
        return fn, b, tuple(idxs)

    # -- staging (host pack + host→device transfer) --------------------------
    def _stage_chunk(self, chunk: Sequence[Triple], route) -> dict:
        """Pack one sub-batch and move it to its device(s). Runs on the
        staging worker when double-buffered; the returned blob is
        everything dispatch needs, so the dispatch thread never touches
        host marshalling."""
        from ..ops import ed25519 as _e
        fn, b, idxs = route
        # one (b, 128) uint8 array, written at the bucket's size
        prep = _e.prepare_batch(
            [t[0] for t in chunk], [t[1] for t in chunk],
            [t[2] for t in chunk], size=b)
        return {"arg": self._device_arg(prep["packed"], idxs),
                "pre_ok": prep["pre_ok"], "n": len(chunk), "b": b,
                "fn": fn, "idxs": idxs}

    def _stage_inline(self, chunk: Sequence[Triple]) -> dict:
        """Route and stage on the dispatching thread (chunk 0, a re-stage
        after a stall): `crypto.stage`, on the drain's critical path. The
        staging worker's span is `crypto.stage_ahead` (_StagingJob): two
        names because a reader of spans sees names, not threads."""
        with self.ctx.span("crypto.stage", n=len(chunk)):
            return self._stage_chunk(chunk, self._route(len(chunk)))

    def _device_arg(self, packed, idxs: tuple):
        """Explicit host→device placement of a dispatch's one input
        array: sharded over the mesh for a fleet dispatch, committed to
        its device otherwise — the transfer happens here (inside the
        stage span, on the staging thread when overlapped), not inside
        the jit call."""
        import jax
        devs, _health = self._fleet()
        if len(idxs) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            _fn, mesh = self._mesh_fns[idxs]
            target = NamedSharding(mesh, P("dp"))
        else:
            target = devs[idxs[0]] if idxs else devs[0]
        return jax.device_put(packed, target)

    # -- cockpit-driven warm start (ISSUE 11 tentpole) -----------------------
    def _load_warmup_plan(self):
        """(buckets, source): the persisted cockpit plan when present
        and still valid against the candidate ladder, else the full
        default BUCKETS."""
        import json
        if self.warmup_plan_path is None:
            return list(self.BUCKETS), "default"
        try:
            with open(self.warmup_plan_path) as fh:
                blob = json.load(fh)
            buckets = [int(b) for b in blob["buckets"]]
            if buckets and all(b in self.BUCKETS for b in buckets):
                return buckets, "cockpit"
            log.warning("persisted warmup plan %r does not fit the "
                        "candidate ladder %r; using the default set",
                        buckets, tuple(self.BUCKETS))
        except (OSError, ValueError, KeyError, TypeError):
            pass
        return list(self.BUCKETS), "default"

    def save_warmup_plan(self) -> Optional[str]:
        """Persist the cockpit-derived bucket plan (warmup_plan over the
        shared VerifierStats) at `warmup_plan_path` — node state, kept
        out of the compile cache so a cache shared between runs carries
        executables only and one run cannot choose another's warm set.
        No-op until the cockpit has seen traffic — a default plan is not
        evidence worth persisting. Returns the path written, or None."""
        if self.ctx.stats is None or self.warmup_plan_path is None:
            return None
        buckets, info = warmup_plan(self.ctx.stats, self.BUCKETS)
        if info.get("source") != "cockpit":
            return None
        import json
        import os
        path = self.warmup_plan_path
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump({"version": 1, "buckets": buckets,
                           "candidates": sorted(self.BUCKETS),
                           "traffic": {str(k): v for k, v in
                                       sorted(info["traffic"].items())},
                           "low_occupancy_extra":
                               info["low_occupancy_extra"]}, fh)
            os.replace(tmp, path)
        except OSError as e:
            log.warning("could not persist warmup plan: %s", e)
            return None
        return path

    def warmup(self, wait: bool = False) -> None:
        """AOT-compile every bucket shape off the consensus path (startup
        background thread; reference analog: no lazy work on first
        envelope). Idempotent. A failure is recorded in the cockpit and
        the node keeps running; a caller that waits gets it raised."""
        if self._warmed:
            return
        if self._warmup_thread is None:
            self._warmup_thread = spawn_worker(
                "crypto.verify-warmup", self._warmup_impl)
        if wait:
            self._warmup_thread.join()
            if self._warmup_error is not None:
                raise self._warmup_error

    def _compile_bucket(self, b: int) -> None:
        """AOT-compile (or cache-load) one bucket shape, routed exactly
        like live traffic (mesh-sharded at or above SHARD_MIN_BATCH) so
        warmup compiles the executables dispatch will actually use."""
        import numpy as np
        from ..ops.ed25519 import PACKED_WIDTH
        fn, bb, idxs = self._route(b)
        zeros = np.zeros((bb, PACKED_WIDTH), np.uint8)
        np.asarray(fn(self._device_arg(zeros, idxs)))

    def _warmup_impl(self) -> None:
        from ..parallel.device import (
            cache_hit, compile_cache_dir, compile_cache_events,
        )
        st = self.ctx.stats
        try:
            if st is not None:
                st.set_compile_cache_dir(compile_cache_dir())
            planned, source = self._load_warmup_plan()
            if st is not None:
                st.warmup_begin(planned, source=source)
            for b in planned:
                t0 = real_monotonic()
                with compile_cache_events() as events:
                    self._compile_bucket(b)
                dt = real_monotonic() - t0
                if st is not None:
                    st.warmup_bucket_done(b, dt, cache_hit(events))
            self._warmed = True
            if st is not None:
                st.warmup_done()
            log.info("verify kernel warmup complete (%s buckets, "
                     "%s plan)", len(planned), source)
        except Exception as e:
            log.warning("verify kernel warmup failed: %s", e)
            self._warmup_error = e
            if st is not None:
                st.warmup_failed(repr(e))

    def _bucket(self, n: int) -> int:
        for b in self.BUCKETS:
            if n <= b:
                return b
        return self.BUCKETS[-1]

    def verify_many(self, triples: Sequence[Triple]) -> List[bool]:
        import numpy as np
        import jax

        if self._platform is None:
            # the ACTUAL backing platform ("tpu"/"cpu"/…): a jax-on-CPU
            # run of this verifier is a fallback and must trace as one
            self._platform = jax.devices()[0].platform
        out: List[bool] = []
        st = self.ctx.stats
        with self.ctx.span("crypto.verify_many", backend=self.name,
                        platform=self._platform, n=len(triples)) as sp:
            chunks: List[Sequence[Triple]] = []
            i = 0
            while i < len(triples):
                chunks.append(triples[i:i + self.BUCKETS[-1]])
                i += len(chunks[-1])
            batches = 0
            pad_waste = 0
            staged_s = overlap_s = 0.0
            staged_chunks = 0
            staged = self._stage_inline(chunks[0]) if chunks else None
            for k in range(len(chunks)):
                # double buffer: chunk K+1 packs + device_puts on the
                # staging worker while the device executes chunk K
                job = None
                if k + 1 < len(chunks):
                    # Thread.start() returns once the worker runs, and the
                    # worker may keep the interpreter for a switch interval
                    with self.ctx.span("crypto.stage_spawn"):
                        job = _StagingJob(self, chunks[k + 1], cause=sp.sid)
                n, b, idxs = staged["n"], staged["b"], staged["idxs"]
                if st is not None:
                    for di in idxs:
                        st.set_device_inflight(di, True)
                with self.ctx.span("crypto.dispatch", backend=self.name,
                                n=n, bucket=b, pad=b - n,
                                devices=len(idxs)):
                    try:
                        with self.ctx.span("crypto.launch"):
                            ok_dev = staged["fn"](staged["arg"])  # async
                        wait_t0 = real_monotonic()
                        with self.ctx.span("crypto.device_wait"):
                            ok = np.asarray(ok_dev)  # blocks on the fleet
                        wait_t1 = real_monotonic()
                    except Exception:
                        # a raising fleet dispatch counts against every
                        # participating device's breaker (attribution to
                        # ONE chip needs the fault-injection path); the
                        # batch itself is completed by the boundary's
                        # fallback
                        health = self._fleet_health
                        if health is not None:
                            for di in idxs:
                                health.record_failure(di)
                        raise
                    finally:
                        if st is not None:
                            for di in idxs:
                                st.set_device_inflight(di, False)
                    with self.ctx.span("crypto.unpack"):
                        # every participant's breaker sees the success —
                        # single-device dispatches included, so transient
                        # failures spread over time never read as
                        # consecutive and a half-open device can recover
                        # via small drains too
                        health = self._fleet_health
                        if health is not None:
                            for di in idxs:
                                health.record_success(di)
                        out.extend((ok[:n] & staged["pre_ok"]).tolist())
                        self.batches_dispatched += 1
                        self.sigs_verified += n
                        batches += 1
                        pad_waste += b - n
                        if st is not None:
                            st.record_h2d(staged["arg"].nbytes)
                            # keyed by the LADDER shape, not the mesh-
                            # rounded padded size: a degraded 3-device
                            # fleet rounds 8192 to 8193, and an off-ladder
                            # key would both escape warmup_plan's
                            # pad-waste rule and mint unbounded
                            # verifier.bucket.<b>.* metric families
                            st.record_bucket_dispatch(self._bucket(n), n,
                                                      b - n)
                            lanes = b // len(idxs)
                            for j, di in enumerate(idxs):
                                real = min(max(n - j * lanes, 0), lanes)
                                st.record_device_dispatch(di, real,
                                                          lanes - real)
                if job is not None:
                    with self.ctx.span("crypto.stage_wait"):
                        staged, s_s, o_s, stalled = job.result(wait_t0,
                                                               wait_t1)
                    if stalled:
                        # staging stalled: re-stage synchronously so the
                        # drain still completes (the device idles for
                        # one chunk; the stall meter says so). The
                        # failed attempt does NOT count toward the
                        # overlap headline — a drain that stalled every
                        # chunk must not report near-100% overlap.
                        if st is not None:
                            st.record_staging_stall()
                        staged = self._stage_inline(chunks[k + 1])
                    else:
                        staged_s += s_s
                        overlap_s += o_s
                        staged_chunks += 1
            total = len(triples)
            if sp.live:
                sp.set_tag("batches", batches)
                sp.set_tag("pad_waste", pad_waste)
                sp.set_tag("occupancy_pct", round(
                    100.0 * total / (total + pad_waste), 1)
                    if total + pad_waste else 100.0)
                if staged_chunks:
                    sp.set_tag("staging_overlap_pct", round(
                        100.0 * overlap_s / staged_s, 1) if staged_s > 0
                        else 100.0)
            if st is not None:
                if staged_chunks:
                    st.record_staging(staged_s, overlap_s, staged_chunks)
                st.record_drain(self.name, total, pad=pad_waste,
                                splits=batches, bucketed=True)
            self._drains_since_plan_save += 1
            if self._drains_since_plan_save >= self.PLAN_AUTOSAVE_DRAINS:
                self._drains_since_plan_save = 0
                self.save_warmup_plan()
        return out


class _StagingJob:
    """One double-buffer staging unit: packs + device_puts drain chunk
    K+1 on the `crypto.verify-staging` worker while the dispatch thread
    waits on chunk K. Timing uses util.timer.real_monotonic (sanctioned:
    host/device overlap is real elapsed time even under a frozen virtual
    clock). A staging failure (including the verify.staging-stall fault
    point) is reported as `stalled` — the caller re-stages synchronously
    so the drain always completes."""

    __slots__ = ("v", "chunk", "cause", "staged", "error", "t0", "t1",
                 "thread")

    def __init__(self, verifier: "TpuSigVerifier",
                 chunk: Sequence[Triple], cause: int = 0) -> None:
        self.v = verifier
        self.chunk = chunk
        self.cause = cause      # the drain's crypto.verify_many span
        self.staged = None
        self.error: Optional[Exception] = None
        self.t0 = self.t1 = 0.0
        self.thread = spawn_worker("crypto.verify-staging", self._run)

    def _run(self) -> None:
        self.t0 = real_monotonic()
        try:
            if self.v.ctx.faults is not None:
                self.v.ctx.faults.fire_point("verify.staging-stall")
            with self.v.ctx.span("crypto.stage_ahead", cause=self.cause,
                              n=len(self.chunk)):
                self.staged = self.v._stage_chunk(
                    self.chunk, self.v._route(len(self.chunk)))
        except Exception as e:
            self.error = e
        self.t1 = real_monotonic()

    def result(self, wait_t0: float, wait_t1: float):
        """(staged, staged_s, overlap_s, stalled): overlap is the
        intersection of the staging window with the caller's
        device-wait window [wait_t0, wait_t1]."""
        self.thread.join()
        staged_s = max(0.0, self.t1 - self.t0)
        overlap_s = max(0.0, min(self.t1, wait_t1) -
                        max(self.t0, wait_t0))
        if self.error is not None:
            log.warning("verify staging stalled (%s); re-staging chunk "
                        "synchronously", self.error)
            return None, staged_s, overlap_s, True
        return self.staged, staged_s, overlap_s, False


class DeviceFleetHealth:
    """Per-device circuit breakers over the verify fleet (ISSUE 11
    satellite): the boundary's single breaker (SigVerifier) treats the
    whole engine as one unit; this ring trips and recovers per chip,
    so one sick device degrades the mesh to N-1 devices instead of
    dropping every drain to the CPU fallback. State is exported as
    `verifier.device.<i>.breaker` gauges (0 closed / 1 open / 2
    half-open) plus trip/recover meters and a flight dump per trip.

    Attribution honesty: a whole-mesh dispatch failure cannot name the
    guilty chip, so it counts against every participant (and, via the
    boundary, the global breaker); single-chip attribution comes
    from the verify.device-lost fault point and device-identifiable
    runtime errors."""

    def __init__(self, n_devices: int, threshold: int = 3,
                 cooldown_s: float = 30.0,
                 now_fn: Optional[Callable[[], float]] = None,
                 owner=None) -> None:
        self.owner = owner     # the engine; stats read off its context
        # the ring is mutated from the dispatch thread AND the staging
        # worker (_route runs on both): one lock makes allow()/record_*
        # transitions atomic, so a just-tripped chip can never race its
        # own cooldown back into the mesh. Lock order: fleet-health ->
        # verifier-stats (the trip/recover callbacks record telemetry);
        # nothing acquires them in reverse.
        self._lock = TrackedLock("crypto.fleet-health")
        self.breakers: List[CircuitBreaker] = []
        for i in range(n_devices):
            self.breakers.append(CircuitBreaker(
                threshold=threshold, cooldown_s=cooldown_s, now_fn=now_fn,
                on_trip=(lambda i=i: self._on_trip(i)),
                on_recover=(lambda i=i: self._on_recover(i))))

    def _stats(self):
        return self.owner.ctx.stats if self.owner is not None else None

    def healthy(self) -> List[int]:
        """Device indices whose breaker admits a dispatch right now
        (open breakers past their cooldown flip to half-open here —
        the next fleet dispatch is their reprobe)."""
        with self._lock:
            return [i for i, br in enumerate(self.breakers)
                    if br.allow()]

    def record_failure(self, idx: int) -> bool:
        with self._lock:
            tripped = self.breakers[idx].record_failure()
        self._sync_gauge(idx)
        return tripped

    def record_success(self, idx: int) -> None:
        with self._lock:
            self.breakers[idx].record_success()
        self._sync_gauge(idx)

    def _sync_gauge(self, idx: int) -> None:
        st = self._stats()
        if st is not None:
            st.set_device_breaker(idx, self.breakers[idx].state_code())

    def _on_trip(self, idx: int) -> None:
        log.warning("verify device %d breaker TRIPPED; fleet degrades "
                    "to %d device(s)", idx,
                    sum(1 for br in self.breakers
                        if br.state == CircuitBreaker.CLOSED))
        st = self._stats()
        if st is not None:
            st.device_trip(idx, self.breakers[idx].to_json())

    def _on_recover(self, idx: int) -> None:
        log.info("verify device %d breaker recovered; fleet back to "
                 "full mesh", idx)
        st = self._stats()
        if st is not None:
            st.device_recover(idx)

    def to_json(self) -> dict:
        with self._lock:
            return {"devices": {str(i): br.to_json()
                                for i, br in enumerate(self.breakers)}}


class CircuitBreaker:
    """closed → open → half-open → closed over the device-dispatch path.

    CLOSED: dispatches flow to the primary; `threshold` CONSECUTIVE
    failures trip to OPEN. OPEN: primary is bypassed until `cooldown_s`
    elapses on the injected clock, then the next allow() becomes the
    HALF-OPEN probe. HALF-OPEN: one success re-closes (recover), one
    failure re-opens for another cooldown. Time comes from `now_fn`
    (virtual clock in tests/simulation) so trips and reprobes are
    deterministic."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"
    _STATE_CODE = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2}

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 now_fn: Optional[Callable[[], float]] = None,
                 on_trip: Optional[Callable[[], None]] = None,
                 on_recover: Optional[Callable[[], None]] = None) -> None:
        self.threshold = max(1, threshold)
        self.cooldown_s = cooldown_s
        self._now = now_fn or real_monotonic
        self.on_trip = on_trip
        self.on_recover = on_recover
        self.state = self.CLOSED
        self.consecutive_failures = 0
        self.trips = 0
        self.recoveries = 0
        self._retry_at = 0.0

    def allow(self) -> bool:
        """May the next dispatch try the primary?"""
        if self.state == self.CLOSED:
            return True
        if self.state == self.OPEN and self._now() >= self._retry_at:
            self.state = self.HALF_OPEN
            return True
        return self.state == self.HALF_OPEN

    def record_success(self) -> None:
        recovered = self.state == self.HALF_OPEN
        self.state = self.CLOSED
        self.consecutive_failures = 0
        if recovered:
            self.recoveries += 1
            if self.on_recover is not None:
                self.on_recover()

    def record_failure(self) -> bool:
        """Returns True when this failure tripped (or re-opened) the
        breaker."""
        self.consecutive_failures += 1
        if self.state == self.HALF_OPEN or \
                self.consecutive_failures >= self.threshold:
            reopened = self.state != self.CLOSED
            self.state = self.OPEN
            self._retry_at = self._now() + self.cooldown_s
            if not reopened:
                self.trips += 1
                if self.on_trip is not None:
                    self.on_trip()
            return True
        return False

    def state_code(self) -> int:
        return self._STATE_CODE[self.state]

    def to_json(self) -> dict:
        return {"state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "trips": self.trips, "recoveries": self.recoveries,
                "threshold": self.threshold, "cooldown_s": self.cooldown_s,
                "retry_at": self._retry_at}


class SigVerifier:
    """The verifier boundary: the one object the herder, the tx queue,
    the txset check, catchup and the SignatureChecker hold. Every
    decision that is not an engine's lives here, once:

    - the verdict cache in front (`_cache_probe` / `_cache_store`):
      hits never enqueue and never dispatch;
    - the pending queue: `enqueue` accumulates, `flush` dispatches ONE
      batch. Without a clock the flush runs inline (and `enqueue`
      self-flushes at `max_pending`); with one it runs on the
      `crypto.verify-dispatch` worker, one batch in flight, and futures
      complete on the main loop via clock.post_to_main: the
      enqueue-and-continue protocol SURVEY.md §7 requires at the
      verifyEnvelope/checkValid boundary. `max_pending=0` means no
      queue at all: the `cpu` backend's verdict is ready at the call;
    - the breaker: with a `fallback` engine every dispatch asks the
      breaker whether the engine may be tried; a raising engine records
      a failure and the batch re-runs on the fallback, so callers always
      get results. A trip emits metrics + a flight-recorder dump;
      recovery (first successful half-open probe) the matching recover
      marker: the signals the chaos soak asserts on. Without a fallback
      a dispatch is a plain call.

    `prewarm_many` and `verify_many` are synchronous drains on every
    backend. An engine (CpuSigVerifier, TpuSigVerifier, a test's fake) is
    anything with `name`, `wants_prewarm`, `verify_many(triples)` and a
    `ctx`; the boundary takes its context from the engine, so boundary,
    engine and fallback share one by reference."""

    def __init__(self, engine, fallback=None,
                 breaker: Optional[CircuitBreaker] = None,
                 clock=None, max_pending: int = 8192) -> None:
        self.engine = engine
        self.fallback = fallback
        self.ctx = engine.ctx
        self.breaker: Optional[CircuitBreaker] = None
        if fallback is not None:
            self.breaker = breaker or CircuitBreaker()
            self.breaker.on_trip = self._on_trip
            self.breaker.on_recover = self._on_recover
        # what `GET verifier` prints: `cpu` / `resilient` / `threaded`
        inline = "resilient" if fallback is not None else engine.name
        self.name = "threaded" if clock is not None else inline
        self._batch_backend = "threaded:%s" % inline
        self._clock = clock
        self._max_pending = max_pending
        self._lock = TrackedLock("crypto.threaded-pending")
        # (triple, cache key, future, enqueue app-clock stamp, class,
        # tracer-clock stamp): the app-clock stamp (0.0 without a clock)
        # feeds the crypto.verify.latency enqueue-to-complete timer (the
        # p50/p99 the live SCP path actually feels); the app clock, not
        # wall time, so chaos soaks under a virtual clock stay
        # deterministic. The tracer-clock stamp (0.0 while tracing is
        # off) is the start of the batch's crypto.queue_wait.<class>
        # span.
        self._pending: List[Tuple[Triple, bytes, VerifyFuture, float,
                                  str, float]] = []
        self._inflight = False
        self._drain: Optional[DrainStream] = None   # the one open_drain gave

    # -- what others read ----------------------------------------------------
    @property
    def inner(self):
        """The engine: callers tune BUCKETS / read dispatch counters on
        it (and benchmark/control.py replaces its verify_many)."""
        return self.engine

    @property
    def wants_prewarm(self) -> bool:
        return self.engine.wants_prewarm

    @property
    def stats(self):
        return self.ctx.stats

    @property
    def cache(self):
        return self.ctx.cache

    def warmup(self, wait: bool = False) -> None:
        """Compile the device engine's shapes; a CPU engine has none."""
        if self.engine.wants_prewarm:
            self.engine.warmup(wait)

    def save_warmup_plan(self) -> Optional[str]:
        return self.engine.save_warmup_plan() \
            if self.engine.wants_prewarm else None

    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    # -- the verdict cache ---------------------------------------------------
    def _cache_probe(self, triples: Sequence[Triple]):
        """(verdicts, misses): each triple's cached verdict, None where
        the cache holds none, and for those (index, cache key). Cache
        keys for a whole drain hash in one native call (prep.c
        sct_cache_keys) when available; one lock section a probe."""
        cks = None
        if len(triples) >= 256:   # below this the fixed numpy/ctypes
            # marshalling cost exceeds hashlib's per-triple overhead
            # (the native apply engine calls here once per tx, ~20-ish
            # triples; checkpoint drains come in by the thousand)
            from ..native import cache_keys_native
            cks = cache_keys_native(triples)
        if cks is None:
            cks = [_keys._cache_key(k, s, m) for (k, s, m) in triples]
        verdicts: List[Optional[bool]] = []
        misses: List[Tuple[int, bytes]] = []
        cache = self.ctx.cache
        with cache.lock:
            for i, ck in enumerate(cks):
                hit = cache.store.maybe_get(ck)
                verdicts.append(hit)
                if hit is None:
                    misses.append((i, ck))
        return verdicts, misses

    def _cache_store(self, cks: Sequence[bytes],
                     results: Sequence[bool]) -> None:
        cache = self.ctx.cache
        with cache.lock:
            for ck, ok in zip(cks, results):
                cache.store.put(ck, ok)

    # -- synchronous drains --------------------------------------------------
    def prewarm_many(self, triples: Sequence[Triple]) -> List[bool]:
        """Whole-ledger/checkpoint drain (SURVEY.md §2.2): verify a large
        batch in one dispatch and seed the result cache so subsequent
        synchronous per-signature checks all hit. Already-cached triples
        are not re-dispatched."""
        with self.ctx.span("crypto.prewarm", backend=self.name,
                           n=len(triples)) as sp:
            with self.ctx.span("crypto.cache_probe", n=len(triples)):
                out, misses = self._cache_probe(triples)
            sp.set_tag("cache_hits", len(triples) - len(misses))
            if misses:
                results = self.verify_many(
                    [triples[i] for (i, _ck) in misses])
                self._cache_store([ck for (_i, ck) in misses], results)
                for (i, _ck), ok in zip(misses, results):
                    out[i] = ok
            return out  # type: ignore[return-value]

    def open_drain(self) -> "DrainStream":
        """A streamed drain (catchup's checkpoint replay): one at a
        time, so whatever a former one still holds is cancelled and its
        chunk in flight joined first."""
        self.stop()
        self._drain = DrainStream(self)
        return self._drain

    def stop(self) -> None:
        """Application.stop(): no worker of this boundary outlives the
        node, and none writes the verdict cache after it."""
        drain, self._drain = self._drain, None
        if drain is not None:
            drain.close()

    def verify_many(self, triples: Sequence[Triple]) -> List[bool]:
        """One dispatch, past the cache: breaker, engine, on failure the
        fallback. The engine's verify_many is looked up on the engine at
        every call: benchmark/control.py's negative controls replace it
        on the instance, and every path to the device comes through
        here."""
        engine, fallback, ctx = self.engine, self.fallback, self.ctx
        if fallback is None:
            return engine.verify_many(triples)
        breaker = self.breaker
        if breaker.allow():
            try:
                # the engine's attempt gets its own span so an injected
                # (or real) dispatch failure is tagged on the drain it
                # killed, not floating free on the timeline
                with ctx.span("crypto.dispatch_primary",
                              backend=engine.name, n=len(triples)):
                    if ctx.faults is not None:
                        ctx.faults.fire_point("device.dispatch")
                    out = engine.verify_many(triples)
                breaker.record_success()
                return out
            except Exception as e:
                if ctx.metrics is not None:
                    ctx.metrics.new_meter(
                        "crypto.verify.dispatch-failure").mark()
                tripped = breaker.record_failure()
                if not tripped:
                    log.warning("%s dispatch failed (%s): %d/%d toward "
                                "breaker trip", engine.name, e,
                                breaker.consecutive_failures,
                                breaker.threshold)
        if ctx.metrics is not None:
            # drains served by the fallback while the engine is failing
            # or the breaker is open: the "completed on fallback" signal
            # the chaos soak asserts on
            ctx.metrics.new_meter("crypto.verify.fallback-drain").mark()
        # served_by names the engine that actually ran the drain: the
        # fallback's own verify_many records the drain stats under its
        # name, so cockpit attribution follows the server
        with ctx.span("crypto.verify_fallback", backend="resilient",
                      served_by=fallback.name,
                      n=len(triples), breaker=breaker.state):
            return fallback.verify_many(triples)

    # -- breaker events ------------------------------------------------------
    def _breaker_mark(self, event: str) -> None:
        ctx = self.ctx
        if ctx.metrics is not None:
            ctx.metrics.new_meter("crypto.breaker.%s" % event).mark()
            ctx.metrics.new_counter("crypto.breaker.state").set_count(
                self.breaker.state_code())
        tracer_instant(ctx.tracer, "crypto.breaker.%s" % event,
                       cat="crypto", primary=self.engine.name,
                       failures=self.breaker.consecutive_failures)

    def _on_trip(self) -> None:
        log.warning("verify breaker TRIPPED: %d consecutive %s-dispatch "
                    "failures; falling back to %s for %.0fs",
                    self.breaker.consecutive_failures, self.engine.name,
                    self.fallback.name, self.breaker.cooldown_s)
        self._breaker_mark("trip")
        if self.ctx.flight_recorder is not None:
            self.ctx.flight_recorder.dump(
                "verify-breaker-trip",
                extra={"primary": self.engine.name,
                       "breaker": self.breaker.to_json()})

    def _on_recover(self) -> None:
        log.info("verify breaker recovered: %s backend healthy again",
                 self.engine.name)
        self._breaker_mark("recover")

    # -- the pending queue ---------------------------------------------------
    def enqueue(self, key: PublicKey, sig: bytes, msg: bytes,
                cls: str = "tx") -> VerifyFuture:
        """`cls` names the caller's verify class ("scp" envelopes, "tx"
        signatures): the worker's queue wait is traced per class."""
        f = VerifyFuture()
        if not self._max_pending:
            f._complete(_keys.verify_cached(self.ctx.cache, key, sig, msg))
            return f
        triple = (key.key_bytes, sig, msg)
        verdicts, misses = self._cache_probe((triple,))
        if not misses:
            f._complete(verdicts[0])
            return f
        tr = enabled_tracer(self.ctx.tracer)
        entry = (triple, misses[0][1], f,
                 self._clock.now() if self._clock is not None else 0.0,
                 cls, tr.now() if tr is not None else 0.0)
        with self._lock:
            self._pending.append(entry)
            depth = len(self._pending)
        if self.ctx.stats is not None:
            self.ctx.stats.set_queue_depth(depth)
        if self._clock is None and depth >= self._max_pending:
            self.flush()
        return f

    def flush(self) -> None:
        with self._lock:
            if not self._pending or self._inflight:
                return
            batch, self._pending = self._pending, []
            self._inflight = self._clock is not None
        st = self.ctx.stats
        if st is not None:
            st.set_queue_depth(0)
        if self._clock is None:
            self._complete(batch, self._verify_batch(batch))
            return
        if st is not None:
            st.set_inflight(True)
        tr = enabled_tracer(self.ctx.tracer)
        cause = tr.current_sid() if tr is not None else 0

        def work() -> None:
            # queue-wait: enqueue → dispatch start, per batch; dispatch
            # time is the span's own duration (the engine's verify_many
            # nests)
            if st is not None:
                t_disp = self._clock.now()
                waits = [t_disp - ta for (_t, _ck, _f, ta, _c, _tt)
                         in batch]
                st.record_queue_wait(sum(waits) / len(waits), max(waits))
            if tr is not None:
                # one span per class in the batch, from its oldest
                # enqueue (stamped while tracing was on) to here
                t_disp = tr.now()
                oldest: dict = {}
                for (_t, _ck, _f, _ta, c, tt) in batch:
                    if tt and tt < oldest.get(c, t_disp):
                        oldest[c] = tt
                for c, t0 in oldest.items():
                    tr.record("crypto.queue_wait.%s" % c, "crypto", t0,
                              t_disp - t0, cause=cause, n=len(batch))
            with self.ctx.span("crypto.batch_dispatch", cause=cause,
                               n=len(batch)) as bsp:
                if bsp.live:
                    bsp.set_tag("backend", self._batch_backend)
                results = self._verify_batch(batch)
            self._clock.post_to_main(
                lambda: self._complete(batch, results))

        spawn_worker("crypto.verify-dispatch", work)

    def _verify_batch(self, batch) -> List[bool]:
        """One dispatch for a flushed batch. verify_many (almost) never
        raises behind a breaker: an engine failure is absorbed and the
        batch re-runs on the fallback. When it does raise, the batch is
        verified on the synchronous CPU path: a flush must neither
        strand futures nor (on the worker) die with `_inflight` latched,
        which would no-op every later flush, a permanent wedge."""
        triples = [e[0] for e in batch]
        try:
            return self.verify_many(triples)
        except Exception as e:
            log.warning("batch dispatch failed (%s); completing %d "
                        "verifies on CPU fallback", e, len(batch))
            return self._flush_fallback(triples)

    def _flush_fallback(self, triples: Sequence[Triple]) -> List[bool]:
        """Synchronous CPU re-verify used when a dispatch raises
        mid-flush; counts the event so a silent degradation is visible."""
        if self.ctx.metrics is not None:
            self.ctx.metrics.new_meter(
                "crypto.verify.flush-fallback").mark(len(triples))
        if self.ctx.stats is not None:
            # the CPU served this drain (the raising engine did not)
            self.ctx.stats.record_drain("cpu", len(triples))
        return _keys.raw_verify_batch(triples)

    def _complete(self, batch, results: Sequence[bool]) -> None:
        """Feed the cache and complete the batch's futures: inline, or
        on the main loop where the worker posted it."""
        self._cache_store([e[1] for e in batch], results)
        lat = None
        if self._clock is not None and self.ctx.metrics is not None:
            lat = self.ctx.metrics.new_timer("crypto.verify.latency")
            done = self._clock.now()
        for (_t, _ck, f, t0, _c, _tt), ok in zip(batch, results):
            if lat is not None:
                lat.update(done - t0)
            f._complete(ok)
        if self._clock is None:
            return
        with self._lock:
            self._inflight = False
            more = bool(self._pending)
        if self.ctx.stats is not None:
            self.ctx.stats.set_inflight(False)
        if more:
            # verifies enqueued while the batch was in flight form
            # the next batch immediately
            self.flush()


class DrainStream:
    """A checkpoint drain that streams (ISSUE 30): the caller feeds
    triples in frame order as it collects them and goes on with its own
    work; the misses leave for the device a top bucket at a time on one
    worker (`catchup.prewarm-pipeline`), one chunk in flight, in order,
    and land in the verdict cache chunk by chunk. `feed` returns a gate
    position and `wait(position)` returns once every miss fed up to
    there has landed, so a ledger closes as soon as its own chunk has
    landed while later chunks still run.

    Each chunk goes through SigVerifier.verify_many (breaker, fault
    point, fallback, the engine's verify_many looked up at the call)
    and SigVerifier._cache_store, as prewarm_many's misses do, cut at
    the same places: the dispatches are those of one prewarm_many over
    the same triples. A chunk that raises lands with no verdicts stored
    (cache warm only: the closes verify what it held). The probe runs on
    the feeding thread at every `feed`, so a key is a miss until its
    chunk has landed: a second feed of keys that may be in flight waits
    for `position` first.

    `submit` is the ungated use of the same worker (the cpu + native
    path): a whole prewarm_many beside the closes, which verify inline
    whatever it has not reached."""

    def __init__(self, verifier: SigVerifier) -> None:
        self._v = verifier
        self._cv = threading.Condition()
        self._jobs: list = []       # in-order work for the one worker
        self._misses: List[Tuple[Triple, bytes]] = []   # fed, not handed
        self._ends: List[int] = []  # position at each handed chunk's end
        self.position = 0           # misses fed so far
        self._landed = 0            # position landed through
        self._closed = False
        self._thread: Optional[threading.Thread] = None

    # -- the feeding thread --------------------------------------------------
    def feed(self, triples: Sequence[Triple]) -> int:
        """Probe `triples`, buffer the misses, hand every full top
        bucket to the worker. Returns the gate position of the last."""
        v = self._v
        if triples:
            with v.ctx.span("crypto.cache_probe", n=len(triples)):
                _verdicts, misses = v._cache_probe(triples)
            self._misses.extend((triples[i], ck) for (i, ck) in misses)
            self.position += len(misses)
            lanes = v.engine.BUCKETS[-1]
            while len(self._misses) >= lanes:
                self._hand_over(self._misses[:lanes])
                del self._misses[:lanes]
        return self.position

    def end(self) -> None:
        """The feed is over: the tail goes as it is."""
        if self._misses:
            self._hand_over(self._misses)
            self._misses = []

    def submit(self, triples: Sequence[Triple]) -> None:
        """Ungated: one prewarm_many of `triples` on the worker."""
        self._run_on_worker(lambda: self._v.prewarm_many(triples))

    def wait(self, position: int, seq: int = 0) -> float:
        """The gate: block until the drain has landed through
        `position` (or was closed). This is the replaying thread blocked
        on the device, `crypto.device_wait`; returns the seconds it
        was, 0.0 where the chunk had landed."""
        self.end()
        waited_s = 0.0
        with self._v.ctx.span("crypto.device_wait", seq=seq) as sp:
            with self._cv:
                if self._landed < position and not self._closed:
                    t0 = real_monotonic()
                    while self._landed < position and not self._closed:
                        self._cv.wait()
                    waited_s = real_monotonic() - t0
            if sp.live:
                sp.set_tag("chunk", bisect.bisect_left(self._ends, position))
                sp.set_tag("waited", waited_s > 0.0)
        if waited_s and self._v.ctx.stats is not None:
            self._v.ctx.stats.record_drain_stream("gated")
        return waited_s

    def close(self) -> None:
        """Cancel what is queued and join what is in flight: after this
        nothing of the drain runs, and nothing of it writes the cache."""
        with self._cv:
            self._closed = True
            del self._jobs[:]
            self._cv.notify_all()
            thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join()

    def _hand_over(self, chunk: List[Tuple[Triple, bytes]]) -> None:
        end = (self._ends[-1] if self._ends else 0) + len(chunk)
        self._ends.append(end)
        ctx = self._v.ctx
        if ctx.stats is not None:
            ctx.stats.record_drain_stream("chunks")
        tr = enabled_tracer(ctx.tracer)
        cause = tr.current_sid() if tr is not None else 0
        self._run_on_worker(lambda: self._run_chunk(chunk, end, cause))

    def _run_on_worker(self, job: Callable[[], None]) -> None:
        with self._cv:
            if self._closed:
                return
            self._jobs.append(job)
            if self._thread is None:
                self._thread = spawn_worker("catchup.prewarm-pipeline",
                                            self._run)
            self._cv.notify_all()

    # -- the worker ----------------------------------------------------------
    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    while not self._jobs and not self._closed:
                        self._cv.wait()
                    if self._closed:
                        return
                    job = self._jobs.pop(0)
                try:
                    job()
                except Exception as e:  # cache warm only: never fail catchup
                    log.warning("streamed drain: a job failed (%s); the "
                                "closes verify what it held", e)
        finally:
            with self._cv:      # a worker that is gone gates nothing
                self._closed = True
                self._cv.notify_all()

    def _run_chunk(self, chunk: List[Tuple[Triple, bytes]], end: int,
                   cause: int) -> None:
        v, ctx = self._v, self._v.ctx
        ctx.ahead.cause = cause
        try:
            with ctx.span("crypto.prewarm", backend=v.name, n=len(chunk),
                          cause=cause):
                results = v.verify_many([t for (t, _ck) in chunk])
                v._cache_store([ck for (_t, ck) in chunk], results)
        finally:
            ctx.ahead.cause = None
            if ctx.stats is not None:
                ctx.stats.record_drain_stream("landed")
            with self._cv:
                self._landed = end
                self._cv.notify_all()


def make_verifier(backend: str = "cpu", clock=None,
                  max_pending: int = 8192,
                  metrics=None, tracer=None, faults=None,
                  flight_recorder=None,
                  breaker_threshold: int = 3,
                  breaker_cooldown: float = 30.0,
                  cache=None) -> SigVerifier:
    """Config-gated backend selection (Config.SIG_VERIFY_BACKEND): which
    engine, whether a CPU fallback stands beside it behind a breaker,
    and whether a flush runs on the worker. `cache` (keys.VerdictCache)
    is the node's own verdict cache where Config.VERIFY_CACHE_SCOPE is
    "node"; None keeps the process-wide one.

    Device backends ("tpu", "tpu-async") always have the CPU fallback;
    "cpu-resilient" puts the CPU engine behind the same breaker so chaos
    runs exercise the device failure domain on device-less containers.
    "tpu-async" alone flushes on the worker and completes on `clock`.

    Boundary, engine and fallback share ONE context, so one
    VerifierStats cockpit (`<verifier>.stats`): fallback drains are
    attributed to the engine that served them and the admin `verifier`
    endpoint sees the whole boundary."""
    now_fn = clock.now if clock is not None else None
    ctx = VerifierContext(
        cache=cache, tracer=tracer, metrics=metrics, faults=faults,
        stats=VerifierStats(metrics=metrics, tracer=tracer, now_fn=now_fn,
                            flight_recorder=flight_recorder),
        flight_recorder=flight_recorder)
    if backend == "cpu":
        return SigVerifier(CpuSigVerifier(ctx), max_pending=0)
    if backend == "cpu-resilient":
        engine = CpuSigVerifier(ctx)
    elif backend in ("tpu", "tpu-async"):
        # the per-device breaker ring shares the boundary breaker's
        # threshold/cooldown knobs and the injected app clock, so a
        # chip's trip/reprobe schedule is as deterministic under a
        # virtual clock as the whole-engine breaker's
        engine = TpuSigVerifier(ctx, now_fn=now_fn,
                                device_breaker_threshold=breaker_threshold,
                                device_breaker_cooldown=breaker_cooldown)
    else:
        raise ValueError("unknown sig verify backend %r" % backend)
    if backend == "tpu-async":
        assert clock is not None
    return SigVerifier(
        engine, fallback=CpuSigVerifier(ctx),
        breaker=CircuitBreaker(threshold=breaker_threshold,
                               cooldown_s=breaker_cooldown, now_fn=now_fn),
        clock=clock if backend == "tpu-async" else None,
        max_pending=max_pending)


# for the callers handed no verifier (a SignatureChecker or a frame
# checked on its own, the native-apply fallback): the `cpu` boundary
# over the process-wide cache, silent, with no queue to share
CPU_VERIFIER = SigVerifier(CpuSigVerifier(), max_pending=0)
