"""BatchHasher: the config-gated batched-SHA-256 boundary (ISSUE 12).

Crypto verify moved to the device in PRs 1/10; every hash in the
measured close wall — txset hashing, bucket hashing, result-set
hashing, header hashing — stayed serial host `hashlib.sha256`
(`crypto/hashing.py`). This module is the hashing twin of
`crypto/batch_verifier.py`: same bucketed-batch-shape machinery, same
persistent-XLA-cache AOT warmup, same circuit-breaker degradation, its
own cockpit (`HasherStats`, admin `hasher` endpoint) — the
accelerator-side proof-pipeline direction of ACE Runtime (PAPERS.md,
2603.10242) and SZKP's batched-hash accelerator (2408.05890).

The boundary has two call shapes, because SHA-256 has two traffic
shapes in a ledger close:

    hash_many(msgs, site)   -> [digest]   (one digest PER message: the
        bucket entry-leaf blocks the Merkle state commitment absorbs by
        the thousand — the device-batchable load, one padded fixed-shape
        dispatch per bucket of lanes)
    hash_stream(chunks, site) -> digest   (ONE digest over a
        concatenated stream: txset contents, result sets, bucket file
        identity, header bytes — sequential by construction, served on
        the host but streamed through bounded join groups so peak
        memory stays flat and per-chunk Python overhead is amortized)

Backends:
- CpuBatchHasher — hashlib per message; the default and the fallback.
- TpuBatchHasher — ships message batches to the JAX SHA-256 kernel
  (ops/sha256.py) in padded (lanes × blocks) bucket shapes so the
  kernel compiles once per shape; oversize messages split out to the
  host (`hasher.oversize`). Multi-chunk drains double-buffer host
  padding + host→device transfer on the `crypto.hash-staging` worker
  while the device runs the previous chunk.
- ResilientBatchHasher — circuit breaker between a primary (device)
  backend and the CPU fallback: N consecutive dispatch failures trip to
  the fallback for a cooldown window with a half-open reprobe, so a
  lost device degrades hashing throughput instead of killing a close.
  Digests are SHA-256 on both sides, so a mid-drain trip is
  byte-invisible to consensus (pinned by tests/test_batch_hasher.py).

Fault sites (docs/robustness.md): `hash.device-lost` fires inside the
device backend's drain (the dispatch raises as if the device vanished;
the breaker counts it), `hash.dispatch-fail` fires in the resilient
layer before the primary dispatch (the device-agnostic failure the
chaos soaks arm).

Threading: `hash_many` device dispatches run on the caller's thread
(the close path — main loop — and the admin proof path, which posts to
main); only the short-lived staging job (`crypto.hash-staging`) and
the startup warmup thread (`crypto.hash-warmup`) leave it, and both
touch host buffers + JAX state only — never ledger/consensus objects.
Both spawn through util.threads.spawn_worker under registered names,
so the static T1 walk follows them like any Thread(target=...) site.
Bucket-identity hashing from the merge worker pool stays on the plain
`stream_digest` host path below (no shared device state).
"""

from __future__ import annotations

import hashlib
import threading
from typing import List, Optional, Sequence

from ..util.log import get_logger
from ..util.metrics import MetricsRegistry
from ..util.threads import TrackedLock, spawn_worker
from ..util.timer import real_monotonic
from ..util.tracing import tracer_instant
from .batch_verifier import CircuitBreaker

log = get_logger("Perf")

# bounded join group for streamed digests: one C-level update per ~1 MiB
# keeps per-chunk Python overhead amortized AND peak memory flat on
# large txsets/buckets (the ISSUE 12 result-set streaming fix)
_STREAM_GROUP_BYTES = 1 << 20

# the cockpit's bounded call-site ladder: every hash drain is attributed
# to the close-path site that issued it (docs/observability.md#hash-cockpit)
KNOWN_SITES = ("txset", "result-set", "header", "bucket-entries",
               "bench", "other")


def stream_digest(chunks) -> bytes:
    """One SHA-256 over an iterable of byte chunks, grouped into bounded
    joins (see _STREAM_GROUP_BYTES). The registry-free hot path for
    bucket identity hashing on the merge worker pool; the app-level
    boundary (`hash_stream`) wraps this with cockpit attribution."""
    h = hashlib.sha256()
    buf: List[bytes] = []
    size = 0
    for c in chunks:
        buf.append(c)
        size += len(c)
        if size >= _STREAM_GROUP_BYTES:
            h.update(b"".join(buf))
            buf = []
            size = 0
    if buf:
        h.update(b"".join(buf))
    return h.digest()


class HasherStats:
    """Cockpit aggregation for the batch-hash boundary — the fourth
    cockpit, same pattern as VerifierStats / ApplyStats / OverlayStats:
    ONE instance per make_hasher() stack, shared by every layer so
    drains are attributed to the backend that actually SERVED them, and
    the same aggregates feed the admin `hasher` endpoint (`to_json`),
    the metrics registry (`hasher.*`, scrapeable via
    `metrics?format=prometheus`) and the tracer.

    Clocks: event stamps read the injected app clock (`now_fn`), warmup
    compile DURATIONS read util.timer.real_monotonic (sanctioned: an
    XLA compile takes real time under a frozen virtual clock).
    Recording happens on the caller's thread, the staging worker and
    the warmup thread under `_lock`; registry metric objects are
    individually thread-safe."""

    def __init__(self, metrics=None, tracer=None, now_fn=None,
                 flight_recorder=None) -> None:
        self._now = now_fn or real_monotonic
        self.metrics = metrics if metrics is not None \
            else MetricsRegistry(now_fn=self._now)
        self.tracer = tracer
        self.flight_recorder = flight_recorder
        self._lock = TrackedLock("crypto.hasher-stats")
        self.backends: dict = {}   # name -> {drains, msgs, bytes, pad_blocks}
        self.buckets: dict = {}    # "LxB" -> counts + histograms
        self.sites: dict = {}      # site -> {drains, msgs, bytes}
        self.oversize = 0
        self.staging = {"chunks": 0, "staged_s": 0.0, "overlap_s": 0.0,
                        "last_overlap_pct": None, "stalls": 0}
        self.warmup = {"state": "idle", "planned": [], "begun_t": None,
                       "done_t": None, "error": None, "shapes": {}}
        self.compile_cache = {"enabled": None, "dir": None, "hits": 0,
                              "misses": 0, "unknown": 0, "error": None}
        m = self.metrics
        self._h_batch = m.new_histogram("hasher.drain.batch-size")
        self._h_bytes = m.new_histogram("hasher.drain.bytes")
        self._h_pad = m.new_histogram("hasher.drain.pad-waste")
        self._h_occ = m.new_histogram("hasher.drain.occupancy-pct")
        self._h_splits = m.new_histogram("hasher.drain.splits")
        self._g_overlap = m.new_gauge("hasher.staging.overlap-pct")
        self._g_wstate = m.new_gauge("hasher.warmup.state")
        self._g_wdone = m.new_gauge("hasher.warmup.shapes-done")
        self._h_wsec = m.new_histogram("hasher.warmup.shape-seconds")
        self._g_cc = m.new_gauge("hasher.compile-cache.enabled")
        self._c_hit = m.new_counter("hasher.compile-cache.hit")
        self._c_miss = m.new_counter("hasher.compile-cache.miss")

    # -- drains --------------------------------------------------------------
    def record_drain(self, backend: str, msgs: int, nbytes: int,
                     pad_blocks: int = 0, real_blocks: int = 0,
                     splits: int = 1) -> None:
        """One hash_many drain attributed to the serving backend.
        `pad_blocks` is the total padding waste in 64-byte block units
        across every padded dispatch of the drain (structurally 0 on
        host drains); occupancy is real blocks over padded capacity."""
        total = real_blocks + pad_blocks
        occ = 100.0 * real_blocks / total if total else 100.0
        with self._lock:
            d = self.backends.setdefault(
                backend, {"drains": 0, "msgs": 0, "bytes": 0,
                          "pad_blocks": 0})
            d["drains"] += 1
            d["msgs"] += msgs
            d["bytes"] += nbytes
            d["pad_blocks"] += pad_blocks
        self._h_batch.update(msgs)
        self._h_bytes.update(nbytes)
        self._h_pad.update(pad_blocks)
        self._h_occ.update(occ)
        self._h_splits.update(splits)
        self.metrics.new_meter("hasher.drains.%s" % backend).mark()

    def record_bucket_dispatch(self, lanes: int, blocks: int, msgs: int,
                               real_blocks: int) -> None:
        """One padded device dispatch into the fixed (lanes × blocks)
        shape — names come from the backend's static ladder, so the
        dynamic `hasher.bucket.<b>.*` name space stays bounded."""
        key = "%dx%d" % (lanes, blocks)
        cap = lanes * blocks
        pad = cap - real_blocks
        occ = 100.0 * real_blocks / cap if cap else 100.0
        with self._lock:
            b = self.buckets.get(key)
            if b is None:
                b = self.buckets[key] = {
                    "dispatches": 0, "msgs": 0, "pad_blocks": 0,
                    "_occ": self.metrics.new_histogram(
                        "hasher.bucket.%s.occupancy-pct" % key),
                    "_pad": self.metrics.new_histogram(
                        "hasher.bucket.%s.pad-waste" % key),
                    "_m": self.metrics.new_meter(
                        "hasher.bucket.%s.drains" % key)}
            b["dispatches"] += 1
            b["msgs"] += msgs
            b["pad_blocks"] += pad
        b["_occ"].update(occ)
        b["_pad"].update(pad)
        b["_m"].mark()

    def record_site(self, site: str, msgs: int, nbytes: int) -> None:
        """Close-path attribution: which hashing CONSUMER issued the
        drain. `site` comes from the bounded KNOWN_SITES ladder."""
        if site not in KNOWN_SITES:
            site = "other"
        with self._lock:
            s = self.sites.setdefault(site, {"drains": 0, "msgs": 0,
                                             "bytes": 0})
            s["drains"] += 1
            s["msgs"] += msgs
            s["bytes"] += nbytes
        self.metrics.new_meter("hasher.site.%s.drains" % site).mark()

    def record_oversize(self, n: int) -> None:
        """Messages whose padded block count exceeds the largest device
        shape: hashed on the host instead (split out of the dispatch)."""
        with self._lock:
            self.oversize += n
        self.metrics.new_meter("hasher.oversize").mark(n)

    # -- staging -------------------------------------------------------------
    def record_staging(self, staged_s: float, overlap_s: float,
                       chunks: int) -> None:
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
        with self._lock:
            self.staging["stalls"] += 1
        self.metrics.new_meter("hasher.staging.stall").mark()
        tracer_instant(self.tracer, "hasher.staging.stall", cat="crypto")

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
        self.compile_cache.update({"enabled": False, "error": err})
        self._g_cc.set(0)
        self.metrics.new_meter("hasher.compile-cache.unavailable").mark()
        tracer_instant(self.tracer, "hasher.compile-cache.unavailable",
                       cat="crypto", error=err)
        if self.flight_recorder is not None:
            self.flight_recorder.dump("hash-compile-cache-unavailable",
                                      extra={"error": err})

    WARMUP_STATE_CODE = {"idle": 0, "running": 1, "done": 2, "failed": 3}

    def warmup_begin(self, shapes) -> None:
        with self._lock:
            self.warmup.update({"state": "running", "begun_t": self._now(),
                                "done_t": None, "error": None,
                                "planned": ["%dx%d" % s for s in shapes]})
        self._g_wstate.set(self.WARMUP_STATE_CODE["running"])
        tracer_instant(self.tracer, "hasher.warmup.begin", cat="crypto",
                       shapes=["%dx%d" % s for s in shapes])

    def warmup_shape_done(self, shape, seconds: float, cache_hit) -> None:
        cache = ("hit" if cache_hit is True else
                 "miss" if cache_hit is False else "unknown")
        key = "%dx%d" % shape
        with self._lock:
            self.warmup["shapes"][key] = {
                "seconds": round(seconds, 3), "cache": cache,
                "t": self._now()}
            done = len(self.warmup["shapes"])
            self.compile_cache[
                {"hit": "hits", "miss": "misses",
                 "unknown": "unknown"}[cache]] += 1
        self._h_wsec.update(seconds)
        self._g_wdone.set(done)
        if cache_hit is True:
            self._c_hit.inc()
        elif cache_hit is False:
            self._c_miss.inc()
        tracer_instant(self.tracer, "hasher.warmup.shape", cat="crypto",
                       shape=key, seconds=round(seconds, 3), cache=cache)

    def warmup_done(self) -> None:
        with self._lock:
            self.warmup.update({"state": "done", "done_t": self._now()})
        self._g_wstate.set(self.WARMUP_STATE_CODE["done"])
        tracer_instant(self.tracer, "hasher.warmup.end", cat="crypto",
                       shapes=len(self.warmup["shapes"]))

    def warmup_failed(self, err: str) -> None:
        with self._lock:
            self.warmup.update({"state": "failed", "done_t": self._now(),
                                "error": err})
        self._g_wstate.set(self.WARMUP_STATE_CODE["failed"])
        self.metrics.new_meter("hasher.warmup.failure").mark()
        tracer_instant(self.tracer, "hasher.warmup.failed", cat="crypto",
                       error=err)
        if self.flight_recorder is not None:
            self.flight_recorder.dump("hash-warmup-failed",
                                      extra={"error": err})

    # -- export --------------------------------------------------------------
    def to_json(self) -> dict:
        with self._lock:
            backends = {k: dict(v) for k, v in self.backends.items()}
            buckets = {
                k: {"dispatches": d["dispatches"], "msgs": d["msgs"],
                    "pad_blocks_total": d["pad_blocks"],
                    "occupancy_pct": d["_occ"].snapshot(),
                    "pad_waste": d["_pad"].snapshot()}
                for k, d in sorted(self.buckets.items())}
            sites = {k: dict(v) for k, v in sorted(self.sites.items())}
            staging = dict(self.staging)
            warm = dict(self.warmup)
            warm["shapes"] = {k: dict(v)
                              for k, v in self.warmup["shapes"].items()}
            cc = dict(self.compile_cache)
            oversize = self.oversize
        return {
            "drains": {"by_backend": backends,
                       "batch_size": self._h_batch.snapshot(),
                       "bytes": self._h_bytes.snapshot(),
                       "pad_waste": self._h_pad.snapshot(),
                       "occupancy_pct": self._h_occ.snapshot(),
                       "splits": self._h_splits.snapshot()},
            "buckets": buckets,
            "sites": sites,
            "oversize_msgs": oversize,
            "staging": staging,
            "warmup": warm,
            "compile_cache": cc,
        }


class BatchHasher:
    """Abstract backend; see module docstring. `tracer`/`metrics`/
    `faults`/`stats` are installed by make_hasher; None keeps direct
    constructions (tests, bench children) silent."""

    name = "abstract"
    wants_warmup = False
    tracer = None
    metrics = None
    faults = None
    stats = None

    def _span(self, name: str, **tags):
        from ..util.tracing import tracer_span
        return tracer_span(self.tracer, name, cat="crypto", **tags)

    def hash_many(self, msgs: Sequence[bytes],
                  site: str = "other") -> List[bytes]:
        raise NotImplementedError

    def digest_one(self, data: bytes, site: str = "other") -> bytes:
        """Single-digest convenience (header hash, txset identity):
        always host-served — a one-lane device dispatch would pay the
        round trip for nothing — but attributed to the cockpit like any
        drain, so the close path's hashing is fully accounted."""
        if self.stats is not None:
            self.stats.record_site(site, 1, len(data))
            self.stats.record_drain("host-stream", 1, len(data))
        return hashlib.sha256(data).digest()

    def hash_stream(self, chunks, site: str = "other") -> bytes:
        """One digest over a concatenated stream (txset contents,
        result sets, bucket identity): sequential by construction, so
        it is served on the host via `stream_digest`'s bounded join
        groups — ONE implementation of the grouping algorithm, this
        wrapper only counts chunks/bytes for cockpit attribution under
        `site`."""
        counted = {"n": 0, "bytes": 0}

        def walk():
            for c in chunks:
                counted["n"] += 1
                counted["bytes"] += len(c)
                yield c

        out = stream_digest(walk())
        if self.stats is not None:
            self.stats.record_site(site, counted["n"], counted["bytes"])
            self.stats.record_drain("host-stream", counted["n"],
                                    counted["bytes"])
        return out


class CpuBatchHasher(BatchHasher):
    """Synchronous hashlib backend: the default and the breaker
    fallback."""

    name = "cpu"

    def hash_many(self, msgs: Sequence[bytes],
                  site: str = "other") -> List[bytes]:
        nbytes = sum(len(m) for m in msgs)
        with self._span("crypto.hash_many", backend=self.name,
                        site=site, n=len(msgs), bytes=nbytes):
            out = [hashlib.sha256(m).digest() for m in msgs]
            if self.stats is not None:
                self.stats.record_site(site, len(msgs), nbytes)
                self.stats.record_drain(self.name, len(msgs), nbytes)
            return out


class TpuBatchHasher(BatchHasher):
    """JAX batched backend over ops/sha256.py.

    Dispatch shapes are (lane bucket × block bucket) pairs from the
    static ladders below, so the kernel compiles once per shape and a
    drain of thousands of entry-leaf messages becomes a handful of
    fixed-shape device calls. Messages are stably sorted by block count
    before chunking so a chunk's block bucket fits its longest member
    tightly (pad waste is lanes-bucket rounding, not worst-case blocks);
    digests are returned in the caller's order. Oversize messages
    (beyond the largest block bucket) split out to the host and are
    counted (`hasher.oversize`).

    Double-buffered staging: while the device hashes chunk K, chunk K+1
    pads + device_puts on the `crypto.hash-staging` worker — same
    overlap contract (and stall fallback) as the verify fleet's staging.
    """

    name = "tpu"
    wants_warmup = True
    LANE_BUCKETS = (256, 1024, 4096)
    BLOCK_BUCKETS = (1, 2, 4, 8, 16)
    # shapes the AOT warmup compiles: the small-drain shape the live
    # close path uses plus the bulk entry-leaf shapes
    WARM_SHAPES = ((256, 2), (4096, 2), (4096, 4))

    def __init__(self) -> None:
        self._warmed = False
        self._warmup_thread: Optional[threading.Thread] = None
        self._warmup_error: Optional[BaseException] = None
        self._platform: Optional[str] = None

    # -- buckets -------------------------------------------------------------
    def _lane_bucket(self, n: int) -> int:
        for b in self.LANE_BUCKETS:
            if n <= b:
                return b
        return self.LANE_BUCKETS[-1]

    def _block_bucket(self, blocks: int) -> int:
        for b in self.BLOCK_BUCKETS:
            if blocks <= b:
                return b
        return self.BLOCK_BUCKETS[-1]

    # -- warmup --------------------------------------------------------------
    def warmup(self, wait: bool = False) -> None:
        """AOT-compile every warm shape off the consensus path (startup
        background thread); idempotent. A failure is recorded in the
        cockpit and the node keeps running; a caller that waits gets it
        raised."""
        if self._warmed:
            return
        if self._warmup_thread is None:
            self._warmup_thread = spawn_worker(
                "crypto.hash-warmup", self._hash_warmup_impl)
        if wait:
            self._warmup_thread.join()
            if self._warmup_error is not None:
                raise self._warmup_error

    def _compile_shape(self, lanes: int, blocks: int) -> None:
        import numpy as np
        from ..ops.sha256 import hash_blocks_jit
        np.asarray(hash_blocks_jit(
            np.zeros((lanes, blocks, 16), np.uint32),
            np.ones((lanes,), np.int32)))

    def _hash_warmup_impl(self) -> None:
        from ..parallel.device import (
            cache_hit, compile_cache_dir, compile_cache_events,
        )
        st = self.stats
        try:
            if st is not None:
                st.set_compile_cache_dir(compile_cache_dir())
                st.warmup_begin(self.WARM_SHAPES)
            for shape in self.WARM_SHAPES:
                t0 = real_monotonic()
                with compile_cache_events() as events:
                    self._compile_shape(*shape)
                dt = real_monotonic() - t0
                if st is not None:
                    st.warmup_shape_done(shape, dt, cache_hit(events))
            self._warmed = True
            if st is not None:
                st.warmup_done()
            log.info("hash kernel warmup complete (%d shapes)",
                     len(self.WARM_SHAPES))
        except Exception as e:
            log.warning("hash kernel warmup failed: %s", e)
            self._warmup_error = e
            if st is not None:
                st.warmup_failed(repr(e))

    # -- staging + dispatch --------------------------------------------------
    def _stage_hash_chunk(self, msgs: Sequence[bytes],
                          lanes: int, blocks: int) -> dict:
        """Pad one chunk into its device shape and move it to the
        device; runs on the staging worker when double-buffered."""
        import jax
        from ..ops.sha256 import pad_messages_np
        words, counts = pad_messages_np(msgs, blocks)
        if len(msgs) < lanes:
            import numpy as np
            padw = np.zeros((lanes, blocks, 16), np.uint32)
            padw[:len(msgs)] = words
            padc = np.zeros((lanes,), np.int32)
            padc[:len(msgs)] = counts
            words, counts = padw, padc
        real_blocks = int(counts.sum())
        return {"words": jax.device_put(words),
                "counts": jax.device_put(counts),
                "n": len(msgs), "lanes": lanes, "blocks": blocks,
                "real_blocks": real_blocks}

    def hash_many(self, msgs: Sequence[bytes],
                  site: str = "other") -> List[bytes]:
        import numpy as np
        import jax
        from ..ops.sha256 import (
            blocks_for_len, digests_to_bytes, hash_blocks_jit,
        )
        if self._platform is None:
            self._platform = jax.devices()[0].platform
        if self.faults is not None:
            # the device vanishing mid-drain: the dispatch raises, the
            # resilient layer's breaker counts it and the drain
            # completes on the CPU fallback with identical digests
            self.faults.fire_point("hash.device-lost")
        nbytes = sum(len(m) for m in msgs)
        st = self.stats
        out: List[Optional[bytes]] = [None] * len(msgs)
        with self._span("crypto.hash_many", backend=self.name,
                        platform=self._platform, site=site,
                        n=len(msgs), bytes=nbytes) as sp:
            blocks = [blocks_for_len(len(m)) for m in msgs]
            max_dev = self.BLOCK_BUCKETS[-1]
            dev_idx = [i for i, b in enumerate(blocks) if b <= max_dev]
            over_idx = [i for i, b in enumerate(blocks) if b > max_dev]
            if over_idx:
                # oversize lanes hash on the host, split out of the
                # padded dispatch entirely
                if st is not None:
                    st.record_oversize(len(over_idx))
                for i in over_idx:
                    out[i] = hashlib.sha256(msgs[i]).digest()
            # stable sort by block count: a chunk's block bucket fits
            # its longest member tightly
            dev_idx.sort(key=lambda i: blocks[i])
            chunks: List[List[int]] = []
            k = 0
            while k < len(dev_idx):
                chunks.append(dev_idx[k:k + self.LANE_BUCKETS[-1]])
                k += len(chunks[-1])

            def route(idx_chunk):
                lanes = self._lane_bucket(len(idx_chunk))
                blk = self._block_bucket(
                    max(blocks[i] for i in idx_chunk))
                return lanes, blk

            pad_blocks = 0
            real_total = 0
            batches = 0
            staged_s = overlap_s = 0.0
            staged_chunks = 0
            staged = None
            if chunks:
                lanes, blk = route(chunks[0])
                staged = self._stage_hash_chunk(
                    [msgs[i] for i in chunks[0]], lanes, blk)
            for c in range(len(chunks)):
                job = None
                if c + 1 < len(chunks):
                    nl, nb = route(chunks[c + 1])
                    job = _HashStagingJob(
                        self, [msgs[i] for i in chunks[c + 1]], nl, nb)
                with self._span("crypto.hash.dispatch",
                                backend=self.name, n=staged["n"],
                                lanes=staged["lanes"],
                                blocks=staged["blocks"]):
                    dig_dev = hash_blocks_jit(staged["words"],
                                              staged["counts"])  # async
                    wait_t0 = real_monotonic()
                    dig = np.asarray(dig_dev)    # blocks on the device
                    wait_t1 = real_monotonic()
                raw = digests_to_bytes(dig[:staged["n"]])
                for i, d in zip(chunks[c], raw):
                    out[i] = d
                cap = staged["lanes"] * staged["blocks"]
                pad_blocks += cap - staged["real_blocks"]
                real_total += staged["real_blocks"]
                batches += 1
                if st is not None:
                    st.record_bucket_dispatch(
                        staged["lanes"], staged["blocks"], staged["n"],
                        staged["real_blocks"])
                if job is not None:
                    staged, s_s, o_s, stalled = job.result(wait_t0,
                                                           wait_t1)
                    if stalled:
                        if st is not None:
                            st.record_staging_stall()
                        nl, nb = route(chunks[c + 1])
                        staged = self._stage_hash_chunk(
                            [msgs[i] for i in chunks[c + 1]], nl, nb)
                    else:
                        staged_s += s_s
                        overlap_s += o_s
                        staged_chunks += 1
            sp.set_tag("batches", batches)
            sp.set_tag("pad_blocks", pad_blocks)
            sp.set_tag("oversize", len(over_idx))
            if staged_chunks and sp.live:
                sp.set_tag("staging_overlap_pct", round(
                    100.0 * overlap_s / staged_s, 1) if staged_s > 0
                    else 100.0)
            if st is not None:
                if staged_chunks:
                    st.record_staging(staged_s, overlap_s, staged_chunks)
                st.record_site(site, len(msgs), nbytes)
                st.record_drain(self.name, len(msgs), nbytes,
                                pad_blocks=pad_blocks,
                                real_blocks=real_total,
                                splits=max(1, batches))
        return out  # type: ignore[return-value]


class _HashStagingJob:
    """One double-buffer staging unit: pads + device_puts hash chunk
    K+1 on the `crypto.hash-staging` worker while the dispatch thread
    waits on chunk K. Timing is util.timer.real_monotonic (sanctioned:
    host/device overlap is real elapsed time). A staging failure is
    reported as `stalled`; the caller re-stages synchronously so the
    drain always completes."""

    __slots__ = ("h", "msgs", "lanes", "blocks", "staged", "error",
                 "t0", "t1", "thread")

    def __init__(self, hasher: "TpuBatchHasher", msgs: Sequence[bytes],
                 lanes: int, blocks: int) -> None:
        self.h = hasher
        self.msgs = msgs
        self.lanes = lanes
        self.blocks = blocks
        self.staged = None
        self.error: Optional[Exception] = None
        self.t0 = self.t1 = 0.0
        self.thread = spawn_worker("crypto.hash-staging", self._run)

    def _run(self) -> None:
        self.t0 = real_monotonic()
        try:
            self.staged = self.h._stage_hash_chunk(
                self.msgs, self.lanes, self.blocks)
        except Exception as e:
            self.error = e
        self.t1 = real_monotonic()

    def result(self, wait_t0: float, wait_t1: float):
        self.thread.join()
        staged_s = max(0.0, self.t1 - self.t0)
        overlap_s = max(0.0, min(self.t1, wait_t1) -
                        max(self.t0, wait_t0))
        if self.error is not None:
            log.warning("hash staging stalled (%s); re-staging chunk "
                        "synchronously", self.error)
            return None, staged_s, overlap_s, True
        return self.staged, staged_s, overlap_s, False


class ResilientBatchHasher(BatchHasher):
    """Primary backend behind a circuit breaker, CPU fallback beside it
    (the same closed → open → half-open machinery as the verify
    breaker, on the same injected app clock). A raising primary records
    a failure and the drain re-runs on the fallback — digests are
    SHA-256 either way, so degradation is byte-invisible. A trip emits
    metrics + a flight dump; the first successful half-open probe emits
    the recover marker."""

    name = "resilient"

    def __init__(self, primary: BatchHasher, fallback: BatchHasher,
                 breaker: Optional[CircuitBreaker] = None) -> None:
        self.primary = primary
        self.fallback = fallback
        self.breaker = breaker or CircuitBreaker()
        self.breaker.on_trip = self._on_trip
        self.breaker.on_recover = self._on_recover
        self.flight_recorder = None   # installed by make_hasher

    # -- breaker events ------------------------------------------------------
    def _breaker_mark(self, event: str) -> None:
        if self.metrics is not None:
            self.metrics.new_meter("hasher.breaker.%s" % event).mark()
            self.metrics.new_counter("hasher.breaker.state").set_count(
                self.breaker.state_code())
        tracer_instant(self.tracer, "hasher.breaker.%s" % event,
                       cat="crypto", primary=self.primary.name,
                       failures=self.breaker.consecutive_failures)

    def _on_trip(self) -> None:
        log.warning("hash breaker TRIPPED: %d consecutive %s-dispatch "
                    "failures; falling back to %s for %.0fs",
                    self.breaker.consecutive_failures, self.primary.name,
                    self.fallback.name, self.breaker.cooldown_s)
        self._breaker_mark("trip")
        if self.flight_recorder is not None:
            self.flight_recorder.dump(
                "hash-breaker-trip",
                extra={"primary": self.primary.name,
                       "breaker": self.breaker.to_json()})

    def _on_recover(self) -> None:
        log.info("hash breaker recovered: %s backend healthy again",
                 self.primary.name)
        self._breaker_mark("recover")

    # -- delegation ----------------------------------------------------------
    @property
    def wants_warmup(self) -> bool:
        return self.primary.wants_warmup

    @property
    def inner(self) -> BatchHasher:
        return self.primary

    def warmup(self, wait: bool = False) -> None:
        w = getattr(self.primary, "warmup", None)
        if w is not None:
            w(wait)

    def hash_many(self, msgs: Sequence[bytes],
                  site: str = "other") -> List[bytes]:
        if self.breaker.allow():
            try:
                with self._span("crypto.hash_dispatch_primary",
                                backend=self.primary.name,
                                n=len(msgs)):
                    if self.faults is not None:
                        self.faults.fire_point("hash.dispatch-fail")
                    out = self.primary.hash_many(msgs, site=site)
                self.breaker.record_success()
                return out
            except Exception as e:
                if self.metrics is not None:
                    self.metrics.new_meter(
                        "hasher.dispatch-failure").mark()
                tripped = self.breaker.record_failure()
                if not tripped:
                    log.warning("%s hash dispatch failed (%s): %d/%d "
                                "toward breaker trip", self.primary.name,
                                e, self.breaker.consecutive_failures,
                                self.breaker.threshold)
        if self.metrics is not None:
            self.metrics.new_meter("hasher.fallback-drain").mark()
        with self._span("crypto.hash_fallback", backend=self.name,
                        served_by=self.fallback.name, n=len(msgs),
                        breaker=self.breaker.state):
            return self.fallback.hash_many(msgs, site=site)


def make_hasher(backend: str = "cpu", clock=None,
                metrics=None, tracer=None, faults=None,
                flight_recorder=None,
                breaker_threshold: int = 3,
                breaker_cooldown: float = 30.0) -> BatchHasher:
    """Config-gated backend selection (Config.HASH_BACKEND).

    The device backend ("tpu") is always wrapped in a
    ResilientBatchHasher with a CPU fallback; "cpu-resilient" wraps the
    CPU backend in the same breaker machinery so chaos runs exercise
    the hash failure domain on device-less containers. Every layer
    shares ONE HasherStats cockpit, so fallback drains are attributed
    to the backend that served them."""
    now_fn = clock.now if clock is not None else None
    stats = HasherStats(metrics=metrics, tracer=tracer, now_fn=now_fn,
                        flight_recorder=flight_recorder)

    def resilient(primary: BatchHasher) -> ResilientBatchHasher:
        primary.tracer = tracer
        primary.metrics = metrics
        primary.stats = stats
        primary.faults = faults
        fb = CpuBatchHasher()
        fb.tracer = tracer
        fb.metrics = metrics
        fb.stats = stats
        r = ResilientBatchHasher(
            primary, fb,
            CircuitBreaker(threshold=breaker_threshold,
                           cooldown_s=breaker_cooldown, now_fn=now_fn))
        r.tracer = tracer
        r.flight_recorder = flight_recorder
        r.stats = stats
        return r

    if backend == "cpu":
        h: BatchHasher = CpuBatchHasher()
    elif backend == "cpu-resilient":
        h = resilient(CpuBatchHasher())
    elif backend == "tpu":
        h = resilient(TpuBatchHasher())
    else:
        raise ValueError("unknown hash backend %r" % backend)
    h.tracer = tracer
    h.metrics = metrics
    h.faults = faults
    h.stats = stats
    return h
