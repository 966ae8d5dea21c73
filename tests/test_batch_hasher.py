"""BatchHasher boundary tests (ISSUE 12): kernel/host digest parity,
bucketed dispatch shapes, breaker degradation with identical digests,
streamed close-path hashing, and the warm-restart XLA-cache story for
the hash kernel (the verify kernel's test_cold_start twin)."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from stellar_core_tpu.crypto.batch_hasher import (
    CpuBatchHasher, HasherStats, ResilientBatchHasher, TpuBatchHasher,
    make_hasher, stream_digest,
)
from stellar_core_tpu.crypto.batch_verifier import CircuitBreaker
from stellar_core_tpu.ops.sha256 import (
    blocks_for_len, pad_messages_np, sha256_batch_device,
    sha256_batch_host,
)
from stellar_core_tpu.util.faults import FaultInjector, InjectedFault
from stellar_core_tpu.util.metrics import MetricsRegistry


# --- kernel oracle parity ---------------------------------------------------

def test_kernel_matches_hashlib_over_boundary_lengths():
    """Every FIPS padding boundary: empty, <1 block, the 55/56 split
    (length field crossing into a second block), exact block multiples,
    and multi-block messages."""
    msgs = [b"", b"abc", b"a" * 54, b"a" * 55, b"a" * 56, b"a" * 63,
            b"a" * 64, b"a" * 118, b"a" * 119, b"a" * 120, b"a" * 128,
            os.urandom(250), os.urandom(500)]
    assert sha256_batch_device(msgs) == sha256_batch_host(msgs)


def test_kernel_bucketed_shape_masks_short_lanes():
    """A fixed block bucket larger than any message still produces the
    right digest per lane — the n_blocks mask stops each lane at its
    own final block."""
    msgs = [b"x" * n for n in (0, 1, 60, 200, 400)]
    assert sha256_batch_device(msgs, max_blocks=8) == \
        sha256_batch_host(msgs)


def test_pad_messages_np_block_counts():
    words, counts = pad_messages_np([b"", b"a" * 55, b"a" * 56])
    assert list(counts) == [1, 1, 2]
    assert blocks_for_len(119) == 2 and blocks_for_len(120) == 3
    assert words.shape == (3, 2, 16)


# --- backend parity + bucketing --------------------------------------------

def _mixed_msgs():
    # mixed sizes incl. one oversize (> 16 blocks = > 1015 bytes)
    return [os.urandom(n) for n in
            (0, 3, 40, 64, 119, 300, 900, 1015, 1016, 2048)] * 3


def test_tpu_hasher_matches_cpu_hasher_in_order():
    msgs = _mixed_msgs()
    tpu = make_hasher("tpu")
    cpu = make_hasher("cpu")
    want = sha256_batch_host(msgs)
    assert tpu.hash_many(msgs, site="bench") == want
    assert cpu.hash_many(msgs, site="bench") == want
    j = tpu.stats.to_json()
    # the oversize lanes split out to the host and are counted
    assert j["oversize_msgs"] == 6
    assert j["buckets"], "no bucketed device dispatch recorded"
    assert j["sites"]["bench"]["msgs"] == len(msgs)


def test_hash_stream_equals_one_shot_digest():
    chunks = [os.urandom(1000) for _ in range(40)]
    want = hashlib.sha256(b"".join(chunks)).digest()
    assert stream_digest(iter(chunks)) == want
    h = CpuBatchHasher()
    assert h.hash_stream(iter(chunks), site="result-set") == want
    # cross the bounded-join group boundary (1 MiB) — memory-flat path
    big = [b"z" * (300 * 1024)] * 5
    assert stream_digest(iter(big)) == \
        hashlib.sha256(b"".join(big)).digest()


def test_digest_one_matches_sha256_and_attributes_site():
    stats = HasherStats()
    h = CpuBatchHasher()
    h.stats = stats
    assert h.digest_one(b"header-bytes", site="header") == \
        hashlib.sha256(b"header-bytes").digest()
    assert stats.to_json()["sites"]["header"]["drains"] == 1


# --- resilience -------------------------------------------------------------

class _Boom(TpuBatchHasher):
    def hash_many(self, msgs, site="other"):
        raise RuntimeError("device gone")


def test_breaker_trips_to_fallback_with_identical_digests():
    msgs = [b"m%d" % i for i in range(10)]
    now = [0.0]
    metrics = MetricsRegistry(now_fn=lambda: now[0])
    boom = _Boom()
    fb = CpuBatchHasher()
    r = ResilientBatchHasher(
        boom, fb, CircuitBreaker(threshold=2, cooldown_s=5.0,
                                 now_fn=lambda: now[0]))
    r.metrics = metrics
    for layer in (boom, fb, r):
        layer.stats = HasherStats(metrics=metrics,
                                  now_fn=lambda: now[0])
    want = sha256_batch_host(msgs)
    assert r.hash_many(msgs) == want          # failure 1, fallback
    assert r.hash_many(msgs) == want          # failure 2 -> TRIP
    assert r.breaker.state == CircuitBreaker.OPEN
    assert r.hash_many(msgs) == want          # open: straight fallback
    m = metrics.to_json()
    assert m["hasher.breaker.trip"]["count"] == 1
    assert m["hasher.dispatch-failure"]["count"] == 2
    assert m["hasher.fallback-drain"]["count"] == 3
    # past the cooldown the half-open probe runs the (still-broken)
    # primary once more; a healthy primary would re-close
    now[0] = 6.0
    assert r.hash_many(msgs) == want
    assert r.breaker.state == CircuitBreaker.OPEN


def test_dispatch_fail_fault_site_drives_the_breaker():
    faults = FaultInjector(seed=3)
    faults.configure("hash.dispatch-fail", probability=1.0, count=3)
    r = make_hasher("cpu-resilient", faults=faults,
                    breaker_threshold=3)
    msgs = [b"a", b"bb", b"ccc"]
    want = sha256_batch_host(msgs)
    for _ in range(3):
        assert r.hash_many(msgs) == want
    assert r.breaker.trips == 1


def test_device_lost_fault_fires_inside_the_device_backend():
    faults = FaultInjector(seed=4)
    faults.configure("hash.device-lost", probability=1.0, count=1)
    tpu = TpuBatchHasher()
    tpu.faults = faults
    with pytest.raises(InjectedFault):
        tpu.hash_many([b"x"])
    # wrapped resiliently the same fault degrades, never raises
    faults.configure("hash.device-lost", probability=1.0, count=1)
    r = make_hasher("tpu", faults=faults)
    assert r.hash_many([b"x"]) == [hashlib.sha256(b"x").digest()]


# --- the close path's streamed result hash ---------------------------------

def test_close_result_hash_matches_concatenated_oracle():
    """The streamed result-set hash (ISSUE 12 satellite) must equal the
    old build-the-blob-then-hash path byte for byte: recompute it from
    the stored txhistory rows of a real close."""
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    cfg = Config.test_config(91)
    cfg.DATABASE = "sqlite3://:memory:"
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    lg = LoadGenerator(app)
    lg.generate_accounts(4)
    app.manual_close()
    lg.generate_payments(5)
    app.clock.set_virtual_time(app.clock.now() + 1.0)
    app.manual_close()
    seq = app.ledger_manager.last_closed_ledger_num()
    rows = app.database.execute(
        "SELECT txresult FROM txhistory WHERE ledgerseq=? "
        "ORDER BY txindex", (seq,)).fetchall()
    assert rows, "close stored no txs"
    blob = len(rows).to_bytes(4, "big") + b"".join(r[0] for r in rows)
    assert app.ledger_manager.lcl_header.txSetResultHash == \
        hashlib.sha256(blob).digest()
    # the close path attributes its hashing to the cockpit's site
    # ladder — txset included (the herder/close value check routes the
    # contents hash through the app hasher on cache misses)
    sites = app.batch_hasher.stats.to_json()["sites"]
    for site in ("txset", "result-set", "header"):
        assert sites.get(site, {}).get("drains", 0) >= 1, (site, sites)


# --- warm restart (persistent XLA cache) -----------------------------------

_CHILD = r"""
import json, os
from stellar_core_tpu.crypto.batch_hasher import HasherStats, TpuBatchHasher

import jax
# the tiny test shape compiles in ms on CPU — drop JAX's persistence
# floor so the cache actually records it (the floor only skips compiles
# too cheap to be worth caching)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

def warmed_node():
    h = TpuBatchHasher()
    h.WARM_SHAPES = ((32, 1),)
    h.stats = HasherStats()
    h.warmup(wait=True)
    import hashlib
    assert h.hash_many([b"m"]) == [hashlib.sha256(b"m").digest()]
    return h.stats.to_json()

cold = warmed_node()
entries_after_cold = sum(len(fs) for _d, _s, fs
                         in os.walk(os.environ["SCT_TEST_CACHE"]))
# the "restart": drop every in-memory executable, then a FRESH hasher
# instance warms against the same persistent dir — the same mechanism a
# process restart exercises, without paying a second jax import
jax.clear_caches()
warm = warmed_node()
entries_after_warm = sum(len(fs) for _d, _s, fs
                         in os.walk(os.environ["SCT_TEST_CACHE"]))
print("HASH_COLD_JSON " + json.dumps(
    {"cold_state": cold["warmup"]["state"],
     "cold_cache_enabled": cold["compile_cache"]["enabled"],
     "warm_state": warm["warmup"]["state"],
     "warm_cache_enabled": warm["compile_cache"]["enabled"],
     "cold_class": cold["warmup"]["shapes"]["32x1"]["cache"],
     "warm_class": warm["warmup"]["shapes"]["32x1"]["cache"],
     "entries_after_cold": entries_after_cold,
     "entries_after_warm": entries_after_warm}))
"""


def test_hash_warmup_restart_uses_persistent_cache(tmp_path):
    """Warm-restart of the hasher's XLA cache (ISSUE 12 satellite): a
    cold warmup populates the persistent cache dir; after
    jax.clear_caches() (the in-memory half of a restart) a fresh hasher
    warms against the same dir without writing NEW entries — the
    executable came from the persistent cache. One child process (one
    jax import) keeps the tier-1 cost at half the verifier twin's."""
    cache = str(tmp_path / "hash-xla-cache")
    env = dict(os.environ)
    env["SCT_TEST_CACHE"] = env["JAX_COMPILATION_CACHE_DIR"] = cache
    r = subprocess.run([sys.executable, "-c", _CHILD],
                       capture_output=True, text=True, timeout=900,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    got = None
    for line in r.stdout.splitlines():
        if line.startswith("HASH_COLD_JSON "):
            got = json.loads(line[15:])
    assert got is not None, "no HASH_COLD_JSON: %s" % r.stdout[-300:]
    assert got["cold_state"] == "done" and got["warm_state"] == "done"
    assert got["cold_cache_enabled"] is True
    assert got["entries_after_cold"] > 0, \
        "warmup persisted nothing to the compile cache"
    assert got["entries_after_warm"] == got["entries_after_cold"], \
        "the warm restart re-compiled instead of loading from the cache"
    assert (got["cold_class"], got["warm_class"]) == ("miss", "hit"), got
