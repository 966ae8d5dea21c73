#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served verify path still
starts, and answers right, on the chip.

    python chip_smoke.py          (one process, no arguments)

It needs a TPU: its first act after importing JAX is to require
`jax.devices()[0].platform == "tpu"`, and it exits non-zero, printing no
result, on any other platform. It never sets JAX_PLATFORMS. Where
JAX_COMPILATION_CACHE_DIR is set the compile cache is there, otherwise at
`<checkout>/.jax_cache` (stellar_core_tpu/parallel/device.py).

Set-up: the native engines (prep, apply, XDR) are rebuilt from source
into an emptied `stellar_core_tpu/native/build/` — what a checkout of
the commit would have to do — and a missing compiler, `Python.h` or
build fails the run. Archives, buckets and databases are generated from
fixed seeds under a temp dir.

Three legs, each driven through the `Config` → `Application` → `start()`
wiring the CLI uses, at the default bucket ladder, with the evidence
read from the node's own surfaces (admin `verifier` endpoint, metrics
registry, tracer spans). No leg's exception is caught: the first failed
check ends the run non-zero.

- catchup (`SIG_VERIFY_BACKEND="tpu"`): a cpu-backend publisher writes
  the multisig-20 history (checkpoint frequency 8, 4 dense checkpoints,
  100 txs/ledger, 20-of-20 multisig payments); a fresh node replays it.
  Every replayed header hash equals the publisher's, every distinct
  signature reached the device exactly once, nothing fell back.
- live (`tpu-async`, the example config's setting): a 3-node network
  over the real overlay, node 0 on the device and nodes 1–2 on the CPU
  as the independent reference; 200 single-signature payments per ledger
  for 10 ledgers plus 10 transactions with a corrupted signature, while
  a second thread reads node 0's admin API over real HTTP.
- kernel: a full 8192 batch with every 8th signature corrupted equals
  `raw_verify` item for item; the SHA-256 kernel compiles its warm
  shapes and equals hashlib on 4,096 messages of mixed lengths.

The last line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
The line before it is the run's summary; it claims no end-to-end figure.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

_T0 = time.perf_counter()


def say(msg: str) -> None:
    print("[smoke %6.1fs] %s" % (time.perf_counter() - _T0, msg), flush=True)


def check(ok: bool, what: str, detail=None) -> None:
    if not ok:
        raise AssertionError("%s%s" % (
            what, "" if detail is None else ": %r" % (detail,)))


# -- set-up ------------------------------------------------------------------

def rebuild_native_engines() -> dict:
    """Empty the plain native build directory and build prep, apply and
    XDR from source, as a checkout of the commit has to."""
    from stellar_core_tpu import native
    if os.path.isdir(native._BUILD):
        for name in os.listdir(native._BUILD):
            path = os.path.join(native._BUILD, name)
            if os.path.isfile(path):    # sanitized/tsan subdirs stay
                os.unlink(path)
    t0 = time.perf_counter()
    status = native.engine_status()
    missing = {k: v for k, v in status.items() if v}
    check(not missing, "native engines missing", missing)
    return {"engines": sorted(status),
            "build_s": round(time.perf_counter() - t0, 1)}


def header_chain(app) -> dict:
    """{ledgerseq: header hash} from the node's own SQL store."""
    return dict(app.database.execute(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())


def flight_dumps(flight_dir: str) -> list:
    """Flight-recorder dumps that mean the device path was lost."""
    bad = ("verify-warmup-failed", "compile-cache-unavailable",
           "verify-breaker-trip", "verify-device-trip",
           "hash-warmup-failed", "hash-breaker-trip")
    return sorted(f for f in os.listdir(flight_dir)
                  if any(b in f for b in bad))


def warmup_report(cockpit: dict, shapes_key: str) -> dict:
    """Per-shape warmup seconds + the cockpit's cache class."""
    return {shape: (info["seconds"], info["cache"])
            for shape, info in cockpit["warmup"][shapes_key].items()}


# -- catchup leg --------------------------------------------------------------

def catchup_leg(dev: dict, flight_dir: str, **history_args):
    """Returns (counts, replay app) — the app stays up for the kernel
    leg, which drives its verifier stack."""
    import bench
    from stellar_core_tpu.catchup.catchup_work import CatchupConfiguration
    from stellar_core_tpu.crypto import keys
    from stellar_core_tpu.work.basic_work import State

    t0 = time.perf_counter()
    hist = bench.PublishedHistory(**history_args)
    say("catchup: published to ledger %d (%d dense ledgers) on the cpu "
        "backend in %.1fs" % (hist.tip, hist.dense,
                              time.perf_counter() - t0))
    # the 65K-entry verify-result cache sits in front of every backend:
    # without this flush the replay would send the device nothing
    keys.flush_verify_cache()

    app = hist.node(1, "tpu")
    app.tracer.enable(capacity=65536)
    distinct: set = set()
    prewarm = app.sig_verifier.prewarm_many

    def counting_prewarm(triples):
        distinct.update(triples)
        return prewarm(triples)

    app.sig_verifier.prewarm_many = counting_prewarm
    # set-up, not the leg: start() began compiling the bucket ladder on
    # the warmup thread; wait for it (a failure raises here)
    t0 = time.perf_counter()
    app.sig_verifier.warmup(wait=True)
    cockpit = app.command_handler.cmd_verifier({})
    warm = warmup_report(cockpit, "buckets")
    say("catchup: verifier warmup %.1fs, per bucket (seconds, cache): %s"
        % (time.perf_counter() - t0, json.dumps(warm)))
    from stellar_core_tpu.crypto.batch_verifier import TpuSigVerifier
    check(sorted(warm, key=int) == [str(b) for b in TpuSigVerifier.BUCKETS],
          "the default bucket ladder warmed", warm)

    app.clock.set_virtual_time(hist.pub.clock.now() + 10.0)
    work = app.catchup_manager.start_catchup(CatchupConfiguration.complete())
    t0 = time.perf_counter()
    while not work.is_done():
        app.crank(False)
    replay_s = time.perf_counter() - t0
    check(work.state == State.SUCCESS, "catchup state", work.state)
    lcl = app.ledger_manager.last_closed_ledger_num()
    check(lcl == hist.tip, "replayed to the archive tip", (lcl, hist.tip))
    # the plain reference: every header the device-backed node closed
    # equals the one the CPU publisher closed
    want = {s: h for s, h in header_chain(hist.pub).items() if s <= hist.tip}
    check(len(want) == hist.tip and header_chain(app) == want,
          "replayed header chain equals the CPU publisher's")

    cockpit = app.command_handler.cmd_verifier({})
    check(cockpit["device"] == dev, "verifier endpoint names the device",
          cockpit["device"])
    hidden = bench.device_path_violations(app)
    check(not hidden, "device path hidden", hidden)
    check(not flight_dumps(flight_dir), "flight dumps",
          flight_dumps(flight_dir))
    n_sigs = cockpit["counters"]["sigs_verified"]
    check(n_sigs == len(distinct) > 0,
          "every distinct signature reached the device exactly once",
          (n_sigs, len(distinct)))
    drains = [s for s in app.tracer.spans()
              if s.name == "crypto.verify_many"]
    check(drains and all(s.tags["platform"] == dev["platform"] and
                         s.tags["backend"] == "tpu" for s in drains),
          "every crypto.verify_many span ran on the device",
          [s.tags for s in drains][:3])
    stats = app.ledger_manager.apply_stats
    check(not stats.bails and not stats.closes.get("python", 0),
          "native apply closed every ledger",
          (dict(stats.bails), dict(stats.closes)))
    if dev["count"] > 1:
        per_dev = {i: d["drains"] for i, d in cockpit["devices"].items()}
        check(len(per_dev) == dev["count"] and all(per_dev.values()),
              "every device drained", per_dev)
    counts = {"ledgers": lcl, "sigs_on_device": n_sigs,
              "device_dispatches": cockpit["counters"]["batches_dispatched"],
              "verify_many_drains": len(drains),
              "drains_by_backend": {k: v["drains"] for k, v in
                                    cockpit["drains"]["by_backend"].items()},
              "buckets_used": {b: d["drains"]
                               for b, d in cockpit["buckets"].items()},
              "warmup": warm, "replay_wall_s": round(replay_s, 2)}
    say("catchup: PASS %s" % json.dumps(counts))
    hist.pub.stop()
    return counts, app, hist


# -- live leg -----------------------------------------------------------------

def live_leg(dev: dict, flight_dir: str, n_ledgers: int = 10,
             payments_per_ledger: int = 200, n_corrupt: int = 10) -> dict:
    from stellar_core_tpu.crypto import keys
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.simulation import topologies
    from stellar_core_tpu.simulation.simulation import Simulation
    from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
    from stellar_core_tpu.xdr import TransactionResultCode
    import bench

    keys.flush_verify_cache()
    order = []

    def tweak(cfg) -> None:
        order.append(cfg)
        # node 0 verifies on the device; its two peers are the CPU
        # reference it must agree with at every height
        cfg.SIG_VERIFY_BACKEND = "tpu-async" if len(order) == 1 else "cpu"
        cfg.DATABASE = "sqlite3://:memory:"     # header chain readable
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = 4 * payments_per_ledger
        # every node has its own virtual clock, and an idle crank jumps
        # it to the next timer while node 0 waits on the device in real
        # time: keep the stuck-consensus recovery out of that race
        # (tests/test_batch_boundary.py)
        cfg.CONSENSUS_STUCK_TIMEOUT_SECONDS = 10000.0

    sim = topologies.core(3, 2, mode=Simulation.OVER_PEERS, cfg_tweak=tweak)
    apps = [n.app for n in sim.nodes.values()]
    node0 = apps[0]
    check(node0.config.SIG_VERIFY_BACKEND == "tpu-async" and
          [a.config.SIG_VERIFY_BACKEND for a in apps[1:]] == ["cpu", "cpu"],
          "node 0 on the device, nodes 1-2 on the cpu")
    node0.tracer.enable(capacity=65536)
    sim.start_all_nodes()
    node0.sig_verifier.warmup(wait=True)    # set-up; in-process cache hit
    port = node0.command_handler.start_http(0)

    # the second thread: real HTTP against node 0 while the main thread
    # cranks (handlers hop to node 0's main loop and wait for it)
    scraped = {"info": 0, "verifier": 0, "metrics?filter=crypto": 0}
    last: dict = {}
    stop = threading.Event()
    scrape_error: list = []

    def scrape() -> None:
        try:
            while not stop.is_set():
                for path in scraped:
                    with urllib.request.urlopen(
                            "http://127.0.0.1:%d/%s" % (port, path),
                            timeout=60) as r:
                        last[path] = json.loads(r.read())
                    scraped[path] += 1
                stop.wait(0.05)
        except Exception as e:      # surfaced by the main thread below
            scrape_error.append(e)

    scraper = threading.Thread(target=scrape, name="smoke-scrape",
                               daemon=True)
    scraper.start()

    def crank(pred, what: str, wall_s: float = 240.0) -> None:
        """Crank every node, paced against real time (the dispatch
        worker needs wall clock for the device call), until pred()."""
        deadline = time.time() + wall_s
        while not pred():
            check(time.time() < deadline, "live: timed out waiting for "
                  + what)
            check(not scrape_error, "live: admin scrape failed",
                  scrape_error)
            sim.crank_all_nodes(20)
            for a in apps:
                a.sig_verifier.flush()
            time.sleep(0.0005)

    def lcl_min() -> int:
        return min(a.ledger_manager.last_closed_ledger_num() for a in apps)

    try:
        crank(lambda: lcl_min() >= 2, "the first consensus close")
        ledger = AppLedgerAdapter(node0)
        root = ledger.root_account()
        sks = [SecretKey.from_seed(hashlib.sha256(b"smoke-live-%d" % i)
                                   .digest())
               for i in range(payments_per_ledger)]
        for lo in range(0, len(sks), 100):
            check(node0.submit_transaction(root.tx(
                [root.op_create_account(sk.public_key, 10**9)
                 for sk in sks[lo:lo + 100]])) == 0,
                "create accounts admitted")
            base = lcl_min()
            crank(lambda: lcl_min() > base and
                  ledger.account_exists(sks[lo].public_key),
                  "account creation")
        senders = [TestAccount(ledger, sk) for sk in sks]
        start = lcl_min()
        rejected = 0
        for rnd in range(n_ledgers):
            for i, snd in enumerate(senders):
                check(node0.submit_transaction(snd.tx(
                    [snd.op_payment(root.account_id, 100 + rnd)])) == 0,
                    "payment admitted", (rnd, i))
            if rnd < n_corrupt:
                # a well-formed payment whose signature has one bit
                # flipped: the device must say no, admission must refuse
                snd = senders[rnd]
                bad = snd.tx([snd.op_payment(root.account_id, 7)],
                             seq=snd.next_seq() + 1)
                sig = bad.envelope.value.signatures[0]
                sig.signature = bytes([sig.signature[0] ^ 1]) + \
                    sig.signature[1:]
                status = node0.submit_transaction(bad)
                check(status != 0 and bad.result.code ==
                      TransactionResultCode.txBAD_AUTH,
                      "corrupted signature rejected",
                      (status, bad.result.code))
                rejected += 1
            crank(lambda: all(_applied(a, senders[::50], rnd + 1)
                              for a in apps),
                  "round %d to apply on every node" % rnd)
        crank(lambda: lcl_min() >= start + n_ledgers,
              "%d slots after the first payment" % n_ledgers)
        # one more scrape AFTER the traffic so the endpoint evidence
        # below covers all of it
        n_seen = scraped["verifier"]
        crank(lambda: scraped["verifier"] >= n_seen + 2,
              "a final admin scrape")
    finally:
        stop.set()
        scraper.join(timeout=90)
        node0.command_handler.stop_http()
    check(not scraper.is_alive() and not scrape_error,
          "admin scrape thread finished", scrape_error)

    tip = lcl_min()
    check(tip - start >= n_ledgers, "slots externalized", (start, tip))
    chains = [header_chain(a) for a in apps]
    for seq in range(2, tip + 1):
        check(chains[0][seq] == chains[1][seq] == chains[2][seq],
              "header hashes equal at height %d" % seq)
    check(rejected == n_corrupt, "corrupted transactions rejected",
          rejected)
    # exactly n_ledgers payments per sender applied, on every node: the
    # corrupted ones never did
    for a in apps:
        check(_applied(a, senders, n_ledgers) and
              not _applied(a, senders[:1], n_ledgers + 1),
              "every payment applied once on %s" % a.config.node_name())

    # node 0's cockpit as the second thread read it over HTTP
    cockpit = last["verifier"]
    by_backend = {k: v["drains"]
                  for k, v in cockpit["drains"]["by_backend"].items()}
    check(cockpit["device"] == dev and cockpit["configured_backend"] ==
          "tpu-async", "verifier endpoint names the device", cockpit["device"])
    check(by_backend.get("tpu", 0) > 0 and not by_backend.get("cpu"),
          "admission and envelope verifies served by the device",
          by_backend)
    check(cockpit["breaker"]["state"] == "closed" and
          not cockpit["breaker"]["trips"], "breaker closed",
          cockpit["breaker"])
    lat = last["metrics?filter=crypto"]["crypto.verify.latency"]
    check(lat["count"] > 0, "crypto.verify.latency sampled", lat)
    check(last["info"]["ledger"]["num"] >= start + n_ledgers,
          "info endpoint served", last["info"]["ledger"])
    hidden = bench.device_path_violations(node0)
    check(not hidden, "device path hidden", hidden)
    check(not flight_dumps(flight_dir), "flight dumps",
          flight_dumps(flight_dir))
    spans = [s for s in node0.tracer.spans() if s.name == "crypto.verify_many"]
    check(spans and all(s.tags["platform"] == dev["platform"] for s in spans),
          "every crypto.verify_many span ran on the device")
    n_env = sum(1 for s in node0.tracer.spans()
                if s.name == "crypto.batch_dispatch")
    check(n_env > 0, "SCP envelopes went through the threaded dispatcher")
    counts = {"slots": tip - start, "tip": tip,
              "payments_applied": n_ledgers * payments_per_ledger,
              "corrupted_rejected": rejected,
              "sigs_on_device": cockpit["counters"]["sigs_verified"],
              "device_dispatches": cockpit["counters"]["batches_dispatched"],
              "envelope_batches": n_env,
              "drains_by_backend": by_backend,
              "buckets_used": {b: d["drains"]
                               for b, d in cockpit["buckets"].items()},
              "verify_latency_samples": lat["count"],
              "http_scrapes": dict(scraped)}
    say("live: PASS %s" % json.dumps(counts))
    sim.stop_all_nodes()
    return counts


def _applied(app, senders, n: int) -> bool:
    """Has each of these senders' n-th payment applied on this node? (a
    funded account starts at seqNum = creation ledger << 32)"""
    from stellar_core_tpu.testing import AppLedgerAdapter
    ledger = AppLedgerAdapter(app)
    return all((ledger.seq_num(s.account_id) & 0xFFFFFFFF) >= n
               for s in senders)


# -- kernel leg ---------------------------------------------------------------

def kernel_leg(dev: dict, flight_dir: str, app, batch: int = 8192,
               n_msgs: int = 4096) -> dict:
    import bench
    from stellar_core_tpu.crypto import keys
    from stellar_core_tpu.crypto.batch_hasher import make_hasher
    from stellar_core_tpu.util.metrics import MetricsRegistry

    # negative control through the served stack (resilient → device): a
    # device that answers all-True, or all-False masked by pre_ok, fails
    pubs, sigs, msgs = bench._example_batch(batch, n_keys=64)
    for i in range(7, batch, 8):
        sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 0x10]) + sigs[i][41:]
    before = app.command_handler.cmd_verifier({})["counters"]
    got = app.sig_verifier.verify_many(list(zip(pubs, sigs, msgs)))
    want = [keys.raw_verify(p, s, m) for p, s, m in zip(pubs, sigs, msgs)]
    check(got == want, "device verdicts equal raw_verify item for item",
          [i for i, (g, w) in enumerate(zip(got, want)) if g != w][:10])
    check(sum(want) == batch - batch // 8, "one in eight rejected",
          sum(want))
    cockpit = app.command_handler.cmd_verifier({})
    check(cockpit["counters"]["sigs_verified"] - before["sigs_verified"]
          == batch, "the whole batch ran on the device")
    hidden = bench.device_path_violations(app)
    check(not hidden, "device path hidden", hidden)
    span = [s for s in app.tracer.spans()
            if s.name == "crypto.dispatch"][-1]
    if dev["count"] > 1:
        check(span.tags["devices"] == dev["count"] and
              app.sig_verifier.inner._sharded_fn is not None,
              "the full batch went through the sharded executable",
              span.tags)

    # SHA-256: the hasher stack the node would run with HASH_BACKEND=tpu
    metrics = MetricsRegistry()
    hasher = make_hasher("tpu", metrics=metrics)
    hasher.warmup(wait=True)        # raises if a warm shape won't compile
    hs = hasher.stats.to_json()
    hwarm = warmup_report(hs, "shapes")
    check(hs["warmup"]["state"] == "done" and
          len(hwarm) == len(hasher.inner.WARM_SHAPES), "hash warmup", hwarm)
    # lengths walk every block bucket (1..16 blocks) and a few oversize
    # messages that split out to the host
    msgs = [hashlib.sha512(b"smoke-%d" % i).digest() * 17 for i in range(64)]
    data = [msgs[i % 64][:(i * 37) % 1030] for i in range(n_msgs)]
    digests = hasher.hash_many(data, site="bench")
    check(digests == [hashlib.sha256(m).digest() for m in data],
          "device digests equal hashlib")
    hs = hasher.stats.to_json()
    m = metrics.to_json()
    by_backend = {k: v["drains"] for k, v in hs["drains"]["by_backend"].items()}
    check(by_backend.get("tpu", 0) > 0 and not by_backend.get("cpu") and
          not m.get("hasher.fallback-drain", {}).get("count") and
          not m.get("hasher.dispatch-failure", {}).get("count") and
          hasher.breaker.state == "closed",
          "hash drains served by the device", (by_backend, hs["buckets"]))
    check(not flight_dumps(flight_dir), "flight dumps",
          flight_dumps(flight_dir))
    counts = {"verify_batch": batch, "rejected": batch - sum(want),
              "dispatch": dict(span.tags),
              "hash_msgs": n_msgs, "hash_oversize_on_host":
              hs["oversize_msgs"],
              "hash_shapes_used": {k: v["dispatches"]
                                   for k, v in hs["buckets"].items()},
              "hash_warmup": hwarm}
    say("kernel: PASS %s" % json.dumps(counts))
    return counts


# -- entry ------------------------------------------------------------------

def main() -> int:
    import jax
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print("chip_smoke.py: JAX resolved platform %r (%s x%d), not a "
              "TPU; nothing was run." % (d0.platform, d0.device_kind,
                                         len(jax.devices())),
              file=sys.stderr)
        return 2
    try:
        from stellar_core_tpu.parallel.device import (
            compile_cache_entries, configure_compile_cache, device_info,
        )
    except ImportError as e:
        print("chip_smoke.py: run it from the root of a checkout (%s)" % e,
              file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    dev = device_info()
    import jaxlib
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    say("platform: %s  device_kind: %s  devices: %d" % (
        dev["platform"], dev["device_kind"], dev["count"]))
    say("jax %s  jaxlib %s  libtpu %s  python %s" % (
        jax.__version__, jaxlib.__version__, libtpu_version,
        sys.version.split()[0]))
    entries_before = compile_cache_entries(cache_dir)
    say("compile cache: %s (%d entries; JAX_COMPILATION_CACHE_DIR %s)" % (
        cache_dir, entries_before,
        "honoured" if jax.config.jax_compilation_cache_dir == cache_dir
        else "NOT what JAX holds: %r" % jax.config.jax_compilation_cache_dir))
    check(jax.config.jax_compilation_cache_dir == cache_dir,
          "JAX holds the compile cache directory")

    import logging
    from stellar_core_tpu.util.log import init_logging
    init_logging(logging.WARNING)   # the legs' own lines are the record
    native = rebuild_native_engines()
    say("native engines built from source in %.1fs: %s" % (
        native["build_s"], ", ".join(native["engines"])))

    flight_dir = tempfile.mkdtemp(prefix="sct-smoke-flight-")
    os.environ["SCT_FLIGHT_DIR"] = flight_dir
    hist = None
    try:
        catchup, app, hist = catchup_leg(dev, flight_dir)
        kernel = kernel_leg(dev, flight_dir, app)
        app.stop()
        live = live_leg(dev, flight_dir)
    finally:
        if hist is not None:
            hist.close()
        shutil.rmtree(flight_dir, ignore_errors=True)

    entries_after = compile_cache_entries(cache_dir)
    say("compile cache: %d entries before, %d after" % (
        entries_before, entries_after))
    summary = {
        "device": dev,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "compile_cache": {"dir": cache_dir, "entries_before": entries_before,
                          "entries_after": entries_after},
        "native": native,
        "legs": {"catchup": catchup, "kernel": kernel, "live": live},
        "wall_s": round(time.perf_counter() - _T0, 1),
        "claim": None,
    }
    print("SMOKE_SUMMARY " + json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
