"""Benchmark: batched ed25519 verify throughput on one TPU chip.

`python bench.py` prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

vs_baseline = measured device rate / single-core CPU (OpenSSL) rate — the
reference's implicit baseline is single-call libsodium verify
(BASELINE.md; reference crypto bench harness src/crypto/test/
CryptoTests.cpp:235-258).

One process for each chip: this parent never imports jax. It starts
exactly ONE child, which holds the chip and runs every device leg
(`device_full_bench`); only after that child has exited does a
forced-CPU child run the replay denominator. A machine without a chip,
a missing native engine, a failed warmup or a drain served by the CPU
fallback is an error: the run exits non-zero and prints no value.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


# --- CPU baseline (no jax) -------------------------------------------------

def _example_batch(batch: int, n_keys: int = 32):
    """Deterministic signed batch without importing jax (mirrors
    models/verifier_model.make_example_batch, which pulls in jnp)."""
    from stellar_core_tpu.crypto.keys import SecretKey
    sks = [SecretKey.from_seed(bytes([i + 1] * 32)) for i in range(n_keys)]
    pubs, sigs, msgs = [], [], []
    for i in range(batch):
        sk = sks[i % n_keys]
        m = b"bench-msg-%08d" % i
        pubs.append(sk.public_key.key_bytes)
        sigs.append(sk.sign(m))
        msgs.append(m)
    return pubs, sigs, msgs


def cpu_baseline_rate(n: int = 2000) -> float:
    from stellar_core_tpu.crypto.keys import raw_verify
    pubs, sigs, msgs = _example_batch(n)
    t0 = time.perf_counter()
    ok = True
    for p, s, m in zip(pubs, sigs, msgs):
        ok &= raw_verify(p, s, m)
    dt = time.perf_counter() - t0
    assert ok
    return n / dt


# --- device bench (child process) ------------------------------------------

def _prep_args(batch: int, n_keys: int = 64) -> tuple:
    """Signed batch → device-ready jnp arg tuple for verify_batch_packed
    (the served entry: one packed (B, 128) uint8 array)."""
    import jax.numpy as jnp
    from stellar_core_tpu.ops import ed25519 as E
    pubs, sigs, msgs = _example_batch(batch, n_keys=n_keys)
    return (jnp.asarray(E.prepare_batch(pubs, sigs, msgs)["packed"]),)


def device_bench(batch: int = 8192, iters: int = 10,
                 args: tuple | None = None) -> dict:
    """Runs in the child: jax on whatever platform the env provides."""
    t_init = time.perf_counter()
    import jax
    platform = jax.devices()[0].platform
    init_s = time.perf_counter() - t_init

    from stellar_core_tpu.ops import ed25519 as E
    if args is None:
        args = _prep_args(batch)
    t_c = time.perf_counter()
    ok = E.verify_batch_packed(*args)
    ok.block_until_ready()
    compile_s = time.perf_counter() - t_c
    assert bool(ok.all()), "verify kernel rejected valid signatures"
    best = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        E.verify_batch_packed(*args).block_until_ready()
        dt = time.perf_counter() - t0
        best = max(best, batch / dt)
    out = {"rate": best, "platform": platform, "batch": batch,
           "init_s": round(init_s, 2), "compile_s": round(compile_s, 2)}
    # live-SCP SLO: per-dispatch latency of the SMALL (128) bucket — the
    # p50/p99 consensus actually feels (SCP timers budget ~1s)
    args2 = _prep_args(128, n_keys=32)
    E.verify_batch_packed(*args2).block_until_ready()   # compile shape
    lats = []
    for _ in range(50):
        t0 = time.perf_counter()
        E.verify_batch_packed(*args2).block_until_ready()
        lats.append(time.perf_counter() - t0)
    lats.sort()
    out["latency128_p50_ms"] = round(lats[len(lats) // 2] * 1000, 3)
    out["latency128_p99_ms"] = round(lats[-1] * 1000, 3)
    return out


def device_full_bench(batch: int = 8192, iters: int = 10) -> dict:
    """ALL device legs, in the ONE process that holds the chip. Any
    failure raises: the child exits non-zero and the parent prints no
    value.

    Stages: chip + native engines present → kernel throughput (8192) +
    128-latency SLO → warm recompile via the persistent compile cache →
    cockpit warmup → catchup replay on the tpu backend.
    """
    from stellar_core_tpu import native
    from stellar_core_tpu.parallel.device import (
        configure_compile_cache, device_info,
    )
    results: dict = {"compile_cache_dir": configure_compile_cache()}
    t_init = time.perf_counter()
    dev = device_info()
    if dev["platform"] != "tpu":
        raise RuntimeError(
            "bench.py measures the chip; JAX resolved platform %r (%s x%d)"
            % (dev["platform"], dev["device_kind"], dev["count"]))
    results.update(dev)
    results["init_s"] = init_s = round(time.perf_counter() - t_init, 2)
    missing = {k: v for k, v in native.engine_status().items() if v}
    if missing:
        raise RuntimeError("native engines missing: %r" % missing)

    # stage 1: kernel throughput + latency (_prep_args already touched
    # the device, so device_bench's own init timer would read 0)
    args = _prep_args(batch)
    res = device_bench(batch=batch, iters=iters, args=args)
    res["init_s"] = init_s
    results.update(res)

    # stage 2: warm compile. clear_caches drops the in-memory executable
    # but keeps the persistent on-disk cache, so this re-jit measures the
    # WARM-restart load. (`compile_s` above is the cold number only when
    # the cache had no entry for this kernel/platform.)
    import jax
    from stellar_core_tpu.ops import ed25519 as E
    jax.clear_caches()
    t_w = time.perf_counter()
    E.verify_batch_packed(*args).block_until_ready()
    results["compile_warm_s"] = round(time.perf_counter() - t_w, 2)

    # stage 2b: the verifier's own warmup over the two shapes stage 1
    # dispatched, through the cockpit that classifies each cache load
    from stellar_core_tpu.crypto.batch_verifier import (
        TpuSigVerifier, VerifierContext, VerifierStats)
    v = TpuSigVerifier(VerifierContext(stats=VerifierStats()))
    v.BUCKETS = (128, batch)   # instance override; class attr untouched
    jax.clear_caches()     # a fresh process's in-memory state
    v.warmup(wait=True)    # raises if any bucket fails to compile
    w = v.ctx.stats.warmup
    results["warmup_state"] = w["state"]
    results["warmup_buckets_s"] = {
        b: info["seconds"] for b, info in w["buckets"].items()}
    results["compile_cache"] = dict(v.ctx.stats.compile_cache)
    if not results["compile_cache"]["enabled"]:
        raise RuntimeError("no persistent compile cache: %r"
                           % results["compile_cache"])

    # stage 3: replay on the tpu backend (the cpu denominator runs in a
    # forced-CPU child of the parent, after this process has exited)
    results["replay_tpu"] = replay_bench("tpu")
    return results


class _StandardMix:
    """Mixed-op traffic for the standard replay mix (ISSUE 13): every
    4th dense ledger carries change-trust / allow-trust / offers / path
    payments / manage-data / bump-sequence / account-merge / inflation /
    fee-bump / muxed ops from dedicated role accounts, so the replay
    exercises (and the zero-bail gate covers) every wire op type."""

    def __init__(self, app, adapter, root, roles) -> None:
        self.app = app
        self.adapter = adapter
        self.root = root
        self.roles = roles
        self.issuer = roles[0]
        self.merge_n = 0

    def setup(self) -> None:
        from stellar_core_tpu.xdr import AccountFlags, Asset
        app, issuer = self.app, self.issuer
        app.submit_transaction(issuer.tx([issuer.op_set_options(
            set_flags=AccountFlags.AUTH_REQUIRED_FLAG |
            AccountFlags.AUTH_REVOCABLE_FLAG)]))
        app.manual_close()
        self.USD = Asset.credit("USD", issuer.account_id)
        lines = self.roles[1:9]
        for r in lines:
            app.submit_transaction(
                r.tx([r.op_change_trust(self.USD, 10 ** 12)]))
        app.manual_close()
        app.submit_transaction(issuer.tx(
            [issuer.op_allow_trust(r.account_id, b"USD\x00")
             for r in lines]))
        app.manual_close()
        app.submit_transaction(issuer.tx(
            [issuer.op_payment(r.account_id, 10 ** 9, self.USD)
             for r in lines[:4]]))
        app.manual_close()

    def submit_mixed_ops(self, rnd: int) -> None:
        from stellar_core_tpu.crypto.keys import SecretKey
        from stellar_core_tpu.testing import TestAccount
        from stellar_core_tpu.transactions.transaction_frame import (
            FeeBumpTransactionFrame,
        )
        from stellar_core_tpu.xdr import (
            Asset, EnvelopeType, FeeBumpTransaction,
            FeeBumpTransactionEnvelope, MuxedAccount, OperationBody,
            OperationType, PaymentOp, TransactionEnvelope, _Ext,
        )
        from stellar_core_tpu.xdr.basic import MuxedAccountMed25519
        from stellar_core_tpu.xdr.transaction import (
            BumpSequenceOp, PathPaymentStrictReceiveOp,
            PathPaymentStrictSendOp, _InnerTxEnvelope,
        )
        app, USD = self.app, self.USD
        r = self.roles
        sub = app.submit_transaction
        native = Asset.native()
        # trust-line churn + data + bump-sequence
        sub(r[9].tx([r[9].op_change_trust(USD, 10 ** 10 + rnd),
                     r[9].op_manage_data("bench-k", b"v%d" % rnd)]))
        sub(r[10].tx([r[10].op_manage_data("tmp%d" % (rnd % 3),
                                           b"x" if rnd % 2 else None)]))
        sub(r[11].tx([r[11].op(OperationBody(
            OperationType.BUMP_SEQUENCE,
            BumpSequenceOp(bumpTo=r[11].next_seq() + 3)))]))
        # order book: r[1] posts USD/native, r[2] crosses with a buy,
        # r[3] sends a strict-receive path payment through the book
        sub(r[1].tx([r[1].op_manage_sell_offer(USD, native, 500 + rnd,
                                               2, 1)]))
        sub(r[2].tx([r[2].op_manage_buy_offer(native, USD, 60 + rnd,
                                              1, 2)]))
        sub(r[3].tx([r[3].op(OperationBody(
            OperationType.PATH_PAYMENT_STRICT_RECEIVE,
            PathPaymentStrictReceiveOp(
                sendAsset=USD, sendMax=10 ** 8,
                destination=r[4].muxed, destAsset=native,
                destAmount=40 + rnd, path=[])))]))
        sub(r[4].tx([r[4].op(OperationBody(
            OperationType.PATH_PAYMENT_STRICT_SEND,
            PathPaymentStrictSendOp(
                sendAsset=USD, sendAmount=25 + rnd,
                destination=r[5].muxed, destAsset=native,
                destMin=1, path=[])))]))
        # allow-trust flap on a line with no open offers
        sub(self.issuer.tx([self.issuer.op_allow_trust(
            r[6].account_id, b"USD\x00",
            authorize=2 if rnd % 2 else 1)]))
        # account merge: fund a throwaway, merge it back next round
        if self.merge_n:
            prev = TestAccount(self.adapter, SecretKey.from_seed(
                bytes([93, self.merge_n & 0xFF] + [5] * 30)))
            sub(prev.tx([prev.op(OperationBody(
                OperationType.ACCOUNT_MERGE,
                MuxedAccount.from_account_id(self.root.account_id)))]))
        self.merge_n += 1
        fodder = SecretKey.from_seed(
            bytes([93, self.merge_n & 0xFF] + [5] * 30))
        sub(r[12].tx([r[12].op_create_account(fodder.public_key,
                                              3 * 10 ** 7)]))
        # (no INFLATION tx: at protocol 13 the op is version-retired, so
        # the queue rejects it at admission — it can never reach a
        # txset; the differential oracle covers its native
        # opNOT_SUPPORTED arm instead)
        # fee bump: r[14] sponsors a payment from r[15]
        inner = r[15].tx([r[15].op_payment(self.root.account_id, 5)])
        fb = FeeBumpTransaction(
            feeSource=r[14].muxed, fee=2000,
            innerTx=_InnerTxEnvelope(EnvelopeType.ENVELOPE_TYPE_TX,
                                     inner.envelope.value),
            ext=_Ext.v0())
        env = TransactionEnvelope(
            EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP,
            FeeBumpTransactionEnvelope(tx=fb, signatures=[]))
        frame = FeeBumpTransactionFrame(app.config.network_id, env)
        frame.add_signature(r[14].sk)
        sub(frame)
        # muxed destination payment
        sub(r[16].tx([r[16].op(OperationBody(
            OperationType.PAYMENT,
            PaymentOp(
                destination=MuxedAccount(
                    0x100, MuxedAccountMed25519(
                        id=7, ed25519=r[17].account_id.key_bytes)),
                asset=native, amount=9 + rnd)))]))


class PublishedHistory:
    """A dense synthetic history in a tmpdir file archive, written by a
    cpu-backend publisher node: what `replay_bench` and chip_smoke.py's
    catchup leg replay. `node()` builds further nodes over the same
    archive; `close()` removes the tmpdir.

    mix="multisig" (legacy, history-comparable): every tx a
    sigs_per_tx-of-N multisig payment to one hub account — the shape
    where signature checking dominates checkValid.
    mix="standard" (ISSUE 13): the full-coverage traffic mix — 2-sig
    senders paying DISJOINT partner accounts (conflict-light: the
    parallel close engages), with every 4th ledger carrying the other
    op types (trust lines, allow-trust, offers, path payments, account
    data, bump-sequence, merges, inflation, fee bumps, muxed
    destinations)."""

    FREQ = 8    # checkpoint frequency

    def __init__(self, n_checkpoints: int = 4, txs_per_ledger: int = 100,
                 sigs_per_tx: int = 20, mix: str = "multisig") -> None:
        import tempfile
        self.txs_per_ledger = txs_per_ledger
        self.sigs_per_tx = 2 if mix == "standard" else sigs_per_tx
        self.mix = mix
        self.tmp = tempfile.mkdtemp(prefix="sct-replay-")
        self.archive_root = os.path.join(self.tmp, "archive")
        os.makedirs(self.archive_root, exist_ok=True)
        try:
            self._publish(n_checkpoints)
        except BaseException:
            self.close()
            raise

    def close(self) -> None:
        import shutil
        shutil.rmtree(self.tmp, ignore_errors=True)

    def node(self, n: int, backend: str, writable: bool = False):
        """A started node over this archive (its own bucket dir)."""
        from stellar_core_tpu.history.archive import HistoryArchive
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.main.config import Config
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        cfg = Config.test_config(n)
        cfg.DATABASE = "sqlite3://:memory:"
        cfg.CHECKPOINT_FREQUENCY = self.FREQ
        cfg.SIG_VERIFY_BACKEND = backend
        # production perf config, identical for every node: reference
        # pubnet validators run with no invariants unless configured
        # (Config.h INVARIANT_CHECKS default empty), and the genesis
        # op capacity must admit the 20-op multisig-arming txs
        # (maxTxSetSize counts OPS from protocol 11)
        cfg.INVARIANT_CHECKS = []
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = 10_000
        arch = HistoryArchive.local_dir("bench", self.archive_root)
        d = {"get": arch.get_tmpl, "mkdir": arch.mkdir_tmpl}
        if writable:
            d["put"] = arch.put_tmpl
        cfg.HISTORY = {"bench": d}
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        app.enable_buckets(os.path.join(self.tmp, "node-%d" % n, "buckets"))
        app.start()
        return app

    def _publish(self, n_checkpoints: int) -> None:
        from stellar_core_tpu.crypto.keys import SecretKey
        from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
        txs_per_ledger, sigs_per_tx = self.txs_per_ledger, self.sigs_per_tx
        mix = self.mix
        self.pub = pub = self.node(0, "cpu", writable=True)
        adapter = AppLedgerAdapter(pub)
        root = adapter.root_account()
        # one 100-op tx creates every sender in a single close (per-sender
        # create() closes would advance closeTime past the 60s drift guard)
        n_roles = 20 if mix == "standard" else 0
        sender_sks = [SecretKey.from_seed(bytes([7, i & 0xFF] + [11] * 30))
                      for i in range(txs_per_ledger + n_roles)]
        for lo in range(0, len(sender_sks), 100):
            pub.submit_transaction(root.tx(
                [root.op_create_account(sk.public_key, 10**10)
                 for sk in sender_sks[lo:lo + 100]]))
            pub.manual_close()
        senders = [TestAccount(adapter, sk) for sk in sender_sks]
        roles = senders[txs_per_ledger:]
        senders = senders[:txs_per_ledger]
        extra_signers = {}
        if sigs_per_tx > 1:
            for i, s in enumerate(senders):
                ks = [SecretKey.from_seed(bytes([201 + j, i & 0xFF] + [7] * 30))
                      for j in range(sigs_per_tx - 1)]
                ops = [s.op_add_signer(k.public_key.key_bytes) for k in ks]
                ops.append(s.op_set_options(med=sigs_per_tx))
                pub.submit_transaction(s.tx(ops))
                extra_signers[i] = ks
            pub.manual_close()   # one ledger arms every sender's multisig
        mixer = _StandardMix(pub, adapter, root, roles) \
            if mix == "standard" else None
        if mixer is not None:
            mixer.setup()
        # keep virtual time ahead of ledger closeTime (it advances 1s per
        # close; the herder rejects values >60s ahead of the local clock —
        # reference MAXIMUM_LEDGER_CLOSETIME_DRIFT behavior)
        pub.clock.set_virtual_time(
            pub.clock.now() + pub.ledger_manager.last_closed_ledger_num())
        target_cps = pub.history_manager.published_checkpoints + \
            n_checkpoints
        dense = 0
        while pub.history_manager.published_checkpoints < target_cps:
            if mixer is not None:
                # conflict-light pairs: sender 2k pays sender 2k+1 and
                # vice versa — 50 disjoint clusters per close, so the
                # conflict-graph parallel close engages on replay
                for i, snd in enumerate(senders):
                    partner = senders[i + 1 if i % 2 == 0 else i - 1]
                    pub.submit_transaction(
                        snd.tx([snd.op_payment(partner.account_id, 1000)],
                               extra_signers=extra_signers.get(i)))
                if dense % 4 == 1:
                    mixer.submit_mixed_ops(dense)
            else:
                for i, snd in enumerate(senders):
                    pub.submit_transaction(
                        snd.tx([snd.op_payment(root.account_id, 1000)],
                               extra_signers=extra_signers.get(i)))
            pub.clock.set_virtual_time(pub.clock.now() + 1.0)
            pub.manual_close()
            dense += 1
            # drain queued publish work before closing more (the loop is
            # bounded by published checkpoints, not closes)
            pub.crank_until(
                lambda: pub.history_manager.publish_queue() == [],
                max_cranks=20000)
        # archive tip = newest checkpoint boundary at-or-below the LCL
        # (the queue is drained, so every checkpoint <= lcl is published)
        lcl = pub.ledger_manager.last_closed_ledger_num()
        self.tip = ((lcl + 1) // self.FREQ) * self.FREQ - 1
        self.dense = dense
        # only dense closes inside the replayed range count
        self.dense_in_range = dense - max(0, lcl - self.tip)


def replay_bench(backend: str, n_checkpoints: int = 4,
                 txs_per_ledger: int = 100, sigs_per_tx: int = 20,
                 repeats: int | None = None,
                 mix: str = "multisig") -> dict:
    """Catchup-replay benchmark: the second north-star metric
    (BASELINE.md: >=5x pubnet replay vs libsodium CPU; reference
    methodology /root/reference/performance-eval/performance-eval.md:52-66).

    Publishes a PublishedHistory (cost excluded), then times a fresh
    node replaying it with the given SIG_VERIFY_BACKEND. Runs in a child
    process. The standard mix must drive ledger.apply.native-bail.* to
    zero (asserted by `bench.py --replay-full`); a device backend must
    serve every drain itself (asserted here)."""
    from stellar_core_tpu.catchup.catchup_work import CatchupConfiguration
    from stellar_core_tpu.crypto import keys as _keys
    from stellar_core_tpu.crypto.batch_verifier import TpuSigVerifier
    from stellar_core_tpu.work.basic_work import State

    # One bucket shape for the whole replay, AOT-compiled off the clock in
    # app.start()'s warmup + the explicit warmup(wait=True) below (the
    # r4->r5 0.026x pathology: BUCKETS=(1024,) was never AOT-compiled, and
    # the default warmup raced three other shapes onto the device during
    # the timed window). 8192 is the shape the throughput leg compiles in
    # stage 1 — in-memory hit in the same process, persistent-cache hit in
    # a fresh one.
    old_buckets = TpuSigVerifier.BUCKETS   # restored below: the tiny
    # --compare leg runs this function IN-PROCESS (tier-1 test), where a
    # leaked class-attr override would bleed into later tests
    TpuSigVerifier.BUCKETS = (8192,)
    hist = None
    try:
        hist = PublishedHistory(n_checkpoints, txs_per_ledger, sigs_per_tx,
                                mix)

        # Best-of-`repeats` over the SAME published history: each attempt
        # gets a fresh node + cleared caches.
        def one_replay() -> dict:
            _keys.flush_verify_cache()   # the publisher filled it
            app = hist.node(1, backend)
            # span tracer on for the whole replay: BENCH artifacts carry
            # a machine-generated phase_breakdown instead of a prose
            # Amdahl estimate (ISSUE 2; docs/observability.md). Capacity
            # sized so no replay span is ever evicted (~110 spans/ledger).
            app.tracer.enable(capacity=65536)
            # account time spent inside the verifier's batch drain: the
            # crypto-subsystem speedup (whole-checkpoint batch path)
            # reported alongside the end-to-end ratio
            crypto = {"s": 0.0, "sigs": 0}
            _orig_pw = app.sig_verifier.prewarm_many
            _orig_vm = app.sig_verifier.verify_many

            def timed_prewarm(triples):
                t = time.perf_counter()
                out = _orig_pw(triples)
                crypto["s"] += time.perf_counter() - t
                return out

            def counted_verify_many(triples):
                # only cache MISSES reach verify_many — this is the
                # actual device/CPU crypto work
                crypto["sigs"] += len(triples)
                return _orig_vm(triples)

            app.sig_verifier.prewarm_many = timed_prewarm
            app.sig_verifier.verify_many = counted_verify_many
            app.clock.set_virtual_time(hist.pub.clock.now() + 10.0)
            app.sig_verifier.warmup(wait=True)   # compile off the clock
            work = app.catchup_manager.start_catchup(
                CatchupConfiguration.complete())
            t0 = time.perf_counter()
            for _ in range(10**7):
                if work.is_done():
                    break
                app.crank(False)
            wall = time.perf_counter() - t0
            assert work.state == State.SUCCESS, "catchup replay failed"
            got = app.ledger_manager.last_closed_ledger_num()
            assert got == hist.tip, (got, hist.tip)
            if app.device is not None:
                hidden = device_path_violations(app)
                assert not hidden, "device path hidden: %r" % hidden
            n_ledgers = got - 1   # replayed from genesis
            n_txs = hist.dense_in_range * txs_per_ledger
            # span-derived phase attribution: exclusive per-phase totals
            # (+ untraced remainder) sum to the measured wall; verify
            # drains key by configured backend AND actual platform, so a
            # fallback leg can never masquerade as device time
            phase_breakdown = app.tracer.phase_breakdown(wall_s=wall)
            # close-cockpit apply attribution (ISSUE 9): per-op ms +
            # bail reasons + state-read stats; per_op_ms + other_ms sum
            # to apply_wall_s by construction (ledger/apply_stats.py)
            apply_breakdown = \
                app.ledger_manager.apply_stats.apply_breakdown()
            stats = app.ledger_manager.apply_stats
            return {"backend": backend, "mix": mix,
                    "native_bails": dict(stats.bails),
                    "python_closes": stats.closes.get("python", 0),
                    "clusters": dict(stats.clusters),
                    "ledgers": n_ledgers,
                    "dense_ledgers": hist.dense, "wall_s": round(wall, 3),
                    "ledgers_per_sec": round(n_ledgers / wall, 2),
                    "txs_per_sec": round(n_txs / wall, 1),
                    "txs_per_ledger": txs_per_ledger,
                    "sigs_per_tx": hist.sigs_per_tx,
                    "crypto_s": round(crypto["s"], 3),
                    "crypto_sigs": crypto["sigs"],
                    "phase_breakdown": phase_breakdown,
                    "apply_breakdown": apply_breakdown}

        if repeats is None:
            repeats = int(os.environ.get("BENCH_REPLAY_REPEATS", "2"))
        return min((one_replay() for _ in range(max(1, repeats))),
                   key=lambda r: r["wall_s"])
    finally:
        TpuSigVerifier.BUCKETS = old_buckets
        if hist is not None:
            hist.close()


def device_path_violations(app) -> dict:
    """Everything on a device-backend node's own surfaces (admin
    `verifier` endpoint, metrics registry) that says the device did NOT
    serve the verify path by itself — each of these is absorbed by the
    breaker/fallback layers in production and must fail a measurement.
    Empty dict = the chip did the work."""
    cockpit = app.command_handler.cmd_verifier({})
    m = app.metrics.to_json()
    bad: dict = {}
    drains = cockpit["drains"]["by_backend"]
    if not drains.get("tpu", {}).get("drains"):
        bad["no_device_drains"] = drains
    if drains.get("cpu", {}).get("drains"):
        bad["cpu_drains"] = drains["cpu"]
    for name in ("crypto.verify.dispatch-failure",
                 "crypto.verify.fallback-drain",
                 "crypto.verify.flush-fallback", "crypto.breaker.trip",
                 "verifier.device.trip", "verifier.warmup.failure",
                 "verifier.compile-cache.unavailable",
                 "verifier.staging.stall"):
        if m.get(name, {}).get("count"):
            bad[name] = m[name]["count"]
    if cockpit["breaker"]["state"] != "closed" or cockpit["breaker"]["trips"]:
        bad["breaker"] = cockpit["breaker"]
    if cockpit["warmup"]["state"] != "done":
        bad["warmup"] = cockpit["warmup"]
    return bad


def chaos_smoke(n_ledgers: int = 30, txs_per_ledger: int = 10) -> dict:
    """`bench.py --chaos`: close-latency p95 with the fault schedule on
    vs off (ISSUE 3; docs/robustness.md). Both legs run the same seeded
    standalone load through the cpu-resilient backend; the chaos leg
    injects device-dispatch failures at p=0.2, so drains pay the
    failed-dispatch-plus-fallback cost and the breaker occasionally
    trips. Pure-Python (no jax import): safe to run inline."""
    from stellar_core_tpu.crypto import keys as _keys
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util import rnd
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    def one_leg(faults_on: bool) -> dict:
        rnd.reseed(0xC4A05)
        _keys.flush_verify_cache()
        cfg = Config.test_config(60, backend="cpu-resilient")
        cfg.SIG_VERIFY_BREAKER_COOLDOWN = 0.5
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        app.start()
        if faults_on:
            app.faults.configure("device.dispatch", probability=0.2)
        lg = LoadGenerator(app)
        lg.generate_accounts(20)
        app.manual_close()
        for _ in range(n_ledgers):
            lg.generate_payments(txs_per_ledger)
            # cold verify cache per close: every drain actually dispatches
            _keys.flush_verify_cache()
            app.clock.set_virtual_time(app.clock.now() + 1.0)
            app.manual_close()
        t = app.metrics.new_timer("ledger.ledger.close")
        m = app.metrics.to_json()
        return {
            "close_p95_ms": round(t.percentile(0.95) * 1e3, 3),
            "close_mean_ms": round(t.mean() * 1e3, 3),
            "ledgers": n_ledgers,
            "breaker_trips": app.sig_verifier.breaker.trips,
            "fallback_drains": m.get("crypto.verify.fallback-drain",
                                     {}).get("count", 0),
            "injected": m.get("fault.injected.device.dispatch",
                              {}).get("count", 0),
        }

    off = one_leg(False)
    on = one_leg(True)
    out = {"metric": "chaos_close_latency_p95", "unit": "ms",
           "faults_off": off, "faults_on": on}
    if off["close_p95_ms"] > 0:
        out["p95_ratio_on_vs_off"] = round(
            on["close_p95_ms"] / off["close_p95_ms"], 3)
    return out


def fleet_bench(n_nodes: int = 3, n_ledgers: int = 12) -> dict:
    """`bench.py --fleet`: the multi-node leg (ISSUE 4;
    docs/observability.md#fleet-view). Runs an n-node simulation over
    the REAL overlay stack (Peer handshake/HMAC/flood — the wire
    cockpit needs actual frames to account, ISSUE 10) with per-node
    tracing on, closes >= n_ledgers ledgers under a light payment load,
    and reports the fleet aggregate — slot-latency p50/p95, externalize
    skew, per-slot bandwidth totals, flood duplication ratio and
    tx-latency p50/p95 — from the merged slot timelines + overlay
    exports. Pure Python (no jax import): safe to run inline."""
    from stellar_core_tpu.simulation import topologies
    from stellar_core_tpu.simulation.simulation import Simulation
    from stellar_core_tpu.testing import AppLedgerAdapter
    from stellar_core_tpu.util import rnd

    rnd.reseed(0xF1EE7)
    sim = topologies.core(
        n_nodes, max(2, (n_nodes * 2 + 1) // 3),
        mode=Simulation.OVER_PEERS,
        cfg_tweak=lambda c: (setattr(c, "TRACE_ENABLED", True),
                             setattr(c, "DATABASE", "sqlite3://:memory:")))
    sim.start_all_nodes()
    first = next(iter(sim.nodes.values())).app
    sim.crank_until(lambda: sim.have_all_externalized(2), 60000)
    # payment load through the real overlay: the tx-lifecycle funnel
    # measures submit→applied end to end
    ad = AppLedgerAdapter(first)
    root = ad.root_account()
    base_seq = ad.seq_num(root.account_id)
    for i in range(4):
        first.submit_transaction(root.tx(
            [root.op_payment(root.account_id, 1 + i)],
            seq=base_seq + 1 + i))
    target = 1 + n_ledgers   # genesis is seq 1; n_ledgers consensus closes
    ok = sim.crank_until(lambda: sim.have_all_externalized(target),
                         200000)
    agg = sim.fleet()     # one aggregation feeds both views
    stats = agg.fleet_stats()
    trace = agg.merged_chrome_trace()
    overlay = agg.overlay_breakdown()
    summary = stats["summary"]
    out = {
        "metric": "fleet_slot_latency",
        "unit": "ms",
        # stable gating key for records derived from this payload (the
        # overlay_breakdown normalizer keys per metric+platform)
        "platform": "fleet-sim",
        "nodes": n_nodes,
        "ledgers_closed": min(
            n.app.ledger_manager.last_closed_ledger_num()
            for n in sim.nodes.values()) - 1,
        "converged": bool(ok),
        "fleet": {
            "slot_count": summary["slot_count"],
            "slot_latency_p50_ms": round(
                summary["slot_latency_p50_s"] * 1e3, 3),
            "slot_latency_p95_ms": round(
                summary["slot_latency_p95_s"] * 1e3, 3),
            "externalize_skew_p50_ms": round(
                summary["externalize_skew_p50_s"] * 1e3, 3),
            "externalize_skew_max_ms": round(
                summary["externalize_skew_max_s"] * 1e3, 3),
            "stragglers": summary["stragglers"],
            "trace_events": len(trace["traceEvents"]),
            "dropped_spans": trace["dropped_spans"],
        },
    }
    # wire cockpit (ISSUE 10): fleet bandwidth totals + tx-latency
    # percentiles ride in the fleet block, the full overlay_breakdown
    # is schema-validated by tools/bench_compare.py
    if overlay is not None:
        out["overlay_breakdown"] = overlay
        out["fleet"]["recv_bytes_total"] = overlay["recv_bytes"]
        out["fleet"]["send_bytes_total"] = overlay["send_bytes"]
        out["fleet"]["flood_duplication_ratio"] = \
            overlay["flood"]["duplication_ratio"]
        out["fleet"]["tx_latency_p50_ms"] = \
            overlay["tx_latency_ms"]["p50"]
        out["fleet"]["tx_latency_p95_ms"] = \
            overlay["tx_latency_ms"]["p95"]
    # propagation cockpit (ISSUE 17): relay-tree percentiles + the
    # redundant bandwidth share that must reconcile with the flood
    # duplication ratio (validated by bench_compare.validate_propagation)
    prop = agg.propagation_summary()
    if prop is not None:
        out["propagation"] = prop
    sim.stop_all_nodes()
    return out


def fleet_scale_leg(n_nodes: int, n_ledgers: int, seed: int) -> dict:
    """One N-node consensus run for `bench.py --fleet-scale` (ISSUE 19;
    ROADMAP item 3's 50-100-node study): an n-node quorum over loopback
    channels with a seeded three-region latency matrix, closing
    n_ledgers ledgers under a light payment load. Loopback (not
    OVER_PEERS) on purpose — the scale leg measures consensus-message
    complexity (envelopes per slot, the O(n^2) flood baseline), slot
    convergence under geographic skew, and per-node memory; real-frame
    wire accounting stays with `--fleet`, which this leg would make
    O(n^2)-slow at N=50.

    per_node_rss_mb is the measured process RSS delta across the run
    divided by N: in-process nodes share one interpreter, so per-node
    self-reports all read the same RSS (footprint_table documents the
    same caveat). Legs run in one process, so later legs inherit the
    allocator arena of earlier ones — the delta still tracks each N's
    incremental growth because freed blocks are reused first."""
    import gc
    from stellar_core_tpu.simulation import topologies
    from stellar_core_tpu.simulation.geography import LatencyMatrix
    from stellar_core_tpu.simulation.simulation import Simulation
    from stellar_core_tpu.testing import AppLedgerAdapter
    from stellar_core_tpu.util import rnd
    from stellar_core_tpu.util.footprint import process_stats

    rnd.reseed(seed ^ n_nodes)
    gc.collect()
    rss0 = process_stats()["rss_mb"]
    sim = topologies.core(
        n_nodes, max(2, (n_nodes * 2 + 1) // 3),
        mode=Simulation.OVER_LOOPBACK,
        cfg_tweak=lambda c: (setattr(c, "TRACE_ENABLED", True),
                             setattr(c, "DATABASE", "sqlite3://:memory:")))
    matrix = LatencyMatrix(sorted(sim.nodes), "three-region", seed=seed)
    sim.apply_latency_matrix(matrix)
    sim.start_all_nodes()
    sim.crank_until(lambda: sim.have_all_externalized(2), 200000)
    first = next(iter(sim.nodes.values())).app
    ad = AppLedgerAdapter(first)
    root = ad.root_account()
    base_seq = ad.seq_num(root.account_id)
    for i in range(4):
        first.submit_transaction(root.tx(
            [root.op_payment(root.account_id, 1 + i)],
            seq=base_seq + 1 + i))
    target = 1 + n_ledgers   # genesis is seq 1; n_ledgers consensus closes
    ok = sim.crank_until(lambda: sim.have_all_externalized(target),
                         200000 + 20000 * n_nodes)
    agg = sim.fleet()
    stats = agg.fleet_stats()
    rss1 = process_stats()["rss_mb"]
    scp = stats.get("scp")
    fpt = stats.get("footprint")
    per_node_rss = round(max(0.0, rss1 - rss0) / n_nodes, 3)
    if fpt is not None:
        # replace the shared-interpreter self-report with the measured
        # scaling signal (see docstring)
        fpt["per_node_rss_mb"] = per_node_rss
    leg = {
        "nodes": n_nodes,
        "platform": "fleet-n%d" % n_nodes,
        "converged": bool(ok),
        "ledgers_closed": min(
            n.app.ledger_manager.last_closed_ledger_num()
            for n in sim.nodes.values()) - 1,
        "per_node_rss_mb": per_node_rss,
        "rss_delta_mb": round(max(0.0, rss1 - rss0), 3),
        "externalize_skew_p95_ms": round(
            stats["summary"]["externalize_skew_p95_s"] * 1e3, 3),
        "envelopes_per_slot": scp["envelopes_per_slot"]
        if scp is not None else None,
        "latency": {"profile": matrix.profile, "seed": matrix.seed,
                    "regions": sorted(set(matrix.region.values()))},
        "scp": scp,
        "footprint": fpt,
    }
    sim.stop_all_nodes()
    gc.collect()
    return leg


def fleet_scale_main(argv) -> int:
    """`bench.py --fleet-scale [--sizes 10,25,50] [--ledgers 6]
    [--record] [--history PATH] [--tolerance T] [--out FILE]`: the
    N-vs-cost scaling leg (ISSUE 19). One in-process simulation per
    fleet size, each emitting three gated records under its own
    `fleet-n<N>` platform key — `per_node_rss_mb` (lower; the N-vs-RSS
    curve), `externalize_skew_p95_ms` (lower; convergence under the
    three-region matrix), and `envelopes_per_slot` (lower; the O(n^2)
    flood baseline ROADMAP item 1's BLS quorum certificates must beat)
    — plus the worst ballot round count. Pure Python (no jax import):
    safe to run inline; never touches the chip."""
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --fleet-scale")
    ap.add_argument("--fleet-scale", action="store_true")
    ap.add_argument("--sizes", default="10,25,50")
    ap.add_argument("--ledgers", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0x5CA1E)
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--out", help="also write the block to this file")
    args = ap.parse_args(argv)
    sizes = sorted({int(x) for x in args.sizes.split(",") if x.strip()})

    src = "bench.py --fleet-scale"
    legs = {}
    errors = {}
    records = []
    for n in sizes:
        try:
            leg = fleet_scale_leg(n, args.ledgers, args.seed)
        except Exception as e:                      # noqa: BLE001
            errors["n%d" % n] = "%s: %s" % (type(e).__name__, e)
            continue
        legs[str(n)] = leg
        plat = leg["platform"]
        records.append(bc.make_record(
            "per_node_rss_mb", "MB", leg["per_node_rss_mb"], plat,
            "lower", src))
        records.append(bc.make_record(
            "externalize_skew_p95_ms", "ms",
            leg["externalize_skew_p95_ms"], plat, "lower", src))
        records.extend(bc.scp_records(leg.get("scp"), plat, src))

    out = {
        "metric": "fleet_scale_envelopes_per_slot",
        "unit": "envelopes",
        "value": max((leg["envelopes_per_slot"] or 0.0
                      for leg in legs.values()), default=0.0),
        "platform": "fleet-scale",
        "sizes": sizes,
        "ledgers": args.ledgers,
        "seed": args.seed,
        "legs": legs,
    }
    if errors:
        out["errors"] = errors
    out["records"] = records
    history = bc.load_history(args.history)
    report = bc.compare(records, history, tolerance=args.tolerance)
    if args.record:
        commit = _git_commit()
        now = int(time.time())
        for rec in records:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, records)
    out["compare"] = report
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1, sort_keys=True))
    # a leg that produced no data is a failure, not a green gate
    if not legs or errors:
        return 1
    return 1 if report["regressions"] else 0


def fleet_verify_child(chunk: int = 8192, chunks: int = 3,
                       iters: int = 4) -> dict:
    """One fleet-verify measurement at the CURRENT process's device
    count (the orchestrator forces it per child via
    `--xla_force_host_platform_device_count=N`): a `chunks`-chunk drain
    through the production TpuSigVerifier — sharded mesh dispatch,
    double-buffered staging, cockpit-driven warmup — timed end to end.

    warm_restart_s is construction → warmed → first full-rate drain
    complete, i.e. the time a restarted node pays before verifying at
    full rate (near-zero compile inside when the persistent XLA cache
    is warm)."""
    import jax
    from stellar_core_tpu.crypto.batch_verifier import (
        TpuSigVerifier, VerifierContext, VerifierStats)

    n_devices = jax.device_count()
    n = chunk * chunks
    pubs, sigs, msgs = _example_batch(n, n_keys=64)
    triples = list(zip(pubs, sigs, msgs))

    t0 = time.perf_counter()
    v = TpuSigVerifier(VerifierContext(stats=VerifierStats()),
                       shard_threshold=min(chunk, 2048))
    v.BUCKETS = (chunk,)
    v.warmup(wait=True)    # one shape: this mix is all `chunk`-sized
    first = v.verify_many(triples)
    warm_restart_s = time.perf_counter() - t0
    assert all(first), "fleet verify rejected valid signatures"

    best = 0.0
    for _ in range(iters):
        t1 = time.perf_counter()
        ok = v.verify_many(triples)
        dt = time.perf_counter() - t1
        assert all(ok)
        best = max(best, n / dt)
    j = v.ctx.stats.to_json()
    return {
        "devices": n_devices,
        "platform": jax.devices()[0].platform,
        "chunk": chunk,
        "drain_sigs": n,
        "fleet_sigs_per_s": round(best, 1),
        "per_device_sigs_per_s": round(best / n_devices, 1),
        "warm_restart_s": round(warm_restart_s, 3),
        "warmup_buckets_s": {b: info["seconds"] for b, info in
                             j["warmup"]["buckets"].items()},
        "staging": j["staging"],
        "devices_detail": j["devices"],
    }


def _spawn_fleet_child(n_devices: int, chunk: int,
                       chunks: int) -> subprocess.Popen:
    env = _cpu_env()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=%d"
                        % n_devices).strip()
    return _spawn("import bench, json; "
                  "print('FLEETV_JSON ' + json.dumps("
                  "bench.fleet_verify_child(chunk=%d, chunks=%d)))"
                  % (chunk, chunks), env)


def fleet_verify_main(argv) -> int:
    """`bench.py --fleet-verify [--devices 1,2,4] [--chunk 8192]
    [--record] [--history PATH] [--tolerance T] [--out FILE]`: the
    multi-device verify leg (ISSUE 11; ROADMAP item 1). One child
    process per device count, each on a forced virtual-CPU fleet
    (`--xla_force_host_platform_device_count=N` — the same fake-device
    contract tier-1 uses), running the SAME batch mix through the
    production sharded drain. Emits `fleet_sigs_per_s` /
    `per_device_sigs_per_s` / `warm_restart_s` records under
    `verify-fleet-cpu<N>` platform keys, gated against
    bench/history.jsonl; the N_max/N_1 ratio lands as
    `fleet_verify_speedup`. Never touches the chip."""
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --fleet-verify")
    ap.add_argument("--fleet-verify", action="store_true")
    ap.add_argument("--devices", default="1,2,4")
    ap.add_argument("--chunk", type=int, default=8192)
    ap.add_argument("--chunks", type=int, default=3)
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--out", help="also write the block to this file")
    args = ap.parse_args(argv)
    counts = sorted({int(x) for x in args.devices.split(",") if x.strip()})

    legs = {}
    errors = {}
    for nd in counts:
        proc = _spawn_fleet_child(nd, args.chunk, args.chunks)
        # budget: one cold kernel compile (~150s on this container) +
        # the timed drains; stall-kill well past that
        deadline = time.time() + 900
        while time.time() < deadline and proc.poll() is None:
            time.sleep(1.0)
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
            errors["fleet_cpu%d" % nd] = "killed at deadline"
            continue
        got, err = _harvest(proc, "FLEETV_JSON")
        if err:
            errors["fleet_cpu%d" % nd] = err
        else:
            legs[str(nd)] = got

    out = {
        "metric": "fleet_verify_sigs_per_s",
        "unit": "sigs/s",
        "value": max((leg["fleet_sigs_per_s"] for leg in legs.values()),
                     default=0.0),
        "platform": "verify-fleet-cpu",
        "chunk": args.chunk,
        "drain_sigs": args.chunk * args.chunks,
        "fleet_verify": legs,
    }
    if "1" in legs and len(legs) > 1:
        top = str(max(int(k) for k in legs))
        out["fleet_speedup"] = round(
            legs[top]["fleet_sigs_per_s"] / legs["1"]["fleet_sigs_per_s"],
            3)
        out["fleet_speedup_devices"] = int(top)
    if errors:
        out["errors"] = errors

    src = "bench.py --fleet-verify"
    records = bc.fleet_verify_records(out.get("fleet_verify"), src)
    if "fleet_speedup" in out:
        records.append(bc.make_record(
            "fleet_verify_speedup", "x", out["fleet_speedup"],
            "verify-fleet-cpu", "higher", src))
    out["records"] = records
    history = bc.load_history(args.history)
    report = bc.compare(records, history, tolerance=args.tolerance)
    if args.record:
        commit = _git_commit()
        now = int(time.time())
        for rec in records:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, records)
    out["compare"] = report
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1, sort_keys=True))
    # a leg that produced no data is a failure, not a green gate — an
    # all-children-wedged run must never read as "no regressions"
    if not legs or errors:
        return 1
    return 1 if report["regressions"] else 0


def hash_bench_child(shapes=((256, 2), (1024, 4), (4096, 2),
                             (4096, 4)), iters: int = 5) -> dict:
    """Batched-SHA-256 kernel legs (ISSUE 12), one per (lanes × blocks)
    dispatch shape, in the CURRENT process (the orchestrator spawns
    this in a forced-CPU child — never touches the chip).
    Each leg times the jit'd kernel on messages that exactly fill the
    shape (`msg_bytes = blocks*64 - 9`), best-of-`iters`, against the
    single-core hashlib rate over the same batch."""
    import jax
    import numpy as np
    from stellar_core_tpu.ops.sha256 import (
        hash_blocks_jit, pad_messages_np, sha256_batch_host,
    )
    platform = jax.devices()[0].platform
    out = {"platform": platform, "kernel": {}, "host": {}}
    host_best = 0.0
    for lanes, blocks in shapes:
        msg_bytes = blocks * 64 - 9
        msgs = [bytes([i & 0xFF]) * msg_bytes for i in range(lanes)]
        words, counts = pad_messages_np(msgs, blocks)
        words_d, counts_d = (np.asarray(words), np.asarray(counts))
        t_c = time.perf_counter()
        first = np.asarray(hash_blocks_jit(words_d, counts_d))
        compile_s = time.perf_counter() - t_c
        from stellar_core_tpu.ops.sha256 import digests_to_bytes
        assert digests_to_bytes(first) == sha256_batch_host(msgs), \
            "kernel digests diverged from hashlib"
        best = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            np.asarray(hash_blocks_jit(words_d, counts_d))
            dt = time.perf_counter() - t0
            best = max(best, lanes / dt)
        # host leg over the same batch: hashlib per message
        t0 = time.perf_counter()
        sha256_batch_host(msgs)
        host_rate = lanes / (time.perf_counter() - t0)
        key = "%dx%d" % (lanes, blocks)
        out["kernel"][key] = {
            "platform": platform, "lanes": lanes, "blocks": blocks,
            "msg_bytes": msg_bytes, "compile_s": round(compile_s, 2),
            "hash_msgs_per_s": round(best, 1),
            "hash_bytes_per_s": round(best * msg_bytes, 1),
            "host_msgs_per_s": round(host_rate, 1),
            "vs_host": round(best / host_rate, 3) if host_rate else None,
        }
        host_best = max(host_best, host_rate * msg_bytes)
    out["host"] = {"hash_bytes_per_s": round(host_best, 1)}
    return out


def checkpoint_bench(n_ledgers: int = 20, n_verifies: int = 200) -> dict:
    """Checkpoint/light-client leg (ISSUE 12): a standalone bucketed
    node closes `n_ledgers` under load with the incremental Merkle root
    checked against the from-scratch oracle at EVERY close, then serves
    a signed checkpoint + membership proofs and times
    `light_client_verify` (pure function — the light client's whole
    cost). Pure Python (no jax import): safe to run inline."""
    import json as _json
    import shutil
    import tempfile

    from stellar_core_tpu.ledger.state_commitment import (
        light_client_verify,
    )
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.util import rnd
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr import LedgerKey

    rnd.reseed(0x4A54)
    tmp = tempfile.mkdtemp(prefix="sct-hashbench-")
    try:
        cfg = Config.test_config(77)
        cfg.DATABASE = "sqlite3://:memory:"
        cfg.STATE_CHECKPOINT_INTERVAL = 4
        cfg.INVARIANT_CHECKS = []
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        app.enable_buckets(os.path.join(tmp, "buckets"))
        app.start()
        lg = LoadGenerator(app)
        lg.generate_accounts(20)
        app.manual_close()
        sce = app.state_commitment
        bl = app.bucket_manager.bucket_list
        oracle_equal = True
        update_ms = []
        for _ in range(n_ledgers):
            lg.generate_payments(10)
            app.clock.set_virtual_time(app.clock.now() + 1.0)
            t0 = time.perf_counter()
            app.manual_close()
            update_ms.append((time.perf_counter() - t0) * 1e3)
            if sce.root != sce.from_scratch_root(bl):
                oracle_equal = False
        cp = sce.checkpoint()
        key = LedgerKey.account(app.network_root_key().public_key)
        proof = sce.prove_entry(key)
        assert cp is not None and proof is not None
        net = cfg.network_id
        verify_s = []
        for _ in range(n_verifies):
            t0 = time.perf_counter()
            ok, reason = light_client_verify(proof, cp, net)
            verify_s.append(time.perf_counter() - t0)
            assert ok, reason
        verify_s.sort()
        update_ms.sort()
        m = app.metrics.to_json()
        upd = m.get("commitment.update-ms", {})
        return {
            "ledgers": n_ledgers,
            "oracle_equal": oracle_equal,
            "checkpoints": m.get("commitment.checkpoint.emitted",
                                 {}).get("count", 0),
            "proof_bytes": len(_json.dumps(proof)),
            "verify_p50_ms": round(
                verify_s[len(verify_s) // 2] * 1e3, 4),
            "verify_p95_ms": round(
                verify_s[int(len(verify_s) * 0.95)] * 1e3, 4),
            "verifies": n_verifies,
            # incremental root update cost per close (the engine's own
            # histogram; real elapsed ms)
            "update_p50_ms": round(upd.get("median", 0.0), 3),
            "update_p95_ms": round(upd.get("p95", 0.0), 3),
            "leaves_changed_mean": round(
                m.get("commitment.leaves-changed", {}).get("mean", 0.0),
                2),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _spawn_hash_child() -> subprocess.Popen:
    return _spawn("import bench, json; "
                  "print('HASH_JSON ' + json.dumps("
                  "bench.hash_bench_child()))", _cpu_env())


def hash_main(argv) -> int:
    """`bench.py --hash [--record] [--history PATH] [--tolerance T]
    [--out FILE] [--no-replay]`: the batched-hashing leg (ISSUE 12).
    Kernel throughput per (lanes × blocks) shape runs in a forced-CPU
    child (never touches the chip); the checkpoint/light-client
    leg runs inline; unless --no-replay, a CPU replay leg runs in a
    child so the artifact carries the close `phase_breakdown` whose
    `close.bucket_add` / `close.header_hash` self-times the ISSUE 12
    acceptance compares against BENCH_r08. Records gate against
    bench/history.jsonl; exit 1 on regression or on a failed leg."""
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --hash")
    ap.add_argument("--hash", action="store_true")
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--out", help="also write the block to this file")
    ap.add_argument("--no-replay", action="store_true")
    args = ap.parse_args(argv)

    errors = {}
    hb = None
    proc = _spawn_hash_child()
    deadline = time.time() + 900
    while time.time() < deadline and proc.poll() is None:
        time.sleep(1.0)
    if proc.poll() is None:
        proc.kill()
        proc.communicate()
        errors["hash_kernel"] = "killed at deadline"
    else:
        hb, err = _harvest(proc, "HASH_JSON")
        if err:
            errors["hash_kernel"] = err
    if hb is None:
        hb = {"platform": "none", "kernel": {}, "host": {}}
    try:
        hb["checkpoint"] = checkpoint_bench()
    except Exception as e:   # noqa: BLE001 - recorded, not swallowed
        errors["checkpoint_leg"] = repr(e)[:400]

    out = {
        "metric": "hash_bench",
        "unit": "bytes/s",
        "value": max((leg["hash_bytes_per_s"]
                      for leg in hb.get("kernel", {}).values()),
                     default=0.0),
        "platform": "hash-%s" % hb.get("platform", "none"),
        "hash_bench": hb,
    }

    if not args.no_replay:
        # CPU replay leg: the phase_breakdown evidence for the
        # bucket_add/header_hash shrink. Embedded for the record, NOT
        # normalized into gating records here — the full-leg replay
        # history keys gate via the main bench, not the hash leg.
        proc = _spawn_replay(_cpu_env(), "cpu")
        deadline = time.time() + 600
        while time.time() < deadline and proc.poll() is None:
            time.sleep(1.0)
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
            errors["replay_cpu"] = "killed at deadline"
        else:
            rep, err = _harvest(proc, "REPLAY_JSON")
            if err:
                errors["replay_cpu"] = err
            else:
                out["replay_cpu"] = rep
                phases = rep.get("phase_breakdown", {}).get("phases", {})
                out["close_hash_phases"] = {
                    k: phases[k] for k in
                    ("close.bucket_add", "close.header_hash",
                     "close.result_hash", "close.commitment")
                    if k in phases}

    src = "bench.py --hash"
    # the leg's own differential oracle: a diverged incremental Merkle
    # root must fail the gate AND never be recorded as a baseline
    # (validate_hash_bench enforces the same on committed artifacts)
    oracle_ok = hb.get("checkpoint", {}).get("oracle_equal") is True
    if not oracle_ok:
        errors.setdefault(
            "checkpoint_oracle",
            "incremental Merkle root diverged from the from-scratch "
            "oracle — records withheld from history")
    records = bc.hash_bench_records(hb, src)
    out["records"] = records
    history = bc.load_history(args.history)
    report = bc.compare(records, history, tolerance=args.tolerance)
    if args.record and oracle_ok:
        commit = _git_commit()
        now = int(time.time())
        for rec in records:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, records)
    out["compare"] = report
    if errors:
        out["errors"] = errors
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1, sort_keys=True))
    if not hb.get("kernel") or "checkpoint" not in hb or errors:
        return 1
    return 1 if report["regressions"] else 0


def _bucketdb_seed_state(app, n_accounts: int, seed: int,
                         level: int = 6) -> list:
    """Seeded cold-state generator (ISSUE 14): install `n_accounts`
    deterministic accounts as one deep-level bucket WITHOUT closing
    ledgers — the bucket file, its content hash, the sorted key index
    and the bloom filter are all built in one streamed pass, so 10^6
    accounts never sit in memory as Python entry objects. The installed
    bucket is file-backed only (a slim Bucket with no resident
    entries): every later read exercises the sidecar-index + pread
    path for real. Returns the 32-byte account key list (payment
    destinations for the traffic legs)."""
    import hashlib as _hashlib

    from stellar_core_tpu.bucket.bucket import Bucket, entry_record
    from stellar_core_tpu.bucket.bucket_index import (
        BloomFilter, BucketIndex, key_fingerprint, sidecar_path,
    )
    from stellar_core_tpu.transactions.account_helpers import (
        make_account_entry,
    )
    from stellar_core_tpu.xdr import (
        BucketEntry, PublicKey, ledger_entry_key,
    )

    bm = app.bucket_manager
    proto = app.ledger_manager.lcl_header.ledgerVersion
    # account ids sorted up front: LIVE bucket entries order by
    # (type, accountID XDR), which for same-type keys is raw pubkey order
    keys = sorted(
        _hashlib.sha256(b"bucketdb-bench:%d:%d" % (seed, i)).digest()
        for i in range(n_accounts))
    h = _hashlib.sha256()
    tmp_path = os.path.join(bm.bucket_dir, ".seed-%d.tmp" % n_accounts)
    idx_keys, ordinals, offsets, lengths = [], [], [], []
    bloom = BloomFilter.for_capacity(
        n_accounts, app.config.BUCKETDB_BLOOM_BITS_PER_KEY)
    off = 0
    with open(tmp_path, "wb") as fh:
        meta = entry_record(BucketEntry.meta(proto))
        fh.write(meta)
        h.update(meta)
        off += len(meta)
        for ordinal, kb32 in enumerate(keys, start=1):
            e = make_account_entry(PublicKey.ed25519(kb32), 10**9, 0, 1)
            rec = entry_record(BucketEntry.live(e))
            fh.write(rec)
            h.update(rec)
            lk = ledger_entry_key(e).to_xdr()
            idx_keys.append(lk)
            ordinals.append(ordinal)
            offsets.append(off + 8)        # 4B record mark + 4B union disc
            lengths.append(len(rec) - 8)
            bloom.add(key_fingerprint(lk))
            off += len(rec)
    bucket_hash = h.digest()
    path = bm.bucket_filename(bucket_hash)
    os.replace(tmp_path, path)
    slim = Bucket((), hash_=bucket_hash, path=path)
    BucketIndex(bucket_hash, idx_keys, ordinals, offsets, lengths,
                bloom).save(sidecar_path(path))
    with bm._lock:
        bm._shared[bucket_hash] = slim
    # deep level: nothing spills into (or merges) level 6 within the
    # bench's few dozen closes, so the cold state stays put while the
    # close path hashes the list over it every close
    bm.bucket_list.levels[level].curr = slim
    return keys


def _bucketdb_leg(n_accounts: int, senders: int, closes: int,
                  surge_closes: int, seed: int) -> dict:
    """One scale point of the --bucketdb latency-flatness gate: a
    standalone node over `n_accounts` of seeded bucket-backed cold
    state, closing `closes` ledgers of uniform-random payments into the
    cold set (every destination read is a bloom-filtered index probe)
    and `surge_closes` of hot-key-skewed traffic for the prefetch
    hit-rate gate."""
    import random as _random
    import shutil
    import tempfile

    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr import PublicKey

    tmp = tempfile.mkdtemp(prefix="sct-bucketdb-")
    try:
        cfg = Config.test_config(0)
        cfg.DATABASE = "sqlite3://:memory:"
        cfg.INVARIANT_CHECKS = []
        cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = 10_000
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        app.enable_buckets(os.path.join(tmp, "buckets"))
        app.start()
        # the commitment engine would Merkle an empty root over the
        # slim (non-resident) seeded bucket — disabled for the leg
        # (docs/perf-replay.md#million-account-methodology)
        app.state_commitment = None
        assert app.ledger_manager.root.bucket_backed()
        cold_keys = _bucketdb_seed_state(app, n_accounts, seed)

        adapter = AppLedgerAdapter(app)
        root = adapter.root_account()
        sender_sks = [SecretKey.from_seed(
            bytes([13, i & 0xFF, (i >> 8) & 0xFF, seed & 0xFF] + [29] * 28))
            for i in range(senders)]
        for lo in range(0, senders, 100):
            app.submit_transaction(root.tx(
                [root.op_create_account(sk.public_key, 10**10)
                 for sk in sender_sks[lo:lo + 100]]))
            app.manual_close()
        sender_accs = [TestAccount(adapter, sk) for sk in sender_sks]

        lm = app.ledger_manager
        bdb = app.bucket_manager.bucketdb
        rnd = _random.Random(seed)
        # warm pass: the big sidecar loads ONCE here (index load cost is
        # startup, not steady-state close latency)
        bdb.lookup(_cold_account_key_xdr(cold_keys[0]))

        lm.apply_stats.reset()
        bdb.stats.reset()
        walls = []
        for c in range(closes):
            app.clock.set_virtual_time(app.clock.now() + 1)
            for s in sender_accs:
                dest = PublicKey.ed25519(
                    cold_keys[rnd.randrange(n_accounts)])
                app.submit_transaction(
                    s.tx([s.op_payment(dest, 100)]))
            t0 = time.perf_counter()
            app.manual_close()
            walls.append((time.perf_counter() - t0) * 1e3)
        uniform_reads = lm.apply_stats.to_json()["state_reads"]
        sql_lookups = sum(uniform_reads["lookups"].values())

        # surge: hot-key skew — 80% of payments hammer one destination,
        # 20% still land in the cold set (the prefetch bulk-warm must
        # keep covering both)
        lm.apply_stats.reset()
        hot = PublicKey.ed25519(cold_keys[0])
        for c in range(surge_closes):
            app.clock.set_virtual_time(app.clock.now() + 1)
            for i, s in enumerate(sender_accs):
                dest = hot if i % 5 else PublicKey.ed25519(
                    cold_keys[rnd.randrange(n_accounts)])
                app.submit_transaction(s.tx([s.op_payment(dest, 100)]))
            app.manual_close()
        surge_stats = lm.apply_stats.to_json()
        sql_lookups += sum(
            surge_stats["state_reads"]["lookups"].values())

        walls_sorted = sorted(walls)
        p50 = walls_sorted[len(walls_sorted) // 2]
        bstats = bdb.stats
        out = {
            "accounts": n_accounts,
            "senders": senders,
            "closes": closes,
            "close_ms_p50": round(p50, 3),
            "close_ms_mean": round(sum(walls) / len(walls), 3),
            "close_ms_max": round(max(walls), 3),
            "surge": {
                "closes": surge_closes,
                "prefetch_hit_rate_pct": round(
                    100.0 * surge_stats["prefetch_hit_rate"], 2),
            },
            "bloom_fp_pct": round(
                100.0 * bstats.false_positive_rate(), 4),
            "bucketdb": bdb.stats.to_json(),
            "sql_point_lookups": sql_lookups,
        }
        app.stop()
        app.bucket_manager.shutdown()
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _cold_account_key_xdr(kb32: bytes) -> bytes:
    from stellar_core_tpu.xdr import LedgerKey, PublicKey
    return LedgerKey.account(PublicKey.ed25519(kb32)).to_xdr()


def bucketdb_bench(small: int = 10**4, large: int = 10**6,
                   senders: int = 40, closes: int = 16,
                   surge_closes: int = 8, seed: int = 4242,
                   progress=None) -> dict:
    """`bench.py --bucketdb` (ISSUE 14): close-latency flatness from
    `small` to `large` seeded accounts with bucket-backed reads, plus
    the surge prefetch-hit-rate and bloom false-positive gates. Pure
    CPU/IO — safe to run inline (no jax import)."""
    legs = {}
    for name, n in (("small", small), ("large", large)):
        legs[name] = _bucketdb_leg(n, senders, closes, surge_closes, seed)
        if progress is not None:
            progress(name)
    ratio = legs["large"]["close_ms_p50"] / \
        max(1e-9, legs["small"]["close_ms_p50"])
    return {
        "small": legs["small"],
        "large": legs["large"],
        "latency_ratio": round(ratio, 4),
        "prefetch_hit_rate_pct":
            legs["large"]["surge"]["prefetch_hit_rate_pct"],
        "bloom_fp_pct": legs["large"]["bloom_fp_pct"],
        "sql_point_lookups": legs["small"]["sql_point_lookups"] +
            legs["large"]["sql_point_lookups"],
    }


def bucketdb_main(argv) -> int:
    """`bench.py --bucketdb [--small N] [--large N] [--record]
    [--history PATH] [--tolerance T] [--out FILE]`: the million-account
    BucketDB gate (ISSUE 14). Hard gates (exit 1): close-latency p50
    within 1.25x from --small to --large accounts, surge prefetch
    hit-rate >= 95%, bloom false positives <= 5%, and ZERO apply-path
    SQL point lookups across every measured close (cockpit-asserted).
    Records gate against bench/history.jsonl like every other leg.

    Its seeder (`_bucketdb_seed_state`) writes the bucket only: SQL
    never sees those accounts, so what it installs is no deployment's
    state. The loader that seeds BOTH stores, the one a node restarts
    from and a benchmark cell measures, is
    `benchmark/traffic/state_history.py::bulk_load` (ISSUE 33)."""
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --bucketdb")
    ap.add_argument("--bucketdb", action="store_true")
    ap.add_argument("--small", type=int, default=10**4)
    ap.add_argument("--large", type=int, default=10**6)
    ap.add_argument("--senders", type=int, default=40)
    ap.add_argument("--closes", type=int, default=16)
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--out", help="also write the block to this file")
    args = ap.parse_args(argv)

    t0 = time.time()
    bd = bucketdb_bench(small=args.small, large=args.large,
                        senders=args.senders, closes=args.closes,
                        progress=lambda s: print(
                            "# bucketdb leg %s done (%.0fs)"
                            % (s, time.time() - t0), file=sys.stderr))
    errors = {}
    if bd["latency_ratio"] > 1.25:
        errors["latency_flatness"] = \
            "close p50 grew %.2fx from %d to %d accounts (gate 1.25x)" \
            % (bd["latency_ratio"], args.small, args.large)
    if bd["prefetch_hit_rate_pct"] < 95.0:
        errors["prefetch_hit_rate"] = \
            "surge prefetch hit-rate %.2f%% < 95%%" \
            % bd["prefetch_hit_rate_pct"]
    if bd["bloom_fp_pct"] > 5.0:
        errors["bloom_fp"] = "bloom false-positive rate %.3f%% > 5%%" \
            % bd["bloom_fp_pct"]
    if bd["sql_point_lookups"] != 0:
        errors["sql_point_lookups"] = \
            "%d apply-path SQL point lookups leaked (gate: zero)" \
            % bd["sql_point_lookups"]

    src = "bench.py --bucketdb"
    records = bc.bucketdb_records(bd, src)
    out = {
        "metric": "bucketdb_latency_ratio",
        "unit": "x",
        "value": bd["latency_ratio"],
        "platform": "bucketdb-cpu",
        "at_unix": int(t0),
        "bucketdb_bench": bd,
        "records": records,
    }
    history = bc.load_history(args.history)
    report = bc.compare(records, history, tolerance=args.tolerance)
    if args.record and not errors:
        commit = _git_commit()
        now = int(time.time())
        for rec in records:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, records)
    out["compare"] = report
    if errors:
        out["errors"] = errors
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1, sort_keys=True))
    if errors:
        return 1
    return 1 if report["regressions"] else 0


def _bench_compare_mod():
    """The perf-regression ledger module (tools/bench_compare.py) —
    stdlib-only, never imports jax."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    from tools import bench_compare
    return bench_compare


def _git_commit() -> str | None:
    """Short HEAD hash, or None where the tree is not a git repository
    (the chip tool's copy) or git is absent."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO,
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def compare_leg() -> list:
    """Tiny deterministic CPU replay leg for the regression gate
    (ISSUE 6): seeded content, cpu backend, one checkpoint — the full
    bench compressed into seconds. Records key by platform "cpu-tiny" /
    "openssl-cpu-tiny", so they only ever gate against tiny-leg
    baselines, never against full-leg or device history. Pure Python
    (no jax import): safe to run inline and in tier-1."""
    bc = _bench_compare_mod()
    src = "bench.py --compare"
    r = replay_bench("cpu", n_checkpoints=1, txs_per_ledger=4,
                     sigs_per_tx=2, repeats=1)
    recs = [
        bc.make_record("replay_ledgers_per_sec", "ledgers/s",
                       r["ledgers_per_sec"], "cpu-tiny", "higher", src),
        bc.make_record("replay_txs_per_sec", "txs/s",
                       r["txs_per_sec"], "cpu-tiny", "higher", src),
        bc.make_record("replay_wall_s", "s", r["wall_s"],
                       "cpu-tiny", "lower", src),
        bc.make_record("replay_crypto_s", "s", r["crypto_s"],
                       "cpu-tiny", "lower", src),
        bc.make_record("cpu_openssl_baseline_sigs_per_sec", "sigs/s",
                       round(cpu_baseline_rate(500), 1),
                       "openssl-cpu-tiny", "higher", src),
    ]
    # per-op apply costs gate under the same tiny platform key (ISSUE 9)
    recs.extend(bc.apply_breakdown_records(
        r.get("apply_breakdown"), "cpu-tiny", src))
    return recs


def compare_main(argv) -> int:
    """`bench.py --compare [--record] [--input FILE] [--history PATH]
    [--tolerance T]`: diff a current run against the best committed
    record per (metric, platform) in bench/history.jsonl; exit 1 on any
    regression beyond tolerance. Without `--input` the tiny CPU replay
    leg runs inline; with it, an existing bench-output JSON (or a
    {"records": [...]} blob) is normalized instead. `--record` appends
    the current records (commit- and time-stamped) to the history."""
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --compare")
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--input")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.1)
    args = ap.parse_args(argv)
    if args.input:
        with open(args.input) as fh:
            blob = json.load(fh)
        current = bc.normalize_any(blob, os.path.basename(args.input))
    else:
        current = compare_leg()
    history = bc.load_history(args.history)
    report = bc.compare(current, history, tolerance=args.tolerance)
    if args.record:
        commit = _git_commit()
        now = int(time.time())
        for rec in current:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, current)
    print(json.dumps(report, indent=1, sort_keys=True))
    return 1 if report["regressions"] else 0


def scenario_main(argv) -> int:
    """`bench.py --scenario NAME [--seed N] [--scale tier1|soak]
    [--record] [--history PATH] [--tolerance T] [--out FILE]`: run one
    scenario from the scenario lab (stellar_core_tpu/testing/scenarios.py
    — churn / flood / partition / surge / overload / checkpoint, or
    `suite` for all) and emit its
    fleet bench block. The block's normalized `records` (platform keys
    `scenario-<name>`) are gated against bench/history.jsonl exactly like
    perf records: exit 1 on any regression beyond tolerance (default 0.5
    — slot latencies are wall-clock and jittery; the virtual-clock
    recovery times are tight). `--record` appends the records to the
    history. Pure Python (no jax import): safe to run inline."""
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --scenario")
    ap.add_argument("--scenario", required=True,
                    help="churn|flood|partition|surge|overload|"
                         "checkpoint|suite")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--scale", choices=("tier1", "soak"), default="tier1")
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.5)
    ap.add_argument("--out", help="also write the block to this file")
    args = ap.parse_args(argv)
    from stellar_core_tpu.testing.scenarios import run_scenario, run_suite
    if args.scenario == "suite":
        block = run_suite(seed=args.seed, scale=args.scale)
    else:
        block = run_scenario(args.scenario, seed=args.seed,
                             scale=args.scale)
    current = list(block["records"])
    history = bc.load_history(args.history)
    report = bc.compare(current, history, tolerance=args.tolerance)
    if args.record:
        commit = _git_commit()
        now = int(time.time())
        for rec in current:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, current)
    block["compare"] = report
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(block, fh, indent=1, sort_keys=True)
    print(json.dumps(block, indent=1, sort_keys=True))
    return 1 if report["regressions"] else 0


def _cpu_env() -> dict:
    """Environment of a forced-CPU child: it can never take the chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _spawn(code: str, env: dict | None = None) -> subprocess.Popen:
    """Child-process spawner shared by every bench leg; children share
    one compile cache (parallel.device, the one rule)."""
    from stellar_core_tpu.parallel.device import configure_compile_cache
    env = dict(os.environ if env is None else env)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", configure_compile_cache())
    return subprocess.Popen(
        [sys.executable, "-c", code], cwd=_REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _harvest(proc: subprocess.Popen, prefix: str = "BENCH_JSON") -> tuple:
    """(result_dict | None, error_str | None); proc must have exited."""
    out, err_txt = proc.communicate()
    if proc.returncode != 0:
        return None, ("rc=%d: %s" % (proc.returncode,
                                     err_txt.strip()[-600:]))
    for line in out.splitlines():
        if line.startswith(prefix + " "):
            return json.loads(line[len(prefix) + 1:]), None
    return None, "no %s line in child output: %s" % (
        prefix, out.strip()[-300:])


def _spawn_replay(env: dict, backend: str,
                  mix: str = "multisig") -> subprocess.Popen:
    return _spawn("import bench, json; "
                  "print('REPLAY_JSON ' + json.dumps("
                  "bench.replay_bench(%r, mix=%r)))" % (backend, mix), env)


def parallel_close_bench(n_pairs: int = 300, ops_per_tx: int = 20,
                         rounds: int = 8) -> dict:
    """The conflict-graph parallel-close gate (ISSUE 13): identical
    conflict-light txsets (disjoint sender pairs, multi-op payment txs)
    closed by two native LedgerManagers — one pinned serial, one pinned
    parallel — comparing the ENGINE's tx-execution wall (`apply_ns`:
    cluster scheduling + apply only; parse/verify/fees/emission are
    identical serial work on both sides). Rounds interleave so ambient
    sandbox noise hits both modes alike; the signature cache is
    prewarmed so verify cost cannot masquerade as apply time. Pure
    Python + the native engine — no jax import."""
    import statistics

    from stellar_core_tpu.crypto.hashing import sha256
    from stellar_core_tpu.crypto.keys import SecretKey
    from stellar_core_tpu.crypto.batch_verifier import CPU_VERIFIER
    from stellar_core_tpu.herder.txset import TxSetFrame
    from stellar_core_tpu.ledger.ledger_manager import (
        LedgerCloseData, LedgerManager,
    )
    from stellar_core_tpu.testing import (
        TESTING_NETWORK_ID, TestAccount, root_secret_key,
    )
    from stellar_core_tpu.xdr import StellarValue, StellarValueExt

    class _Cfg:
        DATABASE = "in-memory"
        LEDGER_PROTOCOL_VERSION = 13
        GENESIS_TOTAL_COINS = 10 ** 17
        TESTING_UPGRADE_DESIRED_FEE = 100
        TESTING_UPGRADE_RESERVE = 5_000_000
        TESTING_UPGRADE_MAX_TX_SET_SIZE = 100_000
        NATIVE_PARALLEL_APPLY = True
        NATIVE_PARALLEL_WORKERS = 0
        network_id = TESTING_NETWORK_ID

    class _App:
        config = _Cfg()

        def network_root_key(self):
            return root_secret_key()

    class _Shim:
        def __init__(self, lm):
            self.lm = lm
            self.network_id = TESTING_NETWORK_ID

        def header(self):
            return self.lm.root.get_header()

        def seq_num(self, account_id):
            from stellar_core_tpu.xdr import LedgerKey
            e = self.lm.root.get_entry(LedgerKey.account(account_id))
            return e.data.value.seqNum if e is not None else 0

    def mk(mode):
        lm = LedgerManager(_App())
        lm.start_new_ledger()
        lm.use_native_apply = True
        lm.native_force_mode = mode
        shim = _Shim(lm)
        root = TestAccount(shim, root_secret_key())
        accs = [TestAccount(shim, SecretKey.from_seed(
            sha256(b"pcb%d" % i))) for i in range(2 * n_pairs)]

        def close(frames, prewarm=True):
            if prewarm:
                CPU_VERIFIER.prewarm_many(
                    [(f.tx.sourceAccount.account_id.key_bytes,
                      f.signatures[0].signature, f.contents_hash())
                     for f in frames])
            header = lm.root.get_header()
            ts = TxSetFrame(TESTING_NETWORK_ID, lm.lcl_hash, frames)
            value = StellarValue(
                txSetHash=ts.get_contents_hash(),
                closeTime=header.scpValue.closeTime + 5,
                upgrades=[], ext=StellarValueExt(0, None))
            lm.close_ledger(
                LedgerCloseData(header.ledgerSeq + 1, ts, value))

        for lo in range(0, 2 * n_pairs, 100):
            close([root.tx([root.op_create_account(a.account_id, 10 ** 10)
                            for a in accs[lo:lo + 100]])], prewarm=False)
        return lm, accs, close

    envs = {m: mk(m) for m in ("serial", "parallel")}
    walls = {"serial": [], "parallel": []}
    for rnd in range(rounds):
        for mode in ("serial", "parallel"):
            lm, accs, close = envs[mode]
            frames = []
            for k in range(n_pairs):
                a, b = accs[2 * k], accs[2 * k + 1]
                frames.append(a.tx(
                    [a.op_payment(b.account_id, 100 + rnd)] * ops_per_tx))
                frames.append(b.tx(
                    [b.op_payment(a.account_id, 50 + rnd)] * ops_per_tx))
            close(frames)
            walls[mode].append(
                lm.apply_stats.clusters["last_apply_ms"])
    # ambient sandbox noise only ever ADDS time; the per-mode floor
    # over interleaved rounds is the noise-free cost estimate (the
    # bench's established best-of-repeats rationale)
    ser = min(walls["serial"])
    par = min(walls["parallel"])
    pstats = envs["parallel"][0].apply_stats.clusters
    return {
        "n_pairs": n_pairs, "ops_per_tx": ops_per_tx, "rounds": rounds,
        "serial_apply_ms": round(ser, 3),
        "parallel_apply_ms": round(par, 3),
        "serial_apply_ms_median": round(
            statistics.median(walls["serial"]), 3),
        "parallel_apply_ms_median": round(
            statistics.median(walls["parallel"]), 3),
        "serial_apply_ms_all": [round(x, 3) for x in walls["serial"]],
        "parallel_apply_ms_all": [round(x, 3) for x in walls["parallel"]],
        "parallel_apply_speedup": round(ser / par, 3) if par else 0.0,
        "clusters": pstats["last_count"],
        "workers": pstats["last_workers"],
        "parallel_closes": pstats["parallel_closes"],
    }


def replay_full_main(argv) -> int:
    """`bench.py --replay-full [--record] [--history PATH]
    [--tolerance T] [--out FILE]`: the full-coverage apply leg
    (ISSUE 13). Three measurements, each in a forced-CPU child /
    inline:

    - standard-mix replay (platform `cpu-stdmix`): conflict-light pairs
      + all 14 op types + fee bumps + muxed. ASSERTS zero
      `ledger.apply.native-bail.*` and zero Python-path closes, and
      that per-op ms records exist for the newly-covered op types.
    - legacy multisig replay (platform `cpu-apply-native`,
      history-comparable with BENCH_r08).
    - the parallel-close gate leg (platform `cpu-parallel-close`):
      engine apply-wall serial vs parallel on a conflict-light txset.
    """
    import argparse
    bc = _bench_compare_mod()
    ap = argparse.ArgumentParser(prog="bench.py --replay-full")
    ap.add_argument("--replay-full", action="store_true")
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--history",
                    default=os.path.join(_REPO, "bench", "history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--out", help="also write the block to this file")
    args = ap.parse_args(argv)

    errors = {}
    out = {"metric": "replay_full", "unit": "ledgers/s", "value": 0.0}
    legs = {}
    for label, mx in (("standard", "standard"), ("multisig", "multisig")):
        proc = _spawn_replay(_cpu_env(), "cpu", mix=mx)
        deadline = time.time() + 600
        while time.time() < deadline and proc.poll() is None:
            time.sleep(1.0)
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
            errors["replay_" + label] = "killed at deadline"
            continue
        rep, err = _harvest(proc, "REPLAY_JSON")
        if err:
            errors["replay_" + label] = err
        else:
            legs[label] = rep
    std = legs.get("standard")
    if std is not None:
        out["value"] = std.get("ledgers_per_sec", 0.0)
        # the zero-bail + native-only acceptance (ISSUE 13): real
        # failures, not history comparisons
        if std.get("native_bails"):
            errors["native_bails"] = std["native_bails"]
        if std.get("python_closes"):
            errors["python_closes"] = std["python_closes"]
        per_op = std.get("apply_breakdown", {}).get("per_op_ms", {})
        missing = [op for op in
                   ("change-trust", "allow-trust", "manage-data",
                    "bump-sequence", "account-merge",
                    "manage-sell-offer", "manage-buy-offer",
                    "path-payment-strict-receive",
                    "path-payment-strict-send")
                   if op not in per_op]
        if missing:
            errors["missing_op_coverage"] = missing
        # the zero-SQL acceptance (ISSUE 14): with BucketDB routing the
        # standard mix must close with NO apply-path SQL point lookups
        # (bulk order-book scans are the write-behind index's job and
        # are counted separately)
        sql_lookups = std.get("apply_breakdown", {}) \
            .get("state_reads", {}).get("lookups", {})
        if sql_lookups:
            errors["sql_point_lookups"] = sql_lookups
    try:
        pcb = parallel_close_bench()
        out["parallel_close"] = pcb
    except Exception as e:   # noqa: BLE001 - recorded, not swallowed
        errors["parallel_close"] = repr(e)[:400]
        pcb = None
    out["replay"] = legs

    src = "bench.py --replay-full"
    records = []
    if std is not None and not errors:
        records.extend([
            bc.make_record("replay_ledgers_per_sec", "ledgers/s",
                           std["ledgers_per_sec"], "cpu-stdmix",
                           "higher", src),
            bc.make_record("replay_txs_per_sec", "txs/s",
                           std["txs_per_sec"], "cpu-stdmix", "higher",
                           src),
            bc.make_record("replay_wall_s", "s", std["wall_s"],
                           "cpu-stdmix", "lower", src),
            bc.make_record("native_bail_total", "count",
                           sum(std.get("native_bails", {}).values()),
                           "cpu-stdmix", "lower", src),
        ])
        records.extend(bc.apply_breakdown_records(
            std.get("apply_breakdown"), "cpu-stdmix", src))
    ms = legs.get("multisig")
    if ms is not None:
        records.extend([
            bc.make_record("replay_ledgers_per_sec", "ledgers/s",
                           ms["ledgers_per_sec"], "cpu-apply-native",
                           "higher", src),
            bc.make_record("replay_txs_per_sec", "txs/s",
                           ms["txs_per_sec"], "cpu-apply-native",
                           "higher", src),
        ])
    if pcb is not None:
        records.extend([
            bc.make_record("parallel_apply_speedup", "x",
                           pcb["parallel_apply_speedup"],
                           "cpu-parallel-close", "higher", src),
            bc.make_record("parallel_apply_ms", "ms",
                           pcb["parallel_apply_ms"],
                           "cpu-parallel-close", "lower", src),
            bc.make_record("serial_apply_ms", "ms",
                           pcb["serial_apply_ms"],
                           "cpu-parallel-close", "lower", src),
        ])
    out["records"] = records
    history = bc.load_history(args.history)
    report = bc.compare(records, history, tolerance=args.tolerance)
    if args.record and not errors:
        commit = _git_commit()
        now = int(time.time())
        for rec in records:
            if rec.get("at_unix") is None:
                rec["at_unix"] = now
            if rec.get("commit") is None:
                rec["commit"] = commit
        report["recorded"] = bc.append_history(args.history, records)
    out["compare"] = report
    if errors:
        out["errors"] = errors
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1, sort_keys=True))
    return 1 if (errors or report["regressions"]) else 0



def main() -> int:
    """`python bench.py`: see the module docstring. Exits non-zero, with
    the child's error on stderr and no JSON line, unless the chip and
    every device leg answered."""
    t_start = time.time()
    # the ONE process that initialises the TPU backend; it inherits the
    # environment as it is, and has exited before anything else starts
    proc = _spawn("import bench, json; "
                  "print('BENCH_JSON ' + json.dumps("
                  "bench.device_full_bench()))")
    res, err = _harvest(proc)
    if err:
        print("bench.py: device child failed: %s" % err, file=sys.stderr)
        return 1

    cpu = cpu_baseline_rate()
    rep_tpu = res.pop("replay_tpu")
    # the cpu DENOMINATOR of the replay ratio, in a forced-CPU child run
    # SEQUENTIALLY (nothing else live): concurrent children contend for
    # the same cores and contaminate the timing
    rep_cpu, err = _harvest(_spawn_replay(_cpu_env(), "cpu"), "REPLAY_JSON")
    if err:
        print("bench.py: cpu replay child failed: %s" % err,
              file=sys.stderr)
        return 1

    out = {
        "metric": "ed25519_verifies_per_sec_per_chip",
        "unit": "sigs/s",
        "at_unix": int(t_start),
        "commit": _git_commit(),
        "cpu_openssl_baseline_sigs_per_sec": round(cpu, 1),
        "value": round(res.pop("rate"), 1),
    }
    out["vs_baseline"] = round(out["value"] / cpu, 3)
    out.update(res)     # platform, device_kind, count, compile/warmup legs
    out["replay"] = {"cpu": rep_cpu, "tpu": rep_tpu}
    out["replay_speedup"] = round(
        rep_tpu["ledgers_per_sec"] / rep_cpu["ledgers_per_sec"], 3)
    # crypto-subsystem drain ratio (whole-checkpoint batch path): same
    # replay, time inside the signature drain only
    out["replay_crypto_speedup"] = round(
        rep_cpu["crypto_s"] / rep_tpu["crypto_s"], 3)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    if "--chaos" in sys.argv:
        # chaos smoke leg: close-latency p95 with faults on vs off; does
        # not touch jax or the chip
        print(json.dumps(chaos_smoke()))
    elif "--fleet" in sys.argv:
        # multi-node leg: 3-node consensus with merged timelines; emits
        # the `fleet` block (slot-latency p50/p95, externalize skew);
        # does not touch jax or the chip
        print(json.dumps(fleet_bench()))
    elif "--fleet-scale" in sys.argv:
        # N-vs-cost scaling leg (ISSUE 19): 10/25/50-node sims under a
        # three-region latency matrix; per-node RSS, externalize skew
        # p95, envelopes per slot, gated against bench/history.jsonl;
        # does not touch jax or the chip
        sys.exit(fleet_scale_main(sys.argv[1:]))
    elif "--fleet-verify" in sys.argv:
        # multi-device verify leg (ISSUE 11): sharded drains on forced
        # virtual-CPU fleets, gated against bench/history.jsonl; spawns
        # forced-CPU children only — never touches the chip
        sys.exit(fleet_verify_main(sys.argv[1:]))
    elif "--hash" in sys.argv:
        # batched-hashing leg (ISSUE 12): kernel throughput per bucket
        # shape in a forced-CPU child + inline checkpoint/light-client
        # leg + CPU replay phase evidence; gated against
        # bench/history.jsonl; never touches the chip
        sys.exit(hash_main(sys.argv[1:]))
    elif "--replay-full" in sys.argv:
        # full-coverage apply leg (ISSUE 13): standard-mix zero-bail
        # replay + legacy multisig replay + the parallel-close gate;
        # forced-CPU children only — never touches the chip
        sys.exit(replay_full_main(sys.argv[1:]))
    elif "--bucketdb" in sys.argv:
        # million-account BucketDB leg (ISSUE 14): close-latency
        # flatness from 10^4 to 10^6 seeded accounts over bucket-backed
        # reads, surge prefetch hit-rate, bloom FP rate, zero-SQL gate;
        # pure CPU/IO — does not touch jax or the chip
        sys.exit(bucketdb_main(sys.argv[1:]))
    elif "--scenario" in sys.argv:
        # scenario lab (ISSUE 8): churn / flood / partition / surge
        # robustness scenarios emitting fleet bench blocks gated against
        # bench/history.jsonl; does not touch jax or the chip
        sys.exit(scenario_main(sys.argv[1:]))
    elif "--compare" in sys.argv:
        # perf-regression gate against bench/history.jsonl; does not
        # touch jax or the chip
        sys.exit(compare_main(sys.argv[1:]))
    else:
        sys.exit(main())
