"""Deployment `validator_archive`: the `validator` deployment's
network (`topologies.core(n, threshold, OVER_PEERS)`, node 0 on the
device backend, the others on the cpu backend as the independent
reference, the clocks, the cranking and the admission dispatch), over a
state on disk at size, with node 0 a full validator: it publishes every
checkpoint to a local file archive.

Set-up: a cpu-backend publisher (traffic/archive_live.py, node 0 before
its network formed) loads the configuration's state onto its own disk
and publishes the first checkpoint, the snapshot of its disk is copied
into one directory a node, and the three validators start from their
copies by `Application.start()` with FORCE_SCP and resume consensus at
the ledger after the checkpoint. Node 0's HISTORY is the archive the
publisher wrote. Set-up runs the network on, with empty ledgers, to the
workload's `opens_before_checkpoint` ledgers before the second
checkpoint, so that the window crosses exactly one checkpoint boundary
and holds one publish.

The window submits `archive_live.FreshSources`' payments to node 0. The
plain references: the generator's model of every account a payment
touched, held against both stores of every node; the archive, checked
by `traffic/archive_check.py` against node 0's headers and every node's
header hashes. `validator.Deployment` is not edited.
"""

from __future__ import annotations

import gc
import json
import os
import resource
import time

from ..harness import annotate
from ..harness.runner import RunError
from ..traffic import archive_check
from ..traffic.archive_live import FreshSources, SnapshotHistory
from . import validator

PASSPHRASE = "(sct) simulation network"


class Deployment(validator.Deployment):
    def __init__(self, config: dict, workload: dict, seed: int,
                 workdir: str, trace: bool, node_hook=None) -> None:
        from stellar_core_tpu.history import history_manager
        if not hasattr(history_manager, "PUBLISH_METERS"):
            # say so now, not after a set-up whose publish cannot be read
            raise RunError("this program keeps no publish meters "
                           "(history.publish.bytes/.files/.buckets) nor "
                           "history.publish span: the cell cannot be read "
                           "on it")
        super().__init__(config, workload, seed, workdir, trace,
                         node_hook=node_hook)
        from stellar_core_tpu.simulation import topologies
        self.keys = topologies._keys(int(config["validators"]), b"core")
        self.hist = SnapshotHistory(config, seed, workdir, self.keys[0],
                                    PASSPHRASE)
        self.warm_node = None

    # -- set-up --------------------------------------------------------------
    def _start_warm_node(self):
        """A node on the backend under test whose warm-up compiles the
        device shapes on its thread while the publisher, on this one,
        loads the state (as the catchup deployments' first node); node 0's
        own warm-up then finds them in memory."""
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.main.config import Config
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        cfg = Config.test_config(9, self.backend)
        cfg.DATABASE = "sqlite3://:memory:"
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        if self.node_hook is not None:
            self.node_hook(app)
        node_dir = os.path.join(self.workdir, "warm")
        os.makedirs(node_dir)
        self._warm_plan(node_dir)
        app.enable_buckets(os.path.join(node_dir, "buckets"))
        app.start()
        return app

    def _warm_plan(self, node_dir: str) -> None:
        with open(os.path.join(node_dir, "warmup_buckets.json"), "w") as fh:
            json.dump({"version": 1,
                       "buckets": self.workload["warm_buckets"]}, fh)

    def setup(self) -> dict:
        from stellar_core_tpu.crypto import keys
        from stellar_core_tpu.history.archive import HistoryArchive
        from stellar_core_tpu.simulation import topologies
        from stellar_core_tpu.simulation.simulation import Simulation
        from stellar_core_tpu.testing import AppLedgerAdapter
        c, hist = self.config, self.hist
        keys.flush_verify_cache()
        info = {}
        t0 = time.perf_counter()
        self.warm_node = self._start_warm_node()
        hist.publish()
        hist.close()
        info["load"] = hist.load_info
        info["publish_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.warm_node.sig_verifier.warmup(wait=True)
        self.warm_node.stop()
        self.warm_node = None
        info["warmup_wait_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        n = int(c["validators"])
        dirs = [hist.node_dir(i + 1) for i in range(n)]
        for d in dirs:
            hist.clone_snapshot(d)
        info["clone_s"] = time.perf_counter() - t0
        archive = HistoryArchive.local_dir("archive", hist.archive_root)
        order = []

        def tweak(cfg) -> None:
            i = len(order)
            order.append(cfg)
            cfg.SIG_VERIFY_BACKEND = self.backend if i == 0 \
                else c["backend_reference"]
            cfg.DATABASE = c["database"].replace("<node dir>", dirs[i])
            cfg.INVARIANT_CHECKS = list(c["invariant_checks"])
            cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = int(c["max_tx_set_ops"])
            cfg.CHECKPOINT_FREQUENCY = hist.freq
            cfg.ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING = False
            cfg.EXPECTED_LEDGER_CLOSE_TIME = float(c["ledger_close_time_s"])
            if i == 0:      # the full validator: it publishes
                cfg.HISTORY = {"archive": {"get": archive.get_tmpl,
                                           "put": archive.put_tmpl,
                                           "mkdir": archive.mkdir_tmpl}}

        t0 = time.perf_counter()
        sim = topologies.core(n, int(c["threshold"]), passphrase=PASSPHRASE,
                              mode=Simulation.OVER_PEERS, cfg_tweak=tweak)
        self.sim = sim
        self.apps = apps = [nd.app for nd in sim.nodes.values()]
        self.node0 = node0 = apps[0]
        if node0.config.NODE_SEED.public_key != self.keys[0].public_key:
            raise RuntimeError("node 0 is not the publisher's key")
        if self.node_hook is not None:
            self.node_hook(node0)
        self._warm_plan(dirs[0])
        for app, d in zip(apps, dirs):
            app.enable_buckets(os.path.join(d, "buckets"))
            # every clock past the snapshot's last close time
            app.clock.set_virtual_time(hist.pub_time + 1.0)
        if self.trace:
            node0.tracer.enable(capacity=1 << 18)
        sim.start_all_nodes()
        self.restored_at = [a.ledger_manager.last_closed_ledger_num()
                            for a in apps]
        self.backed_at_start = [a.ledger_manager.root.bucket_backed()
                                for a in apps]
        node0.sig_verifier.warmup(wait=True)
        info["restart_s"] = time.perf_counter() - t0
        cockpit = node0.command_handler.cmd_verifier({})
        info["warmup"] = {b: (w["seconds"], w["cache"]) for b, w in
                          cockpit.get("warmup", {}).get("buckets",
                                                        {}).items()}
        # empty ledgers, the clocks in set-up mode, to two closes short
        # of where the window opens
        t0 = time.perf_counter()
        opens_at = 2 * hist.freq - 1 - int(
            self.workload["opens_before_checkpoint"])
        self._crank_until(lambda: self._lcl_min() >= opens_at - 2,
                          "the restarted network to reach ledger %d"
                          % (opens_at - 2), 300.0)
        info["resume_s"] = time.perf_counter() - t0
        self.ledger = AppLedgerAdapter(node0)
        self.accounts = []      # the model below holds every account
        # what set-up leaves resident (three nodes' indexes, the million
        # ids) goes out of the collector's reach, as in the other deployments
        gc.collect()
        gc.freeze()
        # from here the clocks follow the wall clock, with the room of
        # close time over clock whole (validator.Deployment._crank_once)
        t0 = time.perf_counter()
        self.virtual_t0 = max(
            [a.clock.now() for a in apps] +
            [a.ledger_manager.lcl_header.scpValue.closeTime + 1.0
             for a in apps])
        self.virtual_elapsed = 0.0
        self.wall_t0 = self.wall_last = time.perf_counter()
        self._crank_until(lambda: self._lcl_min() >= opens_at,
                          "two closes at the deployment's cadence", 60.0)
        info["cadence_s"] = time.perf_counter() - t0
        self.lcl0_seen = node0.ledger_manager.last_closed_ledger_num()
        info["lcl_at_open"] = self.lcl0_seen
        return info

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tick) -> None:
        """The validator deployment's window over FreshSources."""
        hist = self.hist
        self.gen = FreshSources(
            self.workload["traffic"], self.seed,
            [sk.public_key.key_bytes for sk in hist.signer_sks], hist.ids,
            seconds)
        batch = int(self.workload["traffic"]["submit_batch"])
        self.seconds = seconds
        self.slots0 = self._lcl_min()
        self.counters0 = self._counters()
        self.t_begin = time.perf_counter()
        cut = False
        while not cut:
            now = time.perf_counter()
            rel = now - self.t_begin
            if tick(now) or rel >= seconds:
                break
            for req in self.gen.take_due(rel, batch):
                now = time.perf_counter()
                cut = cut or tick(now)
                self._submit(req, now - self.t_begin)
            self._crank_once()
            self._note_closes()
        self.t_end = time.perf_counter()
        self.slots1 = self._lcl_min()
        self.counters1 = self._counters()
        self.applied_in_window = sum(
            n for n, at in self.applied_by_ledger.values()
            if at <= self.t_end - self.t_begin)

    def _submit(self, req, now_rel: float) -> None:
        from stellar_core_tpu.testing import TestAccount
        from stellar_core_tpu.xdr import PublicKey
        hist = self.hist
        sk, extra = hist.signer_sks[req.account], hist.extra_sks[req.account]
        src = hist._account(sk.public_key.key_bytes)
        dest = hist.ids[req.dest]
        snd = TestAccount(self.ledger, sk)
        frame = snd.tx([snd.op_payment(PublicKey.ed25519(dest),
                                       req.amount)],
                       seq=src["seq"] + 1, extra_signers=[extra])
        if req.corrupt:
            sig = frame.envelope.value.signatures[0]
            sig.signature = bytes([sig.signature[0] ^ 1]) + sig.signature[1:]
        t0 = time.perf_counter()
        with annotate.span("bench.submit"):
            status = self.node0.submit_transaction(frame)
        t1 = time.perf_counter()
        self.admit_s += t1 - t0
        self.admit_n += 1
        self.late_max = max(self.late_max, now_rel - req.due)
        if status != 0:
            # the right answer to a corrupted signature is a refusal for
            # its authorization, and to any other no refusal at all
            req.refused = not req.corrupt or _bad_auth(frame.result)
            req.done = t1 - self.t_begin
            self.gen.replied(req, req.done)
        else:
            req.refused = False
            req.txid = frame.contents_hash().hex()
            self.inflight[req.txid] = req
            src["seq"] += 1
            src["balance"] -= req.amount + validator.BASE_FEE
            hist._account(dest)["balance"] += req.amount
            hist.fee_pool += validator.BASE_FEE

    # -- results -------------------------------------------------------------
    def _counters(self) -> dict:
        out = super()._counters()
        node0 = self.node0
        m = node0.metrics.to_json(prefix="history.publish")
        for k in ("success", "bytes", "files", "buckets"):
            out["publish_" + k] = m.get("history.publish." + k,
                                        {}).get("count", 0)
        cold = node0.ledger_manager.apply_stats.to_json()[
            "state_reads"]["cold_reads"]
        for phase in ("prefetch", "apply", "admit"):
            out["cold_" + phase] = sum(cold.get(phase, {}).values())
        return out

    def counts(self) -> dict:
        out = super().counts()
        c0, c1 = self.counters0, self.counters1
        for k in ("publish_success", "publish_bytes", "publish_files",
                  "publish_buckets", "cold_prefetch", "cold_apply",
                  "cold_admit"):
            out[k] = c1[k] - c0[k]
        out["publishes"] = out.pop("publish_success")
        out["cold_close"] = out["cold_prefetch"] + out["cold_apply"]
        out["sources_used"] = self.gen.used
        # what is real in size is on the host: the nodes' disks, the
        # archive, the process
        out["disk_bytes"] = {
            name: _du(d) for name, d in
            [("node-%d" % i, self.hist.node_dir(i + 1))
             for i in range(len(self.apps))] +
            [("archive", self.hist.archive_root)]}
        out["peak_rss_bytes"] = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024
        return out

    def compare(self) -> dict:
        """The validator deployment's numbers (header chains, the corrupted
        refused and the valid admitted, every admitted transaction
        applied, the device path); then every account a payment touched
        and the fee pool against the model, on each node, in both of its
        stores; the archive against the plain checker; the publishes;
        and what a restart and its closes have to report."""
        from stellar_core_tpu.crypto.strkey import encode_public_key
        from stellar_core_tpu.xdr import LedgerEntry, LedgerKey, PublicKey
        out = super().compare()
        hist = self.hist
        state = store = checked = 0
        for app in self.apps:
            bdb = app.bucket_manager.bucketdb
            for key, m in hist.model.items():
                row = app.database.execute(
                    "SELECT entry FROM accounts WHERE accountid=?",
                    (encode_public_key(key),)).fetchone()
                served, blob = bdb.lookup(
                    LedgerKey.account(PublicKey.ed25519(key)).to_xdr())
                checked += 1
                if row is None or not served or blob != row[0]:
                    store += 1
                    continue
                acc = LedgerEntry.from_xdr(blob).data.value
                state += acc.balance != m["balance"] or \
                    acc.seqNum != m["seq"]
            checked += 1
            state += app.ledger_manager.lcl_header.feePool != hist.fee_pool
        out["state_mismatches"]["value"] += state
        out["state_checked"] = {"value": checked,
                                "limit": len(self.apps) *
                                (len(hist.model) + 1), "need": "min"}
        out["store_mismatches"] = {"value": store, "limit": 0}
        node0 = self.node0
        chains = [dict(a.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
            for a in self.apps]
        headers = dict(node0.database.execute(
            "SELECT ledgerseq, data FROM ledgerheaders").fetchall())
        got = archive_check.check(
            hist.archive_root, hist.freq,
            {s: bytes(d) for s, d in headers.items()}, chains,
            self.keys[0].public_key.key_bytes)
        out["archive_mismatches"] = {"value": got["mismatches"],
                                     "limit": 0, "detail": got["detail"]}
        lcl0 = node0.ledger_manager.last_closed_ledger_num()
        closed = (lcl0 + 1) // hist.freq
        out["checkpoints_checked"] = {"value": got["checkpoints"],
                                      "limit": max(closed, 2),
                                      "need": "min"}
        out["checkpoints_unpublished"] = {
            "value": max(0, closed - got["checkpoints"]) +
            len(node0.history_manager.publish_queue()), "limit": 0}
        n = self.counters1["publish_success"] - \
            self.counters0["publish_success"]
        out["publishes_in_window"] = {"value": n, "limit": 1,
                                      "need": "min"}
        out["publishes_past_one"] = {"value": max(0, n - 1), "limit": 0}
        out["restarts_off_snapshot"] = {
            "value": sum(r != hist.lcl_at_snapshot
                         for r in self.restored_at), "limit": 0}
        out["bucketdb_detached"] = {
            "value": sum(not (b and a.ledger_manager.root.bucket_backed())
                         for a, b in zip(self.apps, self.backed_at_start)),
            "limit": 0}
        fallbacks = python = bails = 0
        for app in self.apps:
            stats = app.ledger_manager.apply_stats
            cold = stats.to_json()["state_reads"]["cold_reads"]
            fallbacks += app.bucket_manager.bucketdb.stats.sql_fallbacks + \
                sum(by["sql"] for by in cold.values())
            python += stats.closes.get("python", 0)
            bails += sum(stats.bails.values())
        out["sql_fallbacks"] = {"value": fallbacks, "limit": 0}
        out["python_closes"] = {"value": python, "limit": 0}
        out["native_bails"] = {"value": bails, "limit": 0}
        return out

    def release(self) -> None:
        if self.warm_node is not None:
            self.warm_node.stop()
        if hasattr(self, "sim"):
            super().release()
        self.hist.close()
        gc.unfreeze()


def _bad_auth(result) -> bool:
    """Refused for its authorization: txBAD_AUTH; or, where the other
    signature meets the source's low threshold (0) and the payment's
    medium one (2) is what fails, txFAILED with opBAD_AUTH."""
    from stellar_core_tpu.xdr import (
        OperationResultCode, TransactionResultCode,
    )
    if result.code == TransactionResultCode.txBAD_AUTH:
        return True
    return result.code == TransactionResultCode.txFAILED and \
        [op.disc for op in result.op_results] == \
        [OperationResultCode.opBAD_AUTH]


def _du(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(path) for f in files)
