"""Deployment driver `watcher`: `topologies.core(n, threshold, OVER_PEERS,
watchers=1)` in one process. Node 0 is the watcher (NODE_IS_VALIDATOR
off, built first, linked to every validator, following their quorum set)
on the device backend: the node under test. The validators verify on the
cpu backend and are the independent reference. Every client has a home
node, `account % nodes`, as every Horizon has its own core node: a
quarter of the payments are submitted to the watcher, the rest reach it
by flood.

The window, the drain, the clocks and the comparison are the validator
driver's (`validator.Deployment`), which is not edited: "applied" still
means applied on every node, read from node 0's history.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from ..harness.runner import RunError
from . import catchup, validator
from .validator import START_BALANCE

_ORIGIN_METERS = {"admissions_flood": "herder.tx.received.flood",
                  "admissions_local": "herder.tx.received.local"}


class Deployment(validator.Deployment):
    # -- set-up --------------------------------------------------------------
    def setup(self) -> dict:
        """The validator driver's set-up over the watcher topology."""
        from stellar_core_tpu.crypto import keys
        from stellar_core_tpu.crypto.keys import SecretKey
        from stellar_core_tpu.simulation import topologies
        from stellar_core_tpu.simulation.simulation import Simulation
        from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
        c = self.config
        keys.flush_verify_cache()
        order = []

        def tweak(cfg) -> None:
            order.append(cfg)
            cfg.SIG_VERIFY_BACKEND = self.backend if len(order) == 1 \
                else c["backend_reference"]
            if len(order) == 1:
                # what a peer verified is not verified for the watcher
                cfg.VERIFY_CACHE_SCOPE = c["verify_cache_scope"]
            cfg.DATABASE = c["database"]
            cfg.INVARIANT_CHECKS = list(c["invariant_checks"])
            cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = int(c["max_tx_set_ops"])
            cfg.ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING = False
            cfg.EXPECTED_LEDGER_CLOSE_TIME = float(c["ledger_close_time_s"])

        info = {}
        t0 = time.perf_counter()
        try:
            sim = topologies.core(
                int(c["validators"]), int(c["threshold"]),
                mode=Simulation.OVER_PEERS, cfg_tweak=tweak,
                watchers=int(c["watchers"]))
        except TypeError as e:
            # a program from before the watcher deployment: say so now,
            # not after a wait for a close that cannot come
            raise RunError("this program cannot build a core with "
                           "watchers attached: %s" % e)
        self.sim = sim
        self.apps = apps = [n.app for n in sim.nodes.values()]
        self.node0 = node0 = apps[0]
        if node0.config.NODE_IS_VALIDATOR or \
                not all(a.config.NODE_IS_VALIDATOR for a in apps[1:]):
            raise RunError("node 0 is not the one watcher of the topology")
        for i, app in enumerate(apps):
            node_dir = os.path.join(self.workdir, "node-%d" % i)
            os.makedirs(node_dir, exist_ok=True)
            if i == 0:
                if self.node_hook is not None:
                    self.node_hook(app)
                with open(os.path.join(node_dir, "warmup_buckets.json"),
                          "w") as fh:
                    json.dump({"version": 1, "buckets":
                               self.workload["warm_buckets"]}, fh)
            app.enable_buckets(os.path.join(node_dir, "buckets"))
        if self.trace:
            node0.tracer.enable(capacity=1 << 18)
        sim.start_all_nodes()
        warm = getattr(node0.sig_verifier, "warmup", None)
        if warm is not None:
            warm(wait=True)
        info["boot_warm_s"] = time.perf_counter() - t0
        cockpit = node0.command_handler.cmd_verifier({})
        info["warmup"] = {b: (w["seconds"], w["cache"]) for b, w in
                          cockpit.get("warmup", {}).get("buckets",
                                                        {}).items()}
        t0 = time.perf_counter()
        self._crank_until(lambda: self._lcl_min() >= 2,
                          "the first consensus close")
        # the accounts are created through the watcher, as a Horizon
        # would: each create transaction floods to the validators
        ledger = AppLedgerAdapter(node0)
        root = ledger.root_account()
        sks = [SecretKey.from_seed(hashlib.sha256(
            b"bench-watcher/%d/%d" % (self.seed, i)).digest())
            for i in range(self.n_accounts)]
        root_seq = ledger.seq_num(root.account_id)
        self.seq = []
        for lo in range(0, len(sks), 100):
            root_seq += 1
            chunk = sks[lo:lo + 100]
            status = node0.submit_transaction(root.tx(
                [root.op_create_account(sk.public_key, START_BALANCE)
                 for sk in chunk], seq=root_seq))
            if status != 0:
                raise RuntimeError("create accounts refused: %r" % status)
            self._crank_until(
                lambda: ledger.seq_num(root.account_id) >= root_seq,
                "account creation")
            created = ledger.seq_num(chunk[0].public_key)
            self.seq.extend([created] * len(chunk))
        self.accounts = [TestAccount(ledger, sk) for sk in sks]
        self.balance = [START_BALANCE] * len(sks)
        tip = max(a.ledger_manager.last_closed_ledger_num() for a in apps)
        self._crank_until(lambda: self._lcl_min() >= tip,
                          "every node to hold the accounts")
        info["accounts_s"] = time.perf_counter() - t0
        # from here the clocks follow the wall clock, with the room of
        # close time over clock whole (validator.Deployment._crank_once)
        t0 = time.perf_counter()
        self.virtual_t0 = max(
            [a.clock.now() for a in apps] +
            [a.ledger_manager.lcl_header.scpValue.closeTime + 1.0
             for a in apps])
        self.virtual_elapsed = 0.0
        self.wall_t0 = self.wall_last = time.perf_counter()
        self._crank_until(lambda: self._lcl_min() >= tip + 2,
                          "two closes at the deployment's cadence", 60.0)
        info["cadence_s"] = time.perf_counter() - t0
        self.lcl0_seen = node0.ledger_manager.last_closed_ledger_num()
        return info

    # -- the measured window -------------------------------------------------
    def _submit(self, req, now_rel: float) -> None:
        """The validator driver's submission, to the request's home
        node: `node0` stands for "the node submitted to" only there."""
        watcher = self.node0
        self.node0 = self.apps[req.account % len(self.apps)]
        try:
            super()._submit(req, now_rel)
        finally:
            self.node0 = watcher

    # -- results -------------------------------------------------------------
    def _counters(self) -> dict:
        out = super()._counters()
        m = self.node0.metrics.to_json(prefix="herder.tx.received.")
        for key, meter in _ORIGIN_METERS.items():
            out[key] = m.get(meter, {}).get("count", 0)
        return out

    def counts(self) -> dict:
        out = super().counts()
        c0, c1 = self.counters0, self.counters1
        for key in _ORIGIN_METERS:
            out[key] = c1[key] - c0[key]
        out["admissions"] = out["admissions_flood"] + \
            out["admissions_local"]
        # the watcher sends none, so the validator driver's sum of
        # emitted and received is what it received
        out["scp_envelopes_received"] = c1["scp_receive"] - c0["scp_receive"]
        return out

    def compare(self) -> dict:
        """The validator driver's numbers; that the watcher sent no
        envelope; and the catchup driver's negative control on the
        watcher's served verifier stack. A watcher's answers show in no
        other node's state: a verifier that leaves half of a batch out
        still closes the same ledgers, since what its quorum sends it is
        valid, so its verdicts are held against `cryptography`'s on one
        batch of the timed shape with one signature in eight corrupted."""
        out = super().compare()
        m = self.node0.metrics.to_json(prefix="scp.envelope.emit")
        out["watcher_envelopes_emitted"] = {
            "value": m.get("scp.envelope.emit", {}).get("count", 0),
            "limit": 0}
        self.last_node = self.node0     # what _negative_control reads
        mismatches, verdicts = catchup.Deployment._negative_control(self)
        out["verdict_mismatches"] = {"value": mismatches, "limit": 0}
        out["verdicts_compared"] = {
            "value": verdicts, "need": "min",
            "limit": int(self.workload["negative_control_lanes"])}
        return out

    def release(self) -> None:
        if hasattr(self, "sim"):    # set-up may have failed before it
            super().release()
