"""Deployment driver `catchup`: a fresh node on the device backend runs
complete catchup against a local file archive that a cpu-backend
publisher wrote in set-up.

The window replays the archive with a fresh node each time (the verify
cache flushed between replays, node construction inside the window) and
counts ledgers as they close, so a partial last replay counts for what
it closed.
"""

from __future__ import annotations

import json
import os
import time

from ..harness import annotate
from ..harness.device_path import device_path_violations
from ..traffic.history import PublishedHistory


class Deployment:
    def __init__(self, config: dict, workload: dict, seed: int,
                 workdir: str, trace: bool, node_hook=None) -> None:
        self.config, self.workload = config, workload
        self.seed, self.workdir, self.trace = seed, workdir, trace
        self.node_hook = node_hook      # tests only: tiny CPU buckets
        self.backend = config["backend_under_test"]
        self.hist = PublishedHistory(config, workload["traffic"], seed,
                                     workdir)
        self.n_nodes = 0
        self.first = None       # the node that warmed the shapes
        self.replays = []       # one record per replay started
        self.last_node = None   # newest node whose replay completed
        self.ledgers_closed = 0
        self.current = None     # the node replaying now
        self.done_counters = {"sigs": 0, "dispatches": 0}
        self.warm_runs = 0      # kernel runs on zeros by nodes' warm-ups
        self.cut = False        # the window was closed by the traced slice

    # -- nodes ---------------------------------------------------------------
    def _new_node(self):
        from stellar_core_tpu.main.application import Application
        from stellar_core_tpu.util.timer import ClockMode, VirtualClock
        self.n_nodes += 1
        n = self.n_nodes
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                          self.hist.node_config(n, self.backend))
        if self.node_hook is not None:
            self.node_hook(app)
        node_dir = self.hist.node_dir(n)
        # the node's own restart state: warm only this cell's shapes
        with open(os.path.join(node_dir, "warmup_buckets.json"), "w") as fh:
            json.dump({"version": 1,
                       "buckets": self.workload["warm_buckets"]}, fh)
        app.enable_buckets(os.path.join(node_dir, "buckets"))
        if self.trace:
            app.tracer.enable(capacity=1 << 18)
        app.start()
        return app

    def setup(self) -> dict:
        """Start the first node under test before publishing: its
        warm-up thread loads the verify shapes while the publisher, on
        this thread, writes the archive."""
        info = {}
        t0 = time.perf_counter()
        self.first = self._new_node()
        self.hist.publish()
        info["publish_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = getattr(self.first.sig_verifier, "warmup", None)
        if warm is not None:
            warm(wait=True)
        info["warmup_wait_s"] = time.perf_counter() - t0
        cockpit = self.first.command_handler.cmd_verifier({})
        info["warmup"] = {b: (w["seconds"], w["cache"]) for b, w in
                          cockpit.get("warmup", {}).get("buckets",
                                                        {}).items()}
        info["tip"] = self.hist.tip
        info["dense_ledgers"] = self.hist.dense
        info["sigs_issued"] = self.hist.sigs_issued
        return info

    # -- the measured window -------------------------------------------------
    def _replay(self, app, deadline: float, tick) -> None:
        from stellar_core_tpu.catchup.catchup_work import (
            CatchupConfiguration,
        )
        from stellar_core_tpu.crypto import keys
        from stellar_core_tpu.work.basic_work import State
        keys.flush_verify_cache()
        warm = getattr(app.sig_verifier, "warmup", None)
        if warm is not None:
            warm(wait=True)     # in-memory executables: no compile
            if app is not self.first:
                # each warm-up runs every planned shape once on zeros:
                # device time that no dispatch counter sees
                self.warm_runs += len(self.workload["warm_buckets"])
        app.clock.set_virtual_time(self.hist.pub_time + 10.0)
        rec = {"done": False, "ok": False, "closed": 0}
        self.current = app
        self.replays.append(rec)
        lm = app.ledger_manager
        base = lm.last_closed_ledger_num()
        with annotate.span("bench.catchup.start"):
            work = app.catchup_manager.start_catchup(
                CatchupConfiguration.complete())
        while not work.is_done():
            with annotate.span("bench.catchup.crank"):
                app.crank(False)
            now = time.perf_counter()
            if tick(now):
                self.cut = True     # a traced run's slice is full
            if now >= deadline or self.cut:
                break
        rec["closed"] = lm.last_closed_ledger_num() - base
        self.ledgers_closed += rec["closed"]
        rec["done"] = work.is_done()
        rec["ok"] = rec["done"] and work.state == State.SUCCESS and \
            lm.last_closed_ledger_num() == self.hist.tip
        # the answers, read as each replay ends: its header chain
        rec["headers"] = dict(app.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
        cockpit = app.command_handler.cmd_verifier({})
        rec["sigs_on_device"] = cockpit["counters"]["sigs_verified"]
        rec["dispatches"] = cockpit["counters"]["batches_dispatched"]
        rec["buckets"] = {b: dict(drains=d["drains"], sigs=d["sigs"],
                                  pad=d["pad_waste_total"])
                          for b, d in cockpit.get("buckets", {}).items()}
        self.current = None
        self.done_counters["sigs"] += rec["sigs_on_device"]
        self.done_counters["dispatches"] += rec["dispatches"]
        rec["violations"] = device_path_violations(app)
        if not rec["done"]:     # cut before its first drain, perhaps
            rec["violations"].pop("no_device_drains", None)
        if self.trace:
            rec["spans"] = [(s.name, s.t0, s.dur, s.sid, s.parent)
                            for s in app.tracer.spans()
                            if s.dur is not None]
        stats = lm.apply_stats
        rec["python_closes"] = stats.closes.get("python", 0)
        rec["native_bails"] = dict(stats.bails)

    def window(self, seconds: float, tick) -> None:
        self.t_begin = time.perf_counter()
        deadline = self.t_begin + seconds
        app = self.first
        while True:
            self._replay(app, deadline, tick)
            rec = self.replays[-1]
            if rec["ok"]:
                if self.last_node is not None:
                    self.last_node.stop()
                self.last_node = app
            else:
                app.stop()
            if time.perf_counter() >= deadline or self.cut or \
                    (rec["done"] and not rec["ok"]):
                break
            with annotate.span("bench.catchup.new_node"):
                app = self._new_node()
        self.t_end = time.perf_counter()

    def device_counters(self) -> dict:
        """Signatures verified and dispatches so far, over every node
        of this run (the verifier's counters are per node)."""
        out = dict(self.done_counters)
        out["warm_runs"] = self.warm_runs
        if self.current is not None:
            v = self.current.sig_verifier
            v = getattr(v, "inner", v)
            out["sigs"] += getattr(v, "sigs_verified", 0)
            out["dispatches"] += getattr(v, "batches_dispatched", 0)
        return out

    def drain(self) -> None:
        """Nothing is in flight: a replay cut by the window's end counts
        for the ledgers it closed."""

    # -- results -------------------------------------------------------------
    def end_to_end(self) -> dict:
        return {"replay_ledgers_per_s":
                self.ledgers_closed / (self.t_end - self.t_begin)}

    def counts(self) -> dict:
        full = [r for r in self.replays if r["ok"]]
        return {"ledgers": self.ledgers_closed,
                "replays_full": len(full),
                "replays_started": len(self.replays),
                "sigs_on_device": sum(r["sigs_on_device"]
                                      for r in self.replays),
                "dispatches": sum(r["dispatches"] for r in self.replays),
                "buckets": _merge_buckets(self.replays),
                "python_closes": sum(r["python_closes"]
                                     for r in self.replays),
                "native_bails": [r["native_bails"] for r in self.replays
                                 if r["native_bails"]],
                "spans": [r["spans"] for r in self.replays
                          if "spans" in r]}

    def compare(self) -> dict:
        """Each number compared, with its limit. The plain reference:
        the header chain the cpu-backend publisher closed, and the
        generator's own model of every sender's balance and sequence
        number and of the signatures it issued (no program code)."""
        want = self.hist.headers
        header_mismatches = 0
        sigs_missing = 0
        violations = 0
        failed_replays = 0
        for r in self.replays:
            for seq, h in r["headers"].items():
                if seq > 1 and want.get(seq) != h:
                    header_mismatches += 1
            if r["done"] and not r["ok"]:
                failed_replays += 1
            if r["ok"]:
                if len(r["headers"]) != self.hist.tip:
                    header_mismatches += abs(self.hist.tip -
                                             len(r["headers"]))
                sigs_missing += abs(self.hist.sigs_issued -
                                    r["sigs_on_device"])
            violations += len(r["violations"])
        state_mismatches = 0
        state_checked = 0
        if self.last_node is not None:
            root = self.last_node.ledger_manager.ltx_root()
            from stellar_core_tpu.xdr import LedgerKey
            for key in self.hist.sender_keys:
                e = root.get_entry(LedgerKey.account(key))
                m = self.hist.model[key.key_bytes]
                state_checked += 1
                if e is None or e.data.value.balance != m["balance"] or \
                        e.data.value.seqNum != m["seq"]:
                    state_mismatches += 1
            if not self.workload["traffic"].get("mixed_every"):
                state_checked += 1
                pool = self.last_node.ledger_manager.lcl_header.feePool
                if pool != self.hist.fee_pool:
                    state_mismatches += 1
        full = sum(1 for r in self.replays if r["ok"])
        verdict_mismatches, verdicts = self._negative_control()
        return {
            "full_replays": {"value": full, "limit": 1, "need": "min"},
            "failed_replays": {"value": failed_replays, "limit": 0},
            "header_mismatches": {"value": header_mismatches, "limit": 0},
            "state_mismatches": {"value": state_mismatches, "limit": 0},
            "state_checked": {"value": state_checked,
                              "limit": len(self.hist.sender_keys),
                              "need": "min"},
            "sigs_not_on_device": {"value": sigs_missing, "limit": 0},
            "verdict_mismatches": {"value": verdict_mismatches, "limit": 0},
            "verdicts_compared": {"value": verdicts,
                                  "limit": int(self.workload[
                                      "negative_control_lanes"]),
                                  "need": "min"},
            "device_path_violations": {"value": violations, "limit": 0},
        }

    def _negative_control(self) -> tuple:
        """An archive holds valid signatures only, so a verifier that
        says yes to everything would replay it right. Once the window
        has closed, one full batch of the timed shape goes through the
        last node's served verifier stack: signatures made here from the
        seed with `cryptography`, one in eight with a bit flipped, and
        every verdict is held against `cryptography`'s own (no program
        code on the reference's side)."""
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives.asymmetric import ed25519
        from ..harness.stats import rng_for
        n = int(self.workload["negative_control_lanes"])
        if self.last_node is None:
            return 0, 0
        rng = rng_for(self.seed, "negative-control")
        keys = [ed25519.Ed25519PrivateKey.from_private_bytes(
            rng.randbytes(32)) for _ in range(64)]
        pubs = [k.public_key() for k in keys]
        raw = [p.public_bytes_raw() for p in pubs]
        flips = set(rng.sample(range(n), n // 8))
        triples, want = [], []
        for i in range(n):
            msg = rng.randbytes(32)
            sig = keys[i % 64].sign(msg)
            if i in flips:
                j = rng.randrange(64)
                sig = sig[:j] + bytes([sig[j] ^ (1 << rng.randrange(8))]) \
                    + sig[j + 1:]
            try:
                pubs[i % 64].verify(sig, msg)
                want.append(True)
            except InvalidSignature:
                want.append(False)
            triples.append((raw[i % 64], sig, msg))
        got = self.last_node.sig_verifier.verify_many(triples)
        return sum(1 for g, w in zip(got, want) if bool(g) != w), len(got)

    def attempted_failed(self) -> tuple:
        failed = sum(1 for r in self.replays if r["done"] and not r["ok"])
        return len(self.replays), failed

    def release(self) -> None:
        if self.last_node is not None:
            self.last_node.stop()
        self.hist.close()


def _merge_buckets(replays) -> dict:
    out: dict = {}
    for r in replays:
        for b, d in r["buckets"].items():
            acc = out.setdefault(str(b), {"drains": 0, "sigs": 0, "pad": 0})
            for k in acc:
                acc[k] += d[k]
    return out
