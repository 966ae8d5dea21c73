"""Deployment driver `validator`: `topologies.core(n, threshold,
OVER_PEERS)` in one process, node 0 on the device backend, the other
nodes on the cpu backend as the independent reference, every node with
a bucket directory. The window submits the generator's payments to node
0 while every node is cranked.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from ..harness import annotate
from ..harness.device_path import device_path_violations
from ..harness.stats import percentile
from ..traffic.payments import Payments

BASE_FEE = 100
START_BALANCE = 10 ** 9


class Deployment:
    def __init__(self, config: dict, workload: dict, seed: int,
                 workdir: str, trace: bool, node_hook=None) -> None:
        self.config, self.workload = config, workload
        self.seed, self.workdir, self.trace = seed, workdir, trace
        self.node_hook = node_hook
        self.backend = config["backend_under_test"]
        self.n_accounts = int(config["accounts"])
        self.crank_rounds = int(config["crank_rounds"])
        self.inflight = {}          # txid -> Request, admitted
        self.waiting = []           # (ledger, [Request]) applied on node 0
        self.applied_by_ledger = {}
        self.admit_s = 0.0
        self.admit_n = 0
        self.late_max = 0.0
        self.wall_t0 = None     # set when the clocks follow the wall clock
        self.close_times = []   # when node 0 was seen to have closed

    # -- set-up --------------------------------------------------------------
    def setup(self) -> dict:
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
            cfg.DATABASE = c["database"]
            cfg.INVARIANT_CHECKS = list(c["invariant_checks"])
            cfg.TESTING_UPGRADE_MAX_TX_SET_SIZE = int(c["max_tx_set_ops"])
            # the simulation's accelerated time arms the next close 1 ms
            # after the last; the deployment states its cadence instead
            cfg.ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING = False
            cfg.EXPECTED_LEDGER_CLOSE_TIME = float(c["ledger_close_time_s"])

        info = {}
        t0 = time.perf_counter()
        sim = topologies.core(int(c["validators"]), int(c["threshold"]),
                              mode=Simulation.OVER_PEERS, cfg_tweak=tweak)
        self.sim = sim
        self.apps = apps = [n.app for n in sim.nodes.values()]
        self.node0 = node0 = apps[0]
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
        ledger = AppLedgerAdapter(node0)
        root = ledger.root_account()
        sks = [SecretKey.from_seed(hashlib.sha256(
            b"bench-validator/%d/%d" % (self.seed, i)).digest())
            for i in range(self.n_accounts)]
        root_seq = ledger.seq_num(root.account_id)
        self.seq = []
        for lo in range(0, len(sks), 100):
            # the root's next create transaction is admitted only after
            # the previous one closed
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
        # from here the clocks follow the wall clock (see _crank_once);
        # two closes at the deployment's cadence before the window opens,
        # so that it opens on a network in its stride
        t0 = time.perf_counter()
        # a close time may lead the clock by 60 s at the most, and every
        # close adds a second to it: start with that room whole
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

    # -- cranking ------------------------------------------------------------
    def _lcl_min(self) -> int:
        return min(a.ledger_manager.last_closed_ledger_num()
                   for a in self.apps)

    def _device_waiting(self) -> bool:
        """Is node 0 waiting for the device? (`GET verifier`'s queue)"""
        v = self.node0.sig_verifier
        stats = getattr(v, "stats", None)
        return bool(v.pending() or (stats is not None and
                                    stats.queue.get("inflight")))

    def _crank_once(self) -> None:
        """Every node has a virtual clock of its own, and an idle crank
        of one clock jumps it to its next timer. Left alone the clocks
        drift apart by a second or so a slot, and past 60 s the nodes
        refuse each other's close times: a run of more than some 60
        slots loses a node for good (seen in this PR's rehearsal; PR 21's
        smoke closed 30). So the driver keeps them in step, two ways.

        Set-up (accounts): as the reference's Simulation does, work that
        is ready runs without moving time; only when no node has any,
        and node 0 is not waiting for the device, does every clock jump
        to its next timer, and all are then set to the latest. Forty
        account-creating closes take seconds.

        Window and drain: the clocks follow the wall clock, second for
        second. A virtual clock that moves only when every node is idle
        never moves under load, so a close would wait for the generator
        to pause and the measured cadence would be the generator's (400
        transactions a close, a close every 5 s: my chip run, PR 24).

        Every close adds a whole second to the close time, which may
        lead a node's clock by 60 s at the most. The deployment arms
        the next close `ledger_close_time_s` (half a second) after the
        last, so at most two a second: set-up ends with that room whole,
        and it lasts a minute however fast a later PR makes a round. (The simulation's own accelerated time arms it after
        1 ms; the cadence is then the host's, 1.9 closes a second today,
        and a PR that made rounds faster would use the room up inside
        the window and lose nodes: seen at 6.3 closes a second.)"""
        with annotate.span("bench.crank"):
            ran = 0
            for _ in range(self.crank_rounds):
                for a in self.apps:
                    ran += a.clock.crank_ready()
                    a.sig_verifier.flush()
                if self.wall_t0 is not None:
                    self._follow_wall_clock()
                    if not ran:
                        break
            if self.wall_t0 is None and not ran and \
                    not self._device_waiting():
                for a in self.apps:
                    a.clock.crank(False)
                    a.sig_verifier.flush()
                self._set_clocks(max(a.clock.now() for a in self.apps))
        if not ran and (self.wall_t0 is not None or self._device_waiting()):
            time.sleep(0.0002)      # nothing ready: let the worker run

    def _set_clocks(self, t: float) -> None:
        for a in self.apps:
            if a.clock.now() < t:
                a.clock.set_virtual_time(t)

    def _follow_wall_clock(self) -> None:
        """A stall of this thread that is the harness's own (stopping
        the profiler takes the better part of a minute) is no time of
        the system's: the clocks take a quarter of a second of it."""
        now = time.perf_counter()
        self.virtual_elapsed += min(now - self.wall_last, 0.25)
        self.wall_last = now
        self._set_clocks(self.virtual_t0 + self.virtual_elapsed)

    def _crank_until(self, pred, what: str, wall_s: float = 120.0) -> None:
        """Crank every node against real time (node 0's dispatch worker
        needs wall clock for the device call) until pred()."""
        deadline = time.perf_counter() + wall_s
        while not pred():
            if time.perf_counter() > deadline:
                raise TimeoutError("timed out after %.0f s waiting for %s"
                                   % (wall_s, what))
            self._crank_once()

    # -- the measured window -------------------------------------------------
    def _submit(self, req, now_rel: float) -> None:
        from stellar_core_tpu.xdr import TransactionResultCode
        acct = self.accounts[req.account]
        dest = self.accounts[req.dest].account_id
        seq = self.seq[req.account] + 1
        frame = acct.tx([acct.op_payment(dest, req.amount)], seq=seq)
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
        if req.corrupt:
            # the right answer is a refusal with txBAD_AUTH
            req.refused = status != 0 and frame.result.code == \
                TransactionResultCode.txBAD_AUTH
            req.done = t1 - self.t_begin
            self.gen.replied(req, req.done)
        elif status != 0:
            req.refused = True
            req.done = t1 - self.t_begin
            self.gen.replied(req, req.done)
        else:
            req.refused = False
            req.txid = frame.contents_hash().hex()
            self.inflight[req.txid] = req
            self.seq[req.account] = seq
            self.balance[req.account] -= req.amount + BASE_FEE
            self.balance[req.dest] += req.amount

    def _note_closes(self) -> None:
        """Which admitted transactions node 0 applied in the ledgers it
        closed since the last look, and which of those ledgers every
        node has closed by now."""
        lcl0 = self.node0.ledger_manager.last_closed_ledger_num()
        if lcl0 > self.lcl0_seen:
            self.close_times.append(time.perf_counter())
            with annotate.span("bench.read_closed"):
                for seq in range(self.lcl0_seen + 1, lcl0 + 1):
                    rows = self.node0.database.execute(
                        "SELECT txid FROM txhistory WHERE ledgerseq = ?",
                        (seq,)).fetchall()
                    hit = [self.inflight.pop(r[0]) for r in rows
                           if r[0] in self.inflight]
                    for req in hit:
                        req.ledger = seq
                    if hit:
                        self.waiting.append((seq, hit))
                self.lcl0_seen = lcl0
        if self.waiting:
            everywhere = self._lcl_min()
            now = time.perf_counter() - self.t_begin
            while self.waiting and self.waiting[0][0] <= everywhere:
                seq, hit = self.waiting.pop(0)
                for req in hit:
                    req.done = now
                    self.gen.replied(req, now)
                self.applied_by_ledger[seq] = (len(hit), now)

    def window(self, seconds: float, tick) -> None:
        t = self.workload["traffic"]
        self.gen = Payments(t, self.seed, self.n_accounts, seconds)
        batch = int(t["submit_batch"])
        self.seconds = seconds
        self.slots0 = self._lcl_min()
        self.counters0 = self._counters()
        self.t_begin = time.perf_counter()
        cut = False             # a traced run's slice is full
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

    def drain(self) -> None:
        """Wait for every admitted transaction's reply, up to a minute
        past the close; one that comes late is late, not wrong."""
        deadline = time.perf_counter() + float(
            self.workload.get("drain_s", 60.0))
        batch = int(self.workload["traffic"]["submit_batch"])
        while (self.inflight or self.waiting or self._unsent()) and \
                time.perf_counter() < deadline:
            for req in self.gen.take_due(self.seconds, batch):
                self._submit(req, time.perf_counter() - self.t_begin)
            self._crank_once()
            self._note_closes()
        self.never_applied = len(self.inflight) + sum(
            len(h) for _s, h in self.waiting)
        # one more close everywhere, so that the chains compared below
        # cover the last ledger that applied a payment
        tip = max(a.ledger_manager.last_closed_ledger_num()
                  for a in self.apps)
        self._crank_until(lambda: self._lcl_min() >= tip,
                          "every node to reach ledger %d" % tip, 60.0)

    # -- results -------------------------------------------------------------
    def _counters(self) -> dict:
        cockpit = self.node0.command_handler.cmd_verifier({})
        m = self.node0.metrics.to_json()
        return {"dispatches": cockpit["counters"]["batches_dispatched"],
                "sigs": cockpit["counters"]["sigs_verified"],
                "buckets": {str(b): dict(drains=d["drains"], sigs=d["sigs"],
                                         pad=d["pad_waste_total"])
                            for b, d in cockpit.get("buckets", {}).items()},
                "scp_emit": m.get("scp.envelope.emit", {}).get("count", 0),
                "scp_receive": m.get("scp.envelope.receive",
                                     {}).get("count", 0)}

    def device_counters(self) -> dict:
        v = self.node0.sig_verifier
        v = getattr(v, "inner", v)      # as the `verifier` endpoint reads
        return {"sigs": getattr(v, "sigs_verified", 0),
                "dispatches": getattr(v, "batches_dispatched", 0),
                "warm_runs": 0}

    def _unsent(self) -> int:
        """Arrivals that were due in the window and that the generator
        never got to send: failed, and missing the tail."""
        return 0 if self.gen.closed else len(self.gen.arrivals)

    def end_to_end(self) -> dict:
        wall = self.t_end - self.t_begin
        reqs = self.gen.requests
        lat = [(r.done - r.due) * 1e3
               if r.done is not None and not self._failed(r)
               else float("inf") for r in reqs]
        lat += [float("inf")] * self._unsent()
        out = {"applied_tx_per_s": self.applied_in_window / wall}
        if lat:
            p95 = percentile(lat, 0.95)
            if p95 != float("inf"):
                out["submit_to_applied_p95_ms"] = p95
            out["_p50_ms"] = percentile(lat, 0.50)
        return out

    def _failed(self, r) -> bool:
        if r.corrupt:
            return r.refused is not True
        return r.refused is not False or r.ledger is None or r.done is None

    def counts(self) -> dict:
        c0, c1 = self.counters0, self.counters1
        buckets = {}
        for b, d in c1["buckets"].items():
            d0 = c0["buckets"].get(b, {"drains": 0, "sigs": 0, "pad": 0})
            buckets[b] = {k: d[k] - d0[k] for k in d}
        spans = []
        if self.trace:
            lo, hi = self.t_begin, self.t_end
            spans = [[(s.name, s.t0, s.dur, s.sid, s.parent)
                      for s in self.node0.tracer.spans()
                      if s.dur is not None and lo <= s.t0 <= hi]]
        reqs = self.gen.requests
        return {"ledgers": self.slots1 - self.slots0,
                "submissions": self.admit_n,
                "admitted": sum(1 for r in reqs if r.refused is False),
                "applied_in_window": self.applied_in_window,
                "admit_s": self.admit_s,
                "dispatches": c1["dispatches"] - c0["dispatches"],
                "sigs_on_device": c1["sigs"] - c0["sigs"],
                "scp_envelopes": (c1["scp_emit"] + c1["scp_receive"]) -
                                 (c0["scp_emit"] + c0["scp_receive"]),
                "buckets": buckets, "spans": spans,
                "generator_late_max_ms": self.late_max * 1e3,
                "close_gaps_ms": _gap_summary(
                    [t for t in self.close_times
                     if self.t_begin <= t <= self.t_end]),
                "no_idle_account": self.gen.no_idle_account,
                "unsent": self._unsent()}

    def attempted_failed(self) -> tuple:
        reqs = self.gen.requests
        return len(reqs) + self._unsent(), \
            sum(1 for r in reqs if self._failed(r)) + self._unsent()

    def compare(self) -> dict:
        """Each number compared, with its limit. The plain reference is
        the generator's own ledger of what it was told was admitted:
        every account's balance and sequence number after exactly one
        application of each admitted payment (no program code), held
        against the state of every node; and the cpu-backend nodes'
        header chains against node 0's."""
        from stellar_core_tpu.xdr import LedgerKey
        reqs = self.gen.requests
        corrupt = [r for r in reqs if r.corrupt]
        valid = [r for r in reqs if not r.corrupt]
        state_mismatches = 0
        for app in self.apps:
            root = app.ledger_manager.ltx_root()
            for i, acct in enumerate(self.accounts):
                e = root.get_entry(LedgerKey.account(acct.account_id))
                if e is None or e.data.value.balance != self.balance[i] \
                        or e.data.value.seqNum != self.seq[i]:
                    state_mismatches += 1
        chains = [dict(a.database.execute(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())
            for a in self.apps]
        tip = self._lcl_min()
        header_mismatches = sum(
            1 for seq in range(2, tip + 1)
            if len({c.get(seq) for c in chains}) != 1
            or chains[0].get(seq) is None)
        violations = device_path_violations(self.node0)
        return {
            "state_mismatches": {"value": state_mismatches, "limit": 0},
            "header_mismatches": {"value": header_mismatches, "limit": 0},
            "heights_compared": {"value": tip - 1, "limit": 2,
                                 "need": "min"},
            "corrupt_not_refused": {
                "value": sum(1 for r in corrupt if r.refused is not True),
                "limit": 0},
            "valid_refused": {
                "value": sum(1 for r in valid if r.refused is not False),
                "limit": 0},
            "admitted_never_applied": {"value": self.never_applied,
                                       "limit": 0},
            "device_path_violations": {"value": len(violations),
                                       "limit": 0,
                                       "detail": sorted(violations)},
        }

    def release(self) -> None:
        self.sim.stop_all_nodes()


def _gap_summary(times: list) -> dict:
    """Gaps between node 0's closes in the window, for the earlier
    lines: a slot that needs an SCP timeout shows here as a long one."""
    gaps = sorted((b - a) * 1e3 for a, b in zip(times, times[1:]))
    if not gaps:
        return {}
    return {"n": len(gaps), "median": round(gaps[len(gaps) // 2]),
            "max": round(gaps[-1]),
            "over_1500": sum(1 for g in gaps if g > 1500)}
