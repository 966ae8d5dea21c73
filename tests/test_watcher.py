"""A watcher (NODE_IS_VALIDATOR off; reference docs/software/admin.md, the
node every Horizon submits through) follows a `core(3, 2)` quorum over
the real overlay stack: it closes every ledger its quorum externalizes,
admits and relays transactions like a validator, and emits no SCP
envelope (ISSUE 27).

The plain reference is what the benchmark's validator cells use: the
test's own ledger of what it was told was admitted, each payment applied
once (no program code), held against every node's state; and the three
`cpu` validators' header chains held against the watcher's. The watcher
runs once on the `cpu` backend and once on `tpu-async` under jax-CPU at
the 32-lane bucket.
"""

import os
import random
import time

import pytest

from stellar_core_tpu.crypto import keys as K
from stellar_core_tpu.crypto.hashing import sha256
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.herder import HerderState
from stellar_core_tpu.main.application import Application, AppState
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.simulation import topologies
from stellar_core_tpu.simulation.simulation import Simulation
from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr import LedgerKey, TransactionResultCode

BASE_FEE = 100
START_BALANCE = 10 ** 9
N_ACCOUNTS = 12
LEDGERS = 32


def count(app, meter: str) -> int:
    return app.metrics.to_json().get(meter, {}).get("count", 0)


def chain(app) -> dict:
    return dict(app.database.execute(
        "SELECT ledgerseq, ledgerhash FROM ledgerheaders").fetchall())


def applied_txids(app) -> set:
    return {r[0] for r in app.database.execute(
        "SELECT txid FROM txhistory").fetchall()}


class Net:
    """`topologies.core(3, 2, OVER_PEERS, watchers=1)`: apps[0] is the
    watcher (built first), apps[1:] the validators on the cpu backend."""

    def __init__(self, backend: str = "cpu", tweak=None,
                 trace: bool = False) -> None:
        K.flush_verify_cache()
        self.device = backend != "cpu"
        order = []

        def cfg_tweak(cfg) -> None:
            order.append(cfg)
            watcher = len(order) == 1
            cfg.SIG_VERIFY_BACKEND = backend if watcher else "cpu"
            # one process holds all four: what a validator verified is
            # not verified for the watcher
            cfg.VERIFY_CACHE_SCOPE = "node" if watcher else "process"
            cfg.SIG_VERIFY_WARMUP = False
            cfg.DATABASE = "sqlite3://:memory:"
            # the clocks are virtual and each node's own: an idle node's
            # jumps to its next timer, so 35 s of silence pass at once
            cfg.CONSENSUS_STUCK_TIMEOUT_SECONDS = 10000.0
            cfg.TRACE_ENABLED = trace and watcher
            if tweak is not None:
                tweak(cfg, len(order) - 1)

        self.sim = topologies.core(3, 2, mode=Simulation.OVER_PEERS,
                                   cfg_tweak=cfg_tweak, watchers=1)
        self.names = list(self.sim.nodes)
        if self.device:
            v = self.watcher.sig_verifier.inner
            v.BUCKETS = (32,)
            v.warmup(wait=True)
        self.sim.start_all_nodes()

    @property
    def apps(self) -> list:
        return [n.app for n in self.sim.nodes.values()]

    @property
    def watcher(self):
        return self.sim.nodes[self.names[0]].app

    def lcl_min(self) -> int:
        return min(a.ledger_manager.last_closed_ledger_num()
                   for a in self.apps)

    def _device_waiting(self) -> bool:
        v = self.watcher.sig_verifier
        stats = getattr(v, "stats", None)
        return bool(v.pending() or (stats is not None and
                                    stats.queue.get("inflight")))

    def crank_until(self, pred, what: str, wall_s: float = 240.0) -> None:
        """As the benchmark's set-up does: work that is ready runs
        without moving time, and only when no node has any and the
        watcher is not waiting for the device does every clock jump to
        its next timer. Nobody waits for a watcher: on clocks that ran
        free, validators closing a ledger a millisecond would leave one
        that verifies on a jax-CPU "device" behind for good (catching up
        from an archive is not its job here). The deadline guards a
        hang."""
        deadline = time.time() + wall_s
        while not pred():
            assert time.time() < deadline, "timed out waiting for " + what
            ran = 0
            nodes = [n for n in self.sim.nodes.values() if not n.stopped]
            for _ in range(20):
                for n in nodes:
                    ran += n.app.clock.crank_ready()
                    n.app.sig_verifier.flush()
            if ran:
                continue
            if self.device and self._device_waiting():
                time.sleep(0.0005)      # let the dispatch worker run
            else:
                for n in nodes:
                    n.app.clock.crank(False)

    def close(self, n: int = 1) -> None:
        target = self.lcl_min() + n
        self.crank_until(lambda: self.lcl_min() >= target,
                         "%d more ledger(s) on all four" % n)

    # -- the plain ledger ----------------------------------------------------
    def fund(self, seed: int) -> None:
        """N_ACCOUNTS accounts created by the root through a validator."""
        self.rng = random.Random(seed)
        ledger = AppLedgerAdapter(self.apps[1])
        root = ledger.root_account()
        sks = [SecretKey.from_seed(sha256(b"watcher-test/%d/%d"
                                          % (seed, i)))
               for i in range(N_ACCOUNTS)]
        assert self.apps[1].submit_transaction(root.tx(
            [root.op_create_account(sk.public_key, START_BALANCE)
             for sk in sks])) == 0
        self.crank_until(
            lambda: all(AppLedgerAdapter(a).account_exists(
                sks[-1].public_key) for a in self.apps),
            "the accounts on all four")
        self.accounts = [TestAccount(ledger, sk) for sk in sks]
        self.seq = [ledger.seq_num(sk.public_key) for sk in sks]
        self.balance = [START_BALANCE] * N_ACCOUNTS
        self.admitted = {}      # txid -> home node index

    def payment(self, i: int):
        acct = self.accounts[i]
        dest = self.rng.randrange(N_ACCOUNTS - 1)
        dest += dest >= i
        amount = 1 + self.rng.randrange(10000)
        frame = acct.tx([acct.op_payment(self.accounts[dest].account_id,
                                         amount)], seq=self.seq[i] + 1)
        return frame, dest, amount

    def submit(self, i: int, home: int) -> str:
        """Account i's next payment, submitted to node `home`; the plain
        ledger applies it once."""
        frame, dest, amount = self.payment(i)
        assert self.apps[home].submit_transaction(frame) == 0
        self.seq[i] += 1
        self.balance[i] -= amount + BASE_FEE
        self.balance[dest] += amount
        txid = frame.contents_hash().hex()
        self.admitted[txid] = home
        return txid

    def applied_everywhere(self, i: int) -> bool:
        key = self.accounts[i].account_id
        return all(AppLedgerAdapter(a).seq_num(key) == self.seq[i]
                   for a in self.apps)

    def run_payments(self, ledgers: int) -> None:
        """Closed loop: client i is homed on node i mod 4 and sends its
        account's next payment when the last is applied on all four."""
        target = self.lcl_min() + ledgers
        idle = list(range(N_ACCOUNTS))
        busy = []

        def step() -> bool:
            for i in idle:
                self.submit(i, i % 4)
            busy.extend(idle)
            del idle[:]
            done = [i for i in busy if self.applied_everywhere(i)]
            for i in done:
                busy.remove(i)
            if self.lcl_min() < target:
                idle.extend(done)
            return self.lcl_min() >= target and not busy

        self.crank_until(step, "%d ledgers of payments" % ledgers)

    def stop(self) -> None:
        self.sim.stop_all_nodes()


@pytest.fixture(scope="module", params=["cpu", "tpu-async"])
def net(request):
    n = Net(request.param, trace=True)
    try:
        n.close(1)
        n.fund(seed=27)
        n.run_payments(LEDGERS)
        n.close(1)      # the chains below cover the last payment's ledger
        yield n
    finally:
        n.stop()


# ------------------------------------------------- thirty ledgers of payments

def test_equal_header_hashes_at_every_height(net):
    chains = [chain(a) for a in net.apps]
    tip = net.lcl_min()
    assert tip >= LEDGERS + 2
    for seq in range(2, tip + 1):
        hashes = {c.get(seq) for c in chains}
        assert len(hashes) == 1 and None not in hashes, seq


def test_state_equals_the_plain_ledger_on_all_four(net):
    assert len(net.admitted) >= 2 * N_ACCOUNTS
    for app in net.apps:
        root = app.ledger_manager.ltx_root()
        for i, acct in enumerate(net.accounts):
            e = root.get_entry(LedgerKey.account(acct.account_id))
            assert e.data.value.balance == net.balance[i], i
            assert e.data.value.seqNum == net.seq[i], i
        # each admitted payment exactly once
        rows = [r[0] for r in app.database.execute(
            "SELECT txid FROM txhistory").fetchall()]
        assert len(rows) == len(set(rows))
        assert set(net.admitted) <= set(rows)


def test_the_watcher_emits_no_envelope_and_proposes_nothing(net):
    w = net.watcher
    assert count(w, "scp.envelope.emit") == 0
    assert count(w, "scp.value.nominated") == 0
    assert count(w, "scp.envelope.receive") > 6 * LEDGERS
    assert count(w, "scp.value.externalized") == \
        w.ledger_manager.last_closed_ledger_num() - 1
    assert w.herder.scp_stats.totals["sent"] == 0
    for v in net.apps[1:]:
        assert count(v, "scp.envelope.emit") > 0
    # what it queued left its queue when a ledger it did not propose
    # applied it
    assert w.herder.tx_queue.size_ops() == 0
    if net.device:
        inner = w.sig_verifier.inner
        assert inner.batches_dispatched > 0 and inner.sigs_verified > 0


def test_the_watcher_verifies_for_itself(net):
    """VERIFY_CACHE_SCOPE "node": no verdict of a validator is taken
    for the watcher's own, so every payment and every envelope of its
    quorum goes through its verifier once."""
    w = net.watcher
    own = w.sig_verifier.cache
    assert own is not K.PROCESS_CACHE
    assert all(v.sig_verifier.cache is K.PROCESS_CACHE
               for v in net.apps[1:])
    closed = w.ledger_manager.last_closed_ledger_num() - 1
    assert len(own.store) >= len(net.admitted) + 6 * closed
    if net.device:
        inner = w.sig_verifier.inner
        assert inner.sigs_verified >= len(net.admitted) + 6 * closed
        assert w.command_handler.cmd_verifier({})["drains"][
            "by_backend"].get("cpu", {}).get("drains", 0) == 0


def test_transactions_flood_both_ways(net):
    w, validators = net.watcher, net.apps[1:]
    homed_on_watcher = {t for t, h in net.admitted.items() if h == 0}
    homed_elsewhere = set(net.admitted) - homed_on_watcher
    assert homed_on_watcher and homed_elsewhere
    # submitted to a validator: reached the watcher by flood
    assert count(w, "herder.tx.received.flood") >= len(homed_elsewhere)
    assert count(w, "herder.tx.received.local") == len(homed_on_watcher)
    assert homed_elsewhere <= applied_txids(w)
    # submitted to the watcher: reached the validators
    for v in validators:
        assert count(v, "herder.tx.received.flood") >= \
            len(homed_on_watcher)
        assert homed_on_watcher <= applied_txids(v)
    # the origin meters count admissions, once a transaction; the flood
    # delivers most of them more than once to a node with three peers
    # (the funding transaction came by flood too)
    assert count(w, "herder.tx.received.flood") == len(homed_elsewhere) + 1
    assert count(w, "herder.tx.accepted") == len(net.admitted) + 1
    assert count(w, "herder.tx.received") > len(net.admitted) + 1


def test_the_watcher_is_in_sync(net):
    w = net.watcher
    assert w.state == AppState.APP_SYNCED
    assert w.herder.state == HerderState.HERDER_TRACKING_STATE
    assert w.herder.tracking_slot >= \
        w.ledger_manager.last_closed_ledger_num() - 1
    info = w.command_handler.cmd_info({})
    assert info["state"] == "Synced!" and info["ledger"]["synced"]
    assert info["quorum"]["validating"] is False
    assert info["quorum"]["state"] == "tracking"
    for v in net.apps[1:]:
        assert v.get_info()["quorum"]["validating"] is True
        assert v.state == AppState.APP_SYNCED


def test_spans_of_a_follower(net):
    """`scp.slot` runs from the first envelope of the slot (a watcher
    has no trigger) to externalize. A flood-received transaction is
    parked under `overlay.recv_tx` and admitted under the drain's
    `herder.admit_batch` (ISSUE 28); only a copy of one the queue
    already holds is answered at receipt, as `overlay.recv_tx`'s
    child."""
    w = net.watcher
    spans = w.tracer.spans()
    by_sid = {s.sid: s for s in spans}
    slots = [s for s in spans if s.name == "scp.slot"]
    assert slots and all(s.parent == 0 and s.dur >= 0.0 for s in slots)
    # (a slot whose first envelope it saw before it closed the one
    # before, as a slow follower does, starts at its later envelopes)
    seqs = [s.tags["slot"] for s in slots]
    assert seqs == sorted(set(seqs)) and seqs[0] == 2
    assert len(seqs) >= (w.ledger_manager.last_closed_ledger_num() - 1) // 2
    assert not [s for s in spans if s.name == "herder.trigger"]
    admits = [s for s in spans if s.name == "herder.admit"]
    flood = [s for s in admits if s.tags["origin"] == "flood"]
    local = [s for s in admits if s.tags["origin"] == "local"]
    assert flood and local and len(flood) + len(local) == len(admits)
    drained = [s for s in flood
               if by_sid[s.parent].name == "herder.admit_batch"]
    at_receipt = [s for s in flood
                  if by_sid[s.parent].name == "overlay.recv_tx"]
    assert len(drained) + len(at_receipt) == len(flood)
    # every first sight went through a drain; what the queue answered
    # at receipt was a copy
    homed_elsewhere = [t for t, h in net.admitted.items() if h != 0]
    assert len([s for s in drained if s.tags["status"] == 0]) == \
        len(homed_elsewhere) + 1        # the funding transaction too
    assert at_receipt and all(s.tags["status"] == 1 for s in at_receipt)
    assert all(s.parent == 0 for s in local)
    batches = [s for s in spans if s.name == "herder.admit_batch"]
    assert all(s.parent == 0 and set(s.tags) == {"n", "triples",
                                                 "dispatched"}
               for s in batches)
    assert sum(s.tags["n"] for s in batches) == len(drained)
    assert max(s.tags["n"] for s in batches) >= 2
    if net.device:
        # one signature a payment, verified by nobody before the watcher
        assert all(s.tags["triples"] == s.tags["n"] for s in batches)
        assert sum(s.tags["dispatched"] for s in batches) == \
            len(homed_elsewhere) + 1
    else:
        assert all(s.tags["triples"] == s.tags["dispatched"] == 0
                   for s in batches)
    # a copy of a frame still parked is recorded for the flood and has
    # no admission of its own
    recv = [s for s in spans if s.name == "overlay.recv_tx"]
    assert len(recv) >= len(flood)
    hist = w.metrics.to_json()["herder.admit_batch.size"]
    assert hist["count"] == len(batches)


def test_a_corrupted_signature_is_refused_and_not_relayed(net):
    """Last of the module: it leaves account 0's sequence number as the
    plain ledger has it (the refused payment never applies)."""
    w, validators = net.watcher, net.apps[1:]
    frame, _dest, _amount = net.payment(0)
    sig = frame.envelope.value.signatures[0]
    sig.signature = bytes([sig.signature[0] ^ 1]) + sig.signature[1:]
    before = [count(v, "herder.tx.received") for v in validators]
    assert w.submit_transaction(frame) != 0
    assert frame.result.code == TransactionResultCode.txBAD_AUTH
    net.close(2)
    assert [count(v, "herder.tx.received") for v in validators] == before
    txid = frame.contents_hash().hex()
    assert all(txid not in applied_txids(a) for a in net.apps)
    assert net.applied_everywhere(0)


# ------------------------------------------------------------ herder and app

def test_a_stack_with_its_own_cache_trusts_no_other_verdict():
    from stellar_core_tpu.crypto.batch_verifier import make_verifier
    K.flush_verify_cache()
    sk = SecretKey.from_seed(b"w" * 32)
    msg = b"verified elsewhere"
    sig = sk.sign(msg)
    assert K.PubKeyUtils.verify_sig(sk.public_key, sig, msg)
    shared = make_verifier("cpu")
    own = make_verifier("cpu-resilient", cache=K.VerdictCache())
    assert shared.cache is K.PROCESS_CACHE
    assert own.ctx is own.engine.ctx is own.fallback.ctx
    assert own.cache is own.ctx.cache
    hits = K.verify_cache_stats()["hits"]
    assert shared.enqueue(sk.public_key, sig, msg).result()
    assert K.verify_cache_stats()["hits"] == hits + 1
    assert own.prewarm_many([(sk.public_key.key_bytes, sig, msg)]) == [True]
    assert K.verify_cache_stats()["hits"] == hits + 1
    assert len(own.cache.store) == 1 and own.cache.store.hits == 0
    f = own.enqueue(sk.public_key, sig, msg)
    assert f.done() and f.result() and own.cache.store.hits == 1
    cfg = Config.test_config(0)
    cfg.VERIFY_CACHE_SCOPE = "nodes"
    with pytest.raises(ValueError):
        Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)


def watcher_app(**overrides):
    cfg = Config.test_config(0)
    cfg.NODE_IS_VALIDATOR = False
    cfg.FORCE_SCP = True
    cfg.MANUAL_CLOSE = True
    for k, v in overrides.items():
        setattr(cfg, k, v)
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def test_a_watcher_starts_acquiring_and_its_trigger_proposes_nothing():
    app = watcher_app()
    try:
        assert app.state == AppState.APP_ACQUIRING_CONSENSUS
        assert app.get_info()["state"] == "Catching up"
        assert app.herder.state == HerderState.HERDER_SYNCING_STATE
        assert app.ledger_manager.is_synced()
        app.manual_close()      # reference: "Non-validating node,
        app.herder.trigger_next_ledger(2)   # skipping ledger triggering"
        assert app.herder.scp.empty()
        assert app.ledger_manager.last_closed_ledger_num() == 1
        assert count(app, "scp.value.nominated") == 0
        assert count(app, "scp.envelope.emit") == 0
    finally:
        app.stop()


def test_a_watcher_that_hears_nothing_goes_looking():
    """No value to track from the start: the stuck timer runs from
    bootstrap, and its fire starts the out-of-sync recovery poll."""
    app = watcher_app(CONSENSUS_STUCK_TIMEOUT_SECONDS=5.0,
                      FLIGHT_RECORDER_DIR=os.devnull)
    try:
        assert app.herder.recovery_started_at is None
        app.crank_until(lambda: app.herder.recoveries >= 1, 1000)
        assert app.herder.recoveries == 1
        assert count(app, "herder.recovery.lost-sync") == 1
        assert app.state == AppState.APP_ACQUIRING_CONSENSUS
    finally:
        app.stop()


def test_app_state_follows_a_watchers_herder_and_not_a_validators():
    app = watcher_app()
    try:
        app.herder.set_tracking(7)
        assert app.state == AppState.APP_SYNCED
        app.herder._lost_sync()
        assert app.state == AppState.APP_ACQUIRING_CONSENSUS
        app.herder.set_tracking(8)
        assert app.state == AppState.APP_SYNCED
    finally:
        app.stop()
    cfg = Config.test_config(1)
    cfg.MANUAL_CLOSE = True
    val = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    val.start()
    try:
        assert val.state == AppState.APP_SYNCED
        val.herder._lost_sync()
        assert val.state == AppState.APP_SYNCED     # as before this PR
    finally:
        val.stop()


# ------------------------------------------------------- restart and recovery

def test_a_restarted_watcher_rejoins(tmp_path):
    """The watcher stops, its quorum goes on without it, it restarts
    over its database and closes the gap from its peers' SCP state."""
    def tweak(cfg, i) -> None:
        if i == 0:
            cfg.DATABASE = "sqlite3://%s" % (tmp_path / "watcher.db")

    n = Net("cpu", tweak=tweak)
    try:
        n.close(1)
        n.fund(seed=5)
        n.run_payments(4)
        name = n.names[0]
        lcl_at_stop = n.watcher.ledger_manager.last_closed_ledger_num()
        n.sim.stop_node(name)
        validators = n.apps[1:]
        ledger = AppLedgerAdapter(validators[0])
        # the validators close five ledgers more, one with a payment
        target = lcl_at_stop + 5
        frame, dest, amount = n.payment(1)
        assert validators[0].submit_transaction(frame) == 0
        n.seq[1] += 1
        n.balance[1] -= amount + BASE_FEE
        n.balance[dest] += amount
        n.crank_until(lambda: n.sim.have_all_externalized(target),
                      "the validators to go on alone")
        assert ledger.seq_num(n.accounts[1].account_id) == n.seq[1]
        n.sim.restart_node(name)
        w = n.watcher
        assert w.ledger_manager.last_closed_ledger_num() == lcl_at_stop
        assert w.state == AppState.APP_ACQUIRING_CONSENSUS
        n.crank_until(lambda: n.lcl_min() >= target + 2,
                      "the watcher to rejoin")
        assert w.state == AppState.APP_SYNCED
        assert w.ledger_manager.is_synced()
        assert count(w, "scp.envelope.emit") == 0
        chains = [chain(a) for a in n.apps]
        for seq in range(2, n.lcl_min() + 1):
            assert len({c.get(seq) for c in chains}) == 1, seq
        assert n.applied_everywhere(1)
        # and it serves again: a payment through it reaches every node
        n.submit(2, 0)
        n.crank_until(lambda: n.applied_everywhere(2),
                      "a payment through the restarted watcher")
    finally:
        n.stop()


def test_a_partitioned_watcher_loses_sync_and_recovers():
    """Cut off from every validator the watcher hears nothing, its stuck
    timer drops it out of sync and the recovery poll starts; with the
    links back, its peers' SCP state closes the gap and the first slot
    it externalizes makes it track again."""
    stuck = 30.0

    def tweak(cfg, i) -> None:
        if i == 0:
            cfg.CONSENSUS_STUCK_TIMEOUT_SECONDS = stuck
            cfg.FLIGHT_RECORDER_DIR = os.devnull

    n = Net("cpu", tweak=tweak)
    try:
        sim, w, name = n.sim, n.watcher, n.names[0]
        n.close(3)
        assert w.state == AppState.APP_SYNCED
        for v in n.names[1:]:
            # a partition eats frames; the link is remade on healing
            sim.reconnect_peers(name, v, chaos=True)
        n.close(2)
        lcl = w.ledger_manager.last_closed_ledger_num()
        # (an idle node's virtual clock jumps: the silence before the
        # first slot already cost it one episode)
        episodes = w.herder.recoveries
        resumed = count(w, "herder.recovery.resumed")
        for v in n.names[1:]:
            sim.set_partition(name, v)
        n.crank_until(
            lambda: w.herder.state == HerderState.HERDER_SYNCING_STATE,
            "the watcher to lose sync")
        assert w.state == AppState.APP_ACQUIRING_CONSENSUS
        assert w.get_info()["state"] == "Catching up"
        assert w.herder.recoveries == episodes + 1
        assert w.ledger_manager.last_closed_ledger_num() <= lcl + 1
        tip = max(a.ledger_manager.last_closed_ledger_num()
                  for a in n.apps[1:])
        assert tip > lcl + 1        # its quorum went on without it
        for v in n.names[1:]:
            sim.reconnect_peers(name, v)
        n.crank_until(lambda: n.lcl_min() >= tip + 2,
                      "the watcher to close the gap")
        assert w.state == AppState.APP_SYNCED
        assert w.herder.recovery_started_at is None
        assert count(w, "herder.recovery.resumed") == resumed + 1
        assert count(w, "scp.envelope.emit") == 0
        chains = [chain(a) for a in n.apps]
        for seq in range(2, n.lcl_min() + 1):
            assert len({c.get(seq) for c in chains}) == 1, seq
    finally:
        n.stop()
