"""Flood-received transactions that arrive in one crank share one verify
dispatch (ISSUE 28): `Peer._dispatch` hands the herder the frame,
`Herder.recv_flood_transaction` parks it, and the drain posted on the
node's clock admits what one crank delivered after ONE prewarm over all
their candidate signatures. A local submission keeps the synchronous
path and its status.

Three nodes in a line over the real overlay stack: a sender S, the node
under test R (once on `cpu`, once on `tpu-async` under jax-CPU at the
32-lane bucket, its verdict cache its own) and a downstream D that shows
what R relayed. Each node is its own quorum and closes by hand, so
nothing runs but what a test cranks. S only forwards (`broadcast_message`):
nobody verified a signature before R.

The plain reference is the per-frame path itself: the same traffic
delivered one frame a crank.
"""

import time

import pytest

from stellar_core_tpu.crypto import keys as K
from stellar_core_tpu.crypto.batch_verifier import TpuSigVerifier
from stellar_core_tpu.crypto.hashing import sha256
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.herder import Herder
from stellar_core_tpu.simulation.simulation import Simulation
from stellar_core_tpu.testing import AppLedgerAdapter, TestAccount
from stellar_core_tpu.xdr import (
    MessageType, SCPQuorumSet, StellarMessage, TransactionResultCode,
)

START_BALANCE = 10 ** 9
N_ACCOUNTS = 200


def count(app, name: str) -> int:
    return app.metrics.to_json().get(name, {}).get("count", 0)


def msg_of(frame) -> StellarMessage:
    return StellarMessage(MessageType.TRANSACTION, frame.envelope)


def corrupt(frame):
    sig = frame.envelope.value.signatures[0]
    sig.signature = bytes([sig.signature[0] ^ 1]) + sig.signature[1:]
    return frame


class Line:
    """S — R — D. `apps` in that order."""

    def __init__(self, backend: str, buckets=(32,), tweak=None,
                 ladder=None) -> None:
        K.flush_verify_cache()
        self.device = backend != "cpu"
        self.sim = sim = Simulation(mode=Simulation.OVER_PEERS)
        order = []

        def cfg_tweak(cfg) -> None:
            order.append(cfg)
            under_test = len(order) == 2
            cfg.MANUAL_CLOSE = True
            cfg.QUORUM_SET = cfg.self_qset()
            cfg.SIG_VERIFY_BACKEND = backend if under_test else "cpu"
            cfg.VERIFY_CACHE_SCOPE = "node" if under_test else "process"
            cfg.SIG_VERIFY_WARMUP = False
            cfg.DATABASE = "sqlite3://:memory:"
            cfg.CONSENSUS_STUCK_TIMEOUT_SECONDS = 10000.0
            cfg.TRACE_ENABLED = under_test
            if tweak is not None and under_test:
                tweak(cfg)

        sks = [SecretKey.from_seed(sha256(b"flood-batch-node-%d" % i))
               for i in range(3)]
        placeholder = SCPQuorumSet(threshold=1,
                                   validators=[sks[0].public_key],
                                   innerSets=[])
        self.names = [sim.add_node(sk, placeholder, name=n,
                                   cfg_tweak=cfg_tweak).name
                      for sk, n in zip(sks, "SRD")]
        self.S, self.R, self.D = self.apps
        # what R relayed: every transaction a peer hands D's herder
        self.d_saw = []
        d_recv = self.D.herder.recv_flood_transaction

        def recv(frame, on_verdict=None):
            self.d_saw.append(frame.full_hash())
            d_recv(frame, on_verdict)
        self.D.herder.recv_flood_transaction = recv
        if self.device:
            v = self.R.sig_verifier.inner
            v.BUCKETS = buckets
            v.warmup(wait=True)
            if ladder is not None:
                v.BUCKETS = ladder      # the further shapes: not loaded
        sim.start_all_nodes()
        sim.connect_peers("S", "R")
        sim.connect_peers("R", "D")
        self.settle()
        assert all(len(a.overlay_manager.authenticated_peers) == n
                   for a, n in zip(self.apps, (1, 2, 1)))
        self._fund()

    @property
    def apps(self) -> list:
        return [self.sim.nodes[n].app for n in self.names]

    def settle(self, wall_s: float = 120.0) -> None:
        """Run what is ready on every node, and what that makes ready,
        until nothing is left; no clock moves."""
        deadline = time.time() + wall_s
        while True:
            assert time.time() < deadline, "the line never went quiet"
            ran = 0
            for a in self.apps:
                ran += a.clock.crank_ready()
                a.sig_verifier.flush()
            v = self.R.sig_verifier
            stats = getattr(v, "stats", None)
            waiting = bool(v.pending() or (stats is not None and
                                           stats.queue.get("inflight")))
            if not ran and not waiting:
                return
            if not ran:
                time.sleep(0.0005)

    def close_all(self) -> None:
        for a in self.apps:
            a.manual_close()
        self.settle()

    def _fund(self) -> None:
        """The same accounts on every node: the root's create
        transactions are submitted to R, flood both ways, and each node
        closes them by hand."""
        ledger = AppLedgerAdapter(self.R)
        root = ledger.root_account()
        sks = [SecretKey.from_seed(sha256(b"flood-batch-acct-%d" % i))
               for i in range(N_ACCOUNTS)]
        seq = ledger.seq_num(root.account_id)
        for lo in range(0, N_ACCOUNTS, 100):
            seq += 1
            assert self.R.submit_transaction(root.tx(
                [root.op_create_account(sk.public_key, START_BALANCE)
                 for sk in sks[lo:lo + 100]], seq=seq)) == 0
            self.settle()
            self.close_all()
        assert all(AppLedgerAdapter(a).account_exists(sks[-1].public_key)
                   for a in self.apps)
        self.ledger = ledger
        self.accounts = [TestAccount(ledger, sk) for sk in sks]
        self._next = 0

    def take(self, n: int) -> list:
        """n accounts no other test has touched."""
        out = self.accounts[self._next:self._next + n]
        assert len(out) == n, "the module ran out of accounts"
        self._next += n
        return out

    def payment(self, acct, seq_offset: int = 1, amount: int = 7,
                extra_signers=None):
        dest = self.accounts[0].account_id
        return acct.tx([acct.op_payment(dest, amount)],
                       seq=self.ledger.seq_num(acct.account_id) + seq_offset,
                       extra_signers=extra_signers)

    # -- what a test watches ---------------------------------------------------
    def device_counters(self) -> tuple:
        if not self.device:
            return (0, 0)
        inner = self.R.sig_verifier.inner
        return (inner.batches_dispatched, inner.sigs_verified)

    def queued(self) -> dict:
        """R's queue: account -> the hashes of its chain, in order."""
        return {acc: [f.full_hash() for f in chain]
                for acc, chain in self.R.herder.tx_queue._pending.items()
                if chain}

    def flood_admits(self) -> list:
        """(status, parent span's name) of R's flood `herder.admit`
        spans since the last `mark`, in order."""
        spans = self.R.tracer.spans()
        by_sid = {s.sid: s for s in spans}
        return [(s.tags["status"], by_sid[s.parent].name)
                for s in spans if s.name == "herder.admit"
                and s.tags["origin"] == "flood" and s.sid > self._mark]

    def batches(self) -> list:
        return [dict(s.tags) for s in self.R.tracer.spans()
                if s.name == "herder.admit_batch" and s.sid > self._mark]

    def mark(self) -> None:
        spans = self.R.tracer.spans()
        self._mark = max([s.sid for s in spans], default=0)
        self._d_seen = len(self.d_saw)

    def relayed(self) -> list:
        return self.d_saw[self._d_seen:]

    # -- delivery ----------------------------------------------------------------
    def flood_from_s(self, frames) -> None:
        """S forwards the frames: they sit on R's clock, not yet run."""
        for f in frames:
            self.S.overlay_manager.broadcast_message(msg_of(f), True)

    def in_one_crank(self, frames) -> None:
        self.flood_from_s(frames)
        assert self.R.clock.crank_ready() >= len(frames)   # parked
        self.settle()                                      # drained

    def one_a_crank(self, frames) -> None:
        for f in frames:
            self.flood_from_s([f])
            self.settle()

    def stop(self) -> None:
        self.sim.stop_all_nodes()


@pytest.fixture(scope="module", params=["cpu", "tpu-async"])
def line(request):
    ln = Line(request.param)
    try:
        yield ln
    finally:
        ln.stop()


def test_the_lane_bound_is_the_first_bucket_of_the_ladder():
    assert Herder.ADMIT_BATCH_LANES == TpuSigVerifier.BUCKETS[0] == 128


# --------------------------------------------------------- one shared dispatch

@pytest.mark.parametrize("k", [1, 2, 12])
def test_k_payments_in_one_crank_share_one_dispatch(line, k):
    accts = line.take(k)
    frames = [line.payment(a) for a in accts]
    line.mark()
    d0, s0 = line.device_counters()
    received = count(line.R, "herder.tx.received")
    flood = count(line.R, "herder.tx.received.flood")
    broadcast = count(line.R, "overlay.message.broadcast")
    sizes = count(line.R, "herder.admit_batch.size")
    back = count(line.S, "herder.tx.received")
    line.flood_from_s(frames)
    # the crank that delivers them parks them: nothing verified, nothing
    # queued, nothing relayed, one drain posted
    assert line.R.clock.crank_ready() == k
    assert line.device_counters() == (d0, s0)
    assert len(line.R.herder._parked) == k and line.R.herder._drain_posted
    assert count(line.R, "herder.tx.received.flood") == flood
    assert count(line.R, "overlay.message.broadcast") == broadcast
    # the next crank drains them
    line.settle()
    assert not line.R.herder._parked and not line.R.herder._drain_posted
    d1, s1 = line.device_counters()
    if line.device:
        assert (d1 - d0, s1 - s0) == (1, k)
    assert line.flood_admits() == [(0, "herder.admit_batch")] * k
    assert line.batches() == [{"n": k, "triples": k if line.device else 0,
                               "dispatched": k if line.device else 0}]
    assert count(line.R, "herder.tx.received") == received + k
    assert count(line.R, "herder.tx.received.flood") == flood + k
    assert count(line.R, "herder.admit_batch.size") == sizes + 1
    # each admitted, each relayed once, in arrival order
    hashes = [f.full_hash() for f in frames]
    assert all(h in line.R.herder.tx_queue._known_hashes for h in hashes)
    assert count(line.R, "overlay.message.broadcast") == broadcast + k
    assert line.relayed() == hashes
    assert all(h in line.D.herder.tx_queue._known_hashes for h in hashes)
    # and never sent back to where it came from
    assert count(line.S, "herder.tx.received") == back


def _mixed_traffic(line):
    """Thirteen frames of every kind the queue tells apart: nine plain
    payments, seq n+1 and n+2 of one account, one corrupted signature,
    one sequence number with a gap before it. (Copies have a test of
    their own: a sender's floodgate sends a message once.)"""
    accts = line.take(12)
    frames = [line.payment(a) for a in accts[:8]]
    frames.append(line.payment(accts[8], 1))
    frames.append(line.payment(accts[8], 2))
    frames.append(corrupt(line.payment(accts[9])))
    frames.append(line.payment(accts[10], 3))       # a gap: refused
    frames.append(line.payment(accts[11]))
    return accts, frames


def _observe(line, accts, frames) -> dict:
    """What admission left behind, by position in the traffic and not
    by hash, so that two rounds over different accounts compare."""
    index = {f.full_hash(): i for i, f in enumerate(frames)}
    queue = line.queued()
    return {
        "statuses": sorted((s for s, _p in line.flood_admits())),
        "queue": [[index[h] for h in queue.get(a.account_id.key_bytes, [])]
                  for a in accts],
        "relayed": [index[h] for h in line.relayed()],
    }


def test_the_drain_leaves_what_the_per_frame_path_leaves(line):
    """The same traffic in one crank and one frame a crank: verdicts,
    queue contents and relays entry for entry."""
    accts, frames = _mixed_traffic(line)
    line.mark()
    d0, s0 = line.device_counters()
    line.in_one_crank(frames)
    together = _observe(line, accts, frames)
    d1, s1 = line.device_counters()
    accts2, frames2 = _mixed_traffic(line)
    line.mark()
    line.one_a_crank(frames2)
    apart = _observe(line, accts2, frames2)
    d2, s2 = line.device_counters()
    assert together == apart
    assert together["statuses"] == [0] * 11 + [2, 2]
    assert together["relayed"] == [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 12]
    assert together["queue"][8] == [8, 9] and together["queue"][9] == []
    bad = frames[10]
    assert bad.full_hash() not in line.R.herder.tx_queue._known_hashes
    assert bad.full_hash() not in line.d_saw
    if line.device:
        # one dispatch of thirteen signatures against thirteen of one
        assert (d1 - d0, s1 - s0) == (1, 13)
        assert (d2 - d1, s2 - s1) == (13, 13)


def test_one_corrupted_signature_among_twelve(line):
    accts = line.take(12)
    frames = [line.payment(a) for a in accts]
    corrupt(frames[5])
    line.mark()
    d0, s0 = line.device_counters()
    line.in_one_crank(frames)
    d1, s1 = line.device_counters()
    if line.device:
        assert (d1 - d0, s1 - s0) == (1, 12)
    good = [f.full_hash() for i, f in enumerate(frames) if i != 5]
    assert line.relayed() == good
    known = line.R.herder.tx_queue._known_hashes
    assert all(h in known for h in good)
    assert frames[5].full_hash() not in known
    assert [s for s, _p in line.flood_admits()] == [0] * 5 + [2] + [0] * 6
    # nobody downstream ever sees it
    line.close_all()
    assert frames[5].full_hash() not in line.d_saw


def test_copies_of_a_parked_hash_take_no_lane(line):
    a, b = line.take(2)
    fa, fb = line.payment(a), line.payment(b)
    line.mark()
    d0, s0 = line.device_counters()
    received = count(line.R, "herder.tx.received")
    flood = count(line.R, "herder.tx.received.flood")
    # D sends copies of both as well: four deliveries, two transactions
    line.flood_from_s([fa, fb])
    for f in (fa, fb):
        line.D.overlay_manager.broadcast_message(msg_of(f), True)
    assert line.R.clock.crank_ready() == 4
    assert len(line.R.herder._parked) == 2
    line.settle()
    d1, s1 = line.device_counters()
    if line.device:
        assert (d1 - d0, s1 - s0) == (1, 2)
    assert count(line.R, "herder.tx.received") == received + 4
    assert count(line.R, "herder.tx.received.flood") == flood + 2
    assert line.flood_admits() == [(0, "herder.admit_batch")] * 2
    assert line.batches()[0]["n"] == 2
    # both peers sent both: relayed to neither
    assert line.relayed() == []
    # a copy that comes after the drain is the queue's duplicate, at
    # receipt, with no dispatch (S forgets that it sent it)
    line.mark()
    line.S.overlay_manager.forget_flooded_msg(msg_of(fa))
    line.in_one_crank([fa])
    assert line.flood_admits() == [(1, "overlay.recv_tx")]
    assert line.batches() == [] and line.device_counters() == (d1, s1)
    assert count(line.R, "herder.tx.received.flood") == flood + 2


def test_two_sequence_numbers_of_one_account_in_one_drain(line):
    a, = line.take(1)
    first, second = line.payment(a, 1), line.payment(a, 2)
    line.mark()
    d0, s0 = line.device_counters()
    line.in_one_crank([first, second])
    d1, s1 = line.device_counters()
    if line.device:
        assert (d1 - d0, s1 - s0) == (1, 2)
    assert line.queued()[a.account_id.key_bytes] == \
        [first.full_hash(), second.full_hash()]
    assert line.relayed() == [first.full_hash(), second.full_hash()]
    # the other way round the second is refused, as one a crank
    b, = line.take(1)
    first, second = line.payment(b, 1), line.payment(b, 2)
    line.mark()
    line.in_one_crank([second, first])
    assert [s for s, _p in line.flood_admits()] == [2, 0]
    assert line.queued()[b.account_id.key_bytes] == [first.full_hash()]


def test_a_ledger_close_between_park_and_drain(line):
    """R closes a ledger that applies another payment of account a
    while a's flooded payment is parked: the drain refuses it (its
    sequence number is used) and admits b's, as the per-frame path
    would at that moment; nothing depends on the shared prewarm."""
    a, b = line.take(2)
    parked_a, parked_b = line.payment(a), line.payment(b)
    rival = line.payment(a, amount=9)
    line.mark()
    line.flood_from_s([parked_a, parked_b])
    assert line.R.clock.crank_ready() == 2
    assert len(line.R.herder._parked) == 2
    lcl = line.R.ledger_manager.last_closed_ledger_num()
    assert line.R.submit_transaction(rival) == 0
    # the close runs before the drain: trigger_next_ledger closes the
    # ledger in the call, the drain is next on the clock
    line.R.herder.trigger_next_ledger(lcl + 1)
    line.settle()
    assert line.R.ledger_manager.last_closed_ledger_num() == lcl + 1
    assert line.ledger.seq_num(a.account_id) == rival.seq_num
    assert not line.R.herder._parked
    assert [s for s, _p in line.flood_admits()] == [2, 0]
    assert line.queued().get(b.account_id.key_bytes) == \
        [parked_b.full_hash()]
    assert parked_b.full_hash() in line.relayed()
    assert parked_a.full_hash() not in line.d_saw
    # S and D close what they hold, so the line agrees on a again
    for app in (line.S, line.D):
        app.manual_close()
    line.settle()
    line.close_all()


def test_a_local_submission_keeps_its_status_and_its_own_dispatch(line):
    a, b = line.take(2)
    line.mark()
    d0, s0 = line.device_counters()
    good = line.payment(a)
    assert line.R.submit_transaction(good) == 0
    d1, s1 = line.device_counters()
    # verified, queued and broadcast inside the call
    assert good.full_hash() in line.R.herder.tx_queue._known_hashes
    assert not line.R.herder._parked and not line.R.herder._drain_posted
    bad = corrupt(line.payment(b))
    assert line.R.submit_transaction(bad) == 2
    assert bad.result.code == TransactionResultCode.txBAD_AUTH
    d2, s2 = line.device_counters()
    if line.device:
        assert (d1 - d0, s1 - s0) == (1, 1)
        assert (d2 - d1, s2 - s1) == (1, 1)
    assert line.batches() == [] and line.flood_admits() == []
    line.settle()
    assert good.full_hash() in line.relayed()
    assert bad.full_hash() not in line.d_saw


def test_a_full_list_drains_at_once(line):
    """The list holds `ADMIT_BATCH_LANES` frames at the most: the frame
    that fills it drains it inside its own delivery, and the drain that
    was posted takes what came after."""
    frames = [line.payment(a) for a in line.take(5)]
    line.mark()
    line.R.herder.ADMIT_BATCH_LANES = 4      # on this herder alone
    try:
        line.flood_from_s(frames)
        assert line.R.clock.crank_ready() == 5
        assert list(line.R.herder._parked) == [frames[4].full_hash()]
        assert [b["n"] for b in line.batches()] == [4]
        assert line.R.herder._drain_posted
        line.settle()
    finally:
        del line.R.herder.ADMIT_BATCH_LANES
    assert [b["n"] for b in line.batches()] == [4, 1]
    assert line.flood_admits() == [(0, "herder.admit_batch")] * 5
    assert line.relayed() == [f.full_hash() for f in frames]


def test_a_callback_that_raises_costs_the_others_nothing(line):
    """The herder calls back whatever it was given: one that raises is
    logged, and the frames parked behind it are admitted and answered."""
    frames = [line.payment(a) for a in line.take(3)]
    line.mark()
    verdicts = []

    def raises(status):
        verdicts.append(("raised", status))
        raise RuntimeError("a relay that fails")
    herder = line.R.herder
    herder.recv_flood_transaction(frames[0], raises)
    herder.recv_flood_transaction(
        frames[1], lambda status: verdicts.append(("second", status)))
    herder.recv_flood_transaction(frames[2])
    assert len(herder._parked) == 3
    line.settle()            # the crank that drains them does not raise
    assert verdicts == [("raised", 0), ("second", 0)]
    assert [s for s, _p in line.flood_admits()] == [0, 0, 0]
    assert all(f.full_hash() in herder.tx_queue._known_hashes
               for f in frames)


def test_get_metrics_shows_the_drain_histograms(line):
    a = line.take(3)
    line.in_one_crank([line.payment(x) for x in a])
    out = line.R.command_handler.cmd_metrics({})
    size = out["herder.admit_batch.size"]
    assert size["type"] == "histogram" and size["count"] >= 1
    assert size["max"] >= 3
    if line.device:
        shared = out["herder.admit_batch.dispatched"]
        assert shared["count"] <= size["count"] and shared["max"] >= 3
    else:
        assert "herder.admit_batch.dispatched" not in out


# -------------------------------------------------- lines built for one test

@pytest.mark.parametrize("backend", ["cpu", "tpu-async"])
def test_a_peer_dropped_between_park_and_drain(backend):
    """The sender goes away while its transactions are parked: they are
    admitted and relayed all the same (the verdict is about the
    signature, not about the peer), and nothing is scored against a
    peer that is gone."""
    ln = Line(backend)
    try:
        a, b = ln.take(2)
        frames = [ln.payment(a), ln.payment(b)]
        ln.mark()
        ln.flood_from_s(frames)
        assert ln.R.clock.crank_ready() == 2
        peer = ln.R.overlay_manager.get_peer(
            ln.S.config.node_id().to_xdr())
        peer.drop("the test says so")
        assert len(ln.R.overlay_manager.authenticated_peers) == 1
        ln.settle()
        assert [s for s, _p in ln.flood_admits()] == [0, 0]
        assert ln.relayed() == [f.full_hash() for f in frames]
        assert count(ln.R, "overlay.flood.backpressure") == 0
        # a verdict that would score the sender finds it gone
        peer._tx_verdict(msg_of(frames[0]), 3)
        peer._tx_verdict(msg_of(frames[0]), None)
        assert count(ln.R, "overlay.flood.backpressure") == 0
    finally:
        ln.stop()


@pytest.mark.parametrize("backend", ["cpu", "tpu-async"])
def test_a_relay_that_raises_costs_its_peer_alone(backend):
    """Relaying the first of three raises, from inside the drain: the
    peer that sent it is dropped, as `Peer.recv` would have dropped it,
    and the other two are admitted and relayed all the same."""
    ln = Line(backend)
    try:
        frames = [ln.payment(a) for a in ln.take(3)]
        ln.mark()
        broadcast = ln.R.overlay_manager.broadcast_message
        first = msg_of(frames[0]).to_xdr()

        def failing(msg, force=False):
            if msg.to_xdr() == first:
                raise RuntimeError("a send that fails")
            return broadcast(msg, force)
        ln.R.overlay_manager.broadcast_message = failing
        ln.in_one_crank(frames)
        assert [s for s, _p in ln.flood_admits()] == [0, 0, 0]
        assert ln.relayed() == [f.full_hash() for f in frames[1:]]
        assert not ln.R.herder._parked
        assert len(ln.R.overlay_manager.authenticated_peers) == 1
    finally:
        ln.stop()


@pytest.mark.parametrize("backend", ["cpu", "tpu-async"])
def test_a_throttled_source_spends_no_lane(backend):
    """INGRESS_ENABLED, a class of one transaction a burst: the second
    and third payments of one source are thrown back at receipt, before
    any signature is paid for, and their sender is scored; the first
    and another source's share the drain."""
    def tight(cfg) -> None:
        assert cfg.INGRESS_ENABLED
        cfg.INGRESS_CLASSES = {"untrusted": {"rate": 0.001, "burst": 1.0}}

    ln = Line(backend, tweak=tight)
    try:
        a, b = ln.take(2)
        ln.R.herder.ingress.set_class(a.account_id.key_bytes, "untrusted")
        frames = [ln.payment(a, 1), ln.payment(a, 2), ln.payment(a, 3),
                  ln.payment(b)]
        ln.mark()
        d0, s0 = ln.device_counters()
        ln.flood_from_s(frames)
        assert ln.R.clock.crank_ready() == 4
        assert list(ln.R.herder._parked) == [frames[0].full_hash(),
                                              frames[3].full_hash()]
        assert count(ln.R, "herder.ingress.throttled") == 2
        assert count(ln.R, "overlay.flood.backpressure") == 2
        assert ln.device_counters() == (d0, s0)
        ln.settle()
        d1, s1 = ln.device_counters()
        if ln.device:
            assert (d1 - d0, s1 - s0) == (1, 2)
        assert ln.batches() == [{"n": 2,
                                 "triples": 2 if ln.device else 0,
                                 "dispatched": 2 if ln.device else 0}]
        assert ln.relayed() == [frames[0].full_hash(),
                                frames[3].full_hash()]
        lc = ln.R.herder.tx_lifecycle.to_json()
        assert lc["outcomes"]["throttled"] == 2
    finally:
        ln.stop()


@pytest.mark.parametrize("backend", ["cpu", "tpu-async"])
def test_130_triples_take_two_dispatches_of_the_128_bucket(backend):
    """Thirteen payments of ten signatures each, drained in chunks of
    128 triples: two dispatches of the 128 bucket, none of the 512 one
    (which is never even compiled here)."""
    ln = Line(backend, buckets=(128,), ladder=(128, 512))
    try:
        accts = ln.take(13)
        cosigners = [[SecretKey.from_seed(sha256(b"cosigner-%d-%d" % (i, j)))
                      for j in range(9)] for i in range(13)]
        for acct, sks in zip(accts, cosigners):
            ops = [acct.op_add_signer(sk.public_key.key_bytes)
                   for sk in sks]
            ops.append(acct.op_set_options(low=10, med=10, high=10))
            assert ln.R.submit_transaction(acct.tx(ops)) == 0
        ln.settle()
        ln.close_all()
        ln.close_all()      # 130 operations: more than one ledger holds
        frames = [ln.payment(acct, extra_signers=sks)
                  for acct, sks in zip(accts, cosigners)]
        ln.mark()
        d0, s0 = ln.device_counters()
        cockpit0 = ln.R.command_handler.cmd_verifier({}).get("buckets", {})
        ln.in_one_crank(frames)
        assert ln.flood_admits() == [(0, "herder.admit_batch")] * 13
        d1, s1 = ln.device_counters()
        if ln.device:
            assert (d1 - d0, s1 - s0) == (2, 130)
            assert ln.batches() == [{"n": 13, "triples": 130,
                                     "dispatched": 130}]
            cockpit = ln.R.command_handler.cmd_verifier({})["buckets"]
            assert cockpit["128"]["drains"] - \
                cockpit0.get("128", {}).get("drains", 0) == 2
            assert cockpit.get("512", {}).get("drains", 0) == 0
        assert ln.batches()[1:] == []
        assert ln.relayed() == [f.full_hash() for f in frames]
    finally:
        ln.stop()
