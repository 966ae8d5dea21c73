"""SCP protocol tests against a mock driver.

Role parity: reference `src/scp/test/SCPUnitTests.cpp` (quorum math) and
`src/scp/test/SCPTests.cpp` (TestSCP mock driver; nomination → ballot →
externalize scenarios).
"""

from typing import Dict, List, Optional

import pytest

from stellar_core_tpu.crypto.hashing import sha256
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.scp.driver import SCPDriver, ValidationLevel
from stellar_core_tpu.scp.local_node import LocalNode
from stellar_core_tpu.scp.scp import SCP
from stellar_core_tpu.xdr import PublicKey, SCPEnvelope, SCPQuorumSet


def nid(i: int) -> PublicKey:
    return PublicKey.ed25519(bytes([i]) * 32)


def qset(threshold: int, *nodes, inner=()) -> SCPQuorumSet:
    return SCPQuorumSet(threshold=threshold, validators=list(nodes),
                        innerSets=list(inner))


# ---------------------------------------------------------------- unit math

def test_is_quorum_slice():
    q = qset(2, nid(1), nid(2), nid(3))
    assert LocalNode.is_quorum_slice(q, {nid(1).key_bytes, nid(2).key_bytes})
    assert not LocalNode.is_quorum_slice(q, {nid(1).key_bytes})
    # nested
    q2 = qset(2, nid(1), inner=[qset(1, nid(2), nid(3))])
    assert LocalNode.is_quorum_slice(
        q2, {nid(1).key_bytes, nid(3).key_bytes})
    assert not LocalNode.is_quorum_slice(q2, {nid(1).key_bytes})


def test_is_v_blocking():
    q = qset(2, nid(1), nid(2), nid(3))
    # any 2 nodes are v-blocking for threshold 2-of-3 (slack 1)
    assert LocalNode.is_v_blocking(q, {nid(1).key_bytes, nid(2).key_bytes})
    assert not LocalNode.is_v_blocking(q, {nid(1).key_bytes})
    # threshold 3-of-3: single node blocks
    q3 = qset(3, nid(1), nid(2), nid(3))
    assert LocalNode.is_v_blocking(q3, {nid(2).key_bytes})
    # empty set blocks nothing
    assert not LocalNode.is_v_blocking(q, set())


def test_node_weight():
    q = qset(2, nid(1), nid(2), nid(3), nid(4))
    w = LocalNode.get_node_weight(nid(1).key_bytes, q)
    assert abs(w - (2**64 - 1) // 2) < 2**32
    assert LocalNode.get_node_weight(nid(9).key_bytes, q) == 0


# ------------------------------------------------------------- mock driver

class TestDriver(SCPDriver):
    def __init__(self, network: "TestNetwork", node_name: str) -> None:
        self.network = network
        self.node_name = node_name
        self.emitted: List[SCPEnvelope] = []
        self.externalized: Dict[int, bytes] = {}
        self.timers: Dict[int, tuple] = {}
        self.heard_quorum = False

    def validate_value(self, slot_index, value, nomination):
        return ValidationLevel.FULLY_VALIDATED

    def combine_candidates(self, slot_index, candidates):
        # deterministic: lexicographically largest candidate
        return sorted(candidates)[-1]

    def sign_envelope(self, envelope):
        envelope.signature = sha256(
            self.node_name.encode() + envelope.statement.to_xdr())[:32]

    def emit_envelope(self, envelope):
        self.emitted.append(envelope)
        self.network.outbox.append((self.node_name, envelope))

    def get_qset(self, qset_hash):
        return self.network.qsets.get(qset_hash)

    def setup_timer(self, slot_index, timer_id, timeout, cb):
        if cb is None:
            self.timers.pop(timer_id, None)  # reference cancel idiom
        else:
            self.timers[timer_id] = (timeout, cb)

    def fire_timer(self, timer_id) -> bool:
        t = self.timers.pop(timer_id, None)
        if t is None:
            return False
        t[1]()
        return True

    def value_externalized(self, slot_index, value):
        assert slot_index not in self.externalized, "double externalize"
        self.externalized[slot_index] = value

    def ballot_did_hear_from_quorum(self, slot_index, ballot):
        self.heard_quorum = True


class TestNetwork:
    def __init__(self, n: int, threshold: int) -> None:
        self.qsets: Dict[bytes, SCPQuorumSet] = {}
        self.outbox: List[tuple] = []
        self.nodes: Dict[str, SCP] = {}
        self.drivers: Dict[str, TestDriver] = {}
        ids = [nid(i + 1) for i in range(n)]
        q = qset(threshold, *ids)
        self.qsets[sha256(q.to_xdr())] = q
        for i in range(n):
            name = "n%d" % (i + 1)
            d = TestDriver(self, name)
            self.drivers[name] = d
            self.nodes[name] = SCP(d, ids[i], True, q)

    def deliver_all(self, max_rounds: int = 50) -> None:
        rounds = 0
        while self.outbox and rounds < max_rounds:
            rounds += 1
            batch, self.outbox = self.outbox, []
            for sender, env in batch:
                for name, node in self.nodes.items():
                    if name != sender:
                        node.receive_envelope(env)

    def externalized_values(self, slot: int) -> List[Optional[bytes]]:
        return [d.externalized.get(slot) for d in self.drivers.values()]


def test_single_node_externalizes():
    net = TestNetwork(1, 1)
    scp = net.nodes["n1"]
    assert scp.nominate(1, b"value-A", b"prev")
    net.deliver_all()
    # 1-of-1: own nomination is a quorum; candidate → ballot → externalize
    assert net.drivers["n1"].externalized.get(1) == b"value-A"


def test_four_node_externalization():
    net = TestNetwork(4, 3)
    # all nodes nominate different values; protocol converges on one
    for i, (name, scp) in enumerate(net.nodes.items()):
        scp.nominate(1, b"value-%d" % i, b"prev")
        net.deliver_all()
    net.deliver_all(200)
    vals = net.externalized_values(1)
    assert all(v is not None for v in vals), vals
    assert len(set(vals)) == 1  # agreement


def test_externalize_with_minority_silent():
    net = TestNetwork(4, 3)
    # only 3 of 4 nominate — still a quorum
    for name in ["n1", "n2", "n3"]:
        net.nodes[name].nominate(1, b"V", b"prev")
        net.deliver_all()
    net.deliver_all(200)
    assert net.drivers["n1"].externalized.get(1) == b"V"
    assert net.drivers["n2"].externalized.get(1) == b"V"
    assert net.drivers["n3"].externalized.get(1) == b"V"


def test_ballot_timeout_bumps_counter():
    net = TestNetwork(4, 3)
    for name in net.nodes:
        net.nodes[name].nominate(1, b"V", b"prev")
        net.deliver_all()
    net.deliver_all(200)
    d = net.drivers["n1"]
    slot = net.nodes["n1"].get_slot(1, False)
    assert slot is not None
    # externalized already; ballot timer should not fire meaningfully
    if slot.ballot.phase != 2:
        before = slot.ballot.b[0]
        from stellar_core_tpu.scp.driver import SCPTimerID
        if d.fire_timer(SCPTimerID.BALLOT):
            assert slot.ballot.b[0] >= before


def test_heard_from_quorum():
    net = TestNetwork(4, 3)
    for name in net.nodes:
        net.nodes[name].nominate(1, b"V", b"prev")
        net.deliver_all()
    net.deliver_all(200)
    assert net.drivers["n1"].heard_quorum


def test_nomination_leader_votes_adopted():
    """Non-leader nodes echo leader votes rather than self-nominating."""
    net = TestNetwork(4, 3)
    names = list(net.nodes)
    first = names[0]
    net.nodes[first].nominate(1, b"W", b"prev")
    net.deliver_all(300)
    for name in names[1:]:
        net.nodes[name].nominate(1, b"W", b"prev")
        net.deliver_all(300)
    vals = net.externalized_values(1)
    assert all(v is not None for v in vals)
    assert len(set(vals)) == 1


def test_restore_state_from_envelopes():
    net = TestNetwork(1, 1)
    scp = net.nodes["n1"]
    scp.nominate(1, b"value-A", b"prev")
    net.deliver_all()
    msgs = scp.get_current_state(1)
    assert msgs
    # a fresh instance restores and reports externalized state
    net2 = TestNetwork(1, 1)
    net2.qsets.update(net.qsets)
    scp2 = net2.nodes["n1"]
    for env in msgs:
        scp2.set_state_from_envelope(env)
    slot = scp2.get_slot(1, False)
    assert slot is not None


def test_purge_slots():
    net = TestNetwork(1, 1)
    scp = net.nodes["n1"]
    for s in (1, 2, 3):
        scp.nominate(s, b"v%d" % s, b"prev")
        net.deliver_all()
    scp.purge_slots(3)
    assert scp.get_slot(1, False) is None
    assert scp.get_slot(3, False) is not None


# ---------------------------------------------------------------- followers
# A watcher's SCP (is_validator False, reference SCP.h isValidator): it
# follows its quorum's statements to externalize and sends none.

def _with_follower(n: int, threshold: int):
    """A network of validators plus one follower, outside every quorum
    set, that is handed every envelope the validators emit."""
    net = TestNetwork(n, threshold)
    q = next(iter(net.qsets.values()))
    d = TestDriver(net, "follower")
    net.drivers["follower"] = d
    net.nodes["follower"] = SCP(d, nid(99), False, q)
    return net, net.nodes["follower"], d


def test_follower_externalizes_what_its_quorum_does_and_emits_nothing():
    from stellar_core_tpu.scp.driver import SCPTimerID
    net, follower, d = _with_follower(3, 2)
    for name in ("n1", "n2", "n3"):
        net.nodes[name].nominate(1, b"V", b"prev")
        net.deliver_all()
    net.deliver_all(200)
    assert net.externalized_values(1) == [b"V"] * 4
    slot = follower.get_slot(1, False)
    assert not slot.fully_validated
    assert slot.ballot.phase == 2 and slot.externalized_value() == b"V"
    assert d.emitted == []
    # its own statements are kept for its quorum math and never sent
    assert slot.ballot.last_envelope is not None
    assert slot.ballot.last_envelope_emit is None
    assert slot.get_latest_messages_send() == []
    # nomination never started: no round, no leader, no vote, no timer
    nom = slot.nomination
    assert not nom.nomination_started and nom.round_number == 0
    assert not nom.votes and not nom.round_leaders
    assert nom.last_envelope is None
    assert len(nom.latest_nominations) == 3
    assert SCPTimerID.NOMINATION not in d.timers
    assert SCPTimerID.BALLOT not in d.timers     # cancelled on externalize


def test_follower_may_not_nominate():
    _net, follower, d = _with_follower(3, 2)
    with pytest.raises(AssertionError):
        follower.nominate(1, b"V", b"prev")
    assert d.emitted == [] and d.timers == {}


def test_follower_ballot_timer_bumps_its_counter_and_sends_nothing():
    """With its quorum stuck at PREPARE(1, V) the follower hears from a
    quorum and arms its ballot timer like any node; the fire moves its
    own counter and reaches nobody."""
    from stellar_core_tpu.scp.driver import SCPTimerID
    from stellar_core_tpu.xdr import (
        SCPBallot, SCPPledges, SCPPrepare, SCPStatement, SCPStatementType,
    )
    net, follower, d = _with_follower(3, 2)
    qh = next(iter(net.qsets))
    b1 = SCPBallot(counter=1, value=b"V")
    for i in (1, 2):
        st = SCPStatement(
            nodeID=nid(i), slotIndex=1, pledges=SCPPledges(
                SCPStatementType.SCP_ST_PREPARE,
                SCPPrepare(quorumSetHash=qh, ballot=b1, prepared=b1,
                           preparedPrime=None, nC=0, nH=0)))
        assert follower.receive_envelope(
            SCPEnvelope(statement=st, signature=b"")) == 1
    slot = follower.get_slot(1, False)
    assert slot.ballot.b == (1, b"V") and slot.ballot.phase == 0
    assert d.heard_quorum and SCPTimerID.BALLOT in d.timers
    assert d.fire_timer(SCPTimerID.BALLOT)
    assert slot.ballot.b == (2, b"V")
    assert d.emitted == [] and net.outbox == []
    assert d.externalized == {}
