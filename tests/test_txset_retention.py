"""A node forgets the transaction sets of the slots it purges.

Role parity: reference `src/herder/PendingEnvelopes.cpp` — `addTxSet`
files a set with the last slot that named it, `eraseBelow` drops the
sets below the purged slot (slot 0: not known, kept). Before this a
live node kept every set it had ever built or fetched, frames and all,
and each pass of the collector walked them.
"""

import pytest

from stellar_core_tpu.herder.pending_envelopes import PendingEnvelopes
from stellar_core_tpu.simulation import topologies
from stellar_core_tpu.testing import AppLedgerAdapter


def test_a_set_goes_with_the_last_slot_that_named_it():
    pe = PendingEnvelopes(herder=None)
    pe.add_tx_set(b"a" * 32, "A", 5)
    pe.add_tx_set(b"b" * 32, "B", 9)
    pe.add_tx_set(b"c" * 32, "C")           # slot not known
    pe.add_tx_set(b"b" * 32, "B", 7)        # an older slot names it again
    pe.erase_below(9)
    assert set(pe.txsets) == {b"b" * 32, b"c" * 32}
    assert pe.txset_slots == {b"b" * 32: 9, b"c" * 32: 0}
    pe.erase_below(10)
    assert set(pe.txsets) == {b"c" * 32}
    assert pe.get_tx_set(b"c" * 32) == "C"


@pytest.mark.parametrize("payments", [0, 3])
def test_a_live_node_keeps_the_sets_of_the_slots_it_remembers(payments):
    sim = topologies.core(3, 2)
    sim.start_all_nodes()
    apps = [n.app for n in sim.nodes.values()]
    assert sim.crank_until(lambda: sim.have_all_externalized(2), 20000)
    ledger = AppLedgerAdapter(apps[0])
    root = ledger.root_account()
    seq = ledger.seq_num(root.account_id)
    for i in range(payments):
        frame = root.tx([root.op_payment(root.account_id, 1)],
                        seq=seq + 1 + i)
        assert apps[0].submit_transaction(frame) == 0
    remember = apps[0].config.MAX_SLOTS_TO_REMEMBER
    target = 2 * remember + 6
    assert sim.crank_until(lambda: sim.have_all_externalized(target),
                           200000)
    for app in apps:
        lm, pe = app.ledger_manager, app.herder.pending
        lcl = lm.last_closed_ledger_num()
        # the closed ledger's set is still served; none older than the
        # slots the node remembers is held
        assert pe.get_tx_set(lm.lcl_header.scpValue.txSetHash) is not None
        assert min(pe.txset_slots.values()) >= lcl - remember + 1
        assert set(pe.txsets) == set(pe.txset_slots)
        # a slot's own set and those its peers proposed: a few a slot
        assert len(pe.txsets) <= 3 * (remember + 1)
