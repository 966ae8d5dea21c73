"""Offer semantics depth (reference OfferTests.cpp crossing matrix subset):
passive offers, buy offers, multi-offer book walks in price order, and
herder value validation (closeTime rules) from HerderTests."""

import pytest

import stellar_core_tpu.xdr as X
from stellar_core_tpu.testing import (
    TestAccount, TestLedger, root_secret_key,
)
from stellar_core_tpu.xdr import Asset

XLM = Asset.native()


@pytest.fixture
def market():
    led = TestLedger()
    root = TestAccount(led, root_secret_key())
    issuer = root.create(10**10)
    usd = Asset.credit("USD", issuer.account_id)
    a = root.create(10**10)
    b = root.create(10**10)
    c = root.create(10**10)
    for acct in (a, b, c):
        assert acct.change_trust(usd, 10**12)
        assert issuer.pay(acct, 10**9, usd)
    return led, root, issuer, usd, a, b, c


def _op_buy(acct, selling, buying, amount, n, d, offer_id=0):
    from stellar_core_tpu.xdr import ManageBuyOfferOp, Price
    return acct.op(X.OperationBody(
        X.OperationType.MANAGE_BUY_OFFER,
        ManageBuyOfferOp(selling=selling, buying=buying,
                         buyAmount=amount, price=Price(n=n, d=d),
                         offerID=offer_id)))


def _op_passive(acct, selling, buying, amount, n, d):
    from stellar_core_tpu.xdr import CreatePassiveSellOfferOp, Price
    return acct.op(X.OperationBody(
        X.OperationType.CREATE_PASSIVE_SELL_OFFER,
        CreatePassiveSellOfferOp(selling=selling, buying=buying,
                                 amount=amount, price=Price(n=n, d=d))))


def test_passive_offer_does_not_cross_equal_price(market):
    """A passive sell at exactly the opposing price RESTS instead of
    crossing (reference createPassiveSellOffer semantics)."""
    led, root, issuer, usd, a, b, c = market
    assert led.apply_frame(
        a.tx([a.op_manage_sell_offer(XLM, usd, 1000, 1, 1)]))
    f = b.tx([_op_passive(b, usd, XLM, 500, 1, 1)])
    assert led.apply_frame(f), f.result
    succ = f.result.op_results[0].value.value.value
    assert len(succ.offersClaimed) == 0      # no trade at equal price
    assert succ.offer.disc == 0              # rests on the book
    # a's offer untouched
    rem = led.root.get_entry(X.LedgerKey.offer(a.account_id, 1))
    assert rem.data.value.amount == 1000


def test_passive_offer_still_crosses_better_price(market):
    led, root, issuer, usd, a, b, c = market
    # a sells XLM at 0.5 USD (good deal for a USD seller)
    assert led.apply_frame(
        a.tx([a.op_manage_sell_offer(XLM, usd, 1000, 1, 2)]))
    f = b.tx([_op_passive(b, usd, XLM, 100, 1, 1)])
    assert led.apply_frame(f), f.result
    succ = f.result.op_results[0].value.value.value
    assert len(succ.offersClaimed) == 1      # strictly-better price crosses


@pytest.mark.min_version(11)
def test_buy_offer_acquires_exact_buy_amount(market):
    """ManageBuyOffer expresses the amount to BUY; crossing delivers
    exactly that much of the buying asset."""
    led, root, issuer, usd, a, b, c = market
    assert led.apply_frame(
        a.tx([a.op_manage_sell_offer(XLM, usd, 1000, 1, 1)]))
    before = b.balance()
    f = b.tx([_op_buy(b, usd, XLM, 300, 1, 1)])   # buy 300 XLM with USD
    assert led.apply_frame(f), f.result
    fee = led.header().baseFee
    assert b.balance() == before + 300 - fee
    rem = led.root.get_entry(X.LedgerKey.offer(a.account_id, 1))
    assert rem.data.value.amount == 700


def test_crossing_walks_book_in_price_order(market):
    """A large taker consumes multiple offers best-price-first, partially
    filling the worst one (the OfferTests crossing-matrix core)."""
    led, root, issuer, usd, a, b, c = market
    assert led.apply_frame(
        a.tx([a.op_manage_sell_offer(XLM, usd, 100, 2, 1)]))   # 2.0 (worst)
    assert led.apply_frame(
        b.tx([b.op_manage_sell_offer(XLM, usd, 100, 1, 1)]))   # 1.0 (best)
    assert led.apply_frame(
        c.tx([c.op_manage_sell_offer(XLM, usd, 100, 3, 2)]))   # 1.5
    taker = root.create(10**10)
    assert taker.change_trust(usd, 10**12)
    assert issuer.pay(taker, 10**9, usd)
    # buy 250 XLM paying up to 2.0 USD each
    f = taker.tx([taker.op_manage_sell_offer(usd, XLM, 500, 1, 2)])
    assert led.apply_frame(f), f.result
    succ = f.result.op_results[0].value.value.value
    claimed = [(atom.sellerID.key_bytes, atom.amountSold)
               for atom in succ.offersClaimed]
    # price order: b (1.0) fully, c (1.5) fully, a (2.0) partially
    assert claimed[0] == (b.account_id.key_bytes, 100)
    assert claimed[1] == (c.account_id.key_bytes, 100)
    assert claimed[2][0] == a.account_id.key_bytes
    assert 0 < claimed[2][1] <= 100


def test_update_offer_preserves_passive_flag(market):
    led, root, issuer, usd, a, b, c = market
    f = a.tx([_op_passive(a, XLM, usd, 1000, 2, 1)])
    assert led.apply_frame(f)
    oid = f.result.op_results[0].value.value.value.offer.value.offerID
    # update amount through manage_sell_offer keeps PASSIVE_FLAG
    f2 = a.tx([a.op_manage_sell_offer(XLM, usd, 500, 2, 1, oid)])
    assert led.apply_frame(f2)
    e = led.root.get_entry(X.LedgerKey.offer(a.account_id, oid))
    from stellar_core_tpu.transactions.offers import OfferEntryFlags
    assert e.data.value.flags & OfferEntryFlags.PASSIVE_FLAG
    assert e.data.value.amount == 500


def test_update_by_id_moves_an_offer_to_another_pair(market):
    """An update by offerID may name another pair (reference
    ManageOfferOpFrameBase: the old offer is pulled, the new one built
    from the op): the offer leaves its old side and is the best of its
    new one. The native engine's price index is held to this by
    tests/test_native_apply.py::test_index_update_moves_offer_to_another_pair."""
    led, root, issuer, usd, a, b, c = market
    eur = Asset.credit("EUR", issuer.account_id)
    for acct in (a, b):
        assert acct.change_trust(eur, 10**12)
        assert issuer.pay(acct, 10**9, eur)
    f = a.tx([a.op_manage_sell_offer(usd, XLM, 100, 2, 1)])
    assert led.apply_frame(f)
    oid = f.result.op_results[0].value.value.value.offer.value.offerID
    assert led.apply_frame(
        c.tx([c.op_manage_sell_offer(usd, XLM, 100, 3, 1)]))
    assert led.apply_frame(
        a.tx([a.op_manage_sell_offer(eur, XLM, 100, 2, 1, oid)]))
    moved = led.root.get_entry(X.LedgerKey.offer(a.account_id, oid))
    assert moved.data.value.selling == eur
    # a taker of USD meets c's offer at 3, not a's old one at 2 ...
    f = b.tx([_op_buy(b, XLM, usd, 10, 5, 1)])
    assert led.apply_frame(f), f.result
    succ = f.result.op_results[0].value.value.value
    assert [atom.sellerID.key_bytes for atom in succ.offersClaimed] == \
        [c.account_id.key_bytes]
    # ... and a taker of EUR meets a's
    f = b.tx([_op_buy(b, XLM, eur, 10, 5, 1)])
    assert led.apply_frame(f), f.result
    succ = f.result.op_results[0].value.value.value
    assert [(atom.offerID, atom.amountSold)
            for atom in succ.offersClaimed] == [(oid, 10)]


# ------------------------------------------------ herder value validation

def test_herder_rejects_bad_close_times():
    """HerderSCPDriver.validate_value: closeTime must advance past the LCL
    and stay within the +60s drift window (HerderTests closeTime rules)."""
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.scp.driver import ValidationLevel
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr import StellarValue, StellarValueExt

    cfg = Config.test_config(0)
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()
    drv = app.herder.scp_driver
    lm = app.ledger_manager
    slot = lm.lcl_header.ledgerSeq + 1
    lcl_ct = lm.lcl_header.scpValue.closeTime
    now = int(app.clock.system_now())

    def sv(ct):
        return StellarValue(txSetHash=b"\x11" * 32, closeTime=ct,
                            upgrades=[], ext=StellarValueExt(0, None)).to_xdr()

    # not after the LCL close time → invalid
    assert drv.validate_value(slot, sv(lcl_ct), False) == \
        ValidationLevel.INVALID
    # implausibly far future → invalid
    assert drv.validate_value(slot, sv(now + 3600), False) == \
        ValidationLevel.INVALID
    # sane close time but unknown txset → MAYBE_VALID specifically
    assert drv.validate_value(slot, sv(max(lcl_ct + 1, now)), False) == \
        ValidationLevel.MAYBE_VALID
    # garbage value bytes → invalid
    assert drv.validate_value(slot, b"\x01\x02", False) == \
        ValidationLevel.INVALID


@pytest.mark.min_version(11)
def test_combine_candidates_prefers_size_then_fees():
    """reference HerderSCPDriver::combineCandidates + compareTxSets: the
    winning txset has the most capacity units, then (v11+) the highest
    total fees; closeTime is the max and upgrades merge per-type max."""
    from stellar_core_tpu.herder.txset import TxSetFrame
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.testing import AppLedgerAdapter
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr import StellarValue, StellarValueExt

    cfg = Config.test_config(0)
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    ad = AppLedgerAdapter(app)
    root = ad.root_account()
    a = root.create(10**9)
    b = root.create(10**9)
    lm = app.ledger_manager
    drv = app.herder.scp_driver
    slot = lm.lcl_header.ledgerSeq + 1
    ct = lm.lcl_header.scpValue.closeTime + 5

    # same size (1 op each), different fee bids
    low = TxSetFrame(app.config.network_id, lm.lcl_hash,
                     [a.tx([a.op_payment(root.account_id, 1)], fee=100)])
    high = TxSetFrame(app.config.network_id, lm.lcl_hash,
                      [b.tx([b.op_payment(root.account_id, 1)], fee=900)])
    pend = app.herder.pending
    pend.add_tx_set(low.get_contents_hash(), low)
    pend.add_tx_set(high.get_contents_hash(), high)

    def val(ts, close):
        return StellarValue(txSetHash=ts.get_contents_hash(),
                            closeTime=close, upgrades=[],
                            ext=StellarValueExt(0, None)).to_xdr()

    combined = drv.combine_candidates(
        slot, [val(low, ct), val(high, ct + 3)])
    got = StellarValue.from_xdr(combined)
    assert got.txSetHash == high.get_contents_hash()  # higher fees win
    assert got.closeTime == ct + 3                    # max close time

    # a bigger (2-op) set beats higher fees
    big = TxSetFrame(app.config.network_id, lm.lcl_hash, [
        a.tx([a.op_payment(root.account_id, 1),
              a.op_payment(root.account_id, 2)], fee=200,
             seq=low.frames[0].seq_num)])
    pend.add_tx_set(big.get_contents_hash(), big)
    combined = drv.combine_candidates(slot, [val(big, ct), val(high, ct)])
    got = StellarValue.from_xdr(combined)
    assert got.txSetHash == big.get_contents_hash()

    # txsets based on the WRONG previous ledger are excluded
    stale = TxSetFrame(app.config.network_id, b"\x77" * 32,
                       [a.tx([a.op_payment(root.account_id, 9)], fee=999,
                             seq=low.frames[0].seq_num)])
    pend.add_tx_set(stale.get_contents_hash(), stale)
    combined = drv.combine_candidates(slot, [val(stale, ct), val(low, ct)])
    got = StellarValue.from_xdr(combined)
    assert got.txSetHash == low.get_contents_hash()


@pytest.mark.min_version(11)
def test_signed_stellar_values_rules():
    """v11+ nomination values must be SIGNED and verify; ballot values
    must be BASIC (reference validateValueHelper:203-334,
    signStellarValue/verifyStellarValueSignature)."""
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.config import Config
    from stellar_core_tpu.scp.driver import ValidationLevel
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    from stellar_core_tpu.xdr import StellarValue, StellarValueExt

    cfg = Config.test_config(0)
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    app.manual_close()
    drv = app.herder.scp_driver
    lm = app.ledger_manager
    slot = lm.lcl_header.ledgerSeq + 1
    ct = max(lm.lcl_header.scpValue.closeTime + 1,
             int(app.clock.system_now()))

    def make(signed, tamper=False):
        sv = StellarValue(txSetHash=b"\x22" * 32, closeTime=ct,
                          upgrades=[], ext=StellarValueExt(0, None))
        if signed:
            app.herder.sign_stellar_value(sv)
            if tamper:
                sig = bytearray(sv.ext.value.signature)
                sig[0] ^= 1
                sv.ext.value.signature = bytes(sig)
        return sv.to_xdr()

    # nomination at v13: BASIC rejected, SIGNED accepted (as MAYBE/FULL
    # depending on txset availability — unknown txset → MAYBE_VALID)
    assert drv.validate_value(slot, make(False), True) == \
        ValidationLevel.INVALID
    assert drv.validate_value(slot, make(True), True) == \
        ValidationLevel.MAYBE_VALID
    # a tampered signature is rejected outright
    assert drv.validate_value(slot, make(True, tamper=True), True) == \
        ValidationLevel.INVALID
    # ballot protocol never accepts SIGNED
    assert drv.validate_value(slot, make(True), False) == \
        ValidationLevel.INVALID
    # live consensus still externalizes end to end with signed nomination
    from stellar_core_tpu.testing import AppLedgerAdapter
    ad = AppLedgerAdapter(app)
    root = ad.root_account()
    assert ad.apply_frame(root.tx([root.op_payment(root.account_id, 1)]))
