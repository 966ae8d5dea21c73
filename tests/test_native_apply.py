"""Differential test: native transaction-apply ≡ Python apply.

The native engine (native/applyc.c via ledger/native_apply.py) must be
entry-for-entry identical to the Python fee+apply phases: same ledger
state, same TransactionResult XDR, same fee/tx meta XDR, same header
hash. Two LedgerManagers close identical LedgerCloseData — one with the
engine enabled, one pinned to the Python path — and every close compares
the full observable surface. The randomized matrix drives the
payment/create-account/multisig workload of the replay bench plus every
failure arm the engine claims to implement; unsupported ops exercise the
bail-to-Python contract (both sides must still agree).
"""

import random

import pytest

from stellar_core_tpu.crypto.hashing import sha256
from stellar_core_tpu.crypto.keys import SecretKey
from stellar_core_tpu.herder.txset import TxSetFrame
from stellar_core_tpu.ledger.ledger_manager import (
    LedgerCloseData, LedgerManager,
)
from stellar_core_tpu.native import apply_engine
from stellar_core_tpu.testing import (
    TESTING_NETWORK_ID, TestAccount, root_secret_key,
)
from stellar_core_tpu.transactions.transaction_frame import TransactionFrame
from stellar_core_tpu.xdr import (
    Asset, LedgerEntryChanges, StellarValue, StellarValueExt, TimeBounds,
    TransactionEnvelope, TransactionResultCode,
)
from stellar_core_tpu.xdr.codec import Unpacker, xdr_bytes

pytestmark = pytest.mark.skipif(
    apply_engine() is None, reason="native apply engine unavailable")

FEE = 100
RESERVE = 5_000_000
MIN0 = 2 * RESERVE


class _StubConfig:
    DATABASE = "in-memory"
    LEDGER_PROTOCOL_VERSION = 13
    GENESIS_TOTAL_COINS = 10 ** 17
    TESTING_UPGRADE_DESIRED_FEE = FEE
    TESTING_UPGRADE_RESERVE = RESERVE
    TESTING_UPGRADE_MAX_TX_SET_SIZE = 1000
    network_id = TESTING_NETWORK_ID


class _StubApp:
    config = _StubConfig()

    def network_root_key(self):
        return root_secret_key()


class _Shim:
    """TestAccount's ledger surface over one side's root (seq/header
    reads for tx building only)."""

    def __init__(self, lm):
        self.lm = lm
        self.network_id = TESTING_NETWORK_ID

    def header(self):
        return self.lm.root.get_header()

    def seq_num(self, account_id):
        from stellar_core_tpu.xdr import LedgerKey
        e = self.lm.root.get_entry(LedgerKey.account(account_id))
        return e.data.value.seqNum if e is not None else 0


class DiffHarness:
    """Two LedgerManagers over identical genesis; every close applies the
    same envelopes to both and asserts the full observable surface
    matches. Transactions are BUILT against the native side's state (the
    states are asserted identical after every close)."""

    def __init__(self):
        self.native = self._mk(True)
        self.python = self._mk(False)
        self.shim = _Shim(self.native)
        self.closes_native = 0  # closes the engine actually handled

    @staticmethod
    def _mk(native):
        lm = LedgerManager(_StubApp())
        lm.start_new_ledger()
        lm.use_native_apply = native
        return lm

    def account(self, sk):
        return TestAccount(self.shim, sk)

    def close(self, frames):
        """Close one ledger on both sides from the same wire bytes;
        returns the native side's frames (results installed)."""
        blobs = [f.envelope_bytes() for f in frames]
        out = []
        for lm in (self.native, self.python):
            fr = [TransactionFrame.make_from_wire(
                TESTING_NETWORK_ID, TransactionEnvelope.from_xdr(b))
                for b in blobs]
            header = lm.root.get_header()
            ts = TxSetFrame(TESTING_NETWORK_ID, lm.lcl_hash, fr)
            value = StellarValue(
                txSetHash=ts.get_contents_hash(),
                closeTime=header.scpValue.closeTime + 5,
                upgrades=[], ext=StellarValueExt(0, None))
            lm.close_ledger(
                LedgerCloseData(header.ledgerSeq + 1, ts, value))
            out.append(ts.sort_for_apply())
        nat, pyf = out
        self._compare(nat, pyf)
        if any(f._native_meta_b is not None for f in nat):
            assert all(f._native_meta_b is not None for f in nat)
            self.closes_native += 1
        return nat

    def _compare(self, nat_frames, py_frames):
        # header hash covers txSetResultHash, bucketListHash and feePool
        assert self.native.lcl_hash == self.python.lcl_hash, \
            "header hash diverged"
        ents_n = sorted(e.to_xdr() for e in self.native.root.all_entries())
        ents_p = sorted(e.to_xdr() for e in self.python.root.all_entries())
        assert ents_n == ents_p, "ledger state diverged"
        for fn, fp in zip(nat_frames, py_frames):
            assert fn.contents_hash() == fp.contents_hash()
            assert fn.result.to_xdr() == fp.result.to_xdr(), \
                "tx result diverged for %s" % fn.contents_hash().hex()[:8]
            assert xdr_bytes(LedgerEntryChanges, fn.fee_meta) == \
                xdr_bytes(LedgerEntryChanges, fp.fee_meta), \
                "fee meta diverged"
            assert fn.tx_meta().to_xdr() == fp.tx_meta().to_xdr(), \
                "tx meta diverged"


def _mk_accounts(h, n_users=6):
    """Fund users/issuers, configure multisig + trustlines through the
    (both-sides-Python) setup closes; returns the account handles."""
    root = h.account(root_secret_key())
    users = [h.account(SecretKey.from_seed(sha256(b"user%d" % i)))
             for i in range(n_users)]
    ix = h.account(SecretKey.from_seed(sha256(b"issuer-x")))
    iy = h.account(SecretKey.from_seed(sha256(b"issuer-y")))

    h.close([root.tx(
        [root.op_create_account(u.account_id, 50 * MIN0) for u in users] +
        [root.op_create_account(a.account_id, 50 * MIN0)
         for a in (ix, iy)])])

    # u0: 2 extra signers, med threshold 3 (master 1 + 1 + 1)
    # u1: 19 extra signers, med threshold 20 (the bench's 20-of-20 shape)
    u0_sks = [SecretKey.from_seed(sha256(b"u0-s%d" % i)) for i in range(2)]
    u1_sks = [SecretKey.from_seed(sha256(b"u1-s%d" % i)) for i in range(19)]
    from stellar_core_tpu.xdr import AccountFlags
    h.close([
        users[0].tx([users[0].op_add_signer(sk.public_key.key_bytes)
                     for sk in u0_sks] +
                    [users[0].op_set_options(med=3)]),
        users[1].tx([users[1].op_add_signer(sk.public_key.key_bytes)
                     for sk in u1_sks] +
                    [users[1].op_set_options(med=20)]),
        iy.tx([iy.op_set_options(
            set_flags=AccountFlags.AUTH_REQUIRED_FLAG)]),
    ])

    X = Asset.credit("USD", ix.account_id)
    Y = Asset.credit("EURO12CHARSX", iy.account_id)
    h.close([
        users[2].tx([users[2].op_change_trust(X, 10 ** 12)]),
        users[3].tx([users[3].op_change_trust(X, 10 ** 12),
                     users[3].op_change_trust(Y, 10 ** 12)]),
        users[4].tx([users[4].op_change_trust(X, 1000)]),
    ])
    # seed credit balances (issuer-source arm of the native engine)
    h.close([ix.tx([ix.op_payment(users[2].account_id, 10 ** 9, X),
                    ix.op_payment(users[3].account_id, 10 ** 9, X)])])
    return root, users, ix, iy, X, Y, u0_sks, u1_sks


def test_native_apply_smoke():
    """Tier-1 smoke: success + core failure arms agree native-vs-Python
    on a small ledger, and the engine actually handled the payment
    closes (differential equality is vacuous otherwise)."""
    h = DiffHarness()
    root, users, ix, iy, X, Y, u0_sks, u1_sks = _mk_accounts(h)
    ghost = SecretKey.from_seed(sha256(b"ghost"))

    frames = h.close([
        users[2].tx([users[2].op_payment(users[3].account_id, 12345, X)]),
        users[3].tx([users[3].op_payment(users[4].account_id, 500, X),
                     users[3].op_payment(root.account_id, 777)]),
        users[0].tx([users[0].op_payment(root.account_id, 1)],
                    extra_signers=u0_sks),
        users[1].tx([users[1].op_payment(root.account_id, 1)],
                    extra_signers=u1_sks),
        users[5].tx([users[5].op_payment(ghost.public_key, 5)]),
        users[4].tx([users[4].op_payment(users[2].account_id, 10 ** 14)]),
    ])
    codes = [f.result.code for f in frames]
    assert codes.count(TransactionResultCode.txSUCCESS) == 4
    assert codes.count(TransactionResultCode.txFAILED) == 2
    assert h.closes_native >= 1, "engine never ran — test is vacuous"

    # bad seq / insufficient fee / time bounds / bad auth arms
    frames = h.close([
        users[2].tx([users[2].op_payment(root.account_id, 1)],
                    seq=users[2].next_seq() + 7),
        users[3].tx([users[3].op_payment(root.account_id, 1)], fee=1),
        users[5].tx([users[5].op_payment(root.account_id, 1)],
                    time_bounds=TimeBounds(minTime=2 ** 40, maxTime=0)),
        root.tx([root.op_payment(users[0].account_id, 1)],
                extra_signers=[ghost]),   # extra unused sig
    ])
    assert sorted(f.result.code for f in frames) == sorted([
        TransactionResultCode.txBAD_SEQ,
        TransactionResultCode.txINSUFFICIENT_FEE,
        TransactionResultCode.txTOO_EARLY,
        TransactionResultCode.txBAD_AUTH_EXTRA,
    ])  # frames come back in sort_for_apply order
    assert h.closes_native >= 2


def test_native_apply_set_options_arms():
    """SET_OPTIONS joined the engine's subset (the bench's multisig-
    arming ledgers are 100% set_options): every arm the Python frame
    implements must agree entry-for-entry — signer add/update/remove,
    thresholds, flags (incl. immutable lockout), homeDomain,
    inflationDest, TOO_MANY_SIGNERS and LOW_RESERVE failures."""
    from stellar_core_tpu.xdr import AccountFlags, Signer, SignerKey

    h = DiffHarness()
    root = h.account(root_secret_key())
    a = h.account(SecretKey.from_seed(sha256(b"so-a")))
    b = h.account(SecretKey.from_seed(sha256(b"so-b")))
    poor = h.account(SecretKey.from_seed(sha256(b"so-poor")))
    h.close([root.tx([root.op_create_account(a.account_id, 50 * MIN0),
                      root.op_create_account(b.account_id, 50 * MIN0),
                      root.op_create_account(poor.account_id, MIN0)])])
    sks = [SecretKey.from_seed(sha256(b"so-s%d" % i)) for i in range(21)]

    # add, update weight, remove, thresholds, homeDomain, inflationDest
    frames = h.close([
        a.tx([a.op_add_signer(sks[0].public_key.key_bytes, 5),
              a.op_add_signer(sks[1].public_key.key_bytes, 7),
              a.op_add_signer(sks[0].public_key.key_bytes, 9),   # update
              a.op_add_signer(sks[1].public_key.key_bytes, 0),   # remove
              a.op_set_options(master_weight=11, low=1, med=15, high=20,
                               home_domain="example.com",
                               inflation_dest=b.account_id)]),
        b.tx([b.op_set_options(set_flags=AccountFlags.AUTH_REQUIRED_FLAG |
                               AccountFlags.AUTH_REVOCABLE_FLAG),
              b.op_set_options(clear_flags=AccountFlags.AUTH_REVOCABLE_FLAG)]),
        poor.tx([poor.op_set_options(
            inflation_dest=SecretKey.from_seed(
                sha256(b"so-ghost")).public_key)]),  # INVALID_INFLATION
    ])
    codes = [f.result.code for f in frames]  # sort_for_apply order
    assert codes.count(TransactionResultCode.txSUCCESS) == 2
    assert codes.count(TransactionResultCode.txFAILED) == 1  # poor: infl
    assert h.closes_native >= 2

    # the updated signer set actually gates auth: MED is 15, so the
    # master (11) alone cannot move a payment — sks[0] (weight 9,
    # updated from 5) must be consumed too
    frames = h.close([
        a.tx([a.op_payment(root.account_id, 1)], extra_signers=[sks[0]]),
    ])
    assert frames[0].result.code == TransactionResultCode.txSUCCESS

    # immutable lockout + TOO_MANY_SIGNERS + LOW_RESERVE arms
    h.close([b.tx([b.op_set_options(
        set_flags=AccountFlags.AUTH_IMMUTABLE_FLAG)])])
    frames = h.close([
        b.tx([b.op_set_options(clear_flags=1)]),          # CANT_CHANGE
        a.tx([a.op_add_signer(sk.public_key.key_bytes) for sk in sks],
             extra_signers=[sks[0]]),                     # 21st: TOO_MANY
        poor.tx([poor.op_add_signer(sks[2].public_key.key_bytes)]),
    ])
    assert [f.result.code for f in frames].count(
        TransactionResultCode.txFAILED) == 3  # poor: LOW_RESERVE
    assert h.closes_native >= 5


def test_native_apply_residual_bails():
    """Inputs still outside the engine's subset after full op coverage
    (ISSUE 13) fall back to Python on the native side — and both sides
    still agree. A wire threshold over 255 is one such residual: the
    Python oracle raises mid-close on it at apply, so the engine must
    decline BEFORE mutating state."""
    h = DiffHarness()
    root = h.account(root_secret_key())
    a = h.account(SecretKey.from_seed(sha256(b"bail-a")))
    h.close([root.tx([root.op_create_account(a.account_id, 20 * MIN0)])])
    before = h.closes_native
    # ops that USED to bail the close now run natively end-to-end
    Z = Asset.credit("ZZZ", root.account_id)
    frames = h.close([
        a.tx([a.op_change_trust(Z, 100),
              a.op_payment(root.account_id, 5)]),
    ])
    assert h.closes_native == before + 1  # full-coverage: no bail
    assert frames[0].result.code == TransactionResultCode.txSUCCESS
    # residual: threshold-range stays on the Python path (the oracle
    # RAISES applying it, so both sides must agree by both declining —
    # the frame build itself is fine, only apply would blow up). Build
    # the >255 threshold at the XDR layer; assert the native side
    # classifies the bail instead of running the close.
    from stellar_core_tpu.ledger.native_apply import native_apply_txset
    from stellar_core_tpu.ledger.ledgertxn import LedgerTxn
    bad = a.tx([a.op_set_options(med=300)])
    lm = h.native
    ltx = LedgerTxn(lm.root)
    try:
        header = ltx.load_header()
        header.ledgerSeq += 1
        assert not native_apply_txset(lm, ltx, [bad], None, None)
    finally:
        ltx.rollback()


def test_native_apply_differential_randomized():
    """Randomized matrix over the engine's whole claimed subset: native
    payments, credit payments (incl. issuer source/dest, unauthorized
    lines, small limits), create-account arms, multisig sources, bad
    seq/fee/timebounds/auth, multi-op txs with distinct op sources."""
    rng = random.Random(0xAB1E)
    h = DiffHarness()
    root, users, ix, iy, X, Y, u0_sks, u1_sks = _mk_accounts(h)
    ghost = SecretKey.from_seed(sha256(b"rand-ghost"))
    fresh_n = 0

    def rand_frames():
        nonlocal fresh_n
        frames = []
        # each close: every account is a tx source at most once, so the
        # builder's seq reads stay truthful whatever fails
        sources = [root, users[2], users[3], users[4], users[5],
                   users[0], users[1]]
        rng.shuffle(sources)
        for src in sources:
            if rng.random() < 0.25:
                continue
            kind = rng.random()
            extra = None
            kwargs = {}
            if src is users[0]:
                extra = u0_sks
            elif src is users[1]:
                extra = u1_sks
            if kind < 0.30:   # native payment, occasionally absurd amount
                amt = rng.choice([1, 10 ** 6, 10 ** 15, 10 ** 18])
                ops = [src.op_payment(
                    rng.choice(users + [root]).account_id, amt)]
            elif kind < 0.50:  # credit payment on X
                amt = rng.choice([1, 500, 10 ** 8, 5 * 10 ** 9])
                dest = rng.choice([users[2], users[3], users[4],
                                   users[5], ix])
                ops = [src.op_payment(dest.account_id, amt, X)]
            elif kind < 0.60:  # Y arms: unauthorized / no trust
                ops = [src.op_payment(
                    rng.choice([users[3], iy]).account_id, 10, Y)]
            elif kind < 0.75:  # create-account arms
                fresh_n += 1
                dest = rng.choice([
                    SecretKey.from_seed(sha256(b"fresh%d" % fresh_n))
                    .public_key,
                    users[3].account_id,          # ALREADY_EXIST
                ])
                amt = rng.choice([MIN0 - 1, MIN0, 3 * MIN0, 10 ** 17])
                ops = [src.op_create_account(dest, amt)]
            elif kind < 0.80:  # set_options arms (engine-native): random
                # signer/threshold/flag/home/inflation mutations — lockouts
                # and stale-signer auth failures are fair game, both sides
                # must just agree
                from stellar_core_tpu.xdr import Signer, SignerKey
                kw = {}
                if rng.random() < 0.5:
                    kw["signer"] = Signer(
                        key=SignerKey.ed25519(SecretKey.from_seed(
                            sha256(b"so-rnd%d" % rng.randrange(3)))
                            .public_key.key_bytes),
                        weight=rng.choice([0, 1, 2]))
                if rng.random() < 0.35:
                    kw["low"] = rng.choice([0, 1])
                    kw["med"] = rng.choice([0, 1])
                    kw["high"] = rng.choice([0, 1])
                if rng.random() < 0.3:
                    kw["home_domain"] = rng.choice(
                        ["", "a.example", "x" * 32])
                if rng.random() < 0.3:
                    kw["inflation_dest"] = rng.choice(
                        [users[2].account_id, ghost.public_key])
                if rng.random() < 0.3:
                    kw["set_flags" if rng.random() < 0.5
                       else "clear_flags"] = rng.choice([1, 2, 3])
                ops = [src.op_set_options(**kw)]
            elif kind < 0.85:  # multi-op, second op from another source
                if src is users[1]:
                    continue  # 19 signers + other + master > 20-sig cap
                other = rng.choice([u for u in users[2:] if u is not src])
                ops = [src.op_payment(other.account_id, 100),
                       other.op(other.op_payment(
                           src.account_id, 50).body,
                           source=other.account_id)]
                extra = (extra or []) + [other.sk]
            elif kind < 0.90:  # bad seq
                frames.append(src.tx(
                    [src.op_payment(root.account_id, 1)],
                    seq=src.next_seq() + rng.choice([1, 5]),
                    extra_signers=extra))
                continue
            elif kind < 0.95:  # fee / time bounds
                ops = [src.op_payment(root.account_id, 1)]
                if rng.random() < 0.5:
                    kwargs["fee"] = rng.choice([1, 99])
                else:
                    kwargs["time_bounds"] = rng.choice([
                        TimeBounds(minTime=2 ** 40, maxTime=0),
                        TimeBounds(minTime=0, maxTime=1),
                    ])
            else:              # auth failure: unconsumable extra sig
                if src is users[1]:
                    continue  # 19 signers + master leave no room for a
                    # 21st signature under the envelope cap
                ops = [src.op_payment(root.account_id, 1)]
                extra = (extra or []) + [ghost]   # BAD_AUTH_EXTRA
            frames.append(src.tx(ops, extra_signers=extra, **kwargs))
        return frames

    seen = set()
    for _ in range(6):
        for f in h.close(rand_frames()):
            seen.add(f.result.code)
    assert h.closes_native >= 4, \
        "engine handled too few closes (%d)" % h.closes_native
    assert TransactionResultCode.txSUCCESS in seen
    assert TransactionResultCode.txFAILED in seen


# ---------------------------------------------------------------------------
# Full op-type coverage (ISSUE 13): every wire op, fee bumps, muxed
# accounts — the native engine must agree with the Python oracle on all
# of them, entry for entry.

def _muxed(pk, sub_id=7):
    from stellar_core_tpu.xdr import CryptoKeyType, MuxedAccount
    from stellar_core_tpu.xdr.basic import MuxedAccountMed25519
    return MuxedAccount(CryptoKeyType.KEY_TYPE_MUXED_ED25519,
                        MuxedAccountMed25519(id=sub_id,
                                             ed25519=pk.key_bytes))


def _fee_bump(h, sponsor, inner_frame, fee=2000, signers=None,
              muxed_source=False):
    from stellar_core_tpu.transactions.transaction_frame import (
        FeeBumpTransactionFrame,
    )
    from stellar_core_tpu.xdr import (
        EnvelopeType, FeeBumpTransaction, FeeBumpTransactionEnvelope,
        TransactionEnvelope, _Ext,
    )
    from stellar_core_tpu.xdr.transaction import _InnerTxEnvelope
    fb = FeeBumpTransaction(
        feeSource=_muxed(sponsor.account_id) if muxed_source
        else sponsor.muxed,
        fee=fee,
        innerTx=_InnerTxEnvelope(EnvelopeType.ENVELOPE_TYPE_TX,
                                 inner_frame.envelope.value),
        ext=_Ext.v0())
    env = TransactionEnvelope(
        EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP,
        FeeBumpTransactionEnvelope(tx=fb, signatures=[]))
    frame = FeeBumpTransactionFrame(TESTING_NETWORK_ID, env)
    for sk in (signers if signers is not None else [sponsor.sk]):
        frame.add_signature(sk)
    return frame


def _op_muxed_payment(src, dest_pk, amount, asset=None, sub_id=9):
    from stellar_core_tpu.xdr import OperationBody, OperationType, PaymentOp
    return src.op(OperationBody(
        OperationType.PAYMENT,
        PaymentOp(destination=_muxed(dest_pk, sub_id),
                  asset=asset or Asset.native(), amount=amount)))


def _coverage_world(h):
    """Accounts + trustlines + an auth-required issuer + a resting order
    book, built through both-sides closes."""
    root = h.account(root_secret_key())
    users = [h.account(SecretKey.from_seed(sha256(b"cov%d" % i)))
             for i in range(8)]
    ix = h.account(SecretKey.from_seed(sha256(b"cov-ix")))
    ir = h.account(SecretKey.from_seed(sha256(b"cov-ir")))  # auth required
    h.close([root.tx(
        [root.op_create_account(u.account_id, 50 * MIN0) for u in users] +
        [root.op_create_account(a.account_id, 50 * MIN0)
         for a in (ix, ir)])])
    from stellar_core_tpu.xdr import AccountFlags
    h.close([ir.tx([ir.op_set_options(
        set_flags=AccountFlags.AUTH_REQUIRED_FLAG |
        AccountFlags.AUTH_REVOCABLE_FLAG)])])
    X = Asset.credit("USD", ix.account_id)
    R = Asset.credit("RST", ir.account_id)
    h.close([
        users[0].tx([users[0].op_change_trust(X, 10 ** 12),
                     users[0].op_change_trust(R, 10 ** 12)]),
        users[1].tx([users[1].op_change_trust(X, 10 ** 12),
                     users[1].op_change_trust(R, 10 ** 12)]),
        users[2].tx([users[2].op_change_trust(X, 10 ** 12)]),
        users[3].tx([users[3].op_change_trust(X, 10 ** 12)]),
    ])
    h.close([
        ir.tx([ir.op_allow_trust(users[0].account_id, b"RST\x00"),
               ir.op_allow_trust(users[1].account_id, b"RST\x00")]),
        ix.tx([ix.op_payment(users[0].account_id, 10 ** 9, X),
               ix.op_payment(users[1].account_id, 10 ** 9, X)]),
    ])
    return root, users, ix, ir, X, R


def test_native_apply_all_op_types():
    """Scripted pass over every op type the wire knows, asserted
    entry-for-entry equal between the engine and the oracle, with the
    engine actually running every close."""
    h = DiffHarness()
    root, users, ix, ir, X, R = _coverage_world(h)
    u0, u1, u2, u3, u4, u5, u6, u7 = users
    before = h.closes_native

    # change_trust / allow_trust / manage_data / bump_seq / set_options
    frames = h.close([
        u4.tx([u4.op_change_trust(X, 500),          # create small line
               u4.op_manage_data("k1", b"v1"),      # data create
               u4.op_manage_data("k1", b"v2"),      # data update
               u4.op_manage_data("k2", b"zz")]),
        u5.tx([u5.op_manage_data("gone", None)]),   # NAME_NOT_FOUND
        u6.tx([u6.op(u6.op_manage_data("tmp", b"x").body),
               u6.op(u6.op_manage_data("tmp", None).body)]),  # delete
        ir.tx([ir.op_allow_trust(u1.account_id, b"RST\x00",
                                 authorize=0)]),    # full revoke
    ])
    assert frames[0].result.code == TransactionResultCode.txSUCCESS
    # bump_sequence: up, then a no-op bump (lower target)
    cur = u7.next_seq()
    from stellar_core_tpu.xdr import OperationBody, OperationType
    from stellar_core_tpu.xdr.transaction import BumpSequenceOp
    h.close([
        u7.tx([u7.op(OperationBody(OperationType.BUMP_SEQUENCE,
                                   BumpSequenceOp(bumpTo=cur + 50))),
               u7.op(OperationBody(OperationType.BUMP_SEQUENCE,
                                   BumpSequenceOp(bumpTo=3)))]),
    ])

    # offers: resting book, crossing, passive, buy offers, update/delete
    h.close([
        u0.tx([u0.op_manage_sell_offer(X, Asset.native(), 1000, 2, 1),
               u0.op_manage_sell_offer(X, Asset.native(), 500, 3, 1)]),
        u1.tx([u1.op_create_passive_sell_offer(Asset.native(), X, 100,
                                               1, 2)]),
    ])
    frames = h.close([
        u2.tx([u2.op_manage_sell_offer(Asset.native(), X, 600, 1, 1)]),
        u3.tx([u3.op_manage_buy_offer(Asset.native(), X, 300, 1, 2)]),
    ])
    for f in frames:
        assert f.result.code == TransactionResultCode.txSUCCESS, \
            f.result.to_xdr()
    # offer update + delete by id (ids are deterministic: idPool order)
    hdr = h.native.root.get_header()
    assert hdr.idPool >= 3
    h.close([
        u0.tx([u0.op_manage_sell_offer(X, Asset.native(), 700, 2, 1,
                                       offer_id=1),
               u0.op_manage_sell_offer(X, Asset.native(), 0, 2, 1,
                                       offer_id=2)]),
    ])

    # path payments: strict receive + strict send through X
    frames = h.close([
        u0.tx([u0.op(OperationBody(
            OperationType.PATH_PAYMENT_STRICT_RECEIVE,
            __import__("stellar_core_tpu.xdr.transaction",
                       fromlist=["PathPaymentStrictReceiveOp"])
            .PathPaymentStrictReceiveOp(
                sendAsset=X, sendMax=10 ** 9,
                destination=u3.muxed, destAsset=Asset.native(),
                destAmount=50, path=[])))]),
    ])
    # inflation at protocol 13: opNOT_SUPPORTED -> txFAILED (native)
    frames = h.close([
        u5.tx([u5.op(OperationBody(OperationType.INFLATION, None))]),
    ])
    assert frames[0].result.code == TransactionResultCode.txFAILED

    # account merge: fresh account merges into its funder
    fresh = h.account(SecretKey.from_seed(sha256(b"cov-merge")))
    h.close([root.tx([root.op_create_account(fresh.account_id,
                                             3 * MIN0)])])
    from stellar_core_tpu.xdr import MuxedAccount
    frames = h.close([
        fresh.tx([fresh.op(OperationBody(
            OperationType.ACCOUNT_MERGE,
            MuxedAccount.from_account_id(root.account_id)))]),
    ])
    assert frames[0].result.code == TransactionResultCode.txSUCCESS

    # fee bumps + muxed accounts
    sponsor = u6
    inner = u5.tx([u5.op_payment(root.account_id, 11)])
    frames = h.close([
        _fee_bump(h, sponsor, inner),
        u4.tx([_op_muxed_payment(u4, root.account_id, 5)]),
    ])
    codes = sorted(f.result.code for f in frames)
    assert TransactionResultCode.txFEE_BUMP_INNER_SUCCESS in codes
    # muxed fee source + failing inner (bad seq)
    inner_bad = u5.tx([u5.op_payment(root.account_id, 1)],
                      seq=u5.next_seq() + 9)
    frames = h.close([
        _fee_bump(h, sponsor, inner_bad, muxed_source=True),
    ])
    assert frames[0].result.code == \
        TransactionResultCode.txFEE_BUMP_INNER_FAILED

    assert h.closes_native - before >= 9, \
        "engine skipped closes (%d)" % (h.closes_native - before)


def test_native_apply_revoke_pulls_offers():
    """AllowTrust full revoke releases the trustor's offer liabilities
    and erases the offers (the order-book walk through the engine's
    acct_offers callback) — asserted against the oracle."""
    h = DiffHarness()
    root, users, ix, ir, X, R = _coverage_world(h)
    u0 = users[0]
    # u0 posts offers selling R (the auth-required asset) and buying R
    h.close([
        u0.tx([u0.op_manage_sell_offer(R, Asset.native(), 50, 1, 1),
               u0.op_manage_sell_offer(Asset.native(), R, 40, 1, 1)]),
        ix.tx([ix.op_payment(u0.account_id, 0, X)]),  # keep close mixed
    ])
    before = h.closes_native
    frames = h.close([
        ir.tx([ir.op_allow_trust(u0.account_id, b"RST\x00",
                                 authorize=0)]),
    ])
    assert frames[0].result.code == TransactionResultCode.txSUCCESS
    assert h.closes_native == before + 1  # revoke ran natively


class ParallelDiffHarness:
    """Three managers over identical genesis: native forced-parallel,
    native forced-serial, and the Python oracle. Every close must agree
    across all three — the serial-equivalence contract of the
    conflict-graph parallel close."""

    def __init__(self):
        self.parallel = DiffHarness._mk(True)
        self.parallel.native_force_mode = "parallel"
        self.serial = DiffHarness._mk(True)
        self.serial.native_force_mode = "serial"
        self.python = DiffHarness._mk(False)
        self.shim = _Shim(self.parallel)

    def account(self, sk):
        return TestAccount(self.shim, sk)

    def close(self, frames):
        blobs = [f.envelope_bytes() for f in frames]
        outs = []
        for lm in (self.parallel, self.serial, self.python):
            fr = [TransactionFrame.make_from_wire(
                TESTING_NETWORK_ID, TransactionEnvelope.from_xdr(b))
                for b in blobs]
            header = lm.root.get_header()
            ts = TxSetFrame(TESTING_NETWORK_ID, lm.lcl_hash, fr)
            value = StellarValue(
                txSetHash=ts.get_contents_hash(),
                closeTime=header.scpValue.closeTime + 5,
                upgrades=[], ext=StellarValueExt(0, None))
            lm.close_ledger(
                LedgerCloseData(header.ledgerSeq + 1, ts, value))
            outs.append(ts.sort_for_apply())
        assert self.parallel.lcl_hash == self.serial.lcl_hash, \
            "parallel schedule diverged from serial native"
        assert self.parallel.lcl_hash == self.python.lcl_hash, \
            "native diverged from oracle"
        par, ser, _py = outs
        for fp, fs in zip(par, ser):
            assert fp.result.to_xdr() == fs.result.to_xdr()
            assert fp.tx_meta().to_xdr() == fs.tx_meta().to_xdr()
            assert xdr_bytes(LedgerEntryChanges, fp.fee_meta) == \
                xdr_bytes(LedgerEntryChanges, fs.fee_meta)
        return par


def test_native_apply_parallel_equality():
    """Forced-parallel vs forced-serial vs Python: a conflict-light
    txset (disjoint account pairs) must close identically whatever the
    schedule, and the parallel manager must actually have run clusters
    concurrently."""
    h = ParallelDiffHarness()
    root = h.account(root_secret_key())
    pairs = [(h.account(SecretKey.from_seed(sha256(b"pA%d" % i))),
              h.account(SecretKey.from_seed(sha256(b"pB%d" % i))))
             for i in range(12)]
    h.close([root.tx(
        [root.op_create_account(a.account_id, 30 * MIN0)
         for a, b in pairs] +
        [root.op_create_account(b.account_id, 30 * MIN0)
         for a, b in pairs])])
    # disjoint pairs: 12 independent clusters
    for _round in range(3):
        h.close([a.tx([a.op_payment(b.account_id, 1000 + _round)])
                 for a, b in pairs])
    # conflict-heavy mix (shared hub) + a multi-op cluster chain still
    # produce identical output — clusters just collapse
    hub = h.account(SecretKey.from_seed(sha256(b"pHub")))
    h.close([root.tx([root.op_create_account(hub.account_id,
                                             30 * MIN0)])])
    h.close([a.tx([a.op_payment(hub.account_id, 7)])
             for a, b in pairs[:6]] +
            [b.tx([b.op_payment(a.account_id, 3)])
             for a, b in pairs[6:]])
    st = h.parallel.apply_stats.clusters
    assert st["parallel_closes"] >= 3, st
    assert h.serial.apply_stats.clusters["parallel_closes"] == 0
    # width telemetry saw the disjoint rounds (clusters of 2 accounts)
    assert st["last_count"] >= 1


@pytest.mark.parametrize("seed", [7, 11])
def test_native_apply_parallel_seeded(seed):
    """Seeded randomized conflict mixes over the forced-parallel vs
    forced-serial vs oracle triple. These are the ParallelDiffHarness
    legs the ThreadSanitizer runtime gate re-drives under a
    `-fsanitize=thread` build (tests/test_native_sanitized.py,
    docs/static-analysis.md) — every schedule the seeds produce must
    close identically AND race-free."""
    rng = random.Random(seed)
    h = ParallelDiffHarness()
    root = h.account(root_secret_key())
    accs = [h.account(SecretKey.from_seed(sha256(b"ps%d-%d" % (seed, i))))
            for i in range(10)]
    h.close([root.tx([root.op_create_account(a.account_id, 40 * MIN0)
                      for a in accs])])
    for _round in range(4):
        frames = []
        for a in accs:
            if rng.random() < 0.25:
                continue
            dest = rng.choice([x for x in accs if x is not a])
            frames.append(a.tx([a.op_payment(dest.account_id,
                                             rng.randrange(1, 5000))]))
        if frames:
            h.close(frames)
    assert h.parallel.apply_stats.clusters["parallel_closes"] >= 1


def _random_full_frames(rng, h, world, fresh_counter, max_offer_id=9):
    """One close worth of random frames over ALL op types; an offer op
    that names an offer draws its id below `max_offer_id`."""
    root, users, ix, ir, X, R = world
    frames = []
    sources = list(users) + [ix]
    rng.shuffle(sources)
    for src in sources:
        if rng.random() < 0.3:
            continue
        kind = rng.random()
        if kind < 0.18:   # payments (native/credit/muxed)
            dest = rng.choice(users + [root])
            if rng.random() < 0.3:
                ops = [_op_muxed_payment(src, dest.account_id,
                                         rng.choice([1, 999]))]
            else:
                asset = rng.choice([None, X])
                ops = [src.op_payment(dest.account_id,
                                      rng.choice([1, 10 ** 7]), asset)]
        elif kind < 0.30:  # offers
            if rng.random() < 0.5:
                ops = [src.op_manage_sell_offer(
                    rng.choice([X, Asset.native()]),
                    rng.choice([Asset.native(), X]),
                    rng.choice([0, 10, 500]),
                    rng.randrange(1, 4), rng.randrange(1, 4),
                    offer_id=rng.choice(
                        [0, 0, rng.randrange(1, max_offer_id)]))]
            else:
                ops = [src.op_manage_buy_offer(
                    Asset.native(), X, rng.choice([0, 25, 400]),
                    rng.randrange(1, 4), rng.randrange(1, 4),
                    offer_id=rng.choice(
                        [0, 0, rng.randrange(1, max_offer_id)]))]
            if ops[0].body.value.selling == ops[0].body.value.buying:
                continue
        elif kind < 0.40:  # path payments
            from stellar_core_tpu.xdr.transaction import (
                PathPaymentStrictReceiveOp, PathPaymentStrictSendOp,
            )
            from stellar_core_tpu.xdr import OperationBody, OperationType
            dest = rng.choice(users)
            if rng.random() < 0.5:
                body = PathPaymentStrictReceiveOp(
                    sendAsset=rng.choice([X, Asset.native()]),
                    sendMax=rng.choice([10, 10 ** 9]),
                    destination=dest.muxed,
                    destAsset=rng.choice([Asset.native(), X]),
                    destAmount=rng.choice([5, 120]), path=[])
                ops = [src.op(OperationBody(
                    OperationType.PATH_PAYMENT_STRICT_RECEIVE, body))]
            else:
                body = PathPaymentStrictSendOp(
                    sendAsset=rng.choice([X, Asset.native()]),
                    sendAmount=rng.choice([5, 80]),
                    destination=dest.muxed,
                    destAsset=rng.choice([Asset.native(), X]),
                    destMin=rng.choice([1, 10 ** 8]), path=[])
                ops = [src.op(OperationBody(
                    OperationType.PATH_PAYMENT_STRICT_SEND, body))]
            if body.sendAsset == body.destAsset:
                continue
        elif kind < 0.52:  # change_trust arms
            ops = [src.op_change_trust(
                rng.choice([X, R]),
                rng.choice([0, 400, 10 ** 12]))]
        elif kind < 0.60:  # allow_trust (incl. revokes)
            if src is not ir:
                continue
            ops = [ir.op_allow_trust(
                rng.choice(users).account_id, b"RST\x00",
                authorize=rng.choice([0, 1, 2]))]
        elif kind < 0.70:  # manage_data
            name = rng.choice(["d1", "d2", "x" * 64])
            val = rng.choice([None, b"", b"payload", b"z" * 64])
            ops = [src.op_manage_data(name, val)]
        elif kind < 0.76:  # bump sequence
            from stellar_core_tpu.xdr import OperationBody, OperationType
            from stellar_core_tpu.xdr.transaction import BumpSequenceOp
            ops = [src.op(OperationBody(
                OperationType.BUMP_SEQUENCE,
                BumpSequenceOp(bumpTo=rng.choice([0, src.next_seq() + 40,
                                                  2 ** 40]))))]
        elif kind < 0.82:  # set_options
            ops = [src.op_set_options(
                home_domain=rng.choice(["", "cov.example"]),
                low=rng.choice([None, 0, 1]))]
        elif kind < 0.90:  # account merge of a throwaway
            fresh_counter[0] += 1
            fresh = h.account(SecretKey.from_seed(
                sha256(b"rfresh%d" % fresh_counter[0])))
            frames.append(src.tx([src.op_create_account(
                fresh.account_id, rng.choice([2 * MIN0, 3 * MIN0]))]))
            continue
        elif kind < 0.94:  # inflation (opNOT_SUPPORTED at v13)
            from stellar_core_tpu.xdr import OperationBody, OperationType
            ops = [src.op(OperationBody(OperationType.INFLATION, None))]
        else:              # fee bump (random sponsor)
            sponsor = rng.choice(users)
            if sponsor is src:
                continue
            inner = src.tx([src.op_payment(root.account_id,
                                           rng.choice([1, 17]))])
            frames.append(_fee_bump(h, sponsor, inner,
                                    fee=rng.choice([300, 5000]),
                                    muxed_source=rng.random() < 0.3))
            continue
        frames.append(src.tx(ops))
    return frames


def _run_randomized_full(rounds, seed):
    rng = random.Random(seed)
    h = DiffHarness()
    world = _coverage_world(h)
    fresh_counter = [0]
    native_before = h.closes_native
    for _ in range(rounds):
        frames = _random_full_frames(rng, h, world, fresh_counter)
        if frames:
            h.close(frames)
    assert h.closes_native > native_before


def test_native_apply_randomized_full_matrix():
    """Seeded randomized differential matrix over ALL op types, fee
    bumps, and muxed accounts (tier-1 fast variant)."""
    _run_randomized_full(6, 0xC0FFEE)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_native_apply_randomized_full_matrix_soak(seed):
    """The slow soak: more rounds, independent seeds."""
    _run_randomized_full(20, seed)


def test_cluster_fail_fault_degrades_to_serial():
    """`apply.cluster-fail` (util.faults): a would-be-parallel close
    runs the SAME close serially — never the Python path — and the
    cockpit counts the degrade. The oracle still agrees."""
    from stellar_core_tpu.util.faults import FaultInjector

    h = DiffHarness()
    h.native.app.faults = FaultInjector(seed=1)
    h.native.app.faults.configure("apply.cluster-fail", probability=1.0)
    # pin the pool width: auto sizing is min(16, cpu_count), so on a
    # 1-core host the close would never attempt parallel and the fault
    # would have nothing to degrade (instance attr — the class-level
    # config is shared with the python side)
    h.native.app.config = _StubConfig()
    h.native.app.config.NATIVE_PARALLEL_WORKERS = 4
    root = h.account(root_secret_key())
    pairs = [(h.account(SecretKey.from_seed(sha256(b"cfA%d" % i))),
              h.account(SecretKey.from_seed(sha256(b"cfB%d" % i))))
             for i in range(6)]
    h.close([root.tx(
        [root.op_create_account(a.account_id, 20 * MIN0)
         for a, b in pairs] +
        [root.op_create_account(b.account_id, 20 * MIN0)
         for a, b in pairs])])
    before = h.closes_native
    h.close([a.tx([a.op_payment(b.account_id, 100)]) for a, b in pairs])
    st = h.native.apply_stats.clusters
    assert h.closes_native == before + 1      # still native
    assert st["degraded"] >= 1                # the fault fired
    assert st["parallel_closes"] == 0         # and the close ran serial
    # clean up the class-level stub app attribute
    del h.native.app.faults


def test_pipeline_stall_fault_runs_prewarm_inline():
    """`apply.pipeline-stall` (util.faults): the catchup prewarm
    pipeline degrades to sequential — triples verify inline on the
    main thread, no worker is spawned, and the stall meter marks."""
    from stellar_core_tpu.historywork.apply_works import (
        ApplyCheckpointWork,
    )
    from stellar_core_tpu.util.faults import FaultInjector
    from stellar_core_tpu.util.metrics import MetricsRegistry

    calls = []

    class _Verifier:
        name = "cpu"

        def prewarm_many(self, triples):
            calls.append(len(triples))

    class _App:
        faults = FaultInjector(seed=2)
        metrics = MetricsRegistry()
        sig_verifier = _Verifier()

    app = _App()
    app.faults.configure("apply.pipeline-stall", probability=1.0)
    work = ApplyCheckpointWork.__new__(ApplyCheckpointWork)
    work.app = app
    work._pipeline = None
    work._range_triples = lambda first, last: [(b"k" * 32, b"s", b"m")]
    work._pipeline_submit(8, 15)
    assert calls == [1]                       # verified INLINE
    assert work._pipeline is None             # no worker spawned
    m = app.metrics.to_json().get("catchup.pipeline.stall")
    assert m and m["count"] == 1


# ------------------------------------------- the order-book index (ISSUE 32)
#
# The engine keeps each book side in price order (native/applyc.c, section
# "order books") and reads the best offer off the head of that index. The
# oracle is the Python path, which walks the side; these cases put the
# index through every way an offer's place in it changes — and assert, as
# every case of this file does, equal results, meta, delta and header
# hash on every close.

NATIVE = Asset.native()
RUNGS, PER_RUNG, LOT = 20, 15, 100


def _with_source(acct, op):
    """`op` as built by a TestAccount, with `acct` as its source: a
    transaction of ops by several accounts applies them in ITS order."""
    return TestAccount.op(op.body, source=acct.account_id)


def _claims(frame, op_index=0):
    """(offerID, amountSold) of every ClaimOfferAtom of one manage-offer
    op result."""
    succ = frame.result.op_results[op_index].value.value.value
    return [(a.offerID, a.amountSold) for a in succ.offersClaimed]


def _book(h, selling, buying):
    """The native side's resting offers of one pair, best first:
    [(offerID, seller key, n, d, amount)]."""
    return _book_of(h.native, selling, buying)


def _book_of(lm, selling, buying):
    from stellar_core_tpu.xdr import LedgerEntryType
    out = []
    for e in lm.root.all_entries():
        if e.data.disc != LedgerEntryType.OFFER:
            continue
        o = e.data.value
        if o.selling == selling and o.buying == buying:
            out.append((o.offerID, o.sellerID.key_bytes, o.price.n,
                        o.price.d, o.amount))
    out.sort(key=lambda r: (r[2] / r[3], r[0]))
    assert all(a[2] * b[3] <= b[2] * a[3] for a, b in zip(out, out[1:]))
    return out


class _DeepWorld:
    """A side of RUNGS x PER_RUNG = 300 offers selling X for native, from
    five makers, three offers a maker a rung (ties at every rung, within
    a maker and across makers): rung k asks (20 + k) / 10 native a unit
    of X, LOT units an offer (orders come in tens of units, so every fill
    is exact). A second asset Y of the same issuer and a
    taker with funds in everything."""

    def __init__(self, h, second_side=False):
        self.h = h
        root = h.account(root_secret_key())
        self.root = root
        self.ix = h.account(SecretKey.from_seed(sha256(b"deep-ix")))
        self.makers = [h.account(SecretKey.from_seed(sha256(b"deep-m%d" % i)))
                       for i in range(5)]
        self.taker = h.account(SecretKey.from_seed(sha256(b"deep-t")))
        self.other = h.account(SecretKey.from_seed(sha256(b"deep-o")))
        everyone = self.everyone = self.makers + [self.taker, self.other]
        h.close([root.tx(
            [root.op_create_account(a.account_id, 10 ** 11)
             for a in everyone + [self.ix]])])
        self.X = Asset.credit("USD", self.ix.account_id)
        self.Y = Asset.credit("EUR", self.ix.account_id)
        h.close([a.tx([a.op_change_trust(self.X, 10 ** 15),
                       a.op_change_trust(self.Y, 10 ** 15)])
                 for a in everyone])
        h.close([self.ix.tx(
            [self.ix.op_payment(a.account_id, 10 ** 10, asset)
             for a in everyone for asset in (self.X, self.Y)])])
        h.close([m.tx([m.op_manage_sell_offer(self.X, NATIVE, LOT,
                                              20 + k, 10)
                       for k in range(RUNGS) for _ in range(3)])
                 for m in self.makers])
        if second_side:
            # the other side, as deep: bids of native for X from 0.6 X a
            # native unit upwards (asks start at 2 native: no cross)
            h.close([m.tx([m.op_manage_sell_offer(NATIVE, self.X, LOT,
                                                  6 + k, 10)
                           for k in range(RUNGS) for _ in range(3)])
                     for m in self.makers])
        self.asks = _book(h, self.X, NATIVE)
        assert len(self.asks) == RUNGS * PER_RUNG
        assert len({(n, d) for _, _, n, d, _ in self.asks}) == RUNGS

    def maker_of(self, offer_id):
        key = next(r[1] for r in self.asks if r[0] == offer_id)
        return next(m for m in self.makers
                    if m.account_id.key_bytes == key)

    def take(self, units, n=10, d=1, acct=None):
        """An offer by the taker to buy `units` of X at up to n/d native
        each (a manage_buy_offer selling native)."""
        a = acct or self.taker
        return _with_source(a, a.op_manage_buy_offer(NATIVE, self.X, units,
                                                     n, d))

    def in_order(self, first_ops, then_ops):
        """Two transactions of the taker's, which apply in sequence
        order: `first_ops`, then `then_ops`; each signed by the taker and
        by exactly the other sources of its ops."""
        seq = self.taker.next_seq()
        return [_signed_by_sources(self.taker, ops, seq + i, self.everyone)
                for i, ops in enumerate((first_ops, then_ops))]


def _signed_by_sources(acct, ops, seq, accounts):
    """`acct`'s transaction of `ops`, with the signature of every other
    account an op names as its source (an unused signature fails a
    transaction, so no more than those)."""
    mine = acct.account_id.key_bytes
    keys = []
    for op in ops:
        src = op.sourceAccount
        key = src.account_id.key_bytes if src is not None else mine
        if key != mine and key not in keys:
            keys.append(key)
    by_key = {a.account_id.key_bytes: a for a in accounts}
    return acct.tx(ops, seq=seq, extra_signers=[by_key[k].sk for k in keys])


def _expect_fills(asks, units):
    """What a taker of `units` takes off `asks` (best first, whole lots
    of LOT, the last in part): [(offerID, amountSold)]."""
    out = []
    for oid, _, _, _, amount in asks:
        if units <= 0:
            break
        out.append((oid, min(amount, units)))
        units -= amount
    return out


def test_index_deep_side_crossed_across_rungs():
    """300 offers on 20 rungs; three takers in one close take 2.4, 1.1
    and 0.2 rungs' worth: price order across rungs, id order inside a
    rung, partial fill of the last."""
    h = DiffHarness()
    w = _DeepWorld(h)
    rung = PER_RUNG * LOT
    sizes = [2 * rung + 640, rung + 130, 270]
    before = h.closes_native
    frames = h.close(w.in_order([w.take(sizes[0])],
                                [w.take(sizes[1]), w.take(sizes[2])]))
    assert h.closes_native == before + 1
    assert all(f.result.code == TransactionResultCode.txSUCCESS
               for f in frames)
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    got = _claims(by_seq[0]) + _claims(by_seq[1], 0) + _claims(by_seq[1], 1)
    # one continuous walk of the side in (price, id) order; an order ends
    # inside an offer and the next goes on with that offer's rest
    want, book = [], [list(r) for r in w.asks]
    for units in sizes:
        fills = _expect_fills(book, units)
        want += fills
        for oid, sold in fills:
            row = next(r for r in book if r[0] == oid)
            row[4] -= sold
        book = [r for r in book if r[4] > 0]
    assert got == want and len(got) >= 50
    assert len({(r[2], r[3]) for r in w.asks
                if r[0] in {o for o, _ in got}}) >= 4   # rungs crossed
    assert len(_book(h, w.X, NATIVE)) == RUNGS * PER_RUNG - (len(got) - 3)


@pytest.mark.parametrize("loaded_first", [False, True],
                         ids=["side-unloaded", "side-loaded"])
def test_index_requote_better_and_worse_then_crossed(loaded_first):
    """A re-quote by offerID that moves an offer to the head of the side,
    and one that moves the head to the tail, then a taker in the same
    close: the index follows both — whether the re-quoted offers come
    from the lookup callback (side not loaded yet) or from the side's
    own rows (a taker loaded it first)."""
    h = DiffHarness()
    w = _DeepWorld(h)
    first = [w.take(30)] if loaded_first else []
    head = w.asks[1 if loaded_first else 0][0]
    mid = w.asks[10 * PER_RUNG + 4][0]    # somewhere on rung 10
    m_head, m_mid = w.maker_of(head), w.maker_of(mid)
    frames = h.close(w.in_order(
        first +
        [_with_source(m_mid, m_mid.op_manage_sell_offer(
            w.X, NATIVE, LOT, 150, 100, offer_id=mid)),      # better
         _with_source(m_head, m_head.op_manage_sell_offer(
             w.X, NATIVE, LOT, 900, 100, offer_id=head))],   # worse
        [w.take(3 * LOT)]))
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    assert all(f.result.code == TransactionResultCode.txSUCCESS
               for f in frames)
    rest = [r for r in w.asks if r[0] not in (head, mid)]
    if loaded_first:
        assert _claims(by_seq[0], 0) == [(w.asks[0][0], 30)]
        assert _claims(by_seq[1]) == [(mid, LOT), (rest[0][0], LOT - 30),
                                      (rest[1][0], LOT), (rest[2][0], 30)]
    else:
        assert _claims(by_seq[1]) == [(mid, LOT), (rest[0][0], LOT),
                                      (rest[1][0], LOT)]
    book = _book(h, w.X, NATIVE)
    assert book[-1][0] == head and (book[-1][2], book[-1][3]) == (900, 100)


@pytest.mark.parametrize("loaded_first", [False, True],
                         ids=["new-pair-unloaded", "new-pair-loaded"])
def test_index_update_moves_offer_to_another_pair(loaded_first):
    """An update by offerID that changes the pair: the offer leaves the
    old pair's side and joins the new pair's, whether or not the engine
    had loaded the new pair's side before the move."""
    h = DiffHarness()
    w = _DeepWorld(h)
    head = w.asks[0][0]
    m = w.maker_of(head)
    t = w.taker
    first = []
    if loaded_first:
        # the taker asks for the best offer of (Y, native) before the
        # move: nothing there yet, its bid rests
        first.append(_with_source(t, t.op_manage_buy_offer(
            NATIVE, w.Y, 10, 1, 100)))
    first.append(_with_source(m, m.op_manage_sell_offer(
        w.Y, NATIVE, LOT, 3, 1, offer_id=head)))
    frames = h.close(w.in_order(
        first,
        [w.take(LOT),                                      # the old pair
         _with_source(t, t.op_manage_buy_offer(NATIVE, w.Y, 40, 3, 1))]))
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    assert all(f.result.code == TransactionResultCode.txSUCCESS
               for f in frames)
    assert _claims(by_seq[1], 0) == [(w.asks[1][0], LOT)]
    assert _claims(by_seq[1], 1) == [(head, 40)]
    assert [r[0] for r in _book(h, w.Y, NATIVE)] == [head]
    assert head not in {r[0] for r in _book(h, w.X, NATIVE)}


@pytest.mark.parametrize("loaded_first", [False, True],
                         ids=["side-unloaded", "side-loaded"])
def test_index_offer_created_then_crossed_in_one_close(loaded_first):
    """An offer created in a close is found by a taker later in the same
    close, ahead of the 300 the root holds."""
    h = DiffHarness()
    w = _DeepWorld(h)
    o = w.other
    first = [w.take(30)] if loaded_first else []
    first.append(_with_source(o, o.op_manage_sell_offer(
        w.X, NATIVE, 70, 3, 2)))
    new_id = h.native.root.get_header().idPool + 1
    frames = h.close(w.in_order(first, [w.take(LOT)]))
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    assert all(f.result.code == TransactionResultCode.txSUCCESS
               for f in frames)
    rest = LOT - 30 if loaded_first else LOT
    assert _claims(by_seq[1]) == [(new_id, 70), (w.asks[0][0], min(rest, 30))]


@pytest.mark.parametrize("first", ["requote-better", "requote-pair",
                                   "requote-worse-then-fill",
                                   "requote-pair-then-fill",
                                   "fill-part", "fill-whole", "create",
                                   "delete", "cross-self"])
def test_index_rollback_restores_the_side(first):
    """A multi-op transaction whose LAST op fails after an earlier one
    re-priced, moved, filled, erased or created an offer: the rollback
    puts pair, price and existence back, and a taker later in the same
    close meets the side as the root has it."""
    h = DiffHarness()
    w = _DeepWorld(h)
    t, o = w.taker, w.other
    head, second = w.asks[0][0], w.asks[1][0]
    deep = w.asks[7 * PER_RUNG][0]
    m_head, m_deep = w.maker_of(head), w.maker_of(deep)
    if first == "requote-better":
        ops = [_with_source(m_deep, m_deep.op_manage_sell_offer(
            w.X, NATIVE, LOT, 101, 100, offer_id=deep))]
    elif first == "requote-pair":
        ops = [_with_source(m_head, m_head.op_manage_sell_offer(
            w.Y, NATIVE, LOT, 2, 1, offer_id=head))]
    elif first == "requote-worse-then-fill":
        # the taker's lookup drops the head's record, dead since the
        # re-quote, before the rollback brings the head back
        ops = [_with_source(m_head, m_head.op_manage_sell_offer(
            w.X, NATIVE, LOT, 7, 1, offer_id=head)), w.take(40)]
    elif first == "requote-pair-then-fill":
        ops = [_with_source(m_head, m_head.op_manage_sell_offer(
            w.Y, NATIVE, LOT, 2, 1, offer_id=head)), w.take(40)]
    elif first == "fill-part":
        ops = [w.take(40)]
    elif first == "fill-whole":
        ops = [w.take(2 * LOT + 10)]
    elif first == "create":
        ops = [_with_source(o, o.op_manage_sell_offer(w.X, NATIVE, 55, 1, 1))]
    elif first == "delete":
        ops = [_with_source(m_head, m_head.op_manage_sell_offer(
            w.X, NATIVE, 0, 2, 1, offer_id=head))]
    else:
        # the maker of a rung-0 offer takes its own side: the offers ahead
        # of its own fill, then the op fails on its own offer and the op's
        # own rollback (not the transaction's) restores them
        third = w.asks[2][0]
        mk = w.maker_of(third)
        if mk in (w.maker_of(head), w.maker_of(second)):
            third = next(r[0] for r in w.asks[:PER_RUNG]
                         if w.maker_of(r[0]) not in
                         (w.maker_of(head), w.maker_of(second)))
            mk = w.maker_of(third)
        ops = [w.take(PER_RUNG * LOT, acct=mk)]
    if first != "cross-self":
        # the op that fails: more native than the taker has
        ops.append(_with_source(t, t.op_payment(o.account_id, 10 ** 13)))
    frames = h.close(w.in_order(ops, [w.take(2 * LOT + 20)]))
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    assert by_seq[0].result.code == TransactionResultCode.txFAILED
    assert by_seq[1].result.code == TransactionResultCode.txSUCCESS
    assert _claims(by_seq[1]) == [(head, LOT), (second, LOT),
                                  (w.asks[2][0], 20)]
    assert _book(h, w.Y, NATIVE) == []


def test_index_revoke_erases_indexed_offers_mid_close():
    """An allow-trust revoke in the middle of a close erases a maker's
    offers from a side the engine has indexed, the head among them; the
    takers before and after it meet what is live."""
    from stellar_core_tpu.xdr import AccountFlags
    h = DiffHarness()
    root = h.account(root_secret_key())
    ir = h.account(SecretKey.from_seed(sha256(b"rev-ir")))
    makers = [h.account(SecretKey.from_seed(sha256(b"rev-m%d" % i)))
              for i in range(3)]
    t = h.account(SecretKey.from_seed(sha256(b"rev-t")))
    h.close([root.tx([root.op_create_account(a.account_id, 10 ** 10)
                      for a in makers + [t, ir]])])
    h.close([ir.tx([ir.op_set_options(
        set_flags=AccountFlags.AUTH_REQUIRED_FLAG |
        AccountFlags.AUTH_REVOCABLE_FLAG)])])
    R = Asset.credit("RST", ir.account_id)
    h.close([a.tx([a.op_change_trust(R, 10 ** 12)]) for a in makers + [t]])
    h.close([ir.tx([ir.op_allow_trust(a.account_id, b"RST\x00")
                    for a in makers + [t]] +
                   [ir.op_payment(a.account_id, 10 ** 8, R)
                    for a in makers + [t]])])
    # 3 makers x 4 rungs x 5: maker i's offers lead rung i
    h.close([m.tx([m.op_manage_sell_offer(R, NATIVE, LOT, 2 + k, 1)
                   for k in range(4) for _ in range(5)])
             for m in makers])
    asks = _book(h, R, NATIVE)
    assert len(asks) == 60
    gone = next(m for m in makers
                if m.account_id.key_bytes == asks[0][1])
    mine = {r[0] for r in asks if r[1] == gone.account_id.key_bytes}
    left = [r for r in asks if r[0] not in mine]

    def buy(units):
        return _with_source(t, t.op_manage_buy_offer(NATIVE, R, units,
                                                     10, 1))
    seq = ir.next_seq()
    first = _signed_by_sources(
        ir, [buy(30),
             ir.op_allow_trust(gone.account_id, b"RST\x00", authorize=0),
             buy(LOT + 10)], seq, [t])
    second = _signed_by_sources(ir, [buy(LOT)], seq + 1, [t])
    before = h.closes_native
    frames = h.close([first, second])
    assert h.closes_native == before + 1
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    assert all(f.result.code == TransactionResultCode.txSUCCESS
               for f in frames)
    assert _claims(by_seq[0], 0) == [(asks[0][0], 30)]
    assert _claims(by_seq[0], 2) == [(left[0][0], LOT), (left[1][0], 10)]
    assert _claims(by_seq[1], 0) == [(left[1][0], LOT - 10),
                                     (left[2][0], 10)]
    assert not mine & {r[0] for r in _book(h, R, NATIVE)}


def test_index_passive_offer_at_the_head_at_an_equal_price():
    """A passive offer heads the side. A taker whose limit EQUALS its
    price does not cross it (and rests); a taker with a better limit
    crosses it first; the order of the side is unmoved by its flag."""
    h = DiffHarness()
    w = _DeepWorld(h)
    o, t = w.other, w.taker
    h.close([o.tx([o.op_create_passive_sell_offer(w.X, NATIVE, 80, 3, 2)])])
    passive = h.native.root.get_header().idPool
    assert _book(h, w.X, NATIVE)[0][0] == passive
    frames = h.close(w.in_order(
        # buying X at exactly 3/2 native: its price as a seller of native
        # is 2/3 X a unit
        [_with_source(t, t.op_manage_sell_offer(NATIVE, w.X, 300, 2, 3))],
        [w.take(LOT, n=2, d=1)]))
    by_seq = sorted(frames, key=lambda f: f.envelope.value.tx.seqNum)
    assert all(f.result.code == TransactionResultCode.txSUCCESS
               for f in frames)
    assert _claims(by_seq[0]) == []
    assert _claims(by_seq[1]) == [(passive, 80), (w.asks[0][0], 20)]
    assert len(_book(h, NATIVE, w.X)) == 1


def _deepen_books(h, world, per_side=200):
    """Rest `per_side` offers on each side of X/native from the fuzz
    world's two funded users, at the prices the fuzz itself draws from
    (n, d in 1..3) that cannot cross each other: asks at 2, 3 and 3/2
    native, bids at 1, 2, 3 and 3/2 X."""
    root, users, ix, ir, X, R = world
    u0, u1 = users[0], users[1]
    h.close([root.tx([root.op_payment(u.account_id, 10 ** 10)
                      for u in (u0, u1)])])
    asks = [(2, 1), (3, 1), (3, 2)]
    bids = [(1, 1), (2, 1), (3, 1), (3, 2)]
    half = per_side // 2
    for u in (u0, u1):
        h.close([u.tx([u.op_manage_sell_offer(X, NATIVE, 6 + 2 * (i % 5),
                                              *asks[i % len(asks)])
                       for i in range(half)])])
        h.close([u.tx([u.op_manage_sell_offer(NATIVE, X, 6 + 6 * (i % 4),
                                              *bids[i % len(bids)])
                       for i in range(half)])])
    assert len(_book(h, X, NATIVE)) >= per_side
    assert len(_book(h, NATIVE, X)) >= per_side


@pytest.mark.parametrize("seed", [0xD0E5, 0xB00C])
def test_native_apply_randomized_deep_books(seed):
    """The seeded matrix over all op types (offers with and without an
    offerID, buy offers, path payments both ways, allow-trust revokes)
    against books of 200 offers a side."""
    rng = random.Random(seed)
    h = DiffHarness()
    world = _coverage_world(h)
    _deepen_books(h, world)
    fresh_counter = [0]
    native_before = h.closes_native
    for _ in range(8):
        frames = _random_full_frames(rng, h, world, fresh_counter,
                                     max_offer_id=450)
        if frames:
            h.close(frames)
    assert h.closes_native > native_before
    book = h.native.apply_stats.to_json()["book"]
    assert book["best_queries"] > 0


def _count_world(h, per_side):
    """`per_side` asks and as many bids on X/native from 25 makers, one
    offer a maker a rung, posted through the engine alone (no oracle: `h`
    is one native manager); returns (makers, X, close)."""
    shim = _Shim(h)
    root = TestAccount(shim, root_secret_key())
    ix = TestAccount(shim, SecretKey.from_seed(sha256(b"cnt-ix")))
    makers = [TestAccount(shim, SecretKey.from_seed(sha256(b"cnt-m%d" % i)))
              for i in range(25)]
    rungs = per_side // len(makers)

    def close(frames):
        header = h.root.get_header()
        ts = TxSetFrame(TESTING_NETWORK_ID, h.lcl_hash, frames)
        value = StellarValue(
            txSetHash=ts.get_contents_hash(),
            closeTime=header.scpValue.closeTime + 5,
            upgrades=[], ext=StellarValueExt(0, None))
        h.close_ledger(LedgerCloseData(header.ledgerSeq + 1, ts, value))
        assert all(f.result.code == TransactionResultCode.txSUCCESS
                   for f in frames), [f.result.code for f in frames]
        assert all(f._native_meta_b is not None for f in frames)

    close([root.tx([root.op_create_account(a.account_id, 10 ** 11)
                    for a in makers + [ix]])])
    X = Asset.credit("USD", ix.account_id)
    close([m.tx([m.op_change_trust(X, 10 ** 15)]) for m in makers])
    close([ix.tx([ix.op_payment(m.account_id, 10 ** 9, X)
                  for m in makers])])
    # asks from 2.00 native a unit of X upwards, bids from 0.51 X a native
    # unit upwards (2.00 x 0.51 > 1: the sides do not cross)
    close([m.tx([m.op_manage_sell_offer(X, NATIVE, 100, 200 + i, 100)
                 for i in range(rungs)]) for m in makers])
    close([m.tx([m.op_manage_sell_offer(NATIVE, X, 100, 51 + i, 100)
                 for i in range(rungs)]) for m in makers])
    return makers, X, close


@pytest.mark.parametrize("per_side", [250, 2500])
def test_best_offer_cost_does_not_grow_with_the_side(per_side):
    """By count, not by time: over sides of 250 and of 2,500 resting
    offers the same 50 re-quotes that cross nothing each ask for the
    best offer of the other side once, and each answer looks at a
    handful of index records at either size. A walk reads the side:
    250 or 2,500 records a query."""
    h = DiffHarness._mk(True)
    makers, X, close = _count_world(h, per_side)
    # the posting of the bids asked for the best ask once an offer
    assert h.apply_stats.to_json()["book"]["rows"] == per_side
    before = dict(h.apply_stats.to_json()["book"])
    # each maker re-quotes its best ask and its best bid a tick towards
    # the other side: neither crosses
    close([m.tx([m.op_manage_sell_offer(X, NATIVE, 100, 199, 100,
                                        offer_id=ask),
                 m.op_manage_sell_offer(NATIVE, X, 100, 505, 1000,
                                        offer_id=bid)])
           for m, ask, bid in _own_best(h, makers, X)])
    after = h.apply_stats.to_json()["book"]
    queries = after["best_queries"] - before["best_queries"]
    steps = after["best_steps"] - before["best_steps"]
    assert after["rows"] - before["rows"] == 2 * per_side
    # one query a re-quote: cross_offers asks, meets a price past the
    # limit and stops
    assert queries == 50
    assert h.apply_stats.to_json()["last_close"]["book"] == {
        "best_queries": queries, "best_steps": steps}
    assert 50 <= steps <= 4 * 50, steps


def _own_best(h, makers, X):
    """(maker, id of its best ask, id of its best bid) for each maker."""
    asks, bids = _book_of(h, X, NATIVE), _book_of(h, NATIVE, X)
    out = []
    for m in makers:
        key = m.account_id.key_bytes
        out.append((m, next(r[0] for r in asks if r[1] == key),
                    next(r[0] for r in bids if r[1] == key)))
    return out

