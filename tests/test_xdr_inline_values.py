"""An XDR value is one object to the collector: every producer of struct
and union values (the declarative unpack, fastcodec's compiled unpack,
the native decoder, the compiled copy) and every memo written on a value
leaves the instance's attributes inline, so no value carries a
materialized `__dict__` — a second GC-tracked container that every
collector pass would walk (docs/observability.md, "XDR values are one
object to the collector")."""

import gc
from types import SimpleNamespace

import pytest

import stellar_core_tpu.xdr as X
from stellar_core_tpu.bucket.bucket import entry_record
from stellar_core_tpu.ledger.apply_stats import txset_prefetch_keys
from stellar_core_tpu.ledger.ledgertxn import _kb
from stellar_core_tpu.util.footprint import process_stats, tracked_objects
from stellar_core_tpu.util.xdrstream import frame_record
from stellar_core_tpu.xdr import codec as C
from stellar_core_tpu.xdr import fastcodec


def _pk(i):
    return X.PublicKey.ed25519(bytes([i]) * 32)


def _bucket_entry():
    """An account with 20 signers, wrapped as a bucket holds it."""
    ae = X.AccountEntry(
        accountID=_pk(1), balance=10 ** 10, seqNum=7, numSubEntries=20,
        inflationDest=None, flags=0, homeDomain="example.org",
        thresholds=b"\x01\x00\x00\x00",
        signers=[X.Signer(key=X.SignerKey.ed25519(bytes([40 + j]) * 32),
                          weight=1) for j in range(20)],
        ext=X.AccountEntryExt.v0())
    return X.BucketEntry.live(X.LedgerEntry(
        lastModifiedLedgerSeq=3,
        data=X.LedgerEntryData(X.LedgerEntryType.ACCOUNT, ae),
        ext=X._Ext.v0()))


def _envelope():
    """A payment signed 20 times (a 20-of-20 multisig source)."""
    op = X.Operation(sourceAccount=None, body=X.OperationBody(
        X.OperationType.PAYMENT,
        X.PaymentOp(destination=X.MuxedAccount.from_account_id(_pk(2)),
                    asset=X.Asset.native(), amount=10)))
    tx = X.Transaction(
        sourceAccount=X.MuxedAccount.from_account_id(_pk(1)), fee=2000,
        seqNum=8, timeBounds=X.TimeBounds(minTime=0, maxTime=0),
        memo=X.Memo.none(), operations=[op], ext=X._Ext.v0())
    sigs = [X.DecoratedSignature(hint=bytes([j]) * 4,
                                 signature=bytes([j]) * 64)
            for j in range(20)]
    return X.TransactionEnvelope.for_tx(tx, sigs)


VALUES = {
    "bucket_entry": (X.BucketEntry, _bucket_entry),
    "envelope": (X.TransactionEnvelope, _envelope),
    "ledger_key": (X.LedgerKey, lambda: X.LedgerKey.account(_pk(3))),
}


def _native_unpack(t, wire):
    from stellar_core_tpu.native import xdr_unpack_fn
    nf = xdr_unpack_fn(t)
    if nf is None:
        pytest.skip("native XDR engine unavailable")
    v, end = nf(wire)
    assert end == len(wire)
    return v


def _fast_unpack(t, wire):
    v, end = fastcodec.compile_unpack(t)(wire, 0)
    assert end == len(wire)
    return v


def _declarative_unpack(t, wire):
    u = C.Unpacker(wire)
    v = t.unpack(u)
    u.assert_done()
    return v


PRODUCERS = {
    "declarative_unpack": _declarative_unpack,
    "fastcodec_unpack": _fast_unpack,
    "native_unpack": _native_unpack,
    "compile_copy": lambda t, wire: fastcodec.compile_copy(t)(
        fastcodec.compile_unpack(t)(wire, 0)[0]),
}


def _xdr_instances(v):
    """Every struct and union instance reachable from `v` through fields,
    arms and arrays."""
    out, stack = [], [v]
    while stack:
        o = stack.pop()
        if isinstance(o, C.XdrStruct):
            out.append(o)
            stack.extend(getattr(o, n) for n, _ in o.xdr_fields)
        elif isinstance(o, C.XdrUnion):
            out.append(o)
            stack.append(o.value)
        elif isinstance(o, list):
            stack.extend(o)
    return out


def _with_dict(v):
    return [type(o).__name__ for o in _xdr_instances(v)
            if any(type(r) is dict for r in gc.get_referents(o))]


@pytest.mark.parametrize("value", sorted(VALUES))
@pytest.mark.parametrize("producer", sorted(PRODUCERS))
def test_values_carry_no_instance_dict(producer, value):
    t, make = VALUES[value]
    original = make()
    wire = X.xdr_bytes(t, original)
    got = PRODUCERS[producer](t, wire)
    instances = _xdr_instances(got)
    assert len(instances) >= 3
    assert _with_dict(got) == []
    assert X.xdr_bytes(t, got) == wire
    assert got == original


def test_the_walk_sees_a_materialized_dict():
    v = _bucket_entry()
    v.value.data.value.signers[7].__dict__
    assert _with_dict(v) == ["Signer"]


def test_entry_record_memoizes_on_the_value():
    e = _fast_unpack(X.BucketEntry, X.xdr_bytes(X.BucketEntry,
                                                 _bucket_entry()))
    rec = entry_record(e)
    assert rec == frame_record(e.to_xdr())
    assert entry_record(e) is rec
    assert _with_dict(e) == []


def test_ledger_key_bytes_memoize_on_the_key():
    key = _fast_unpack(X.LedgerKey, X.xdr_bytes(
        X.LedgerKey, X.LedgerKey.account(_pk(3))))
    kb = _kb(key)
    assert kb == key.to_xdr()
    assert _kb(key) is kb
    assert _with_dict(key) == []


def test_prefetch_keys_come_with_their_bytes_memoized():
    tx = _envelope().value.tx
    keys = txset_prefetch_keys([SimpleNamespace(tx=tx)])
    assert len(keys) == 2
    for key in keys:
        kb = key._kb
        assert kb == key.to_xdr() and _kb(key) is kb
        assert _with_dict(key) == []


def test_footprint_counts_tracked_objects_by_generation():
    tracked = tracked_objects()
    assert len(tracked) == 3
    assert all(type(n) is int and n >= 0 for n in tracked)
    assert sum(tracked) > 0
    g = process_stats()["gc"]
    assert len(g["tracked"]) == 3
    assert len(g["generations"]) == 3
    gc.collect()   # tuples a young pass would untrack mid-count go first
    before = sum(tracked_objects())
    held = [[] for _ in range(2000)]
    assert sum(tracked_objects()) >= before + 1900
    del held
