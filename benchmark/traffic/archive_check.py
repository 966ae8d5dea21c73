"""A plain checker of a history archive, with `hashlib`, `gzip` and the
XDR codec only: nothing of the program's history, catchup, bucket or
herder code. Every checkpoint the archive holds is checked:

  - every bucket its HistoryArchiveState names is in the archive, and
    the SHA-256 of its gunzipped contents is its name;
  - the bucket-list hash computed from the HAS's levels (a level's hash
    is SHA-256 of curr and snap, the list's SHA-256 of the levels') is
    the `bucketListHash` of the reference header at that checkpoint;
  - each ledger header's XDR hashes to the hash its entry carries, which
    is the hash every node recorded for that ledger, and each header
    names the one before it in `previousLedgerHash`;
  - each ledger's transaction set hashes, by the protocol-13 rule
    (SHA-256 of the previous ledger's hash and the envelopes' XDR sorted
    by their own SHA-256), to its header's `txSetHash`, an empty set
    where the archive holds none for the ledger;
  - each ledger's result set hashes to its header's `txSetResultHash`;
  - each ledger but the genesis one has an SCP entry with the
    reference node's EXTERNALIZE of its header's value.

`check(root, freq, headers, nodes_headers, node_id)` returns
{"mismatches": n, "checkpoints": n, "ledgers": n, "detail": [the first
few faults]}. `freq` is the checkpoint frequency, `headers` maps a
ledger sequence to the reference node's header XDR, `nodes_headers` is
every node's {sequence: header hash hex}, `node_id` the reference
node's public key.
"""

from __future__ import annotations

import glob
import gzip
import hashlib
import json
import os
import struct


def _stream(path: str, xdr_type) -> list:
    """The records of a gzipped XDR stream (RFC 5531 record marks)."""
    with gzip.open(path, "rb") as fh:
        data = fh.read()
    out, i = [], 0
    while i < len(data):
        n, = struct.unpack(">I", data[i:i + 4])
        n &= 0x7FFFFFFF
        out.append(xdr_type.from_xdr(data[i + 4:i + 4 + n]))
        i += 4 + n
    return out


def _path(root: str, category: str, checkpoint: int, suffix: str) -> str:
    h = "%08x" % checkpoint
    return os.path.join(root, category, h[0:2], h[2:4], h[4:6],
                        "%s-%s%s" % (category, h, suffix))


def _bucket_path(root: str, hh: str) -> str:
    return os.path.join(root, "bucket", hh[0:2], hh[2:4], hh[4:6],
                        "bucket-%s.xdr.gz" % hh)


def _txset_hash(prev: bytes, envelopes: list) -> bytes:
    blobs = sorted((e.to_xdr() for e in envelopes),
                   key=lambda b: hashlib.sha256(b).digest())
    h = hashlib.sha256(prev)
    for b in blobs:
        h.update(b)
    return h.digest()


def check(root: str, freq: int, headers: dict, nodes_headers: list,
          node_id: bytes) -> dict:
    from stellar_core_tpu.xdr import (
        LedgerHeader, LedgerHeaderHistoryEntry, SCPHistoryEntry,
        TransactionHistoryEntry, TransactionHistoryResultEntry,
        TransactionResultSet,
    )
    faults = []
    bucket_ok = {}          # a bucket named by several checkpoints
    ledgers = 0
    prev_hash = None
    has_files = sorted(glob.glob(os.path.join(
        root, "history", "*", "*", "*", "history-*.json")))

    def fault(what: str) -> None:
        faults.append(what)

    for has_path in has_files:
        with open(has_path) as fh:
            has = json.load(fh)
        cp = int(has["currentLedger"])
        if os.path.basename(has_path) != "history-%08x.json" % cp:
            fault("%s names ledger %d" % (has_path, cp))
            continue
        # the buckets
        level_hashes = []
        for lv in has["currentBuckets"]:
            named = [lv["curr"], lv["snap"]]
            nxt = lv.get("next") or {}
            named += [nxt[k] for k in ("output", "curr", "snap")
                      if nxt.get(k)]
            named += list(nxt.get("shadow") or [])
            for hh in named:
                if hh == "0" * 64 or hh in bucket_ok:
                    if not bucket_ok.get(hh, True):
                        fault("checkpoint %d: bucket %s" % (cp, hh[:12]))
                    continue
                p = _bucket_path(root, hh)
                ok = False
                if os.path.exists(p):
                    d = hashlib.sha256()
                    try:
                        with gzip.open(p, "rb") as fh:
                            for chunk in iter(lambda: fh.read(1 << 22),
                                              b""):
                                d.update(chunk)
                        ok = d.hexdigest() == hh
                    except (OSError, EOFError):
                        ok = False
                bucket_ok[hh] = ok
                if not ok:
                    fault("checkpoint %d: bucket %s missing or not its "
                          "hash" % (cp, hh[:12]))
            level_hashes.append(hashlib.sha256(
                bytes.fromhex(lv["curr"]) + bytes.fromhex(lv["snap"])
            ).digest())
        ref = headers.get(cp)
        if ref is None:
            fault("checkpoint %d: no reference header" % cp)
            continue
        if hashlib.sha256(b"".join(level_hashes)).digest() != \
                LedgerHeader.from_xdr(ref).bucketListHash:
            fault("checkpoint %d: bucket-list hash" % cp)
        # the four streams
        try:
            hdrs = _stream(_path(root, "ledger", cp, ".xdr.gz"),
                           LedgerHeaderHistoryEntry)
            txs = {e.ledgerSeq: e for e in _stream(
                _path(root, "transactions", cp, ".xdr.gz"),
                TransactionHistoryEntry)}
            res = {e.ledgerSeq: e for e in _stream(
                _path(root, "results", cp, ".xdr.gz"),
                TransactionHistoryResultEntry)}
            scp = {e.value.ledgerMessages.ledgerSeq: e.value
                   for e in _stream(_path(root, "scp", cp, ".xdr.gz"),
                                    SCPHistoryEntry)}
        except (OSError, EOFError, ValueError, struct.error) as e:
            fault("checkpoint %d: a stream is unreadable (%s)" % (cp, e))
            continue
        if [e.header.ledgerSeq for e in hdrs] != \
                list(range(max(1, cp + 1 - freq), cp + 1)):
            fault("checkpoint %d: not its ledgers' headers" % cp)
        for e in hdrs:
            seq, hd = e.header.ledgerSeq, e.header
            ledgers += 1
            hx = hd.to_xdr()
            h = hashlib.sha256(hx).digest()
            if h != e.hash or any(n.get(seq) != h.hex()
                                  for n in nodes_headers):
                fault("ledger %d: header hash" % seq)
            if headers.get(seq) is not None and headers[seq] != hx:
                fault("ledger %d: header is not the node's" % seq)
            if prev_hash is not None and hd.previousLedgerHash != prev_hash:
                fault("ledger %d: previousLedgerHash" % seq)
            prev_hash = h
            if seq == 1:
                continue
            t = txs.get(seq)
            envs = t.txSet.txs if t is not None else []
            if t is not None and t.txSet.previousLedgerHash != \
                    hd.previousLedgerHash:
                fault("ledger %d: txset's previous ledger" % seq)
            if _txset_hash(hd.previousLedgerHash, envs) != \
                    hd.scpValue.txSetHash:
                fault("ledger %d: txSetHash" % seq)
            r = res.get(seq)
            rset = r.txResultSet if r is not None else \
                TransactionResultSet(results=[])
            if hashlib.sha256(rset.to_xdr()).digest() != \
                    hd.txSetResultHash:
                fault("ledger %d: txSetResultHash" % seq)
            s = scp.get(seq)
            value = hd.scpValue.to_xdr()
            if s is None or not any(
                    m.statement.nodeID.key_bytes == node_id and
                    m.statement.pledges.disc == 2 and
                    m.statement.pledges.value.commit.value == value
                    for m in s.ledgerMessages.messages):
                fault("ledger %d: no EXTERNALIZE of its value by the "
                      "node" % seq)
    return {"mismatches": len(faults), "checkpoints": len(has_files),
            "ledgers": ledgers, "detail": faults[:4]}
