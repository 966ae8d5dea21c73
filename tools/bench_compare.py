#!/usr/bin/env python3
"""Perf-regression ledger (ISSUE 6): normalize bench artifacts into
`bench/history.jsonl`, validate their schemas, and gate runs against
the best committed record per (metric, platform).

The committed BENCH_r*.json and MULTICHIP_r*.json snapshots each use one
of three shapes (raw bench output, driver wrapper with a `parsed` blob,
multichip driver record); this module flattens all of them into one
normalized record per measurement:

    {"metric": "replay_ledgers_per_sec", "unit": "ledgers/s",
     "value": 3.34, "platform": "tpu", "direction": "higher",
     "source": "BENCH_r05.json", "round": 5,
     "at_unix": 1785466800, "commit": null}

`direction` says which way is better — the comparator is direction-
aware, so a latency metric regresses UP while a throughput metric
regresses DOWN. `platform` keys baselines apart: a tiny CPU compare leg
("cpu-tiny") never gates against full-leg or device history.

CLI (also driven by `bench.py --compare [--record]`):

    tools/bench_compare.py ingest [--out bench/history.jsonl] [files...]
    tools/bench_compare.py check  [files...]      (alias: --check)
    tools/bench_compare.py compare --current FILE
        [--history bench/history.jsonl] [--tolerance 0.1]

`check` exits 1 on any malformed committed artifact — a bench snapshot
that silently drops out of the trajectory is itself a regression.
`compare` exits 1 on any regression beyond tolerance.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_HISTORY = os.path.join("bench", "history.jsonl")

DIRECTIONS = ("higher", "lower")
REQUIRED_FIELDS = ("metric", "unit", "value", "platform", "direction",
                   "source")

# device platforms whose compile/latency numbers are meaningful
_DEVICE_PLATFORMS = ("tpu",)


# --------------------------------------------------------------------------
# record construction + validation

def make_record(metric: str, unit: str, value, platform: str,
                direction: str, source: str,
                round_no: Optional[int] = None,
                at_unix: Optional[int] = None,
                commit: Optional[str] = None) -> dict:
    return {"metric": metric, "unit": unit, "value": value,
            "platform": platform, "direction": direction,
            "source": source, "round": round_no,
            "at_unix": at_unix, "commit": commit}


def validate_record(rec, where: str = "") -> List[str]:
    errs: List[str] = []
    if not isinstance(rec, dict):
        return ["%s: record is not an object: %r" % (where, rec)]
    for k in REQUIRED_FIELDS:
        if k not in rec:
            errs.append("%s: missing field %r" % (where, k))
    for k in ("metric", "unit", "platform", "source"):
        if k in rec and not isinstance(rec[k], str):
            errs.append("%s: field %r must be a string, got %r"
                        % (where, k, rec[k]))
    v = rec.get("value")
    if "value" in rec and (isinstance(v, bool) or
                           not isinstance(v, (int, float)) or
                           not math.isfinite(v)):
        errs.append("%s: field 'value' must be a finite number, got %r"
                    % (where, v))
    if "direction" in rec and rec["direction"] not in DIRECTIONS:
        errs.append("%s: field 'direction' must be one of %s, got %r"
                    % (where, "/".join(DIRECTIONS), rec.get("direction")))
    for k in ("round", "at_unix"):
        if rec.get(k) is not None and not isinstance(rec[k], int):
            errs.append("%s: field %r must be an int or null, got %r"
                        % (where, k, rec[k]))
    if rec.get("commit") is not None and not isinstance(rec["commit"], str):
        errs.append("%s: field 'commit' must be a string or null"
                    % where)
    return errs


def _round_of(source: str) -> Optional[int]:
    m = re.search(r"_r(\d+)", os.path.basename(source))
    return int(m.group(1)) if m else None


# --------------------------------------------------------------------------
# artifact normalization

def _is_wrapper(blob: dict) -> bool:
    """Driver wrapper: {"n": .., "cmd": .., "rc": .., "tail": ..,
    "parsed": {...}} around the raw bench line."""
    return isinstance(blob, dict) and "tail" in blob and "rc" in blob \
        and "metric" not in blob and "n_devices" not in blob


def _is_multichip(blob: dict) -> bool:
    return isinstance(blob, dict) and "n_devices" in blob


def _num(p: dict, key: str):
    v = p.get(key)
    if isinstance(v, bool) or not isinstance(v, (int, float)) or \
            not math.isfinite(v):
        return None
    return v


def apply_breakdown_records(ab: dict, platform: str, source: str,
                            round_no=None, at_unix=None) -> List[dict]:
    """Normalize an `apply_breakdown` block (ISSUE 9: the close
    cockpit's per-op attribution) into direction-aware per-op records —
    per-op cost regressions gate against bench/history.jsonl exactly
    like every other metric."""
    out: List[dict] = []
    if not isinstance(ab, dict):
        return out
    v = _num(ab, "apply_wall_s")
    if v is not None:
        out.append(make_record("apply_wall_s", "s", v, platform, "lower",
                               source, round_no, at_unix))
    per_op = ab.get("per_op_ms")
    if isinstance(per_op, dict):
        for op, ms in sorted(per_op.items()):
            if _num({"v": ms}, "v") is None:
                continue
            out.append(make_record("apply_op_%s_ms" % op, "ms", ms,
                                   platform, "lower", source, round_no,
                                   at_unix))
    v = _num(ab, "other_ms")
    if v is not None:
        out.append(make_record("apply_other_ms", "ms", v, platform,
                               "lower", source, round_no, at_unix))
    return out


def validate_apply_breakdown(ab, where: str = "") -> List[str]:
    """Schema check for one `apply_breakdown` block (`check`/`--check`):
    the per-op components + residual must exist, be finite, and sum to
    the measured apply wall — a breakdown that silently stops adding up
    is itself a regression."""
    errs: List[str] = []
    if not isinstance(ab, dict):
        return ["%s: apply_breakdown is not an object: %r" % (where, ab)]
    wall = _num(ab, "apply_wall_s")
    if wall is None or wall < 0:
        errs.append("%s: apply_breakdown.apply_wall_s must be a finite "
                    "number >= 0, got %r" % (where, ab.get("apply_wall_s")))
    per_op = ab.get("per_op_ms")
    if not isinstance(per_op, dict):
        errs.append("%s: apply_breakdown.per_op_ms must be an object"
                    % where)
        per_op = {}
    for op, ms in per_op.items():
        if not isinstance(op, str) or _num({"v": ms}, "v") is None:
            errs.append("%s: apply_breakdown.per_op_ms[%r] must be a "
                        "finite number, got %r" % (where, op, ms))
    other = _num(ab, "other_ms")
    if other is None:
        errs.append("%s: apply_breakdown.other_ms must be a finite number"
                    % where)
    for key in ("closes", "bails", "state_reads"):
        if not isinstance(ab.get(key), dict):
            errs.append("%s: apply_breakdown.%s must be an object"
                        % (where, key))
    if wall is not None and other is not None and not errs:
        total_ms = sum(v for v in per_op.values()
                       if isinstance(v, (int, float))) + other
        # per-op values are rounded to 1 µs in the artifact; allow the
        # accumulated rounding slack plus a 0.1% relative band
        tol = max(1.0, 1e-3 * wall * 1e3)
        if abs(total_ms - wall * 1e3) > tol:
            errs.append(
                "%s: apply_breakdown parts sum to %.3f ms but "
                "apply_wall_s is %.3f ms — the breakdown no longer "
                "accounts for the measured wall" % (where, total_ms,
                                                    wall * 1e3))
    return errs


def overlay_breakdown_records(ob: dict, platform: str, source: str,
                              round_no=None, at_unix=None) -> List[dict]:
    """Normalize an `overlay_breakdown` block (ISSUE 10: the wire
    cockpit's fleet aggregate) into direction-aware records — the flood
    duplication ratio (the O(n²) flood waste ROADMAP item 3 wants to
    shrink) and the end-to-end tx latency gate against
    bench/history.jsonl exactly like every other metric. Latency
    records are only emitted when the run actually applied tracked
    transactions: a 0-valued p95 from an idle run must never become the
    committed best baseline."""
    out: List[dict] = []
    if not isinstance(ob, dict):
        return out
    fl = ob.get("flood")
    if isinstance(fl, dict) and _num(fl, "unique") and \
            _num(fl, "duplication_ratio") is not None:
        out.append(make_record("flood_duplication_ratio", "x",
                               fl["duplication_ratio"], platform, "lower",
                               source, round_no, at_unix))
    tx = ob.get("tx_latency_ms")
    if isinstance(tx, dict) and _num(tx, "count"):
        for q in ("p50", "p95"):
            v = _num(tx, q)
            if v is not None:
                out.append(make_record(
                    "tx_latency_total_%s_ms" % q, "ms", v, platform,
                    "lower", source, round_no, at_unix))
    return out


def validate_overlay_breakdown(ob, where: str = "") -> List[str]:
    """Schema check for one `overlay_breakdown` block (`check`/
    `--check`): bandwidth totals, flood dedup (ratio consistent with
    duplicates/unique) and the tx-lifecycle sum contract (stage seconds
    sum to total_seconds) must all hold — a breakdown that silently
    stops adding up is itself a regression."""
    errs: List[str] = []
    if not isinstance(ob, dict):
        return ["%s: overlay_breakdown is not an object: %r" % (where, ob)]
    for key in ("recv_bytes", "send_bytes", "recv_msgs", "send_msgs"):
        v = _num(ob, key)
        if v is None or v < 0:
            errs.append("%s: overlay_breakdown.%s must be a finite "
                        "number >= 0, got %r" % (where, key, ob.get(key)))
    fl = ob.get("flood")
    if not isinstance(fl, dict):
        errs.append("%s: overlay_breakdown.flood must be an object"
                    % where)
    else:
        u, d = _num(fl, "unique"), _num(fl, "duplicates")
        r = _num(fl, "duplication_ratio")
        if u is None or u < 0 or d is None or d < 0 or r is None or r < 0:
            errs.append("%s: overlay_breakdown.flood needs finite "
                        "unique/duplicates/duplication_ratio >= 0, got %r"
                        % (where, fl))
        elif u and abs(r - d / u) > 1e-3:
            errs.append("%s: overlay_breakdown.flood duplication_ratio "
                        "%.4f inconsistent with duplicates/unique %.4f"
                        % (where, r, d / u))
    tx = ob.get("tx_latency_ms")
    if not isinstance(tx, dict) or _num(tx, "count") is None:
        errs.append("%s: overlay_breakdown.tx_latency_ms must be an "
                    "object with a finite count" % where)
    else:
        p50, p95 = _num(tx, "p50"), _num(tx, "p95")
        if p50 is None or p95 is None or p50 < 0 or p95 + 1e-9 < p50:
            errs.append("%s: overlay_breakdown.tx_latency_ms needs "
                        "finite 0 <= p50 <= p95, got %r" % (where, tx))
    stage = ob.get("stage_seconds")
    total = _num(ob, "total_seconds")
    if not isinstance(stage, dict) or total is None or total < 0:
        errs.append("%s: overlay_breakdown needs stage_seconds (object) "
                    "and finite total_seconds >= 0" % where)
    else:
        bad = [s for s, v in stage.items()
               if _num({"v": v}, "v") is None]
        if bad:
            errs.append("%s: overlay_breakdown.stage_seconds has "
                        "non-finite entries %r" % (where, bad))
        else:
            # the tx-lifecycle sum contract: per-tx totals are computed
            # as the sum of the stage durations, so the cumulative
            # aggregates must agree to rounding slack
            s = sum(stage.values())
            tol = max(1e-6, 1e-3 * total)
            if abs(s - total) > tol:
                errs.append(
                    "%s: overlay_breakdown stage_seconds sum to %.6f s "
                    "but total_seconds is %.6f s — the lifecycle "
                    "breakdown no longer accounts for the total"
                    % (where, s, total))
    return errs


def fleet_verify_records(fv: dict, source: str, round_no=None,
                         at_unix=None) -> List[dict]:
    """Normalize a `fleet_verify` block (ISSUE 11: the multi-device
    verify leg) into direction-aware records keyed per forced device
    count — `verify-fleet-cpu<N>` platforms only ever gate against
    their own device-count history, never against single-chip device
    numbers."""
    out: List[dict] = []
    if not isinstance(fv, dict):
        return out
    for nd, leg in sorted(fv.items()):
        if not isinstance(leg, dict):
            continue
        plat = "verify-fleet-cpu%s" % nd
        for key, metric, unit, direction in (
                ("fleet_sigs_per_s", "fleet_sigs_per_s", "sigs/s",
                 "higher"),
                ("per_device_sigs_per_s", "per_device_sigs_per_s",
                 "sigs/s", "higher"),
                ("warm_restart_s", "warm_restart_s", "s", "lower")):
            v = _num(leg, key)
            if v is not None:
                out.append(make_record(metric, unit, v, plat, direction,
                                       source, round_no, at_unix))
    return out


def validate_fleet_verify(fv, where: str = "") -> List[str]:
    """Schema check for one `fleet_verify` block (`check`/`--check`):
    every device-count leg needs finite positive rates whose
    per-device figure is exactly fleet/devices, a non-negative warm
    restart, and a device count matching its key — a fleet artifact
    whose arithmetic stops agreeing is itself a regression."""
    errs: List[str] = []
    if not isinstance(fv, dict):
        return ["%s: fleet_verify is not an object: %r" % (where, fv)]
    for nd, leg in sorted(fv.items()):
        lw = "%s: fleet_verify[%s]" % (where, nd)
        if not isinstance(leg, dict):
            errs.append("%s must be an object" % lw)
            continue
        devices = leg.get("devices")
        if not isinstance(devices, int) or isinstance(devices, bool) \
                or devices < 1 or str(devices) != str(nd):
            errs.append("%s.devices must be a positive int matching its "
                        "key, got %r" % (lw, devices))
            continue
        fleet = _num(leg, "fleet_sigs_per_s")
        per_dev = _num(leg, "per_device_sigs_per_s")
        if fleet is None or fleet <= 0:
            errs.append("%s.fleet_sigs_per_s must be a finite number "
                        "> 0, got %r" % (lw, leg.get("fleet_sigs_per_s")))
        if per_dev is None or per_dev <= 0:
            errs.append("%s.per_device_sigs_per_s must be a finite "
                        "number > 0, got %r"
                        % (lw, leg.get("per_device_sigs_per_s")))
        if fleet is not None and per_dev is not None and fleet > 0:
            want = fleet / devices
            if abs(per_dev - want) > max(0.15, 1e-3 * want):
                errs.append("%s.per_device_sigs_per_s %.1f inconsistent "
                            "with fleet/devices %.1f" % (lw, per_dev,
                                                         want))
        wr = _num(leg, "warm_restart_s")
        if wr is None or wr < 0:
            errs.append("%s.warm_restart_s must be a finite number >= 0,"
                        " got %r" % (lw, leg.get("warm_restart_s")))
    return errs


def hash_bench_records(hb: dict, source: str, round_no=None,
                       at_unix=None) -> List[dict]:
    """Normalize a `hash_bench` block (ISSUE 12: the batched-SHA-256
    leg) into direction-aware records — kernel throughput per
    (lanes × blocks) shape keyed under `hash-<platform>-<shape>`
    platforms (a jax-on-CPU leg only ever gates against its own CPU
    history, never against real-device numbers), the host hashlib
    baseline under `hash-host`, and the checkpoint proof-size /
    light-client verify-cost headlines under `checkpoint-cpu`."""
    out: List[dict] = []
    if not isinstance(hb, dict):
        return out
    kernel = hb.get("kernel")
    if isinstance(kernel, dict):
        for shape, leg in sorted(kernel.items()):
            if not isinstance(leg, dict):
                continue
            plat = "hash-%s-%s" % (leg.get("platform", "cpu"), shape)
            for key, unit in (("hash_bytes_per_s", "bytes/s"),
                              ("hash_msgs_per_s", "msgs/s")):
                v = _num(leg, key)
                if v is not None:
                    out.append(make_record(key, unit, v, plat, "higher",
                                           source, round_no, at_unix))
    host = hb.get("host")
    if isinstance(host, dict):
        v = _num(host, "hash_bytes_per_s")
        if v is not None:
            out.append(make_record("hash_bytes_per_s", "bytes/s", v,
                                   "hash-host", "higher", source,
                                   round_no, at_unix))
    cp = hb.get("checkpoint")
    if isinstance(cp, dict):
        for key, metric, unit in (
                ("proof_bytes", "checkpoint_proof_bytes", "bytes"),
                ("verify_p95_ms", "checkpoint_verify_ms", "ms"),
                ("update_p95_ms", "checkpoint_update_ms", "ms")):
            v = _num(cp, key)
            if v is not None:
                out.append(make_record(metric, unit, v, "checkpoint-cpu",
                                       "lower", source, round_no,
                                       at_unix))
    return out


def validate_hash_bench(hb, where: str = "") -> List[str]:
    """Schema check for one `hash_bench` block (`check`/`--check`):
    every kernel shape leg needs finite positive rates consistent with
    each other, the checkpoint block needs a positive proof size,
    ordered verify percentiles and a TRUE oracle-equality flag — a
    hashing artifact whose own differential oracle failed must never
    read as a committed baseline."""
    errs: List[str] = []
    if not isinstance(hb, dict):
        return ["%s: hash_bench is not an object: %r" % (where, hb)]
    kernel = hb.get("kernel")
    if not isinstance(kernel, dict) or not kernel:
        errs.append("%s: hash_bench.kernel must be a non-empty object"
                    % where)
        kernel = {}
    for shape, leg in sorted(kernel.items()):
        lw = "%s: hash_bench.kernel[%s]" % (where, shape)
        if not isinstance(leg, dict):
            errs.append("%s must be an object" % lw)
            continue
        bps = _num(leg, "hash_bytes_per_s")
        mps = _num(leg, "hash_msgs_per_s")
        mb = _num(leg, "msg_bytes")
        if bps is None or bps <= 0:
            errs.append("%s.hash_bytes_per_s must be a finite number "
                        "> 0, got %r" % (lw, leg.get("hash_bytes_per_s")))
        if mps is None or mps <= 0:
            errs.append("%s.hash_msgs_per_s must be a finite number "
                        "> 0, got %r" % (lw, leg.get("hash_msgs_per_s")))
        if None not in (bps, mps, mb) and mps > 0 and mb > 0:
            want = mps * mb
            if abs(bps - want) > max(1.0, 1e-2 * want):
                errs.append("%s.hash_bytes_per_s %.1f inconsistent with "
                            "msgs/s * msg_bytes %.1f" % (lw, bps, want))
    cp = hb.get("checkpoint")
    if not isinstance(cp, dict):
        errs.append("%s: hash_bench.checkpoint must be an object" % where)
    else:
        pb = _num(cp, "proof_bytes")
        if pb is None or pb <= 0:
            errs.append("%s: hash_bench.checkpoint.proof_bytes must be "
                        "a finite number > 0, got %r"
                        % (where, cp.get("proof_bytes")))
        p50, p95 = _num(cp, "verify_p50_ms"), _num(cp, "verify_p95_ms")
        if p50 is None or p95 is None or p50 < 0 or p95 + 1e-9 < p50:
            errs.append("%s: hash_bench.checkpoint needs finite "
                        "0 <= verify_p50_ms <= verify_p95_ms, got %r"
                        % (where, cp))
        if cp.get("oracle_equal") is not True:
            errs.append("%s: hash_bench.checkpoint.oracle_equal must be "
                        "true — the incremental Merkle root diverged "
                        "from the from-scratch oracle in this artifact"
                        % where)
    return errs


def bucketdb_records(bd: dict, source: str, round_no=None,
                     at_unix=None) -> List[dict]:
    """Normalize a `bucketdb_bench` block (ISSUE 14: the
    million-account bucket-backed read gate) into direction-aware
    records under the `bucketdb-cpu` platform: the latency-flatness
    ratio and large-scale close p50 (lower is better), the surge
    prefetch hit-rate (higher), and the bloom false-positive rate
    (lower)."""
    out: List[dict] = []
    if not isinstance(bd, dict):
        return out
    for key, metric, unit, direction in (
            ("latency_ratio", "bucketdb_latency_ratio", "x", "lower"),
            ("prefetch_hit_rate_pct", "bucketdb_prefetch_hit_rate_pct",
             "pct", "higher"),
            ("bloom_fp_pct", "bucketdb_bloom_fp_pct", "pct", "lower")):
        v = _num(bd, key)
        if v is not None:
            out.append(make_record(metric, unit, v, "bucketdb-cpu",
                                   direction, source, round_no, at_unix))
    large = bd.get("large")
    if isinstance(large, dict):
        v = _num(large, "close_ms_p50")
        if v is not None:
            out.append(make_record("bucketdb_close_large_p50_ms", "ms",
                                   v, "bucketdb-cpu", "lower", source,
                                   round_no, at_unix))
    return out


def validate_bucketdb(bd, where: str = "") -> List[str]:
    """Schema check for one `bucketdb_bench` block (`check`/`--check`):
    both scale legs must exist with finite positive close latencies and
    a strictly larger `large` account count; the recorded
    latency-flatness ratio must actually be the legs' p50 ratio AND
    within the 1.25x acceptance gate; the surge prefetch hit-rate must
    hold >= 95%, the bloom false-positive rate <= 5%, and the
    cockpit-asserted apply-path SQL point-lookup count must be ZERO — a
    committed million-account artifact that fails its own gates is a
    broken baseline, not a measurement."""
    errs: List[str] = []
    if not isinstance(bd, dict):
        return ["%s: bucketdb_bench is not an object: %r" % (where, bd)]
    legs = {}
    for name in ("small", "large"):
        leg = bd.get(name)
        if not isinstance(leg, dict):
            errs.append("%s: bucketdb_bench.%s must be an object"
                        % (where, name))
            continue
        acc = _num(leg, "accounts")
        p50 = _num(leg, "close_ms_p50")
        if acc is None or acc <= 0:
            errs.append("%s: bucketdb_bench.%s.accounts must be a finite "
                        "number > 0, got %r" % (where, name,
                                                leg.get("accounts")))
        if p50 is None or p50 <= 0:
            errs.append("%s: bucketdb_bench.%s.close_ms_p50 must be a "
                        "finite number > 0, got %r"
                        % (where, name, leg.get("close_ms_p50")))
        legs[name] = leg
    if len(legs) == 2 and not errs:
        if legs["large"]["accounts"] <= legs["small"]["accounts"]:
            errs.append("%s: bucketdb_bench.large.accounts must exceed "
                        "small.accounts" % where)
        ratio = _num(bd, "latency_ratio")
        want = legs["large"]["close_ms_p50"] / legs["small"]["close_ms_p50"]
        if ratio is None:
            errs.append("%s: bucketdb_bench.latency_ratio must be a "
                        "finite number" % where)
        else:
            if abs(ratio - want) > max(0.01, 0.01 * want):
                errs.append("%s: bucketdb_bench.latency_ratio %.4f != "
                            "large/small p50 ratio %.4f"
                            % (where, ratio, want))
            if ratio > 1.25:
                errs.append("%s: bucketdb_bench.latency_ratio %.4f "
                            "exceeds the 1.25x flatness gate"
                            % (where, ratio))
    hit = _num(bd, "prefetch_hit_rate_pct")
    if hit is None or hit < 95.0 or hit > 100.0:
        errs.append("%s: bucketdb_bench.prefetch_hit_rate_pct must be in "
                    "[95, 100], got %r"
                    % (where, bd.get("prefetch_hit_rate_pct")))
    fp = _num(bd, "bloom_fp_pct")
    if fp is None or fp < 0.0 or fp > 5.0:
        errs.append("%s: bucketdb_bench.bloom_fp_pct must be in [0, 5], "
                    "got %r" % (where, bd.get("bloom_fp_pct")))
    sql = bd.get("sql_point_lookups")
    if sql != 0:
        errs.append("%s: bucketdb_bench.sql_point_lookups must be 0 "
                    "(the zero-SQL apply-path gate), got %r"
                    % (where, sql))
    return errs


def propagation_records(pb: dict, platform: str, source: str,
                        round_no=None, at_unix=None) -> List[dict]:
    """Normalize a `propagation` block (ISSUE 17: the propagation
    cockpit's fleet-merged relay trees) into direction-aware records:
    hop latency and tree depth percentiles over the reconstructed
    first-delivery spanning trees (lower), the redundant bandwidth
    share — the fraction of flooded bytes that arrived as duplicate
    edges, the O(n²) waste a structured relay would reclaim (lower) —
    and the worst per-peer usefulness score (higher; a peer that only
    ever sends duplicates is pure overhead)."""
    out: List[dict] = []
    if not isinstance(pb, dict) or not _num(pb, "trees"):
        return out
    for key, metric, unit in (
            ("hop_latency_p50_ms", "prop_hop_latency_p50_ms", "ms"),
            ("hop_latency_p95_ms", "prop_hop_latency_p95_ms", "ms"),
            ("tree_depth_p95", "prop_tree_depth_p95", "hops"),
            ("redundant_bandwidth_share",
             "prop_redundant_bandwidth_share", "share")):
        v = _num(pb, key)
        if v is not None:
            out.append(make_record(metric, unit, v, platform, "lower",
                                   source, round_no, at_unix))
    peers = pb.get("peers")
    if isinstance(peers, dict):
        v = _num(peers, "worst_usefulness")
        if v is not None:
            out.append(make_record("prop_worst_peer_usefulness", "share",
                                   v, platform, "higher", source,
                                   round_no, at_unix))
    return out


def validate_propagation(pb, where: str = "", flood=None) -> List[str]:
    """Schema check for one `propagation` block (`check`/`--check`):
    hop/byte totals must be finite and non-negative, the recorded
    redundant share must actually be wasted/flooded bytes, percentiles
    must be ordered — and when the sibling wire cockpit's `flood` block
    is available, duplicates/firsts over the merged hop records must
    reconcile with its duplication ratio within 10% relative tolerance
    (both cockpits count the same Floodgate.add_record receipts, so a
    drift between them means hop attribution lost edges)."""
    errs: List[str] = []
    if not isinstance(pb, dict):
        return ["%s: propagation is not an object: %r" % (where, pb)]
    trees = pb.get("trees")
    if not isinstance(trees, int) or isinstance(trees, bool) or trees < 0:
        errs.append("%s: propagation.trees must be an int >= 0, got %r"
                    % (where, trees))
    vals = {}
    for key in ("firsts", "duplicates", "flood_bytes", "wasted_bytes"):
        v = _num(pb, key)
        if v is None or v < 0:
            errs.append("%s: propagation.%s must be a finite number "
                        ">= 0, got %r" % (where, key, pb.get(key)))
        vals[key] = v
    share = _num(pb, "redundant_bandwidth_share")
    if share is None or share < 0 or share > 1:
        errs.append("%s: propagation.redundant_bandwidth_share must be "
                    "in [0, 1], got %r"
                    % (where, pb.get("redundant_bandwidth_share")))
    elif vals.get("flood_bytes"):
        want = vals["wasted_bytes"] / vals["flood_bytes"]
        if abs(share - want) > max(1e-3, 0.01 * want):
            errs.append("%s: propagation.redundant_bandwidth_share %.4f "
                        "!= wasted/flooded bytes %.4f" % (where, share,
                                                          want))
    p50 = _num(pb, "hop_latency_p50_ms")
    p95 = _num(pb, "hop_latency_p95_ms")
    if p50 is None or p95 is None or p50 < 0 or p95 + 1e-9 < p50:
        errs.append("%s: propagation needs finite "
                    "0 <= hop_latency_p50_ms <= hop_latency_p95_ms, "
                    "got p50=%r p95=%r" % (where,
                                           pb.get("hop_latency_p50_ms"),
                                           pb.get("hop_latency_p95_ms")))
    depth = _num(pb, "tree_depth_p95")
    if depth is None or depth < 0:
        errs.append("%s: propagation.tree_depth_p95 must be a finite "
                    "number >= 0, got %r"
                    % (where, pb.get("tree_depth_p95")))
    peers = pb.get("peers")
    if isinstance(peers, dict):
        wu = peers.get("worst_usefulness")
        if wu is not None and (_num(peers, "worst_usefulness") is None or
                               wu < 0 or wu > 1):
            errs.append("%s: propagation.peers.worst_usefulness must be "
                        "in [0, 1] or null, got %r" % (where, wu))
    # cross-cockpit reconciliation against the wire cockpit's dedup
    # accounting (ISSUE 17 acceptance gate)
    if isinstance(flood, dict) and vals.get("firsts"):
        r = _num(flood, "duplication_ratio")
        if r is not None and r >= 0:
            derived = vals["duplicates"] / vals["firsts"]
            if abs(derived - r) > max(0.05, 0.10 * r):
                errs.append(
                    "%s: propagation duplicates/firsts %.4f does not "
                    "reconcile with flood duplication_ratio %.4f within "
                    "10%% — hop records and flood dedup have drifted "
                    "apart" % (where, derived, r))
    return errs


def ingress_records(ib: dict, platform: str, source: str,
                    round_no=None, at_unix=None) -> List[dict]:
    """Normalize an `ingress` block (ISSUE 18: the admission-tier
    overload leg) into direction-aware records: priority-class goodput
    under overload (higher — the tier's whole point), the shed ratio
    (higher: under a fixed oversubscription, shedding MORE junk at
    admission is the desired behavior — a falling shed ratio means junk
    is leaking into the pool), applied-tx latency p95 (lower), and its
    ratio against the unloaded baseline (lower; the 2x acceptance
    gate)."""
    out: List[dict] = []
    if not isinstance(ib, dict) or not _num(ib, "decided"):
        return out
    pri = ib.get("priority")
    if isinstance(pri, dict):
        v = _num(pri, "goodput")
        if v is not None:
            out.append(make_record("ingress_priority_goodput", "share",
                                   v, platform, "higher", source,
                                   round_no, at_unix))
    for key, metric, unit, direction in (
            ("shed_ratio", "ingress_shed_ratio", "share", "higher"),
            ("tx_latency_p95_ms", "ingress_tx_latency_p95_ms", "ms",
             "lower"),
            ("p95_ratio", "ingress_p95_vs_unloaded_ratio", "x",
             "lower")):
        v = _num(ib, key)
        if v is not None:
            out.append(make_record(metric, unit, v, platform, direction,
                                   source, round_no, at_unix))
    return out


def validate_ingress(ib, where: str = "") -> List[str]:
    """Schema check for one `ingress` block (`check`/`--check`): the
    admission counters must be non-negative ints with the shed ratio
    actually shed/decided, priority goodput must be applied/submitted in
    [0, 1], the p95 ratio must be its own numerator/denominator, the
    intake/source occupancies must respect their declared caps (the
    bounded-memory acceptance gate travels with the artifact), and the
    lifecycle funnel's shed/throttled outcomes can never exceed the
    ingress tier's own decision counts (the funnel tracks first-seen
    txs only)."""
    errs: List[str] = []
    if not isinstance(ib, dict):
        return ["%s: ingress is not an object: %r" % (where, ib)]
    vals = {}
    for key in ("decided", "admitted", "throttled", "shed"):
        v = ib.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errs.append("%s: ingress.%s must be an int >= 0, got %r"
                        % (where, key, v))
            v = None
        vals[key] = v
    if None not in vals.values() and \
            vals["decided"] != vals["admitted"] + vals["throttled"] + \
            vals["shed"]:
        errs.append("%s: ingress.decided %d != admitted+throttled+shed %d"
                    % (where, vals["decided"],
                       vals["admitted"] + vals["throttled"] + vals["shed"]))
    ratio = _num(ib, "shed_ratio")
    if ratio is None or ratio < 0 or ratio > 1:
        errs.append("%s: ingress.shed_ratio must be in [0, 1], got %r"
                    % (where, ib.get("shed_ratio")))
    elif vals.get("decided"):
        want = vals["shed"] / vals["decided"] if vals.get("shed") \
            is not None else None
        if want is not None and abs(ratio - want) > max(1e-3, 0.01 * want):
            errs.append("%s: ingress.shed_ratio %.4f != shed/decided %.4f"
                        % (where, ratio, want))
    pri = ib.get("priority")
    if not isinstance(pri, dict):
        errs.append("%s: ingress.priority must be an object, got %r"
                    % (where, pri))
    else:
        sub, app = pri.get("submitted"), pri.get("applied")
        gp = _num(pri, "goodput")
        if not isinstance(sub, int) or not isinstance(app, int) or \
                isinstance(sub, bool) or isinstance(app, bool) or \
                sub < 0 or app < 0 or app > sub:
            errs.append("%s: ingress.priority needs ints "
                        "0 <= applied <= submitted, got %r/%r"
                        % (where, app, sub))
        elif gp is None or gp < 0 or gp > 1:
            errs.append("%s: ingress.priority.goodput must be in [0, 1], "
                        "got %r" % (where, pri.get("goodput")))
        elif sub and abs(gp - app / sub) > max(1e-3, 0.01 * (app / sub)):
            errs.append("%s: ingress.priority.goodput %.4f != "
                        "applied/submitted %.4f" % (where, gp, app / sub))
    p95 = _num(ib, "tx_latency_p95_ms")
    base = _num(ib, "unloaded_p95_ms")
    pr = _num(ib, "p95_ratio")
    if p95 is None or p95 < 0 or base is None or base <= 0 or \
            pr is None or pr < 0:
        errs.append("%s: ingress needs finite tx_latency_p95_ms >= 0, "
                    "unloaded_p95_ms > 0, p95_ratio >= 0; got %r/%r/%r"
                    % (where, ib.get("tx_latency_p95_ms"),
                       ib.get("unloaded_p95_ms"), ib.get("p95_ratio")))
    elif abs(pr - p95 / base) > max(0.01, 0.01 * pr):
        errs.append("%s: ingress.p95_ratio %.3f != p95/unloaded %.3f"
                    % (where, pr, p95 / base))
    # bounded-memory gate: occupancy <= cap for the intake and the
    # per-source tracking map
    for blk, occ_key in (("intake", "depth"), ("sources", "tracked")):
        sub = ib.get(blk)
        if not isinstance(sub, dict):
            errs.append("%s: ingress.%s must be an object, got %r"
                        % (where, blk, sub))
            continue
        occ, cap = _num(sub, occ_key), _num(sub, "cap")
        if occ is None or cap is None or occ < 0 or cap <= 0:
            errs.append("%s: ingress.%s needs finite %s >= 0 and cap > 0,"
                        " got %r/%r" % (where, blk, occ_key,
                                        sub.get(occ_key), sub.get("cap")))
        elif occ > cap:
            errs.append("%s: ingress.%s.%s %.0f exceeds its cap %.0f — "
                        "an unbounded queue in a committed artifact"
                        % (where, blk, occ_key, occ, cap))
    outcomes = ib.get("outcomes")
    if isinstance(outcomes, dict):
        for kind in ("shed", "throttled"):
            oc = outcomes.get(kind, 0)
            lim = vals.get(kind)
            if isinstance(oc, int) and lim is not None and oc > lim:
                errs.append("%s: lifecycle outcome %s=%d exceeds the "
                            "ingress %s count %d" % (where, kind, oc,
                                                     kind, lim))
    return errs


def scp_records(sb: dict, platform: str, source: str,
                round_no=None, at_unix=None) -> List[dict]:
    """Normalize an `scp` block (ISSUE 19: the consensus cockpit) into
    direction-aware records: envelopes per externalized slot (lower —
    the committed O(n^2) flood baseline that ROADMAP item 1's BLS
    quorum certificates must beat) and the worst ballot round count
    (lower — round inflation is timer retries, not progress)."""
    out: List[dict] = []
    if not isinstance(sb, dict):
        return out
    v = _num(sb, "envelopes_per_slot")
    if v is not None:
        out.append(make_record("envelopes_per_slot", "envelopes", v,
                               platform, "lower", source, round_no,
                               at_unix))
    rounds = sb.get("rounds")
    if isinstance(rounds, dict):
        v = _num(rounds, "ballot")
        if v is not None:
            out.append(make_record("scp_ballot_rounds_worst", "rounds",
                                   v, platform, "lower", source,
                                   round_no, at_unix))
    return out


def footprint_records(fb: dict, platform: str, source: str,
                      round_no=None, at_unix=None) -> List[dict]:
    """Normalize a `footprint` block (ISSUE 19: the node footprint
    census) into direction-aware records: mean per-node RSS (lower —
    the N-vs-RSS scaling curve for the 100-node push)."""
    out: List[dict] = []
    if not isinstance(fb, dict):
        return out
    v = _num(fb, "per_node_rss_mb")
    if v is not None:
        out.append(make_record("per_node_rss_mb", "MB", v, platform,
                               "lower", source, round_no, at_unix))
    return out


def _check_phase_sum(phase_s, wall, lw: str, errs: List[str]) -> None:
    """Phase latencies telescope inside the slot: the sum of non-null
    per-phase seconds can never exceed the slot wall they partition."""
    if not isinstance(phase_s, dict):
        return
    total = 0.0
    for p, v in sorted(phase_s.items()):
        if v is None:
            continue
        pv = _num({"v": v}, "v")
        if pv is None or pv < 0:
            errs.append("%s: phase %r must be a finite number >= 0 or "
                        "null, got %r" % (lw, p, v))
            return
        total += pv
    if wall is not None and total > wall + max(1e-4, 1e-3 * wall):
        errs.append("%s: phase latencies sum to %.6f s but the slot "
                    "wall is %.6f s — phases cannot outlast the slot "
                    "they partition" % (lw, total, wall))


def validate_scp(sb, where: str = "") -> List[str]:
    """Schema check for an `scp` block (`check`/`--check`): phase
    latencies must telescope inside each slot wall and envelope counts
    must be sane non-negative numbers. Accepts both the fleet-merged
    `scp_summary()` shape and a per-node `ScpStats.fleet_json()` blob
    (keyed by the `self`/`totals` fields only the per-node shape has).
    The sum-vs-wall contract only binds per node: the fleet merge takes
    the per-PHASE worst case over nodes, and a sum of maxes can exceed
    the max wall — there the phases are only checked for sanity."""
    errs: List[str] = []
    if not isinstance(sb, dict):
        return ["%s: scp is not an object: %r" % (where, sb)]
    if "self" in sb or "totals" in sb:
        # per-node ScpStats.fleet_json()
        for slot_str, rec in sorted((sb.get("slots") or {}).items()):
            lw = "%s: scp.slots[%s]" % (where, slot_str)
            if not isinstance(rec, dict):
                errs.append("%s must be an object" % lw)
                continue
            ph = rec.get("phases")
            if isinstance(ph, dict):
                _check_phase_sum(ph.get("phase_s"), _num(ph, "wall_s"),
                                 lw, errs)
        return errs
    # fleet-merged scp_summary()
    eps = _num(sb, "envelopes_per_slot")
    if eps is None or eps < 0:
        errs.append("%s: scp.envelopes_per_slot must be a finite number"
                    " >= 0, got %r" % (where, sb.get("envelopes_per_slot")))
    for slot_str, rec in sorted((sb.get("slots") or {}).items()):
        lw = "%s: scp.slots[%s]" % (where, slot_str)
        if not isinstance(rec, dict):
            errs.append("%s must be an object" % lw)
            continue
        env = rec.get("envelopes")
        if not isinstance(env, int) or isinstance(env, bool) or env < 0:
            errs.append("%s.envelopes must be an int >= 0, got %r"
                        % (lw, env))
        # per-phase maxes over nodes: sanity only, no sum-vs-wall bound
        _check_phase_sum(rec.get("phase_s"), None, lw, errs)
        wall = _num(rec, "wall_s")
        if rec.get("wall_s") is not None and (wall is None or wall < 0):
            errs.append("%s.wall_s must be a finite number >= 0, got %r"
                        % (lw, rec.get("wall_s")))
    return errs


def _check_footprint_structs(structs, lw: str, errs: List[str]) -> None:
    if not isinstance(structs, dict):
        errs.append("%s.structs must be an object, got %r"
                    % (lw, structs))
        return
    for sname, entry in sorted(structs.items()):
        if not isinstance(entry, dict):
            errs.append("%s.structs[%s] must be an object" % (lw, sname))
            continue
        if entry.get("error") is not None:
            continue    # scrape-time callback failure; occupancy unknown
        occ, cap = _num(entry, "occupancy"), _num(entry, "capacity")
        if occ is None or cap is None or occ < 0 or cap <= 0:
            errs.append("%s.structs[%s] needs finite occupancy >= 0 and"
                        " capacity > 0, got %r/%r"
                        % (lw, sname, entry.get("occupancy"),
                           entry.get("capacity")))
        elif occ > cap:
            errs.append("%s.structs[%s] occupancy %.0f exceeds its "
                        "capacity %.0f — an unbounded structure in a "
                        "committed artifact" % (lw, sname, occ, cap))


def validate_footprint(fb, where: str = "") -> List[str]:
    """Schema check for a `footprint` block (`check`/`--check`): every
    registered bounded structure must respect its declared capacity —
    the bounded-memory gate travels with the artifact. Accepts both the
    fleet-merged `footprint_table()` shape and a per-node census
    (`BoundedStructRegistry.to_json()`, keyed by its `structs` field).
    """
    errs: List[str] = []
    if not isinstance(fb, dict):
        return ["%s: footprint is not an object: %r" % (where, fb)]
    if "structs" in fb:
        # per-node census
        _check_footprint_structs(fb["structs"], "%s: footprint" % where,
                                 errs)
        oc = fb.get("over_capacity")
        if oc:
            errs.append("%s: footprint.over_capacity is non-empty (%s)"
                        % (where, ", ".join(sorted(oc))))
        return errs
    # fleet-merged footprint_table()
    v = _num(fb, "per_node_rss_mb")
    if v is None or v < 0:
        errs.append("%s: footprint.per_node_rss_mb must be a finite "
                    "number >= 0, got %r"
                    % (where, fb.get("per_node_rss_mb")))
    over = fb.get("over_capacity")
    if isinstance(over, dict):
        for node, names in sorted(over.items()):
            errs.append("%s: footprint.over_capacity[%s] lists %s — a "
                        "bounded structure overran its cap in a "
                        "committed artifact"
                        % (where, node, ", ".join(sorted(names))))
    for node, nb in sorted((fb.get("per_node") or {}).items()):
        if not isinstance(nb, dict):
            errs.append("%s: footprint.per_node[%s] must be an object"
                        % (where, node))
            continue
        _check_footprint_structs(nb.get("structs"),
                                 "%s: footprint.per_node[%s]"
                                 % (where, node), errs)
    return errs


def _replay_leg_records(leg: dict, platform: str, source: str,
                        round_no, at_unix) -> List[dict]:
    out = []
    for key, metric, unit, direction in (
            ("ledgers_per_sec", "replay_ledgers_per_sec", "ledgers/s",
             "higher"),
            ("txs_per_sec", "replay_txs_per_sec", "txs/s", "higher"),
            ("crypto_s", "replay_crypto_s", "s", "lower"),
            ("apply_s", "replay_apply_s", "s", "lower")):
        v = _num(leg, key)
        if v is not None:
            out.append(make_record(metric, unit, v, platform, direction,
                                   source, round_no, at_unix))
    out.extend(apply_breakdown_records(leg.get("apply_breakdown"),
                                       platform, source, round_no, at_unix))
    return out


def _payload_records(p: dict, source: str, round_no,
                     at_unix=None) -> List[dict]:
    """Normalize one bench-output payload (the raw `bench.py` JSON line,
    or a nested last_device / last_real_device_result block)."""
    out: List[dict] = []
    at_unix = p.get("at_unix", at_unix)
    if not isinstance(at_unix, int):
        at_unix = None
    platform = p.get("platform") or "unknown"

    def rec(metric, unit, value, plat, direction):
        out.append(make_record(metric, unit, value, plat, direction,
                               source, round_no, at_unix))

    if isinstance(p.get("metric"), str) and _num(p, "value") is not None \
            and isinstance(p.get("unit"), str):
        rec(p["metric"], p["unit"], p["value"], platform, "higher")
    v = _num(p, "cpu_openssl_baseline_sigs_per_sec")
    if v is not None:
        rec("cpu_openssl_baseline_sigs_per_sec", "sigs/s", v,
            "openssl-cpu", "higher")
    if platform in _DEVICE_PLATFORMS:
        for key, metric in (("compile_s", "device_compile_cold_s"),
                            ("compile_warm_s", "device_compile_warm_s"),
                            ("init_s", "device_init_s"),
                            ("latency128_p50_ms", "verify_latency128_p50_ms"),
                            ("latency128_p99_ms", "verify_latency128_p99_ms")):
            v = _num(p, key)
            if v is not None:
                rec(metric, "ms" if metric.endswith("_ms") else "s", v,
                    platform, "lower")
        # warm-restart trajectory (recorded from ISSUE 6 on): per-bucket
        # AOT warmup seconds through the verifier's cockpit
        wb = p.get("warmup_buckets_s")
        if isinstance(wb, dict) and wb:
            total = 0.0
            for b, secs in sorted(wb.items()):
                if _num({"v": secs}, "v") is None:
                    continue
                rec("warmup_bucket_%s_s" % b, "s", secs, platform, "lower")
                total += secs
            rec("warmup_total_s", "s", round(total, 3), platform, "lower")
    rep = p.get("replay")
    if isinstance(rep, dict):
        for leg_name in ("cpu", "tpu"):
            leg = rep.get(leg_name)
            if isinstance(leg, dict):
                out.extend(_replay_leg_records(
                    leg, leg.get("backend", leg_name), source, round_no,
                    at_unix))
    for key, metric, plat in (
            ("replay_speedup", "replay_speedup", "tpu-vs-cpu"),
            ("replay_crypto_speedup", "replay_crypto_speedup",
             "tpu-vs-cpu")):
        v = _num(p, key)
        if v is not None:
            rec(metric, "x", v, plat, "higher")
    ra = p.get("replay_apply")
    if isinstance(ra, dict):
        for leg_name in ("native", "python"):
            leg = ra.get(leg_name)
            if isinstance(leg, dict):
                out.extend(_replay_leg_records(
                    leg, "cpu-apply-%s" % leg_name, source, round_no,
                    at_unix))
        v = _num(ra, "apply_speedup")
        if v is not None:
            rec("native_apply_speedup", "x", v, "cpu", "higher")
    # wire-cockpit records from a payload-level overlay_breakdown
    # (`bench.py --fleet`; scenario artifacts embed theirs in an
    # explicit `records` list, which normalize_any prefers)
    ob = p.get("overlay_breakdown")
    if isinstance(ob, dict):
        out.extend(overlay_breakdown_records(ob, platform, source,
                                             round_no, at_unix))
    # propagation-cockpit records from a payload-level `propagation`
    # block (`bench.py --fleet`; scenario artifacts embed theirs in an
    # explicit `records` list, which normalize_any prefers)
    pb = p.get("propagation")
    if isinstance(pb, dict):
        out.extend(propagation_records(pb, platform, source, round_no,
                                       at_unix))
    # consensus-cockpit + footprint-census records from payload-level
    # blocks (`bench.py --fleet-scale`; scale artifacts also carry an
    # explicit `records` list, which normalize_any prefers — this path
    # keeps nested/legacy blobs normalizable)
    sb = p.get("scp")
    if isinstance(sb, dict):
        out.extend(scp_records(sb, platform, source, round_no, at_unix))
    fb = p.get("footprint")
    if isinstance(fb, dict):
        out.extend(footprint_records(fb, platform, source, round_no,
                                     at_unix))
    # multi-device verify legs (`bench.py --fleet-verify`; the artifact
    # also carries an explicit `records` list, which normalize_any
    # prefers — this path keeps nested/legacy blobs normalizable)
    fv = p.get("fleet_verify")
    if isinstance(fv, dict):
        out.extend(fleet_verify_records(fv, source, round_no, at_unix))
        v = _num(p, "fleet_speedup")
        if v is not None:
            out.append(make_record("fleet_verify_speedup", "x", v,
                                   "verify-fleet-cpu", "higher", source,
                                   round_no, at_unix))
    # batched-hash legs (`bench.py --hash`; the artifact also carries
    # an explicit `records` list, which normalize_any prefers)
    hb = p.get("hash_bench")
    if isinstance(hb, dict):
        out.extend(hash_bench_records(hb, source, round_no, at_unix))
    # million-account BucketDB leg (`bench.py --bucketdb`; the artifact
    # also carries an explicit `records` list, which normalize_any
    # prefers — this path keeps nested/legacy blobs normalizable)
    bd = p.get("bucketdb_bench")
    if isinstance(bd, dict):
        out.extend(bucketdb_records(bd, source, round_no, at_unix))
    # device history survives device-less rounds via the cached block
    for nest in (p.get("last_device"),
                 (p.get("errors") or {}).get("last_real_device_result")):
        if isinstance(nest, dict):
            out.extend(_payload_records(nest, source, round_no, at_unix))
    return out


def records_from_bench(blob: dict, source: str) -> List[dict]:
    round_no = _round_of(source)
    payload = blob.get("parsed") if _is_wrapper(blob) else blob
    if not isinstance(payload, dict):
        return []
    return _payload_records(payload, source, round_no)


def records_from_multichip(blob: dict, source: str) -> List[dict]:
    if not blob.get("ok"):
        return []      # a failed run leaves no trajectory point
    # the dry run shards over forced host devices, never over chips
    return [make_record("multichip_devices", "devices",
                        blob.get("n_devices", 0), "cpu-virtual", "higher",
                        source, _round_of(source))]


def normalize_any(blob, source: str) -> List[dict]:
    """Records from any supported blob shape: an explicit
    {"records": [...]} list (bench.py --compare output), a multichip
    driver record, or a bench payload/wrapper."""
    if isinstance(blob, dict) and isinstance(blob.get("records"), list):
        return list(blob["records"])
    if _is_multichip(blob):
        return records_from_multichip(blob, source)
    return records_from_bench(blob, source)


# --------------------------------------------------------------------------
# schema checks

def check_artifact(path: str) -> List[str]:
    name = os.path.basename(path)
    try:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
    except OSError as e:
        return ["%s: unreadable: %s" % (name, e)]
    if name.endswith(".jsonl"):
        errs: List[str] = []
        records = []
        for i, line in enumerate(text.splitlines(), 1):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                errs.append("%s:%d: bad JSON: %s" % (name, i, e))
                continue
            errs.extend(validate_record(rec, "%s:%d" % (name, i)))
            records.append(rec)
        errs.extend(_check_direction_consistency(records, name))
        return errs
    try:
        blob = json.loads(text)
    except ValueError as e:
        return ["%s: bad JSON: %s" % (name, e)]
    if _is_multichip(blob):
        errs = []
        for key, typ in (("n_devices", int), ("rc", int), ("ok", bool),
                         ("skipped", bool)):
            if not isinstance(blob.get(key), typ) or \
                    (typ is int and isinstance(blob.get(key), bool)):
                errs.append("%s: multichip field %r must be %s, got %r"
                            % (name, key, typ.__name__, blob.get(key)))
        return errs
    if _is_wrapper(blob):
        if not isinstance(blob.get("rc"), int):
            return ["%s: wrapper field 'rc' must be an int" % name]
        payload = blob.get("parsed")
        if payload is None:
            # a crashed driver run with no parsed line is a valid
            # *failure* artifact only when it says so
            return [] if blob["rc"] != 0 else \
                ["%s: rc=0 wrapper without a 'parsed' payload" % name]
    else:
        payload = blob
    errs = []
    if not isinstance(payload, dict):
        return ["%s: payload is not an object" % name]
    if not isinstance(payload.get("metric"), str):
        errs.append("%s: payload field 'metric' must be a string" % name)
    if not isinstance(payload.get("unit"), str):
        errs.append("%s: payload field 'unit' must be a string" % name)
    v = payload.get("value")
    if isinstance(v, bool) or not isinstance(v, (int, float)) or \
            not math.isfinite(v):
        errs.append("%s: payload field 'value' must be a finite number, "
                    "got %r" % (name, v))
    # every apply_breakdown / overlay_breakdown anywhere in the payload
    # (replay legs, replay_apply legs, scenario blocks, nested
    # last_device blocks) must schema-validate — breakdown sum
    # contracts are enforced in committed artifacts
    _walk_breakdowns(payload, name, errs)
    # every record the normalizer derives must itself validate
    for rec in records_from_bench(blob, name):
        errs.extend(validate_record(rec, name))
    return errs


def validate_parallel_close(pc, where: str = "") -> List[str]:
    """Schema check for a `parallel_close` block (ISSUE 13: the
    conflict-graph parallel-close gate leg): both apply walls must be
    finite positives and the recorded speedup must actually be their
    ratio — a speedup that drifts from its own numerator/denominator is
    a broken artifact, not a measurement."""
    errs: List[str] = []
    if not isinstance(pc, dict):
        return ["%s: parallel_close is not an object" % where]
    ser = _num(pc, "serial_apply_ms")
    par = _num(pc, "parallel_apply_ms")
    spd = _num(pc, "parallel_apply_speedup")
    for key, v in (("serial_apply_ms", ser), ("parallel_apply_ms", par),
                   ("parallel_apply_speedup", spd)):
        if v is None or v <= 0:
            errs.append("%s: parallel_close.%s must be a finite number "
                        "> 0, got %r" % (where, key, pc.get(key)))
    if not isinstance(pc.get("clusters"), int) or pc.get("clusters", 0) < 1:
        errs.append("%s: parallel_close.clusters must be a positive int"
                    % where)
    if not errs and abs(spd - ser / par) > max(0.01, 0.01 * spd):
        errs.append("%s: parallel_close.parallel_apply_speedup %.3f != "
                    "serial/parallel ratio %.3f" % (where, spd, ser / par))
    return errs


def _walk_breakdowns(blob, name: str, errs: List[str],
                     depth: int = 0) -> None:
    if depth > 6:
        return
    if isinstance(blob, list):
        for v in blob:
            _walk_breakdowns(v, name, errs, depth + 1)
        return
    if not isinstance(blob, dict):
        return
    if "apply_breakdown" in blob:
        errs.extend(validate_apply_breakdown(blob["apply_breakdown"], name))
    if "parallel_close" in blob:
        errs.extend(validate_parallel_close(blob["parallel_close"], name))
    if "overlay_breakdown" in blob:
        errs.extend(validate_overlay_breakdown(blob["overlay_breakdown"],
                                               name))
    if blob.get("propagation") is not None:
        ob = blob.get("overlay_breakdown")
        errs.extend(validate_propagation(
            blob["propagation"], name,
            flood=ob.get("flood") if isinstance(ob, dict) else None))
    if blob.get("ingress") is not None:
        errs.extend(validate_ingress(blob["ingress"], name))
    if blob.get("scp") is not None:
        errs.extend(validate_scp(blob["scp"], name))
    if blob.get("footprint") is not None:
        errs.extend(validate_footprint(blob["footprint"], name))
    if "fleet_verify" in blob:
        errs.extend(validate_fleet_verify(blob["fleet_verify"], name))
    if "hash_bench" in blob:
        errs.extend(validate_hash_bench(blob["hash_bench"], name))
    if "bucketdb_bench" in blob:
        errs.extend(validate_bucketdb(blob["bucketdb_bench"], name))
    for v in blob.values():
        if isinstance(v, (dict, list)):
            _walk_breakdowns(v, name, errs, depth + 1)


def _check_direction_consistency(records, name: str) -> List[str]:
    seen: Dict[str, str] = {}
    errs = []
    for rec in records:
        if not isinstance(rec, dict):
            continue
        m, d = rec.get("metric"), rec.get("direction")
        if not isinstance(m, str) or d not in DIRECTIONS:
            continue
        if m in seen and seen[m] != d:
            errs.append("%s: metric %r has conflicting directions %s/%s"
                        % (name, m, seen[m], d))
        seen.setdefault(m, d)
    return errs


# --------------------------------------------------------------------------
# history + comparison

def load_history(path: str) -> List[dict]:
    out: List[dict] = []
    if not os.path.exists(path):
        return out
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            if line.strip():
                out.append(json.loads(line))
    return out


def best_baselines(history) -> Dict[Tuple[str, str], dict]:
    """Best committed record per (metric, platform), direction-aware."""
    best: Dict[Tuple[str, str], dict] = {}
    for rec in history:
        errs = validate_record(rec, "history")
        if errs:
            continue
        key = (rec["metric"], rec["platform"])
        cur = best.get(key)
        if cur is None:
            best[key] = rec
        elif rec["direction"] == "higher" and rec["value"] > cur["value"]:
            best[key] = rec
        elif rec["direction"] == "lower" and rec["value"] < cur["value"]:
            best[key] = rec
    return best


def compare(current, history, tolerance: float = 0.1) -> dict:
    """Diff `current` records against the best committed baseline per
    (metric, platform). A record regresses when it is worse than the
    best baseline by more than `tolerance` (fractional); records with
    no baseline land in `new` and never gate."""
    base = best_baselines(history)
    report = {"tolerance": tolerance, "regressions": [],
              "improvements": [], "ok": [], "new": []}
    for c in current:
        errs = validate_record(c, "current")
        if errs:
            report["regressions"].append(
                {"metric": c.get("metric"), "error": "; ".join(errs)})
            continue
        key = (c["metric"], c["platform"])
        b = base.get(key)
        if b is None:
            report["new"].append({"metric": c["metric"],
                                  "platform": c["platform"],
                                  "value": c["value"]})
            continue
        entry = {"metric": c["metric"], "platform": c["platform"],
                 "current": c["value"], "best": b["value"],
                 "best_source": b.get("source"),
                 "direction": c["direction"]}
        if b["value"]:
            delta = (c["value"] - b["value"]) / abs(b["value"])
            entry["delta_pct"] = round(100.0 * delta, 2)
        if c["direction"] == "higher":
            regressed = c["value"] < b["value"] * (1.0 - tolerance)
            improved = c["value"] > b["value"]
        else:
            regressed = c["value"] > b["value"] * (1.0 + tolerance)
            improved = c["value"] < b["value"]
        (report["regressions"] if regressed else
         report["improvements"] if improved else
         report["ok"]).append(entry)
    return report


def append_history(path: str, records) -> int:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    n = 0
    with open(path, "a", encoding="utf-8") as fh:
        for rec in records:
            fh.write(json.dumps(rec, sort_keys=True) + "\n")
            n += 1
    return n


# --------------------------------------------------------------------------
# ingest

def default_artifacts(root: str = REPO) -> List[str]:
    return sorted(glob.glob(os.path.join(root, "BENCH_*.json")) +
                  glob.glob(os.path.join(root, "MULTICHIP_*.json")))


def ingest(paths, out_path: Optional[str] = None) -> List[dict]:
    """Normalize every artifact into records, deduplicated (cached
    last_device blocks repeat verbatim across rounds) and
    deterministically ordered; optionally write them as JSONL."""
    records: List[dict] = []
    seen = set()
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            blob = json.load(fh)
        for rec in normalize_any(blob, os.path.basename(path)):
            key = (rec["metric"], rec["platform"], rec["value"],
                   rec.get("at_unix"))
            if key in seen:
                continue
            seen.add(key)
            records.append(rec)
    records.sort(key=lambda r: (r.get("round") if r.get("round")
                                is not None else -1,
                                r["source"], r["metric"], r["platform"]))
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            for rec in records:
                fh.write(json.dumps(rec, sort_keys=True) + "\n")
    return records


# --------------------------------------------------------------------------
# CLI

def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # `--check` alias: the tier-1 invocation in ISSUE 6 reads
    # `tools/bench_compare.py --check`
    if argv and argv[0] == "--check":
        argv[0] = "check"
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_in = sub.add_parser("ingest", help="normalize artifacts to JSONL")
    p_in.add_argument("files", nargs="*")
    p_in.add_argument("--out", default=os.path.join(REPO, DEFAULT_HISTORY))
    p_ck = sub.add_parser("check", help="validate artifact schemas")
    p_ck.add_argument("files", nargs="*")
    p_cp = sub.add_parser("compare", help="gate a run against history")
    p_cp.add_argument("--current", required=True)
    p_cp.add_argument("--history",
                      default=os.path.join(REPO, DEFAULT_HISTORY))
    p_cp.add_argument("--tolerance", type=float, default=0.1)
    args = ap.parse_args(argv)

    if args.cmd == "ingest":
        paths = args.files or default_artifacts()
        records = ingest(paths, args.out)
        print("ingested %d records from %d artifacts -> %s"
              % (len(records), len(paths), args.out))
        return 0

    if args.cmd == "check":
        paths = args.files or default_artifacts()
        hist = os.path.join(REPO, DEFAULT_HISTORY)
        if not args.files and os.path.exists(hist):
            paths = paths + [hist]
        errors: List[str] = []
        for p in paths:
            errors.extend(check_artifact(p))
        for e in errors:
            print("MALFORMED %s" % e)
        print("%s: %d artifacts checked, %d errors"
              % ("FAIL" if errors else "OK", len(paths), len(errors)))
        return 1 if errors else 0

    if args.cmd == "compare":
        with open(args.current, encoding="utf-8") as fh:
            blob = json.load(fh)
        current = normalize_any(blob, os.path.basename(args.current))
        history = load_history(args.history)
        report = compare(current, history, tolerance=args.tolerance)
        print(json.dumps(report, indent=1, sort_keys=True))
        return 1 if report["regressions"] else 0

    return 2


if __name__ == "__main__":
    sys.exit(main())
