"""The run's last line: built, validated against the manifest, and only
then printed. A line that fails validation is never printed."""

from __future__ import annotations

import json
import math
import sys

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class LineError(ValueError):
    pass


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def validate(line: dict, expected: dict, trace: bool) -> list:
    """Everything wrong with `line` ([] = printable). `expected` is
    {metric name: unit} from the manifest for this cell and kind of
    run."""
    bad = []
    if not isinstance(line, dict):
        return ["the line is not an object: %r" % type(line).__name__]
    allowed = set(TOP_KEYS) | ({"breakdown"} if trace else set()) | \
        {"compared"}
    for k in TOP_KEYS:
        if k not in line:
            bad.append("missing key %r" % k)
    for k in line:
        if k not in allowed:
            bad.append("key %r does not belong in the line" % k)
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not a boolean: %r" % (line["correct"],))
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            bad.append("%s is not a count: %r" % (k, line[k]))
    if not bad and line["failed"] > line["attempted"]:
        bad.append("failed %d > attempted %d"
                   % (line["failed"], line["attempted"]))
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        bad.append("metrics is not an object")
        metrics = {}
    for name, unit in expected.items():
        m = metrics.get(name)
        if m is None:
            bad.append("metric %r is in the manifest for this cell and "
                       "is not in the line" % name)
        elif not isinstance(m, dict) or set(m) != {"value", "unit"}:
            bad.append("metric %r is not {value, unit}: %r" % (name, m))
        elif not _finite(m["value"]):
            bad.append("metric %r has no finite value: %r"
                       % (name, m["value"]))
        elif m["unit"] != unit:
            bad.append("metric %r has unit %r, the manifest says %r"
                       % (name, m["unit"], unit))
    for name in metrics:
        if name not in expected:
            bad.append("metric %r is not in the manifest for this cell "
                       "and kind of run" % name)
    dev = line["device"]
    if not isinstance(dev, dict):
        return bad + ["device is not an object"]
    for k in DEVICE_KEYS:
        if k not in dev:
            bad.append("device lacks %r" % k)
    if not bad:
        if not isinstance(dev["platform"], str) or \
                not isinstance(dev["kind"], str):
            bad.append("device platform/kind are not strings")
        if not isinstance(dev["count"], int) or dev["count"] < 1:
            bad.append("device count %r" % (dev["count"],))
        if not _finite(dev["memory_peak_bytes"]) or \
                dev["memory_peak_bytes"] <= 0:
            bad.append("device memory_peak_bytes %r"
                       % (dev["memory_peak_bytes"],))
    if trace:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not _finite(w) or not _finite(b):
            bad.append("traced run: device window_s %r / busy_s %r are "
                       "not both finite numbers" % (w, b))
        elif not 0 < b <= w:
            bad.append("traced run: need 0 < busy_s <= window_s, got "
                       "busy_s %r window_s %r" % (b, w))
        bd = line.get("breakdown")
        if bd is not None:
            if not isinstance(bd, dict) or \
                    set(bd) != {"device_ops", "idle_gaps"}:
                bad.append("breakdown is not {device_ops, idle_gaps}")
            else:
                for k, rows in bd.items():
                    if not isinstance(rows, list) or len(rows) > 10 or \
                            any(not (isinstance(r, list) and len(r) == 2
                                     and isinstance(r[0], str)
                                     and _finite(r[1])) for r in rows):
                        bad.append("breakdown.%s is not at most 10 "
                                   "[name, seconds] rows" % k)
    else:
        for k in ("window_s", "busy_s"):
            if k in dev:
                bad.append("untraced run carries device.%s" % k)
    return bad


def build(correct: bool, attempted: int, failed: int, metrics: dict,
          units: dict, device: dict, compared: dict,
          breakdown: dict | None = None) -> dict:
    """`metrics` is {name: value}; units come from the manifest.
    `compared` (each number beside its limit) goes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units.get(k, "?")}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def emit(line: dict, expected: dict, trace: bool, out=None) -> None:
    """Validate, serialise with allow_nan=False, print as one line.
    Raises LineError (nothing printed) when the line is not printable."""
    bad = validate(line, expected, trace)
    if bad:
        raise LineError("; ".join(bad))
    try:
        text = json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise LineError("not serialisable as JSON: %s" % e)
    if "\n" in text:
        raise LineError("the line spans lines")
    out = out or sys.stdout
    out.write(text + "\n")
    out.flush()
