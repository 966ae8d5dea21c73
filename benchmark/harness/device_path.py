"""Copy of bench.py's device_path_violations (PR 21): everything on a
device-backend node's own surfaces that says the device did NOT serve
the verify path by itself. Empty dict = the chip did the work."""

from __future__ import annotations

_METERS = ("crypto.verify.dispatch-failure", "crypto.verify.fallback-drain",
           "crypto.verify.flush-fallback", "crypto.breaker.trip",
           "verifier.device.trip", "verifier.warmup.failure",
           "verifier.compile-cache.unavailable", "verifier.staging.stall")


def device_path_violations(app) -> dict:
    cockpit = app.command_handler.cmd_verifier({})
    m = app.metrics.to_json()
    bad: dict = {}
    drains = cockpit.get("drains", {}).get("by_backend", {})
    if not drains.get("tpu", {}).get("drains"):
        bad["no_device_drains"] = drains
    if drains.get("cpu", {}).get("drains"):
        bad["cpu_drains"] = drains["cpu"]
    for name in _METERS:
        if m.get(name, {}).get("count"):
            bad[name] = m[name]["count"]
    breaker = cockpit.get("breaker")
    if breaker is not None and (breaker["state"] != "closed"
                                or breaker["trips"]):
        bad["breaker"] = breaker
    if cockpit.get("warmup", {}).get("state") != "done":
        bad["warmup"] = cockpit.get("warmup")
    return bad
