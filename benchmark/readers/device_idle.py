"""The device's idle share of the traced slice: 1 - busy_s / window_s,
both from the trace reduction (harness/trace_reduce.py)."""


def read(ctx: dict, args: dict):
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
