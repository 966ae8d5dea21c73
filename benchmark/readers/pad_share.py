"""Share of the dispatched verify lanes that were padding, from the
verifier's per-bucket counters (`GET verifier`: sigs and pad_total per
bucket) over the window. A count: it repeats exactly where the traffic
does. Returns nothing where no lane was dispatched."""


def read(ctx: dict, args: dict):
    buckets = ctx["counts"]["buckets"]
    pad = sum(b["pad"] for b in buckets.values())
    lanes = pad + sum(b["sigs"] for b in buckets.values())
    if not lanes:
        return None
    return 100.0 * pad / lanes
