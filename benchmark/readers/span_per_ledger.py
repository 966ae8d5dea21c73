"""Program spans (the program's own tracer, on in a traced run only)
summed and divided by the ledgers closed in the window.

args: spans      names to sum
      exclusive  true: a span's self time (its duration less its direct
                 children's); false: its whole duration
      scale      multiplier on seconds (1000 for ms)
Returns nothing where none of the spans was recorded."""


def read(ctx: dict, args: dict):
    names = set(args["spans"])
    ledgers = ctx["counts"]["ledgers"]
    total = 0.0
    seen = 0
    for spans in ctx["spans"]:
        child = {}
        if args.get("exclusive"):
            for _name, _t0, dur, _sid, parent in spans:
                if parent:
                    child[parent] = child.get(parent, 0.0) + dur
        for name, _t0, dur, sid, _parent in spans:
            if name in names:
                seen += 1
                total += max(0.0, dur - child.get(sid, 0.0))
    if not seen or not ledgers:
        return None
    return total * float(args.get("scale", 1.0)) / ledgers
