"""Program spans (the program's own tracer, on in a traced run only)
summed and divided by how many there were: a span's mean duration, or
the time of several spans for each span of another name, or for each
unit of one of the run's counts.

args: spans      names to sum
      exclusive  true: a span's self time (its duration less its direct
                 children's); false: its whole duration
      scale      multiplier on seconds (1000 for ms)
      per        the span whose count divides (default: the `spans`)
      per_count  in place of `per`: a key of the run's counts ("ledgers")

Reads 0.0 where there is nothing to divide by or nothing was summed: the
program recorded no such span, as a program from before the span existed
does (the parent of the PR that brings the metric, which the driver runs
with that PR's benchmark files). The harness prints no line at all for a
reader that returns nothing, so "nothing" is not an answer a new metric
can give there; a span that was recorded has a duration, so a real
reading is above 0."""


def read(ctx: dict, args: dict):
    names = set(args["spans"])
    per = {args["per"]} if "per" in args else names
    total = 0.0
    n = 0
    for spans in ctx["spans"]:
        child = {}
        if args.get("exclusive"):
            for _name, _t0, dur, _sid, parent in spans:
                if parent:
                    child[parent] = child.get(parent, 0.0) + dur
        for name, _t0, dur, sid, _parent in spans:
            if name in names:
                total += max(0.0, dur - child.get(sid, 0.0))
            if name in per:
                n += 1
    if "per_count" in args:
        n = ctx["counts"].get(args["per_count"]) or 0
    if not n:
        return 0.0
    return total * float(args.get("scale", 1.0)) / n
