"""The time the `inner` program spans spend inside the `outer` ones: for
each replay's span list, the union of the `outer` spans' intervals (by
`t0` and `dur`, whatever thread recorded them), and of every `inner`
span the part that lies in that union; summed, and divided by one of the
run's counts. It is for an interval that stops every thread, as a pause
of the interpreter's collector does: what it took from a close is the
part of it under a `ledger.close`, on whichever thread it was recorded.

args: inner      names of the spans measured
      outer      names of the spans they are measured inside
      scale      multiplier on seconds (1000 for ms)
      per_count  a key of the run's counts ("ledgers")
      hull       true: in place of the union, ONE interval a span list,
                 from the first `outer` span's start to the last one's
                 end (a replay from its catchup's first phase to its last
                 close: a node's tracer is on from the node's
                 construction, the first node's through all of set-up,
                 and what the collector did there is not the window's)
      count      true: how many `inner` spans start inside, not their time

Reads 0.0, not nothing, where no `inner` span was recorded or the count
is 0: a program from before the span existed records none (the parent of
the PR that brings the metric, which the driver runs with that PR's
benchmark files), and the harness prints no line at all for a reader
that returns nothing (`span_mean`'s rule, for its reason)."""


from ..harness.trace_reduce import clip, total, union


def read(ctx: dict, args: dict):
    inner, outer = set(args["inner"]), set(args["outer"])
    found = 0.0
    for spans in ctx["spans"]:
        cover = union([(t0, t0 + dur) for name, t0, dur, _sid, _parent
                       in spans if name in outer])
        if cover and args.get("hull"):
            cover = [(cover[0][0], cover[-1][1])]
        for name, t0, dur, _sid, _parent in spans:
            if name not in inner:
                continue
            if args.get("count"):
                found += sum(1 for a, b in cover if a <= t0 < b)
            else:
                found += total(clip(cover, t0, t0 + dur))
    n = ctx["counts"].get(args["per_count"]) or 0
    if not n:
        return 0.0
    return found * float(args.get("scale", 1.0)) / n
