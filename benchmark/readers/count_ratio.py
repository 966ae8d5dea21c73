"""One of the run's counts over another (program counters read through
the admin `verifier` endpoint and the metrics registry, and the
benchmark's own tallies). Counts repeat exactly from run to run where
the traffic does.

args: num, den   keys of the run's counts
      scale      multiplier
Returns nothing where the denominator is 0 or a count is absent."""


def read(ctx: dict, args: dict):
    counts = ctx["counts"]
    num, den = counts.get(args["num"]), counts.get(args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
