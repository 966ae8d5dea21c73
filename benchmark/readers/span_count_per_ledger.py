"""How many of the named program spans (instants count: an instant is a
span of no duration) were recorded for each ledger closed in the window.
A count, so 0 spans read 0.0 and not nothing: a run in which no SCP
timer fired has no timeout to report, and says so.

args: spans   names to count
Returns nothing where no ledger closed in the window."""


def read(ctx: dict, args: dict):
    names = set(args["spans"])
    ledgers = ctx["counts"]["ledgers"]
    if not ledgers:
        return None
    n = sum(1 for spans in ctx["spans"] for s in spans if s[0] in names)
    return n / ledgers
