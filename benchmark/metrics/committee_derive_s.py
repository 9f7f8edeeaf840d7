"""Seconds inside the program's ``stf/committees`` spans (a miss of
``stf.attestations.committee_context``: the epoch's seed and, on a
geometry miss, its active set, shuffle and committee bounds), at any
nesting depth, per epoch of blocks or per step."""

NAME = "stf/committees"


def read(ctx):
    found = [v["total_s"] for k, v in ctx.spans.items() if k == NAME or k.endswith("/" + NAME)]
    return sum(found) / ctx.per if found and ctx.per else None
