"""Seconds inside the program's ``stf/bls_changes`` spans, at any nesting
depth, per epoch of blocks: the BLS-to-execution changes' credential checks
and writes (their signatures are verified in the block's batch, under
``native/verify``)."""

NAME = "stf/bls_changes"


def read(ctx):
    found = [v["total_s"] for k, v in ctx.spans.items() if k == NAME or k.endswith("/" + NAME)]
    return sum(found) / ctx.per if found and ctx.per else None
