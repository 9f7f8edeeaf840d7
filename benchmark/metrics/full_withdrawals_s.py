"""Seconds inside the program's ``ops/full_withdrawals`` spans, at any
nesting depth, per epoch of blocks: capella's full-withdrawal sweep at the
epoch boundary (``ops.withdrawals.full_withdrawals``)."""

NAME = "ops/full_withdrawals"


def read(ctx):
    found = [v["total_s"] for k, v in ctx.spans.items() if k == NAME or k.endswith("/" + NAME)]
    return sum(found) / ctx.per if found and ctx.per else None
