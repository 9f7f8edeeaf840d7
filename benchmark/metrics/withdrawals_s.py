"""Seconds inside the program's ``stf/withdrawals`` spans, at any nesting
depth, per epoch of blocks: each capella payload's withdrawals dequeue
check and the payload header it caches (``stf.engine._execution_payload``)."""

NAME = "stf/withdrawals"


def read(ctx):
    found = [v["total_s"] for k, v in ctx.spans.items() if k == NAME or k.endswith("/" + NAME)]
    return sum(found) / ctx.per if found and ctx.per else None
