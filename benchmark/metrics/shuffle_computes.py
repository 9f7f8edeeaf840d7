"""Whole-epoch shuffles computed: the number of the program's
``ops/shuffle`` spans (a miss of ``ops.shuffle.compute_shuffle_permutation``),
at any nesting depth, per epoch of blocks or per step.  An exact count."""

NAME = "ops/shuffle"


def read(ctx):
    found = [v["count"] for k, v in ctx.spans.items() if k == NAME or k.endswith("/" + NAME)]
    return sum(found) / ctx.per if found and ctx.per else None
