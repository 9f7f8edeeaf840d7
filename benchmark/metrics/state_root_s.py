"""Seconds inside the program's ``stf/state_root`` spans
(``stf.slot_roots.state_root``) that are not under a ``stf/process_slots``
span, per epoch of blocks or per step: the post-block root checks in the
blocks cells, the post-epoch root in the epoch cells.  The per-slot roots
of slot advancement are left out."""

NAME = "stf/state_root"
SLOTS = "/stf/process_slots/"


def read(ctx):
    found = [v["total_s"] for k, v in ctx.spans.items()
             if (k == NAME or k.endswith("/" + NAME)) and SLOTS not in "/" + k]
    return sum(found) / ctx.per if found and ctx.per else None
