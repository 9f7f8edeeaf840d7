"""Capella block import: the blocks step (``steps/blocks.py``) on capella
traffic, whose blocks also carry an execution payload and BLS-to-execution
changes, with one more tampered variant: the first block with a BLS
change's signature swapped for a valid one over another message, which
``stf.apply_signed_blocks`` must refuse.

Before any set-up the step asks the program whether its block engine takes
the configuration's fork on its fast path (``stf.engine.FAST_FORKS``) and
stops with that answer if not: a literal replay of an epoch of blocks at
this registry size would not end within a run.
"""
from __future__ import annotations

import copy

from benchmark.steps import blocks


class CapellaBlocks(blocks.Blocks):
    def __init__(self, ctx):
        from consensus_specs_tpu.stf import engine

        fork = ctx.config["fork"]
        fast = getattr(engine, "FAST_FORKS", ())
        if fork not in fast:
            raise RuntimeError(f"the program's block engine does not take {fork} blocks on its "
                               f"fast path (stf.engine.FAST_FORKS = {fast})")
        super().__init__(ctx)

    def _tampered(self) -> tuple:
        early, late = super()._tampered()
        block = copy.deepcopy(self.plain_blocks[0])
        block.bls_changes[0].signature = self.plain_blocks[1].bls_changes[0].signature
        early.append(self._signed(self.plain, block))
        return early, late


class Unverified(blocks.Unverified, CapellaBlocks):
    """The control: the same blocks and variants imported with BLS off."""


STEP, CONTROL = CapellaBlocks, Unverified
