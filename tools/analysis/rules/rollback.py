"""RB01 — rollback safety in the batched transition engine.

``stf/engine.py`` makes invalid-block behavior exact by construction: the
ONLY state writes on the fast path happen between taking the backing
snapshot in ``_apply_one`` and the batch settlement — so on ANY trouble,
``state.set_backing(pre_backing)`` provably restores the pre-block state
before the literal spec replay.  That proof is a whitelist: the helpers
``_fast_transition`` dispatches to are the complete set of state-writing
functions in the subsystem.  A spec-state write added anywhere else in
``consensus_specs_tpu/stf/`` (a resolver, the signature settlement, a
cache helper) would mutate state outside the snapshot-protected region
and silently break the O(1) rollback contract PR 2 shipped.

RB01 flags, inside stf/ modules, any write through a name that
alias-resolves to a spec-state name — ``state``, ``st``, or any
``*_state`` (the subsystem's naming convention; a helper that takes the
BeaconState under another name should rename the parameter, which is
exactly the nudge the rule gives) — attribute or subscript assignment,
augmented assignment, deletion, or a mutating method call
(``append``/``update``/``set_backing``/...) — unless the innermost-out
enclosing-function chain hits the per-file whitelist below.  The
whitelist is the rule's single source of truth: extending the engine
with a new state-writing helper means adding it here, which is exactly
the review conversation the rule exists to force.
"""
from __future__ import annotations

import ast
from typing import Dict, FrozenSet

from ..core import Rule, register
from ..symbols import root_name, written_targets

_MUTATING_METHODS = {"append", "extend", "insert", "pop", "remove", "clear",
                     "update", "setdefault", "add", "discard", "set_backing"}

def _is_state_name(name: str) -> bool:
    return name in ("state", "st") or name.endswith("_state")


# file -> functions allowed to write spec state (the snapshot-protected
# region of apply_signed_blocks and the helpers it dispatches to)
PROTECTED_REGION: Dict[str, FrozenSet[str]] = {
    "engine.py": frozenset({
        "apply_signed_blocks", "_apply_one", "_fast_transition",
        "_header", "_randao_collect", "_operations",
        "_attestations", "_attestations_inner",
        "_attestations_inner_altair",
        # the overlapped pipeline (ISSUE 10): _collect_block is the
        # factored host-phase body of _fast_transition; _begin_block
        # snapshots the backing before it and restores it on failure;
        # _unwind_pending restores a failed speculation's snapshot
        # (successor rolled back first); _apply_pipelined is the loop
        # that owns their ordering
        "_apply_pipelined", "_begin_block", "_collect_block",
        "_unwind_pending",
        # capella's block operations, dispatched from _collect_block and
        # _operations inside the same region: the withdrawals-queue
        # dequeue and the BLS-to-execution credential writes
        "_withdrawals", "_bls_to_execution_changes",
    }),
    "slot_roots.py": frozenset({"process_slots", "_process_slot"}),
    # sync.py's writers run only from _fast_transition, inside the
    # snapshot region (altair-lineage sync-aggregate rewards)
    "sync.py": frozenset({"process_sync_aggregate", "_apply_rewards"}),
    # columns.py's state writers are the staged-view flushes and the sparse
    # balance write of capella's epoch sweep: called from
    # _attestations_inner_altair (snapshot region) and the epoch phases
    # (inside process_slots' epoch boundary, also snapshot-protected); the
    # read-side helpers never write
    "columns.py": frozenset({"flush", "flush_balances", "write_balances"}),
}


@register
class RollbackSafetyRule(Rule):
    """Spec-state write in stf/ outside the snapshot-protected region."""

    code = "RB01"
    summary = "state write outside the stf snapshot-protected region"
    fix_example = """\
# RB01: beacon-state mutation must happen inside the snapshot region so
# a FastPathViolation can roll it back.
-    state.slot = slot          # outside the snapshot scope
+    with snapshot_region(state):
+        state.slot = slot
"""

    protected = PROTECTED_REGION

    def check(self, ctx):
        if ctx.tree is None or "stf" not in ctx.parts:
            return
        allowed = self.protected.get(ctx.path.name, frozenset())
        sym = ctx.symbols
        for node in ast.walk(ctx.tree):
            for kind, t, method in written_targets(node):
                if kind == "method":
                    if method not in _MUTATING_METHODS:
                        continue
                elif not isinstance(t, (ast.Attribute, ast.Subscript)):
                    continue  # rebinding a local named state is not a write
                base = root_name(t)
                if base is None:
                    continue
                if not _is_state_name(sym.scope_of(node).resolve_root(base)):
                    continue
                if any(f.name in allowed
                       for f in sym.enclosing_functions(node)):
                    continue
                yield (node.lineno,
                       "spec-state write outside the snapshot-protected "
                       "region of apply_signed_blocks (rollback contract; "
                       "whitelist: tools/analysis/rules/rollback.py)")
