"""Slot advancement with cheap per-slot state roots.

``process_slots`` (specs/src/phase0.py:785-806, textually identical in
every later fork) computes ``hash_tree_root(state)`` once per slot.  The
persistent node layer already makes that incremental — unchanged subtrees
keep their memoized roots — so the replica below is byte-identical to the
spec loop while routing the one genuinely expensive case through the
resident merkle path: a freshly bulk-written packed balances subtree
(epoch kernels and state loaders rewrite the whole vector through
``ssz/bulk.py``, leaving an unhashed power-of-two subtree of ~n/4 chunks).
When the resident-merkle policy engages (``CSTPU_RESIDENT_MERKLE``, auto =
accelerator backends only — ops/merkle_resident.py:resident_device), that
subtree is reduced on device as one jit dispatch and the 32-byte root is
memoized into the host backing (``memoize_packed_u64_contents_root``), so
empty-slot advancement after an epoch transition stops paying the full
host re-merkleization of the balances vector.  On host backends the
wave-batched hashlib path (ssz/hashing.hash_layer) keeps the same
incremental shape.

Differentially pinned to ``spec.process_slots`` by
tests/spec/phase0/sanity/test_stf_engine_differential.py.
"""
from __future__ import annotations

from consensus_specs_tpu import faults, tracing

# fault probe (tests/chaos/): fires at each slot advance, so an error
# lands with some slots already processed — the engine rollback must
# restore the whole multi-slot advance
_SITE_PROCESS = faults.site("stf.slot_roots.process")


def state_root(spec, state):
    """``hash_tree_root(state)``, with dirty bulk-written balance subtrees
    routed through the device-resident reduction when the policy engages
    (the ``stf/state_root`` span; the reduction its ``ssz/resident_root``
    span: column read, upload, device reduction and readback)."""
    with tracing.span("stf/state_root"):
        _maybe_resident_balances_root(state)
        return spec.hash_tree_root(state)


def _maybe_resident_balances_root(state) -> None:
    from consensus_specs_tpu.ops import merkle_resident

    balances = getattr(state, "balances", None)
    if balances is None or len(balances) < merkle_resident.RESIDENT_MIN:
        return
    backing = balances.get_backing()
    if backing.left._root is not None:
        return  # contents subtree already hashed: incremental path is free
    device = merkle_resident.resident_device()
    if device is None:
        return
    from . import columns

    with tracing.span("ssz/resident_root"):
        # a device error raises: the block engine rolls the block back and
        # replays it literally (counted in stf.stats["replayed_blocks"])
        resident = merkle_resident.ResidentPackedU64List(
            type(balances).LENGTH, device=device)
        # resident-column read: after the epoch transition's flush this is
        # the identity fast path — no tree walk before the device upload
        resident.upload(columns.balance_column(state).astype("u8"))
        merkle_resident.memoize_packed_u64_contents_root(
            balances, resident.contents_subtree_root())
    tracing.count("stf.resident_slot_root")


def process_slots(spec, state, slot) -> None:
    """Spec-identical ``process_slots`` (same asserts, same mutations, the
    spec module's own ``process_epoch``) with per-slot roots through
    ``state_root`` above; one ``stf/process_slots`` span per call."""
    assert state.slot < slot
    with tracing.span("stf/process_slots"):
        while state.slot < slot:
            _SITE_PROCESS()
            _process_slot(spec, state)
            # Process epoch on the start slot of the next epoch
            if (state.slot + 1) % spec.SLOTS_PER_EPOCH == 0:
                spec.process_epoch(state)
            state.slot = spec.Slot(state.slot + 1)


def _process_slot(spec, state) -> None:
    # Cache state root (phase0.py:796-806 verbatim behind state_root)
    previous_state_root = state_root(spec, state)
    state.state_roots[state.slot % spec.SLOTS_PER_HISTORICAL_ROOT] = previous_state_root
    # Cache latest block header state root
    if state.latest_block_header.state_root == spec.Bytes32():
        state.latest_block_header.state_root = previous_state_root
    # Cache block root
    previous_block_root = spec.hash_tree_root(state.latest_block_header)
    state.block_roots[state.slot % spec.SLOTS_PER_HISTORICAL_ROOT] = previous_block_root
