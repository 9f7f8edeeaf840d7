"""Batched block-path transition engine.

``apply_signed_blocks(spec, state, signed_blocks)`` replays signed blocks
with one BLS multi-pairing per block (cross-block triple dedup), whole-
block vectorized attestation application, and resident-routed per-slot
roots — differentially pinned to byte-identical post-states and identical
failure behavior vs the literal ``spec.state_transition``.

Layers (see docs/architecture.md, "The block path"):

* ``attestations`` — committee/attester resolution off the cached shuffle
  permutation, bulk counts via ``ops/segment.py``, registry affine matrix;
* ``verify``       — per-block signature batch: preflattened
  ``BatchFastAggregateVerify`` entries, verified-triple memo, bisection;
* ``sync``         — altair-lineage sync aggregates: seat rows memoized
  per sync period, the signature folded into the block batch, rewards as
  net per-validator deltas;
* ``slot_roots``   — spec-identical ``process_slots`` with dirty bulk
  subtrees routed through the resident merkle path;
* ``columns``      — root-keyed resident validator-state columns
  (participation, balances, registry-derived device buffers) serving
  dict probes where the tree hands out chunk walks;
* ``pipeline``     — cross-block overlapped verification: block N's
  native pairing batch runs on a dispatch worker while block N+1's
  host phases execute (``CSTPU_PIPELINE=0`` opts out);
* ``engine``       — the optimistic fast path + exact-spec replay
  fallback that makes failure behavior literally the spec's
  (fork families: phase0, and altair/bellatrix/capella with the
  execution payload run literally inside the snapshot region and, on
  capella, the withdrawals-queue dequeue and BLS-to-execution changes),
  plus the speculation window's LIFO drain orchestration.
"""
from .attestations import FastPathViolation
from .engine import apply_signed_blocks, reset_stats, stats

__all__ = ["apply_signed_blocks", "FastPathViolation", "reset_stats", "stats"]
