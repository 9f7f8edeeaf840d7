"""Declared spec-mirror parity registry for the SP01–SP03 rules.

The TPU fast paths *reimplement* spec functions — `stf/engine.py`'s block
operations, the numpy/JAX epoch kernels in `ops/`, the builder's
sanctioned substitutions, `forkchoice/batch.py`'s batched on_attestation,
`query/streamproof.py`'s build_proof twin.  Parity with the literal
pyspec otherwise lives only in differential tests that must be
remembered; this registry makes every mirror a *declared* fact the
analyzer can audit, exactly as `concurrency_registry.py` does for the
threading contract:

* ``MirrorSpec`` — one fast-path mirror (a function, nested function, or
  class) with one ``SpecPin`` per spec twin: the AST-normalized SHA-256
  of the twin's source **as compiled into consensus_specs_tpu/specs/**
  per fork, its assert/raise site count + digest, and a guard mapping
  that routes each spec raise site to either a named guard snippet that
  must appear in the mirror's source (SP03 checks presence) or ``None``
  — meaning the site is enforced by literal spec execution instead (the
  engine's replay fallback, a direct ``spec.*`` call inside the mirror,
  or a deferred batch check whose failure raises ``FastPathViolation``
  and triggers replay).
* ``LiteralSpec`` — a spec function the fast path executes *literally*
  (the bellatrix ``process_execution_payload``-inside-snapshot shape, or
  operations the engine loops through ``spec.process_*`` verbatim).  No
  digest pin needed: the spec's own body runs.
* ``WaiverSpec`` — an explicit, justified opt-out from SP02 coverage.

SP01 fires when a pinned digest no longer matches the extracted spec
source (re-audit the mirror, then bump the pin here).  SP02 fires when a
fork in ``stf/engine.py``'s ``FAST_FORKS`` has a reachable spec function
with no pin/literal/waiver — adding ``"capella"`` to ``FAST_FORKS``
turns the gate red until every capella obligation is declared.  SP03
fires when a pin's raise-point map is stale (spec grew an assert) or a
mapped guard string was deleted from the mirror.

Coverage obligations are the state-mutating entry points
(``process_*``/``verify_*``/``on_*``) plus any function pinned or
declared anywhere: pure helpers (``get_domain``, ``compute_epoch_at_slot``,
...) are always exercised through the spec object itself and carry no
independent drift risk beyond their callers' digests.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from . import spec_extract

_PKG = "consensus_specs_tpu"

#: Spec functions SP02 walks the intra-spec call graph from, per fast fork.
ENTRY_FUNCTIONS: Tuple[str, ...] = ("state_transition",)

#: The file whose FAST_FORKS tuple defines the coverage obligation set.
ENGINE_DISPLAY = f"{_PKG}/stf/engine.py"

#: Reachable spec functions matching these prefixes are obligated even if
#: never pinned — they mutate state, so silence would hide a gap.
OBLIGATED_PREFIXES: Tuple[str, ...] = ("process_", "verify_", "on_")

# sha256 of zero raise sites (empty input) — the raise digest of every
# spec function with no assert/raise statements.
_NO_RAISES = "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"

_MAINLINE = ("phase0", "altair", "bellatrix", "capella")
_ALTAIR_ON = ("altair", "bellatrix", "capella")
# before capella's process_operations (which adds BLS-to-execution changes)
_PRE_CAPELLA = ("phase0", "altair", "bellatrix")


@dataclass(frozen=True)
class SpecPin:
    """One spec twin of a mirror: per-fork source digest + raise map."""

    fn: str                             # spec function name
    forks: Tuple[str, ...]              # forks sharing this effective def
    digest: str                         # AST-normalized source sha256
    raise_count: int
    raise_digest: str
    guards: Tuple[Optional[str], ...]   # one slot per spec raise site, in
    #                                     source order: a snippet that must
    #                                     appear in the mirror, or None =
    #                                     routed to literal replay


@dataclass(frozen=True)
class MirrorSpec:
    """One fast-path reimplementation of spec function(s)."""

    name: str           # short audit handle
    module: str         # dotted module holding the mirror
    qualname: str       # possibly-nested def path inside the module
    pins: Tuple[SpecPin, ...]
    description: str


@dataclass(frozen=True)
class LiteralSpec:
    """A spec function the fast path runs literally (no pin needed)."""

    fn: str
    forks: Tuple[str, ...]
    why: str


@dataclass(frozen=True)
class WaiverSpec:
    """An explicit SP02 coverage opt-out, with justification."""

    fn: str
    forks: Tuple[str, ...]
    why: str


MIRRORS: Tuple[MirrorSpec, ...] = (
    # ---- stf/engine.py: the fast-path block transition --------------------
    MirrorSpec(
        name="fast-transition",
        module=f"{_PKG}.stf.engine",
        qualname="_fast_transition",
        pins=(
            SpecPin(
                "state_transition", _MAINLINE,
                "7a87eac890f30b7675bf6ca56e4f2b941fed41cb47471f8a9e73f5598f207ad0",
                2,
                "78f7f952fafd71bdd1d5b78a2fbc4f7c4c7da2522ca1938d7405c9ade02a9a9b",
                ("invalid signature (batch entry",
                 "state root mismatch")),
        ),
        description="state_transition over the snapshot region: slots, "
        "block ops, deferred signature batch, state-root check.",
    ),
    MirrorSpec(
        name="proposer-signature-entry",
        module=f"{_PKG}.stf.engine",
        qualname="_proposer_entry",
        pins=(
            SpecPin(
                "verify_block_signature", _MAINLINE,
                "f04d50e632ca38a4c02ff714b53ba4fa8e20e7d2be2fe4e1297ef7b450384872",
                0, _NO_RAISES, ()),
        ),
        description="verify_block_signature as one deferred batch entry; "
        "a failed pairing raises via _fast_transition's batch guard.",
    ),
    MirrorSpec(
        name="block-header",
        module=f"{_PKG}.stf.engine",
        qualname="_header",
        pins=(
            SpecPin(
                "process_block_header", _MAINLINE,
                "793e75220920fc588a5888b0ba017d7eb181de59b945879719c422560a652848",
                5,
                "7b3da13de88549da45f0a11092f16d887e4b672363b5350494cd7a9371b44d24",
                ("assert block.slot == state.slot",
                 "assert block.slot > state.latest_block_header.slot",
                 "assert block.proposer_index == beacon_proposer_index(spec, state)",
                 "assert block.parent_root == spec.hash_tree_root(state.latest_block_header)",
                 "assert not proposer.slashed")),
        ),
        description="process_block_header with the proposer check against "
        "the numpy fast proposer walk; all five spec asserts transcribed.",
    ),
    MirrorSpec(
        name="randao",
        module=f"{_PKG}.stf.engine",
        qualname="_randao_collect",
        pins=(
            SpecPin(
                "process_randao", _MAINLINE,
                "e85059a9f2f1b39545c8e8a0ed41f9e7c60590ecb884e94618137739b078ee9d",
                1,
                "4dccfd979a4af30941380afc3b02e12c744293e3bf2dbeba4da7e71b80d9434e",
                (None,)),
        ),
        description="process_randao with the reveal's pairing check "
        "deferred into the block batch (None guard: a bad reveal fails "
        "the batch and replays literally).",
    ),
    MirrorSpec(
        name="operations-dispatch",
        module=f"{_PKG}.stf.engine",
        qualname="_operations",
        pins=(
            SpecPin(
                "process_operations", _PRE_CAPELLA,
                "99da53dcefb9e624b16c9e256bbc00042f8f7086ab4e663c4da85a7675ee454e",
                1,
                "4f198dcc492a8114d1dae7ff354d549eafbd824d21edc06c76dabf9283b67d1d",
                ("assert len(body.deposits) == min(",)),
            SpecPin(
                "process_operations", ("capella",),
                "9660e60369dd73f28aae37fdcecbde832bc4f53cf2f4e3a74fbebe7b107afff0",
                1,
                "4f198dcc492a8114d1dae7ff354d549eafbd824d21edc06c76dabf9283b67d1d",
                ("assert len(body.deposits) == min(",)),
        ),
        description="process_operations with the attestation loop swapped "
        "for the vectorized whole-block path and capella's BLS changes "
        "for _bls_to_execution_changes; other operation loops call "
        "spec.process_* literally.",
    ),
    MirrorSpec(
        name="withdrawals-dequeue",
        module=f"{_PKG}.stf.engine",
        qualname="_withdrawals",
        pins=(
            SpecPin(
                "process_withdrawals", ("capella",),
                "4c4314d18a60307aebc6482a2d2840a06b8938349834513a0fb2aa4d42d3c1d9",
                2,
                "9d93745f75650442b965afda6ba5147be66481d57987a6e13c2ade24158da439",
                ("withdrawals != dequeued count",
                 "withdrawal != dequeued entry")),
        ),
        description="capella process_withdrawals: the payload's entries "
        "against the queue head by SSZ root (the spec's container "
        "equality), the dequeue as one subtree refill.",
    ),
    MirrorSpec(
        name="bls-to-execution-change",
        module=f"{_PKG}.stf.engine",
        qualname="_bls_to_execution_changes",
        pins=(
            SpecPin(
                "process_bls_to_execution_change", ("capella",),
                "75281a041f811f8d0b75eb170bf0ca3199810c8e9c4aecb9e142fe8aa959f511",
                4,
                "a42aa94300a89e45967061c7f0654f741a42d4978c8a56f1e1ed527ed110bcd8",
                ("validator index out of range",
                 "credentials not BLS",
                 "from_bls_pubkey mismatch",
                 None)),
        ),
        description="capella process_bls_to_execution_change per change in "
        "order: index and credential asserts transcribed, the signature "
        "deferred into the block batch (None guard: a bad one fails the "
        "batch and replays literally), the credential write.",
    ),
    MirrorSpec(
        name="attestations-phase0",
        module=f"{_PKG}.stf.engine",
        qualname="_attestations_inner",
        pins=(
            SpecPin(
                "process_attestation", ("phase0",),
                "654b5468657a0a8299dd26b07af932cff8a79db1083736c78aff22e94328113b",
                8,
                "a9694889662a8a789cd234f6ee2b0da7f2b772a56333864e47f86da2f18df765",
                (None, None, None, None, None,
                 "source != current justified",
                 "source != previous justified",
                 None)),
        ),
        description="phase0 process_attestation over the whole block: "
        "window/committee asserts live in _BlockResolver (pinned there), "
        "source checks are the two named guards, the indexed-attestation "
        "signature defers into the batch.",
    ),
    MirrorSpec(
        name="attestations-altair",
        module=f"{_PKG}.stf.engine",
        qualname="_attestations_inner_altair",
        pins=(
            SpecPin(
                "process_attestation", _ALTAIR_ON,
                "55fbc8605211de2947e1279200fb5529594021d8cb16417c8bebdd68a3e136c2",
                6,
                "20ddbb76ef88f04e1f0d7399a4ea663cb199f13442d7f3e72e9970715da91c2b",
                (None, None, None, None, None, None)),
        ),
        description="altair-lineage process_attestation vectorized over "
        "participation flags: windows/committees via _BlockResolver "
        "(pinned there), flag asserts via _FlagMaskContext, signature "
        "deferred into the batch.",
    ),
    MirrorSpec(
        name="participation-flag-mask",
        module=f"{_PKG}.stf.engine",
        qualname="_FlagMaskContext.mask",
        pins=(
            SpecPin(
                "get_attestation_participation_flag_indices",
                _ALTAIR_ON,
                "54d35d7bcc27cc3c17f1a97f970775598cd625cc1a19790fcbfe72be715e49e8",
                1,
                "f4318883418141630910c4914c2addd665652c6a31a166d5ab9d7c1c748687c5",
                ("source != justified checkpoint",)),
        ),
        description="get_attestation_participation_flag_indices as a "
        "per-(slot,delay) bitmask with the is_matching_source assert "
        "reproduced as a FastPathViolation.",
    ),
    # ---- stf/slot_roots.py ------------------------------------------------
    MirrorSpec(
        name="slot-advance",
        module=f"{_PKG}.stf.slot_roots",
        qualname="process_slots",
        pins=(
            SpecPin(
                "process_slots", _MAINLINE,
                "0fd11f7afb4c1eb13cafebaef9182c19734cda90f1e9a7acf3e984884b34d123",
                1,
                "335acb1d37cd4d9e3eb4739a6bb7396d7a2cec056e4f5d29e9714176b102113a",
                ("assert state.slot < slot",)),
        ),
        description="process_slots with bulk root hashing; the slot "
        "monotonicity assert is transcribed verbatim.",
    ),
    MirrorSpec(
        name="single-slot",
        module=f"{_PKG}.stf.slot_roots",
        qualname="_process_slot",
        pins=(
            SpecPin(
                "process_slot", _MAINLINE,
                "f7e9bc528cf240abe1a11b4704632a0be581246ea7217e991a77ad67f2fda36b",
                0, _NO_RAISES, ()),
        ),
        description="process_slot's three root writes off the bulk "
        "hash-tree-root path.",
    ),
    # ---- stf/attestations.py ---------------------------------------------
    MirrorSpec(
        name="proposer-index",
        module=f"{_PKG}.stf.attestations",
        qualname="beacon_proposer_index",
        pins=(
            SpecPin(
                "get_beacon_proposer_index", _MAINLINE,
                "80f18635ff69aa84bd45c7873438914701562cb6154f57efd5003547154ef74b",
                0, _NO_RAISES, ()),
            SpecPin(
                "compute_proposer_index", _MAINLINE,
                "9efe34df547642c9724160474a5cc425ba3921fbabd9d4fa823c9046a4824d17",
                1,
                "3b6e21f88e330b9ea3d21754b84a67b05bf6d7ec46adf21efb5279a0cfae6b7f",
                ("assert total > 0",)),
        ),
        description="get_beacon_proposer_index + compute_proposer_index's "
        "rejection-sampling walk over the numpy active set.",
    ),
    MirrorSpec(
        name="committee-context",
        module=f"{_PKG}.stf.attestations",
        qualname="_CommitteeContext",
        pins=(
            SpecPin(
                "get_beacon_committee", _MAINLINE,
                "57b88171e91fbf24ffb4f47e4dac08ff708ca249a72e97074e151af1ab467324",
                0, _NO_RAISES, ()),
            SpecPin(
                "compute_committee", _MAINLINE,
                "aed4517144e5f175520771da6108e78998707bd44b2b85f52b1d896be9bdfc24",
                0, _NO_RAISES, ()),
        ),
        description="per-epoch committee geometry: one whole-permutation "
        "shuffle replacing compute_committee's per-member walk.",
    ),
    MirrorSpec(
        name="block-resolver",
        module=f"{_PKG}.stf.attestations",
        qualname="_BlockResolver",
        pins=(
            SpecPin(
                "process_attestation", ("phase0",),
                "654b5468657a0a8299dd26b07af932cff8a79db1083736c78aff22e94328113b",
                8,
                "a9694889662a8a789cd234f6ee2b0da7f2b772a56333864e47f86da2f18df765",
                ("target epoch outside window",
                 "target epoch != epoch of slot",
                 "inclusion window",
                 "committee index out of range",
                 "aggregation bits != committee size",
                 None, None, None)),
            SpecPin(
                "process_attestation", _ALTAIR_ON,
                "55fbc8605211de2947e1279200fb5529594021d8cb16417c8bebdd68a3e136c2",
                6,
                "20ddbb76ef88f04e1f0d7399a4ea663cb199f13442d7f3e72e9970715da91c2b",
                ("target epoch outside window",
                 "target epoch != epoch of slot",
                 "inclusion window",
                 "committee index out of range",
                 "aggregation bits != committee size",
                 None)),
        ),
        description="process_attestation's precondition asserts (target "
        "window, slot/epoch match, inclusion delay, committee index, bit "
        "length) reproduced as FastPathViolations while resolving each "
        "attestation to committee rows; the indexed-attestation signature "
        "(and phase0 source checks) are handled by the engine/batch.",
    ),
    MirrorSpec(
        name="attesting-plan",
        module=f"{_PKG}.stf.attestations",
        qualname="cached_plan_attesters",
        pins=(
            SpecPin(
                "get_attesting_indices", _MAINLINE,
                "dee2168ca0cdf12e5f30c41d8016c7a95a991d67f32fc77c65bfd31845a7d020",
                0, _NO_RAISES, ()),
        ),
        description="get_attesting_indices over the committee-context "
        "rows, memoized per (state, attestation-plan).",
    ),
    # ---- stf/sync.py ------------------------------------------------------
    MirrorSpec(
        name="sync-aggregate",
        module=f"{_PKG}.stf.sync",
        qualname="process_sync_aggregate",
        pins=(
            SpecPin(
                "process_sync_aggregate", _ALTAIR_ON,
                "26307ed96010cd9f29a403c55c628de59c465903c923df3a6a8b6bd1a12f814c",
                1,
                "aeca6b7e2e1b347c032001a2bbca15d8afee4c124139d076d8bb74694ae777bc",
                ("empty sync set, non-infinity sig",)),
        ),
        description="process_sync_aggregate with the committee signature "
        "deferred into the block batch; eth_fast_aggregate_verify's only "
        "non-pairing acceptance (empty set + infinity sig) is the named "
        "guard, the pairing half fails the batch and replays.",
    ),
    # ---- ops/epoch_jax.py: the phase0 epoch kernels -----------------------
    MirrorSpec(
        name="phase0-deltas-kernel",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="attestation_deltas_for_state",
        pins=(
            SpecPin(
                "get_attestation_deltas", ("phase0",),
                "64de15a4cc5e3db1d17277ed3f803343555461da0d653f0cbcc9ed3ef21ede5d",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_source_deltas", ("phase0",),
                "5e75607d37f765386a2878a03149312c67dfe05f85e6323ffb3b1ca74b87f71b",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_target_deltas", ("phase0",),
                "2de4622f2b5d81df3b1b34856dc72c4c4d1a9a581619dc2a7327e053f5db92d1",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_head_deltas", ("phase0",),
                "d109c6ddbfba7215de621d9457b7f3077e61fd622dbb64557455ceb880ea05c9",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_inclusion_delay_deltas", ("phase0",),
                "bfcc93448fd42cb38dc656f52897e3364cd0319b8163e2fa3fc701af9d8f9ad5",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_inactivity_penalty_deltas", ("phase0",),
                "8665ccbf8cba1c2ad5afd51141fa0b9eeebce433b9a0001468c538a784433025",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_attestation_component_deltas", ("phase0",),
                "336b31ea8e7c703ff7cd286b37b31b33a3521b864e549ac9d12ee8e074ef6bba",
                0, _NO_RAISES, ()),
        ),
        description="get_attestation_deltas and its six component-delta "
        "helpers as one vectorized rewards/penalties kernel.",
    ),
    MirrorSpec(
        name="matching-attestation-scan",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="_matching_scan",
        pins=(
            SpecPin(
                "get_matching_source_attestations", ("phase0",),
                "cb8c7f1fc9651f9b3f389bd4af6a8529799379b6c04de185b431ee315bad7f5f",
                1,
                "3374dc63a73749ad5960a1f6906d73b57c94c558fd64c7abe93989f15efffa31",
                ("assert int(epoch) in (prev_epoch, cur_epoch)",)),
            SpecPin(
                "get_matching_target_attestations", ("phase0",),
                "dd087c98cffcfd77173fb2ed19f54e8d99d3fe09e5e0fc7c7b04f7e18d1f5dd6",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_matching_head_attestations", ("phase0",),
                "44708ec6eb815a27bdaa4cf35670aafe8f058e515733a5a1a486bf6859e79c91",
                0, _NO_RAISES, ()),
        ),
        description="the three matching-attestation filters as one cached "
        "scan; the source filter's epoch-window assert is transcribed.",
    ),
    MirrorSpec(
        name="attesting-balance",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="attesting_balance",
        pins=(
            SpecPin(
                "get_attesting_balance", ("phase0",),
                "45316a74c13ee00c5b95f2f64d0312c4fa8cbe13e2f7bb63bf6c36c1a75dd957",
                0, _NO_RAISES, ()),
        ),
        description="get_attesting_balance summed over the numpy "
        "effective-balance column.",
    ),
    MirrorSpec(
        name="attesting-indices-union",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="attesting_indices",
        pins=(
            SpecPin(
                "get_attesting_indices", _MAINLINE,
                "dee2168ca0cdf12e5f30c41d8016c7a95a991d67f32fc77c65bfd31845a7d020",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_unslashed_attesting_indices", ("phase0",),
                "217958bfc2badbaf3315f9b0480f45aec8305b81e1012b95f9b03ca2a7e92f53",
                0, _NO_RAISES, ()),
        ),
        description="per-attestation attesting sets and their unslashed "
        "union as boolean masks over the registry columns.",
    ),
    MirrorSpec(
        name="total-active-balance",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="total_active_balance",
        pins=(
            SpecPin(
                "get_total_active_balance", _MAINLINE,
                "ef640e5238ec0462f4c6c6da7d02f34b52e1b92f4b24f22bad4acaf7ac9654a9",
                0, _NO_RAISES, ()),
        ),
        description="get_total_active_balance as a masked column sum "
        "(builder-installed for every fork).",
    ),
    MirrorSpec(
        name="active-validator-indices",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="active_validator_indices",
        pins=(
            SpecPin(
                "get_active_validator_indices", _MAINLINE,
                "7f0fb8053d6737f8237785d22b188391b522898a9856486f6e48bedc34127fd3",
                0, _NO_RAISES, ()),
        ),
        description="get_active_validator_indices off the cached "
        "activation/exit epoch columns (builder-installed for every fork).",
    ),
    MirrorSpec(
        name="effective-balance-updates",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="effective_balance_updates",
        pins=(
            SpecPin(
                "process_effective_balance_updates", _MAINLINE,
                "c7d2ce8328c3bd5a31630119a9cd6f74df9d7334fa4f6c199c8e244c9943ebbe",
                0, _NO_RAISES, ()),
        ),
        description="process_effective_balance_updates' hysteresis sweep "
        "vectorized over the balance columns.",
    ),
    MirrorSpec(
        name="registry-updates",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="registry_updates",
        pins=(
            SpecPin(
                "process_registry_updates", _MAINLINE,
                "7e24127a803379dd79f5287c3301599c98a3c6c45c9d68c8667153d8c58bbc46",
                0, _NO_RAISES, ()),
        ),
        description="process_registry_updates' eligibility/ejection/"
        "activation-queue sweep vectorized over the registry columns.",
    ),
    MirrorSpec(
        name="slashings-sweep",
        module=f"{_PKG}.ops.epoch_jax",
        qualname="slashings_sweep",
        pins=(
            SpecPin(
                "process_slashings", ("phase0",),
                "5ca5905ca8c1ce0eee54482da027acb5b99a2a1dd025dbee84d8c7b6eaa00bb8",
                0, _NO_RAISES, ()),
            SpecPin(
                "process_slashings", ("altair",),
                "9d9e75d69c36ce784435efadea16ac1f432273e114c0d50e548071d3bdb01323",
                0, _NO_RAISES, ()),
            SpecPin(
                "process_slashings", ("bellatrix", "capella"),
                "03e45ac7b66a9c0716bfeb7a2f5b9dd5c501b03f1ae0db17383c198b4aa50ffb",
                0, _NO_RAISES, ()),
        ),
        description="process_slashings across all three fork variants, "
        "differing only in the proportional-slashing multiplier "
        "(_SLASHING_MULT per fork).",
    ),
    # ---- ops/epoch_altair.py: the altair-lineage epoch kernels ------------
    MirrorSpec(
        name="altair-justification",
        module=f"{_PKG}.ops.epoch_altair",
        qualname="justification_and_finalization",
        pins=(
            SpecPin(
                "process_justification_and_finalization", _ALTAIR_ON,
                "e3f33fe65ff767bcf0f9148b883eb4cd918b4f1f9a794758ad69493e753b7693",
                0, _NO_RAISES, ()),
        ),
        description="altair+ process_justification_and_finalization off "
        "the participation-flag columns.",
    ),
    MirrorSpec(
        name="altair-rewards",
        module=f"{_PKG}.ops.epoch_altair",
        qualname="rewards_and_penalties",
        pins=(
            SpecPin(
                "process_rewards_and_penalties", _ALTAIR_ON,
                "aea1452a760bcb187e5999d9a54e90e0aa75fcd311baaf967174585e7ece7211",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_flag_index_deltas", _ALTAIR_ON,
                "26142da130a460a244b7e0c1b4dda1bc21eca4722c4dad416f8a64c3b0009411",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_inactivity_penalty_deltas", ("altair",),
                "c5321b40a093a2158442c85c2ef0322f5f6512ec6465c4548fc7c68b19b69641",
                0, _NO_RAISES, ()),
            SpecPin(
                "get_inactivity_penalty_deltas", ("bellatrix", "capella"),
                "9bf4bf09c52fb2f7c1917a0072e3bf522ea9234c0230642be69b765fc3bba1b2",
                0, _NO_RAISES, ()),
        ),
        description="altair+ process_rewards_and_penalties: flag-index "
        "and inactivity deltas (altair vs bellatrix penalty quotients) "
        "as one columnar kernel.",
    ),
    MirrorSpec(
        name="inactivity-updates",
        module=f"{_PKG}.ops.epoch_altair",
        qualname="inactivity_updates",
        pins=(
            SpecPin(
                "process_inactivity_updates", _ALTAIR_ON,
                "b9ffa95fc30a72baece3eaea2e8f39d8aecb854464a43343fb9ee83d0c2a483a",
                0, _NO_RAISES, ()),
        ),
        description="process_inactivity_updates' score bump/decay "
        "vectorized over the inactivity-score column.",
    ),
    MirrorSpec(
        name="participation-flag-rotation",
        module=f"{_PKG}.ops.epoch_altair",
        qualname="participation_flag_updates",
        pins=(
            SpecPin(
                "process_participation_flag_updates", _ALTAIR_ON,
                "d4cffbba85ee8ab702fc975454ab3e73b13341df40f53a51e5cd06191e3b1056",
                0, _NO_RAISES, ()),
        ),
        description="process_participation_flag_updates' epoch rotation "
        "as a column swap + zero fill.",
    ),
    MirrorSpec(
        name="unslashed-participating-mask",
        module=f"{_PKG}.ops.epoch_altair",
        qualname="_unslashed_participating_mask",
        pins=(
            SpecPin(
                "get_unslashed_participating_indices", _ALTAIR_ON,
                "2924aecacd9083131d068c53cdceb14b9d57d1d6fb5f264334cd1bdb59ecb70d",
                1,
                "3374dc63a73749ad5960a1f6906d73b57c94c558fd64c7abe93989f15efffa31",
                (None,)),
        ),
        description="get_unslashed_participating_indices as a boolean "
        "mask; the spec's epoch-window assert is structurally satisfied "
        "(every caller passes previous/current epoch), so the site routes "
        "to literal replay rather than a named guard.",
    ),
    # ---- specs/builder.py: sanctioned in-spec substitutions ---------------
    MirrorSpec(
        name="builder-compute-committee",
        module=f"{_PKG}.specs.builder",
        qualname="_install_optimizations.compute_committee",
        pins=(
            SpecPin(
                "compute_committee", _MAINLINE,
                "aed4517144e5f175520771da6108e78998707bd44b2b85f52b1d896be9bdfc24",
                0, _NO_RAISES, ()),
        ),
        description="compute_committee via one whole-permutation shuffle "
        "per epoch, installed into every compiled spec.",
    ),
    MirrorSpec(
        name="builder-indexed-attestation",
        module=f"{_PKG}.specs.builder",
        qualname="_install_attestation_pubkey_column.is_valid_indexed_attestation",
        pins=(
            SpecPin(
                "is_valid_indexed_attestation", _MAINLINE,
                "b7f57dbe3ee4dbfff347de22107551666d9724025f79100d1627b6bb3ce797ec",
                0, _NO_RAISES, ()),
        ),
        description="is_valid_indexed_attestation with pubkey gathers off "
        "the registry's affine pubkey column.",
    ),
    MirrorSpec(
        name="builder-altair-attestation-kernel",
        module=f"{_PKG}.specs.builder",
        qualname="_install_altair_attestation_kernel.process_attestation",
        pins=(
            SpecPin(
                "process_attestation", _ALTAIR_ON,
                "55fbc8605211de2947e1279200fb5529594021d8cb16417c8bebdd68a3e136c2",
                6,
                "20ddbb76ef88f04e1f0d7399a4ea663cb199f13442d7f3e72e9970715da91c2b",
                ('assert data.target.epoch in (',
                 'assert data.target.epoch == g["compute_epoch_at_slot"](data.slot)',
                 'assert (data.slot + g["MIN_ATTESTATION_INCLUSION_DELAY"]',
                 'assert data.index < g["get_committee_count_per_slot"](',
                 'assert len(attestation.aggregation_bits) == len(committee)',
                 'assert g["is_valid_indexed_attestation"](')),
        ),
        description="altair process_attestation against the scoped "
        "participation mirror; all six spec asserts transcribed verbatim "
        "over the compiled spec's globals.",
    ),
    MirrorSpec(
        name="builder-sync-aggregate-index",
        module=f"{_PKG}.specs.builder",
        qualname="_install_sync_aggregate_index.process_sync_aggregate",
        pins=(
            SpecPin(
                "process_sync_aggregate", _ALTAIR_ON,
                "26307ed96010cd9f29a403c55c628de59c465903c923df3a6a8b6bd1a12f814c",
                1,
                "aeca6b7e2e1b347c032001a2bbca15d8afee4c124139d076d8bb74694ae777bc",
                ('assert g["eth_fast_aggregate_verify"](',)),
        ),
        description="process_sync_aggregate with index-based reward "
        "application; the aggregate-signature assert is transcribed.",
    ),
    MirrorSpec(
        name="builder-phase0-rewards",
        module=f"{_PKG}.specs.builder",
        qualname="_install_phase0_epoch_kernel.process_rewards_and_penalties",
        pins=(
            SpecPin(
                "process_rewards_and_penalties", ("phase0",),
                "01f013e60551d086b7362b8f6765479e231e5ce65ad101d3d071a9299c90b583",
                0, _NO_RAISES, ()),
        ),
        description="phase0 process_rewards_and_penalties applying the "
        "epoch_jax deltas kernel in one balance sweep.",
    ),
    MirrorSpec(
        name="builder-phase0-deltas",
        module=f"{_PKG}.specs.builder",
        qualname="_install_phase0_epoch_kernel.get_attestation_deltas",
        pins=(
            SpecPin(
                "get_attestation_deltas", ("phase0",),
                "64de15a4cc5e3db1d17277ed3f803343555461da0d653f0cbcc9ed3ef21ede5d",
                0, _NO_RAISES, ()),
        ),
        description="get_attestation_deltas adapter returning the "
        "epoch_jax kernel's rewards/penalties as spec Gwei lists.",
    ),
    # ---- ops/withdrawals.py: capella's epoch sweep ------------------------
    MirrorSpec(
        name="full-withdrawals-sweep",
        module=f"{_PKG}.ops.withdrawals",
        qualname="full_withdrawals",
        pins=(
            SpecPin(
                "process_full_withdrawals", ("capella",),
                "2fbe3e8889ab9048d5b77883d11a3dab206421ae05ff2cd9b4e1804a2b83de03",
                0, _NO_RAISES, ()),
            SpecPin(
                "is_fully_withdrawable_validator", ("capella",),
                "d1311b98451f8a123ca201581e8163c35ca9ab3561ffd3e252b4fbe2e745656b",
                0, _NO_RAISES, ()),
            SpecPin(
                "withdraw_balance", ("capella",),
                "4aca23a8cc5165eba6cd386670d68da3d1eda465e31bbdf642d137a29da68518",
                0, _NO_RAISES, ()),
        ),
        description="capella process_full_withdrawals: the eligibility test "
        "as one mask over the registry columns, withdraw_balance and the "
        "fully_withdrawn_epoch write for the selected validators only.",
    ),
    # ---- forkchoice/batch.py ----------------------------------------------
    MirrorSpec(
        name="batched-on-attestation",
        module=f"{_PKG}.forkchoice.batch",
        qualname="_ingest_attestations",
        pins=(
            SpecPin(
                "on_attestation", _MAINLINE,
                "225e58e81ebd1447772a4cfce7a4080a4999a68485288ae828b5da5fc9c11732",
                1,
                "0f0641ad52c78f671502e15e54f96127c427963ae102f4be4264437389239e9e",
                ("assert spec.is_valid_indexed_attestation(target_state, indexed)",)),
            SpecPin(
                "validate_on_attestation", _MAINLINE,
                "69be41da45a52f99cda054c19ab4641927fb6d3a0fe755f36a778308c33c59fd",
                6,
                "af0d95f4cea1dd29ff5496794dc0391c64844eb6b93396376aaddc046319286e",
                (None, None, None, None, None, None)),
            SpecPin(
                "update_latest_messages", _MAINLINE,
                "fbf81b9239c601dcc39e938639bc9bb93d4dbc01ed4d6d7b33c1098720590f00",
                0, _NO_RAISES, ()),
        ),
        description="batched on_attestation: validate_on_attestation runs "
        "literally (spec.validate_on_attestation per dedup key, so its "
        "six raise sites route to the literal call), the "
        "indexed-attestation assert is transcribed, and the latest-message "
        "fold mirrors update_latest_messages.",
    ),
    # ---- query/streamproof.py ---------------------------------------------
    MirrorSpec(
        name="stream-proof",
        module=f"{_PKG}.query.streamproof",
        qualname="proof_at",
        pins=(
            SpecPin(
                "build_proof", ("ssz",),
                "3665e1668f9393bf7029cd78ba6d020bef133da48d2eaae075643c872df77170",
                1,
                "78f3304ffd171e60e2430f73928f02e1fc29b312a087104bbd5f1be5ae7b951e",
                (None,)),
        ),
        description="ssz.gindex.build_proof regenerated off checkpoint "
        "stream offsets; the reference's BranchNode assert maps to "
        "_children's CheckpointError on a leaf-addressed gindex.",
    ),
    MirrorSpec(
        name="proof-verify",
        module=f"{_PKG}.query.streamproof",
        qualname="verify_proof",
        pins=(
            SpecPin(
                "is_valid_merkle_branch", _MAINLINE,
                "318f9706d9d7af45a351606c4fa891dbe005e86800458ef6ed918b2b0195947d",
                0, _NO_RAISES, ()),
        ),
        description="is_valid_merkle_branch's fold over a leaf-side-first "
        "branch, shared by proof serving and its tests.",
    ),
)


LITERALS: Tuple[LiteralSpec, ...] = (
    LiteralSpec("process_block", _MAINLINE,
                "the deferred-verification wrapper calls the spec's own "
                "process_block; the engine's fast path re-dispatches into "
                "the pinned per-operation mirrors"),
    LiteralSpec("process_epoch", _MAINLINE,
                "spec orchestrator: each phase hook it calls is "
                "individually pinned or literal below"),
    LiteralSpec("process_justification_and_finalization", ("phase0",),
                "runs literally at phase0; its matching-attestation and "
                "attesting-balance inputs ride the pinned epoch_jax scans"),
    LiteralSpec("process_eth1_data", _MAINLINE,
                "engine loops spec.process_eth1_data verbatim"),
    LiteralSpec("process_proposer_slashing", _MAINLINE,
                "engine loops spec.process_proposer_slashing verbatim"),
    LiteralSpec("process_attester_slashing", _MAINLINE,
                "engine loops spec.process_attester_slashing verbatim"),
    LiteralSpec("process_deposit", _MAINLINE,
                "engine loops spec.process_deposit verbatim"),
    LiteralSpec("process_voluntary_exit", _MAINLINE,
                "engine loops spec.process_voluntary_exit verbatim"),
    LiteralSpec("process_execution_payload", ("bellatrix", "capella"),
                "literal-inside-snapshot: the engine replays the spec "
                "body (engine pass, payload checks) inside the snapshot "
                "region rather than mirroring it"),
    LiteralSpec("process_eth1_data_reset", _MAINLINE,
                "trivial epoch reset, spec body runs as-is"),
    LiteralSpec("process_slashings_reset", _MAINLINE,
                "trivial epoch reset, spec body runs as-is"),
    LiteralSpec("process_randao_mixes_reset", _MAINLINE,
                "trivial epoch reset, spec body runs as-is"),
    LiteralSpec("process_historical_roots_update", _MAINLINE,
                "append-only epoch bookkeeping, spec body runs as-is"),
    LiteralSpec("process_participation_record_updates", ("phase0",),
                "phase0 attestation-record rotation, spec body runs as-is"),
    LiteralSpec("process_sync_committee_updates", _ALTAIR_ON,
                "periodic committee rotation, spec body runs as-is"),
)

WAIVERS: Tuple[WaiverSpec, ...] = ()


# ---------------------------------------------------------------------------
# queries


def mirror_display(m: MirrorSpec) -> str:
    """Display path of the file holding a mirror."""
    return m.module.replace(".", "/") + ".py"


def mirrors_for_file(display: str) -> Tuple[MirrorSpec, ...]:
    return tuple(m for m in MIRRORS if mirror_display(m) == display)


def mirror_files() -> Tuple[str, ...]:
    seen: List[str] = []
    for m in MIRRORS:
        d = mirror_display(m)
        if d not in seen:
            seen.append(d)
    return tuple(seen)


def pinned_names() -> frozenset:
    return frozenset(p.fn for m in MIRRORS for p in m.pins)


def declared_names() -> frozenset:
    return (pinned_names()
            | frozenset(l.fn for l in LITERALS)
            | frozenset(w.fn for w in WAIVERS))


def coverage(fn: str, fork: str) -> Optional[str]:
    """How (fn, fork) is covered: 'mirror:<name>', 'literal', 'waived',
    or None when the pair has no declaration at all."""
    for m in MIRRORS:
        for p in m.pins:
            if p.fn == fn and fork in p.forks:
                return f"mirror:{m.name}"
    for l in LITERALS:
        if l.fn == fn and fork in l.forks:
            return "literal"
    for w in WAIVERS:
        if w.fn == fn and fork in w.forks:
            return "waived"
    return None


def extra_file_deps() -> Dict[str, Tuple[str, ...]]:
    """Spec-source dependencies the registry adds to the incremental
    cache: each mirror file depends on the full fork chains of its pinned
    forks (an earlier-fork edit can move a later fork's effective def),
    and the engine depends on every spec source (SP02 reads all chains)."""
    deps: Dict[str, List[str]] = {}
    for m in MIRRORS:
        display = mirror_display(m)
        bucket = deps.setdefault(display, [])
        for p in m.pins:
            for fork in p.forks:
                for layer in spec_extract.FORK_CHAINS.get(fork, (fork,)):
                    d = spec_extract.fork_display(layer)
                    if d not in bucket:
                        bucket.append(d)
    engine = deps.setdefault(ENGINE_DISPLAY, [])
    for d in spec_extract.spec_source_displays():
        if d not in engine:
            engine.append(d)
    return {k: tuple(v) for k, v in deps.items()}


def find_def(tree: ast.Module, qualname: str) -> Optional[ast.AST]:
    """Resolve a possibly-nested def path ('_Outer.inner') to its node."""
    scope: List[ast.AST] = list(tree.body)
    node: Optional[ast.AST] = None
    for part in qualname.split("."):
        node = None
        for cand in scope:
            if (isinstance(cand, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef))
                    and cand.name == part):
                node = cand
                break
        if node is None:
            return None
        scope = [n for n in ast.walk(node) if n is not node
                 and isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.ClassDef))]
    return node


_HEX = set("0123456789abcdef")


def registry_errors() -> List[str]:
    """Structural validation, surfaced by tools/lint.py before any run."""
    errors: List[str] = []
    known_forks = set(spec_extract.FORK_CHAINS) | set(
        spec_extract.EXTRA_SOURCES)
    seen: set = set()
    for m in MIRRORS:
        key = (m.module, m.qualname)
        if key in seen:
            errors.append(f"duplicate mirror declaration: {m.module}."
                          f"{m.qualname}")
        seen.add(key)
        if not m.pins:
            errors.append(f"mirror '{m.name}' declares no spec pins")
        if not m.description.strip():
            errors.append(f"mirror '{m.name}' has no description")
        for p in m.pins:
            if len(p.digest) != 64 or not set(p.digest) <= _HEX:
                errors.append(f"mirror '{m.name}' pin '{p.fn}': digest is "
                              "not a sha256 hex string")
            if len(p.raise_digest) != 64 or not set(p.raise_digest) <= _HEX:
                errors.append(f"mirror '{m.name}' pin '{p.fn}': raise "
                              "digest is not a sha256 hex string")
            if len(p.guards) != p.raise_count:
                errors.append(
                    f"mirror '{m.name}' pin '{p.fn}': {p.raise_count} raise "
                    f"site(s) declared but {len(p.guards)} guard slot(s) — "
                    "every spec assert/raise needs a guard or an explicit "
                    "None routing it to literal replay")
            if not p.forks:
                errors.append(f"mirror '{m.name}' pin '{p.fn}': empty fork "
                              "tuple")
            for fork in p.forks:
                if fork not in known_forks:
                    errors.append(f"mirror '{m.name}' pin '{p.fn}': unknown "
                                  f"fork {fork!r}")
    for kind, rows in (("literal", LITERALS), ("waiver", WAIVERS)):
        for r in rows:
            if not r.why.strip():
                errors.append(f"{kind} declaration for '{r.fn}' has no "
                              "justification")
            for fork in r.forks:
                if fork not in known_forks:
                    errors.append(f"{kind} declaration for '{r.fn}': "
                                  f"unknown fork {fork!r}")
    lit = {(l.fn, f) for l in LITERALS for f in l.forks}
    waiv = {(w.fn, f) for w in WAIVERS for f in w.forks}
    for fn, fork in sorted(lit & waiv):
        errors.append(f"'{fn}'@{fork} is declared both literal and waived")
    return errors
