"""Differential suite: the block engine's capella fast path against the
literal ``spec.state_transition``, BLS on, pipeline off and on.

Capella here is the repo's snapshot (the withdrawals-queue draft): each
payload must carry the queue's first ``MAX_WITHDRAWALS_PER_PAYLOAD``
entries, and ``bls_to_execution_changes`` rewrite BLS credentials to
``0x01`` ones.  Valid blocks must land on the fast path (no replay) with
equal post-state roots at every block; each invalid variant — a wrong
withdrawal, a BLS change signed over another message, a change for a
validator that already holds ``0x01`` credentials, a wrong
``from_bls_pubkey`` — must replay and raise the literal spec's exception,
leaving an equally poisoned state.  The tracing counters
``stf.withdrawals`` and ``stf.bls_changes`` are read here.
"""
import random

import numpy as np
import pytest

from consensus_specs_tpu import stf, tracing
from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.ops import epoch_jax
from consensus_specs_tpu.ssz import bulk
from consensus_specs_tpu.testing.context import spec_state_test, with_phases
from consensus_specs_tpu.testing.helpers.block import build_empty_block_for_next_slot
from consensus_specs_tpu.testing.helpers.execution_payload import (
    build_empty_execution_payload,
    build_state_with_complete_transition,
)
from consensus_specs_tpu.testing.helpers.keys import pubkey_to_privkey, pubkeys
from consensus_specs_tpu.testing.helpers.state import (
    next_epoch,
    state_transition_and_sign_block,
)

# validators swept into the queue: more than one payload's
# MAX_WITHDRAWALS_PER_PAYLOAD (16), so the first dequeue is capped
WITHDRAWABLE = 20
# BLS changes per valid block, as positions among the validators that keep
# 0x00 credentials
CHANGES = ((0, 1, 2, 3), (4, 5), (), (6,))


def _capella_state(spec, state):
    """Merge complete, and WITHDRAWABLE validators, drawn from a seed,
    with their balances in the withdrawals queue (0x01 credentials, swept
    at the next boundary).
    They are taken outside both sync committees, whose empty aggregates
    would otherwise penalize a zero balance (a case the engine leaves to
    the literal spec).  Returns the state and the validators that keep
    0x00 credentials."""
    state = build_state_with_complete_transition(spec, state)
    epoch = spec.get_current_epoch(state)
    seated = {bytes(pk) for committee in (state.current_sync_committee,
                                          state.next_sync_committee)
              for pk in committee.pubkeys}
    free = [i for i, v in enumerate(state.validators) if bytes(v.pubkey) not in seated]
    random.Random(2026).shuffle(free)
    swept, kept = free[:WITHDRAWABLE], free[WITHDRAWABLE:]
    for index in swept:
        validator = state.validators[index]
        validator.withdrawal_credentials = (
            bytes(spec.ETH1_ADDRESS_WITHDRAWAL_PREFIX)
            + bytes(validator.withdrawal_credentials)[1:])
        validator.withdrawable_epoch = epoch
    next_epoch(spec, state)
    assert len(state.withdrawals_queue) == WITHDRAWABLE
    return state, kept


def _change(spec, state, index, key_index=None, signed_address=None):
    """A SignedBLSToExecutionChange for ``index`` from the withdrawal key
    the genesis helper derived its credentials from (``key_index`` picks
    another key; ``signed_address`` signs another address than the one
    carried)."""
    pubkey = pubkeys[-1 - (index if key_index is None else key_index)]
    message = spec.BLSToExecutionChange(
        validator_index=index, from_bls_pubkey=pubkey,
        to_execution_address=bytes([index]) * 20)
    signed = message.copy()
    if signed_address is not None:
        signed.to_execution_address = signed_address
    domain = spec.get_domain(state, spec.DOMAIN_BLS_TO_EXECUTION_CHANGE)
    return spec.SignedBLSToExecutionChange(
        message=message,
        signature=bls.Sign(pubkey_to_privkey[pubkey],
                           spec.compute_signing_root(signed, domain)))


def _block(spec, state, changes, tamper=None):
    """The next slot's signed block, dequeuing what is due and carrying
    ``changes`` (validator indices); ``state`` is advanced.
    ``tamper(spec, state, payload, changes)`` edits it into an invalid
    block."""
    block = build_empty_block_for_next_slot(spec, state)
    advanced = state.copy()
    spec.process_slots(advanced, block.slot)
    payload = build_empty_execution_payload(spec, advanced)
    signed_changes = [_change(spec, advanced, index) for index in changes]
    if tamper is not None:
        tamper(spec, advanced, payload, signed_changes)
    block.body.execution_payload = payload
    block.body.bls_to_execution_changes = signed_changes
    return state_transition_and_sign_block(spec, state, block,
                                           expect_fail=tamper is not None)


def _wrong_withdrawal(spec, state, payload, changes, index):
    payload.withdrawals[0].amount += 1


def _signed_over_another_message(spec, state, payload, changes, index):
    changes[0] = _change(spec, state, index, signed_address=b"\x99" * 20)


def _eth1_credentials(spec, state, payload, changes, index):
    swept = next(i for i, v in enumerate(state.validators)
                 if bytes(v.withdrawal_credentials)[:1] == b"\x01")
    changes[0] = _change(spec, state, swept)


def _wrong_from_bls_pubkey(spec, state, payload, changes, index):
    changes[0] = _change(spec, state, index, key_index=index + 1)


INVALID = [_wrong_withdrawal, _signed_over_another_message, _eth1_credentials,
           _wrong_from_bls_pubkey]


def _run(case):
    with_phases(["capella"])(spec_state_test(case))(phase="capella", bls_active=True)


@pytest.mark.parametrize("pipeline", ["0", "1"], ids=["pipeline-off", "pipeline-on"])
def test_capella_blocks_take_the_fast_path(pipeline, monkeypatch):
    monkeypatch.setenv("CSTPU_PIPELINE", pipeline)

    def case(spec, state):
        pre, kept = _capella_state(spec, state)
        chain = pre.copy()
        signed = [_block(spec, chain, [kept[c] for c in changes]) for changes in CHANGES]
        s_spec, s_eng = pre.copy(), pre.copy()
        stf.reset_stats()
        tracing.reset()
        tracing.enable()
        try:
            for i, sb in enumerate(signed):
                spec.state_transition(s_spec, sb, True)
                stf.apply_signed_blocks(spec, s_eng, [sb], True)
                assert bytes(s_spec.hash_tree_root()) == bytes(s_eng.hash_tree_root()), i
            counters = tracing.report()["counters"]
        finally:
            tracing.disable()
            tracing.reset()
        assert stf.stats["replayed_blocks"] == 0, stf.stats["replay_reasons"]
        assert stf.stats["fast_blocks"] == len(signed)
        assert counters["stf.withdrawals"] == WITHDRAWABLE
        assert counters["stf.bls_changes"] == sum(map(len, CHANGES))
        # the whole call at once, through the speculation window when on
        s_all = pre.copy()
        stf.apply_signed_blocks(spec, s_all, signed, True)
        assert bytes(s_all.hash_tree_root()) == bytes(s_spec.hash_tree_root())
        assert bytes(s_eng.validators[kept[0]].withdrawal_credentials)[:1] == b"\x01"
        # the columns the BLS changes re-registered equal a fresh walk
        cached, walked = epoch_jax.registry_columns(s_eng), bulk.validator_columns(s_eng.validators)
        assert cached.keys() == walked.keys()
        assert all(np.array_equal(cached[k], walked[k]) for k in walked)
        yield None

    _run(case)


@pytest.mark.parametrize("pipeline", ["0", "1"], ids=["pipeline-off", "pipeline-on"])
@pytest.mark.parametrize("tamper", INVALID, ids=lambda f: f.__name__.strip("_"))
def test_invalid_capella_block_fails_identically(tamper, pipeline, monkeypatch):
    """The invalid block follows a valid one in the same call, so with the
    pipeline on it is speculated on top of an unsettled predecessor."""
    monkeypatch.setenv("CSTPU_PIPELINE", pipeline)

    def case(spec, state):
        pre, kept = _capella_state(spec, state)
        chain = pre.copy()
        signed = [_block(spec, chain, [kept[c] for c in CHANGES[0]])]
        later = kept[len(CHANGES[0])]
        signed.append(_block(spec, chain.copy(), [later],
                             tamper=lambda *args: tamper(*args, later)))
        exc_spec = exc_eng = None
        s_spec, s_eng = pre.copy(), pre.copy()
        try:
            for sb in signed:
                spec.state_transition(s_spec, sb, True)
        except Exception as e:  # noqa: B001 - parity harness captures anything
            exc_spec = e
        stf.reset_stats()
        try:
            stf.apply_signed_blocks(spec, s_eng, signed, True)
        except Exception as e:  # noqa: B001
            exc_eng = e
        assert exc_spec is not None
        assert type(exc_spec) is type(exc_eng) and str(exc_spec) == str(exc_eng), (exc_spec, exc_eng)
        assert bytes(s_spec.hash_tree_root()) == bytes(s_eng.hash_tree_root())
        assert stf.stats["fast_blocks"] == 1 and stf.stats["replayed_blocks"] == 1
        yield None

    _run(case)
