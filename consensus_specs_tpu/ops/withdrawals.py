"""Capella's full-withdrawal sweep over the registry columns.

The spec's ``process_full_withdrawals`` (specs/src/capella.py, the early
withdrawals-queue draft) visits every validator view at every epoch
boundary to find the few whose ``0x01`` credentials have come due.  Here
the eligibility test is one mask over the cached registry columns
(``ops/epoch_jax.registry_columns``: the credentials' first byte,
``withdrawable_epoch``, ``fully_withdrawn_epoch``), and only the selected
validators are touched, in index order as the spec's loop touches them:
each balance is withdrawn whole (zero balances included, as the spec's
``TODO`` leaves them) and only the balance chunks that hold them are
rewritten, each withdrawal is appended to the queue under the next
``withdrawal_index``, and each validator's ``fully_withdrawn_epoch`` is
set.  The registry's columns are
re-registered under its new root, and the registry-keyed caches carry over
(``ssz.bulk.note_withdrawal_writes``), so the sweep costs no registry walk
of its own.  Differential: tests/test_full_withdrawals.py and the capella
epoch-processing suite.
"""
from __future__ import annotations

import numpy as np

from consensus_specs_tpu import tracing

from .epoch_jax import columns_after_writes, registry_columns


def withdrawable_mask(cols: dict, epoch: int, prefix: int) -> np.ndarray:
    """``is_fully_withdrawable_validator`` over the columns (epochs
    saturate at int64 max, which no reachable epoch meets)."""
    return ((cols["withdrawal_prefix"] == prefix)
            & (cols["withdrawable_epoch"] <= epoch)
            & (epoch < cols["fully_withdrawn_epoch"]))


def full_withdrawals(spec, state) -> None:
    """``process_full_withdrawals`` for ``state`` in place."""
    from consensus_specs_tpu.ssz import bulk
    from consensus_specs_tpu.stf import columns

    with tracing.span("ops/full_withdrawals"):
        epoch = int(spec.get_current_epoch(state))
        cols = registry_columns(state)
        prefix = bytes(spec.ETH1_ADDRESS_WITHDRAWAL_PREFIX)[0]
        selected = np.flatnonzero(withdrawable_mask(cols, epoch, prefix))
        if len(selected) == 0:
            return
        key = bulk.registry_key(state.validators)
        balances = columns.staged_balances(state)
        amounts = balances[selected].copy()
        balances[selected] = 0
        columns.write_balances(state, balances, selected)
        queue = state.withdrawals_queue
        next_index = int(state.withdrawal_index)
        for offset, (index, amount) in enumerate(zip(selected.tolist(), amounts.tolist())):
            validator = state.validators[index]
            queue.append(spec.Withdrawal(
                index=next_index + offset,
                address=bytes(validator.withdrawal_credentials)[12:],
                amount=amount))
            validator.fully_withdrawn_epoch = epoch
        state.withdrawal_index = next_index + len(selected)
        columns_after_writes(state, cols, fully_withdrawn_epoch=(selected, epoch))
        bulk.note_withdrawal_writes(key, state.validators)
