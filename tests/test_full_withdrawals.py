"""The full-withdrawal sweep kernel (``ops/withdrawals.full_withdrawals``)
against capella's literal ``process_full_withdrawals`` loop on a seeded
registry: equal post-state roots, with the edge cases of the eligibility
test placed on purpose (``withdrawable_epoch == epoch``,
``fully_withdrawn_epoch == epoch``, a zero balance, ``0x00`` against
``0x01`` credentials), and the registry columns the kernel re-registers
equal to a fresh walk."""
import numpy as np
import pytest

from consensus_specs_tpu.ops import epoch_jax
from consensus_specs_tpu.ssz import bulk
from consensus_specs_tpu.testing.context import spec_state_test, with_phases


def _seeded_registry(spec, state, seed):
    rng = np.random.default_rng(seed)
    state.slot = 3 * int(spec.SLOTS_PER_EPOCH)
    epoch = 3
    far = int(spec.FAR_FUTURE_EPOCH)

    def pick(*options):
        return options[int(rng.integers(len(options)))]

    for index, validator in enumerate(state.validators):
        prefix = spec.ETH1_ADDRESS_WITHDRAWAL_PREFIX if rng.random() < 0.7 \
            else spec.BLS_WITHDRAWAL_PREFIX
        validator.withdrawal_credentials = bytes(prefix) + rng.bytes(31)
        validator.withdrawable_epoch = pick(epoch - 1, epoch, epoch + 1, far)
        validator.fully_withdrawn_epoch = pick(epoch - 1, epoch, epoch + 1, far, far)
        if rng.random() < 0.2:
            state.balances[index] = 0
    # the boundaries, whatever the draw: due exactly now with a zero
    # balance; due, but withdrawn this epoch; due, but 0x00 credentials
    edge = {0: (spec.ETH1_ADDRESS_WITHDRAWAL_PREFIX, epoch, far),
            1: (spec.ETH1_ADDRESS_WITHDRAWAL_PREFIX, epoch, epoch),
            2: (spec.BLS_WITHDRAWAL_PREFIX, epoch, far)}
    for index, (prefix, withdrawable, withdrawn) in edge.items():
        validator = state.validators[index]
        validator.withdrawal_credentials = bytes(prefix) + bytes(31)
        validator.withdrawable_epoch = withdrawable
        validator.fully_withdrawn_epoch = withdrawn
    state.balances[0] = 0


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_sweep_kernel_equals_the_spec_loop(seed):
    @with_phases(["capella"])
    @spec_state_test
    def case(spec, state):
        _seeded_registry(spec, state, seed)
        literal, kernel = state.copy(), state.copy()
        spec.process_full_withdrawals.__wrapped__(literal)
        epoch_jax.registry_columns(kernel)  # the walk the epoch would have paid
        spec.process_full_withdrawals(kernel)
        assert len(literal.withdrawals_queue) > 3
        assert bytes(kernel.hash_tree_root()) == bytes(literal.hash_tree_root())
        assert int(kernel.withdrawals_queue[0].amount) == 0  # validator 0, zero balance
        cached, walked = epoch_jax.registry_columns(kernel), bulk.validator_columns(kernel.validators)
        assert all(np.array_equal(cached[k], walked[k]) for k in walked)
        yield None

    case(phase="capella")


def test_sweep_with_nothing_due_leaves_the_state_alone():
    @with_phases(["capella"])
    @spec_state_test
    def case(spec, state):
        before = bytes(state.hash_tree_root())
        spec.process_full_withdrawals(state)
        assert bytes(state.hash_tree_root()) == before
        yield None

    case(phase="capella")
