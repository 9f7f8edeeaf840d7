"""What capella adds (see ``forks/altair.py``), as this repo snapshots it
(the early withdrawals-queue draft), on both sides: the merge complete
from the pre-state on, withdrawal credentials, the withdrawals queue, and
per block an execution payload that dequeues it and a set of
BLS-to-execution changes.

The pre-state, from the seed (its ``genesis_validators_root``, which the
generator drew from it): a seeded half of the registry holds ``0x01``
credentials, with an address per key of the 8,192-key table; the rest hold
``0x00`` credentials of the validator's own table key.  The churn limit's
worth of ``0x01`` validators exited an epoch ago and fall due at the
pre-state's epoch, so its boundary sweeps them into the queue.  The queue
holds ``SLOTS_PER_EPOCH * MAX_WITHDRAWALS_PER_PAYLOAD`` withdrawals, so
every payload of an epoch dequeues the most it may.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from benchmark.forks import altair

ETH1_SHARE = 0.5
GAS_LIMIT = 30_000_000


def _rng(s) -> np.random.Generator:
    return np.random.default_rng(np.frombuffer(s.genesis_validators_root, np.uint64))


def pre_state(ref, s, attested: dict, keys) -> None:
    from benchmark.generator import NUM_KEYS
    from benchmark.reference.capella import CapellaValidators, ExecutionPayloadHeader, Withdrawal
    from benchmark.reference.phase0 import FAR_FUTURE_EPOCH

    rng = _rng(s)
    n = len(s.validators)
    epoch = ref.current_epoch(s)
    fields = {f.name: getattr(s.validators, f.name) for f in dataclasses.fields(s.validators)}
    v = s.validators = CapellaValidators(
        **fields, fully_withdrawn_epoch=np.full(n, FAR_FUTURE_EPOCH, dtype=np.uint64))
    table = np.arange(n) % NUM_KEYS
    bls = np.frombuffer(b"".join(ref.BLS_WITHDRAWAL_PREFIX + hashlib.sha256(pk.tobytes()).digest()[1:]
                                 for pk in keys.pubkeys), np.uint8).reshape(NUM_KEYS, 32)
    addresses = np.frombuffer(rng.bytes(NUM_KEYS * 20), np.uint8).reshape(NUM_KEYS, 20)
    eth1 = np.concatenate([np.full((NUM_KEYS, 1), ref.ETH1_ADDRESS_WITHDRAWAL_PREFIX[0], np.uint8),
                           np.zeros((NUM_KEYS, 11), np.uint8), addresses], axis=1)
    is_eth1 = rng.random(n) < ETH1_SHARE
    v.withdrawal_credentials = np.where(is_eth1[:, None], eth1[table], bls[table])
    churn = max(ref.MIN_PER_EPOCH_CHURN_LIMIT, n // ref.CHURN_LIMIT_QUOTIENT)
    exited = np.sort(rng.choice(np.flatnonzero(is_eth1), churn, replace=False))
    v.exit_epoch[exited] = epoch - 1
    v.withdrawable_epoch[exited] = epoch
    ref.memo(s).clear()  # active sets of the registry before the exits
    altair.pre_state(ref, s, attested, keys)
    s.previous_epoch_participation[exited] = 0  # they left before that epoch
    s.latest_execution_payload_header = ExecutionPayloadHeader(
        parent_hash=rng.bytes(32), fee_recipient=rng.bytes(20), state_root=rng.bytes(32),
        receipts_root=rng.bytes(32), logs_bloom=rng.bytes(256), prev_randao=rng.bytes(32),
        block_number=int(rng.integers(17_000_000, 18_000_000)), gas_limit=GAS_LIMIT,
        gas_used=int(rng.integers(GAS_LIMIT)), timestamp=ref.timestamp_at_slot(s, s.slot - 1),
        extra_data=rng.bytes(int(rng.integers(33))), base_fee_per_gas=int(rng.integers(1, 10**11)),
        block_hash=rng.bytes(32), transactions_root=rng.bytes(32), withdrawals_root=rng.bytes(32))
    queued = ref.SLOTS_PER_EPOCH * ref.MAX_WITHDRAWALS_PER_PAYLOAD
    amounts = rng.integers(ref.MAX_EFFECTIVE_BALANCE // 2, ref.MAX_EFFECTIVE_BALANCE * 2, queued)
    owners = rng.integers(NUM_KEYS, size=queued)
    s.withdrawals_queue = [Withdrawal(i, addresses[o].tobytes(), int(a))
                           for i, (o, a) in enumerate(zip(owners, amounts))]
    s.withdrawal_index = queued


def block_jobs(ref, s, slot: int, rng, traffic: dict, keys) -> tuple:
    """The sync aggregate's job (altair), then one per BLS change: from
    validators that still hold ``0x00`` credentials, each signed by the
    validator's own table key."""
    from benchmark.generator import NUM_KEYS
    from benchmark.reference.capella import BLSChange, ExecutionPayload

    jobs, attach_sync = altair.block_jobs(ref, s, slot, rng, traffic, keys)
    prev = s.latest_execution_payload_header
    sizes = rng.integers(*traffic["transaction_bytes"], size=int(rng.integers(
        *traffic["transactions_per_payload"], endpoint=True)), endpoint=True)
    payload = ExecutionPayload(
        parent_hash=prev.block_hash, fee_recipient=rng.bytes(20), state_root=rng.bytes(32),
        receipts_root=rng.bytes(32), logs_bloom=rng.bytes(256),
        prev_randao=ref.randao_mix(s, ref.current_epoch(s)), block_number=prev.block_number + 1,
        gas_limit=prev.gas_limit, gas_used=int(rng.integers(prev.gas_limit)),
        timestamp=ref.timestamp_at_slot(s, slot), extra_data=rng.bytes(int(rng.integers(33))),
        base_fee_per_gas=int(rng.integers(1, 10**11)), block_hash=rng.bytes(32),
        transactions=[rng.bytes(int(size)) for size in sizes], withdrawals=ref.expected_withdrawals(s))
    bls = np.flatnonzero(s.validators.withdrawal_credentials[:, 0] == ref.BLS_WITHDRAWAL_PREFIX[0])
    chosen = np.sort(rng.choice(bls, traffic["bls_changes_per_block"], replace=False))
    changes = [BLSChange(int(i), keys.pubkeys[i % NUM_KEYS].tobytes(), rng.bytes(20)) for i in chosen]
    domain = ref.domain(s, ref.DOMAIN_BLS_TO_EXECUTION_CHANGE)
    jobs = jobs + [(keys.secret([i]), ref.signing_root(c.message_root(), domain))
                   for i, c in zip(chosen, changes)]

    def attach(block, signatures):
        attach_sync(block, signatures)
        for change, signature in zip(changes, signatures[len(signatures) - len(changes):]):
            change.signature = signature
        block.execution_payload, block.bls_changes = payload, changes

    return jobs, attach


def program_state(spec, s, out) -> None:
    """Altair's fields, then the registry rebuilt with capella's 9-field
    ``Validator`` (one backing per distinct row, as ``program.state``
    shares them), the payload header and the withdrawals queue."""
    from consensus_specs_tpu.ssz.node import BranchNode, subtree_fill_to_contents, uint_to_leaf

    altair.program_state(spec, s, out)
    v = s.validators
    unique, first, inverse = np.unique(v.rows(), return_index=True, return_inverse=True)
    nodes = [spec.Validator(
        pubkey=v.pubkey[i].tobytes(), withdrawal_credentials=v.withdrawal_credentials[i].tobytes(),
        effective_balance=int(v.effective_balance[i]), slashed=bool(v.slashed[i]),
        activation_eligibility_epoch=int(v.activation_eligibility_epoch[i]),
        activation_epoch=int(v.activation_epoch[i]), exit_epoch=int(v.exit_epoch[i]),
        withdrawable_epoch=int(v.withdrawable_epoch[i]),
        fully_withdrawn_epoch=int(v.fully_withdrawn_epoch[i])).get_backing() for i in first]
    vlist = type(out.validators)
    contents = subtree_fill_to_contents([nodes[k] for k in inverse.ravel()], vlist.contents_depth())
    out.validators = vlist.view_from_backing(BranchNode(contents, uint_to_leaf(len(v))))
    out.latest_execution_payload_header = spec.ExecutionPayloadHeader(**vars(s.latest_execution_payload_header))
    out.withdrawal_index = s.withdrawal_index
    out.withdrawals_queue = [_withdrawal(spec, w) for w in s.withdrawals_queue]


def _withdrawal(spec, w):
    return spec.Withdrawal(index=w.index, address=w.address, amount=w.amount)


def program_body(spec, b, body) -> None:
    altair.program_body(spec, b, body)
    p = b.execution_payload
    fields = {k: v for k, v in vars(p).items() if k != "withdrawals"}
    body.execution_payload = spec.ExecutionPayload(
        **fields, withdrawals=[_withdrawal(spec, w) for w in p.withdrawals])
    body.bls_to_execution_changes = [spec.SignedBLSToExecutionChange(
        message=spec.BLSToExecutionChange(validator_index=c.validator_index, from_bls_pubkey=c.from_bls_pubkey,
                                          to_execution_address=c.to_execution_address),
        signature=c.signature) for c in b.bls_changes]
