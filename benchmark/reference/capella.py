"""Plain capella state transition: the benchmark's reference, transcribed
from ethereum/consensus-specs specs/capella/beacon-chain.md as this repo
snapshots it (the early withdrawals-queue draft: the epoch sweep fills
``withdrawals_queue``, each payload dequeues up to
``MAX_WITHDRAWALS_PER_PAYLOAD``), over the altair reference with
bellatrix's execution payload and constants.  Imports nothing of the
program.

Departures from the spec text, none of which changes a result:

* signatures are not verified (as in the phase0 reference): the traffic
  signs every message, and the harness checks refusals separately;
* ``notify_new_payload`` is taken as true (no execution engine runs);
* the registry's leaf roots are kept between state roots and recomputed
  for the rows a BLS change rewrote, or all of them after an epoch;
* ``process_full_withdrawals`` loops over Python lists of the columns it
  reads rather than over validator objects.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import numpy as np

from . import ssz
from .altair import Altair
from .phase0 import Block, State, Validators
from .ssz import container_root, mix_in_length, sha256, uint_root


@dataclass
class CapellaValidators(Validators):
    fully_withdrawn_epoch: np.ndarray = None

    def rows(self, at=slice(None)) -> np.ndarray:
        """Each validator's serialized 129 bytes (of the validators ``at``
        selects), as one void per row."""
        def u64(column):
            return column[at].astype("<u8").view(np.uint8).reshape(-1, 8)

        cols = [self.pubkey[at], self.withdrawal_credentials[at], u64(self.effective_balance),
                self.slashed[at].astype(np.uint8).reshape(-1, 1)]
        cols += [u64(getattr(self, f)) for f in ("activation_eligibility_epoch", "activation_epoch",
                                                 "exit_epoch", "withdrawable_epoch", "fully_withdrawn_epoch")]
        return np.ascontiguousarray(np.concatenate(cols, axis=1)).view("V129").ravel()


@dataclass(frozen=True)
class Withdrawal:
    index: int
    address: bytes
    amount: int


@lru_cache(maxsize=8192)
def withdrawal_root(w: Withdrawal) -> bytes:
    return container_root([uint_root(w.index), ssz.bytes_root(w.address), uint_root(w.amount)])


@dataclass
class BLSChange:
    validator_index: int
    from_bls_pubkey: bytes
    to_execution_address: bytes
    signature: bytes = None

    def message_root(self) -> bytes:
        return container_root([uint_root(self.validator_index), ssz.bytes_root(self.from_bls_pubkey),
                               ssz.bytes_root(self.to_execution_address)])


@dataclass
class ExecutionPayload:
    parent_hash: bytes
    fee_recipient: bytes
    state_root: bytes
    receipts_root: bytes
    logs_bloom: bytes
    prev_randao: bytes
    block_number: int
    gas_limit: int
    gas_used: int
    timestamp: int
    extra_data: bytes
    base_fee_per_gas: int
    block_hash: bytes
    transactions: list
    withdrawals: list


@dataclass
class ExecutionPayloadHeader:
    parent_hash: bytes
    fee_recipient: bytes
    state_root: bytes
    receipts_root: bytes
    logs_bloom: bytes
    prev_randao: bytes
    block_number: int
    gas_limit: int
    gas_used: int
    timestamp: int
    extra_data: bytes
    base_fee_per_gas: int
    block_hash: bytes
    transactions_root: bytes
    withdrawals_root: bytes


class Capella(Altair):
    fork_name = "capella"

    # -- hashing -------------------------------------------------------------

    def _list_root(self, roots: list, limit: int) -> bytes:
        chunks = np.frombuffer(b"".join(roots), np.uint8).reshape(-1, 32)
        return mix_in_length(ssz.merkleize(chunks, limit), len(roots))

    def transactions_root(self, transactions: list) -> bytes:
        limit = (self.MAX_BYTES_PER_TRANSACTION + 31) // 32
        roots = [mix_in_length(ssz.merkleize(ssz.pack(tx), limit), len(tx)) for tx in transactions]
        return self._list_root(roots, self.MAX_TRANSACTIONS_PER_PAYLOAD)

    def withdrawals_root(self, withdrawals: list) -> bytes:
        return self._list_root([withdrawal_root(w) for w in withdrawals], self.MAX_WITHDRAWALS_PER_PAYLOAD)

    def _payload_roots(self, p, lists: list) -> bytes:
        extra = mix_in_length(ssz.merkleize(ssz.pack(p.extra_data), (self.MAX_EXTRA_DATA_BYTES + 31) // 32),
                              len(p.extra_data))
        return container_root([
            p.parent_hash, ssz.bytes_root(p.fee_recipient), p.state_root, p.receipts_root,
            ssz.bytes_root(p.logs_bloom), p.prev_randao, uint_root(p.block_number), uint_root(p.gas_limit),
            uint_root(p.gas_used), uint_root(p.timestamp), extra, uint_root(p.base_fee_per_gas, 32),
            p.block_hash] + lists)

    def payload_root(self, p: ExecutionPayload) -> bytes:
        return self._payload_roots(p, [self.transactions_root(p.transactions),
                                       self.withdrawals_root(p.withdrawals)])

    def header_root(self, h: ExecutionPayloadHeader) -> bytes:
        return self._payload_roots(h, [h.transactions_root, h.withdrawals_root])

    def validator_leaves(self, rows: np.ndarray) -> np.ndarray:
        """(n, 32): each 9-field Validator's root, computed once per
        distinct row."""
        unique, inverse = np.unique(rows, return_inverse=True)
        leaf = []
        for row in unique:
            raw = row.tobytes()
            leaf.append(container_root([
                ssz.bytes_root(raw[0:48]), raw[48:80], uint_root(int.from_bytes(raw[80:88], "little")),
                uint_root(raw[88]), *(uint_root(int.from_bytes(raw[89 + 8 * k:97 + 8 * k], "little"))
                                      for k in range(5))]))
        return np.frombuffer(b"".join(leaf), np.uint8).reshape(-1, 32)[inverse.ravel()].copy()

    def validators_root(self, s: State) -> bytes:
        memo = self.memo(s)
        if "validators_root" not in memo:
            leaves = s.__dict__.get("_validator_leaves")
            if leaves is None:
                leaves = s.__dict__["_validator_leaves"] = self.validator_leaves(s.validators.rows())
            memo["validators_root"] = mix_in_length(
                self._tree(s, "validators").root(leaves, self.VALIDATOR_REGISTRY_LIMIT), len(leaves))
        return memo["validators_root"]

    def field_roots(self, s: State) -> list:
        queue = self._list_root([withdrawal_root(w) for w in s.withdrawals_queue],
                                self.WITHDRAWALS_QUEUE_LIMIT)
        return super().field_roots(s) + [self.header_root(s.latest_execution_payload_header),
                                         uint_root(s.withdrawal_index), queue]

    def body_field_roots(self, b: Block) -> list:
        changes = [container_root([c.message_root(), ssz.bytes_root(c.signature)]) for c in b.bls_changes]
        return super().body_field_roots(b) + [self.payload_root(b.execution_payload),
                                              self._list_root(changes, self.MAX_BLS_TO_EXECUTION_CHANGES)]

    # -- block ---------------------------------------------------------------

    def process_block(self, s: State, b: Block) -> None:
        self.process_block_header(s, b)
        self.process_withdrawals(s, b.execution_payload)
        self.process_execution_payload(s, b.execution_payload)
        self.process_randao(s, b)
        self.process_eth1_data(s, b)
        self.process_operations(s, b)
        self.process_sync_aggregate(s, b)

    def expected_withdrawals(self, s: State) -> list:
        return s.withdrawals_queue[:min(self.MAX_WITHDRAWALS_PER_PAYLOAD, len(s.withdrawals_queue))]

    def process_withdrawals(self, s: State, payload: ExecutionPayload) -> None:
        dequeued = self.expected_withdrawals(s)
        assert len(dequeued) == len(payload.withdrawals)
        for mine, theirs in zip(dequeued, payload.withdrawals):
            assert mine == theirs
        s.withdrawals_queue = s.withdrawals_queue[len(dequeued):]

    def timestamp_at_slot(self, s: State, slot: int) -> int:
        return s.genesis_time + slot * self.SECONDS_PER_SLOT

    def process_execution_payload(self, s: State, p: ExecutionPayload) -> None:
        # the merge is complete from the pre-state on
        assert p.parent_hash == s.latest_execution_payload_header.block_hash
        assert p.prev_randao == self.randao_mix(s, self.current_epoch(s))
        assert p.timestamp == self.timestamp_at_slot(s, s.slot)
        s.latest_execution_payload_header = ExecutionPayloadHeader(
            p.parent_hash, p.fee_recipient, p.state_root, p.receipts_root, p.logs_bloom, p.prev_randao,
            p.block_number, p.gas_limit, p.gas_used, p.timestamp, p.extra_data, p.base_fee_per_gas,
            p.block_hash, self.transactions_root(p.transactions), self.withdrawals_root(p.withdrawals))

    def process_operations(self, s: State, b: Block) -> None:
        super().process_operations(s, b)
        assert len(b.bls_changes) <= self.MAX_BLS_TO_EXECUTION_CHANGES
        for change in b.bls_changes:
            self.process_bls_to_execution_change(s, change)

    def process_bls_to_execution_change(self, s: State, change: BLSChange) -> None:
        v = s.validators
        index = change.validator_index
        assert index < len(v)
        credentials = v.withdrawal_credentials[index].tobytes()
        assert credentials[:1] == self.BLS_WITHDRAWAL_PREFIX
        assert credentials[1:] == sha256(change.from_bls_pubkey)[1:]
        new = self.ETH1_ADDRESS_WITHDRAWAL_PREFIX + bytes(11) + change.to_execution_address
        v.withdrawal_credentials[index] = np.frombuffer(new, np.uint8)
        leaves = s.__dict__.get("_validator_leaves")
        if leaves is not None:
            leaves[index] = self.validator_leaves(v.rows([index]))[0]
        self.memo(s).pop("validators_root", None)

    # -- epoch ---------------------------------------------------------------

    def process_epoch(self, s: State) -> None:
        super().process_epoch(s)
        s.__dict__.pop("_validator_leaves", None)

    def epoch_steps(self, s: State) -> None:
        super().epoch_steps(s)
        self.process_full_withdrawals(s)

    def inactivity_penalty_deltas(self, s: State):
        n = len(s.validators)
        penalties = np.zeros(n, dtype=np.uint64)
        target = self.participating(s, 1, self.previous_epoch(s))
        miss = self.eligible(s) & ~target
        penalties[miss] = (s.validators.effective_balance[miss] * s.inactivity_scores[miss]
                           // np.uint64(self.INACTIVITY_SCORE_BIAS * self.INACTIVITY_PENALTY_QUOTIENT_BELLATRIX))
        return np.zeros(n, dtype=np.uint64), penalties

    def slashing_multiplier(self) -> int:
        return self.PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX

    def process_full_withdrawals(self, s: State) -> None:
        epoch = self.current_epoch(s)
        v = s.validators
        prefix = v.withdrawal_credentials[:, 0].tolist()
        withdrawable = v.withdrawable_epoch.tolist()
        withdrawn = v.fully_withdrawn_epoch.tolist()
        eth1 = self.ETH1_ADDRESS_WITHDRAWAL_PREFIX[0]
        for index in range(len(v)):
            if prefix[index] == eth1 and withdrawable[index] <= epoch < withdrawn[index]:
                # TODO in the spec: the zero-balance case is withdrawn too
                amount = int(s.balances[index])
                s.balances[index] = 0
                address = v.withdrawal_credentials[index, 12:].tobytes()
                s.withdrawals_queue.append(Withdrawal(s.withdrawal_index, address, amount))
                s.withdrawal_index += 1
                v.fully_withdrawn_epoch[index] = epoch

