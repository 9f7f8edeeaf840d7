"""Whole-permutation swap-or-not shuffle, as one device program.

The spec's ``compute_shuffled_index`` (reference:
specs/phase0/beacon-chain.md:760-781) maps ONE index through
``SHUFFLE_ROUND_COUNT`` rounds, costing 2 SHA-256 per round per index.
Committees need the image of *every* index, so this module computes the
whole permutation at once: ``permutation[i] ==
compute_shuffled_index(i, n, seed)`` exactly, differential-tested in
tests/test_shuffle.py.

Round ``r`` is the involution ``S_r(x) = flip(x)`` if the source bit at
``max(x, flip(x))`` is set, else ``x``, with ``flip(x) = (pivot_r - x)
mod n``; the permutation is ``S_{R-1} ∘ ... ∘ S_0``.  It is computed in
value form: start from ``arange`` and, for r from R-1 down to 0, replace
the array by ``arr[S_r(j)]`` at every lane j.  ``arr[flip(j)]`` is two
reversed, rotated copies of the lanes (``arr[pivot::-1]`` for j <= pivot,
``arr[:pivot:-1]`` above), so the round body has no modulo and no
data-dependent gather.

Split of the work:

* host: the R pivots, ``int.from_bytes(sha256(seed ‖ r)[:8], "little")
  % n`` with Python ints, as the spec writes them (R hashes, no uint64
  arithmetic on the device);
* device, one jitted program (``jit__shuffle_permutation`` in a trace):
  every source hash ``sha256(seed ‖ r ‖ block_le32)`` of every round and
  256-lane block in one batched single-block SHA-256
  (``sha256_jax.sha256_single_block``), the bits unpacked in the spec's
  order, then the R rounds under ``lax.fori_loop``.  One readback.

n is a traced scalar; the lanes are padded to a bucket (``lanes_for``):
the next power of two, at least 2**12, up to 2**16, then the next
multiple of 2**16 (458,752 lanes at 400k validators).  A registry that
grows recompiles only when it crosses a bucket, and small registries
share the 2**12 bucket.  ``shuffle.program_compiles`` counts the first
use of each (lanes, rounds) program in a process.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from consensus_specs_tpu import tracing
from consensus_specs_tpu.ops.sha256_jax import sha256_single_block

_cache: Dict[Tuple[bytes, int, int], np.ndarray] = {}
_CACHE_MAX = 16

_MIN_LANES = 1 << 12
_STEP_LANES = 1 << 16
_programs: Set[Tuple[int, int]] = set()  # (lanes, rounds) used in this process

# Lane l of a 256-lane block reads byte l // 8, bit l % 8 of the source
# digest; byte k sits at bits 24 - 8 * (k % 4) of big-endian word k // 4.
_LANE = np.arange(256)
_BIT_SHIFT = (24 - 8 * ((_LANE // 8) % 4) + _LANE % 8).astype(np.uint32)


def compute_shuffle_permutation(seed: bytes, index_count: int, round_count: int) -> np.ndarray:
    """Return a read-only int64 array p of length index_count with
    p[i] = compute_shuffled_index(i, index_count, seed).  A cache miss is
    one ``ops/shuffle`` span, the device program's readback included."""
    key = (bytes(seed), int(index_count), int(round_count))
    hit = _cache.get(key)
    if hit is not None:
        tracing.count("shuffle.permutation_cache_hit")
        return hit
    with tracing.span("ops/shuffle"):
        return _compute(key)


def lanes_for(n: int) -> int:
    """Lane bucket of a registry of n indices (see the module docstring)."""
    if n <= _STEP_LANES:
        return max(_MIN_LANES, 1 << (n - 1).bit_length())
    return -(-n // _STEP_LANES) * _STEP_LANES


def _compute(key: Tuple[bytes, int, int]) -> np.ndarray:
    seed, n, round_count = key
    if n == 0:
        m = np.zeros(0, dtype=np.int64)
    else:
        lanes = lanes_for(n)
        if (lanes, round_count) not in _programs:
            _programs.add((lanes, round_count))
            tracing.count("shuffle.program_compiles")
        pivots = np.array(
            [int.from_bytes(hashlib.sha256(seed + bytes([r])).digest()[:8], "little") % n
             for r in range(round_count)], dtype=np.int32)
        seed_words = np.frombuffer(seed, dtype=">u4").astype(np.uint32)
        out = _jit_shuffle(seed_words, pivots, np.int32(n), lanes=lanes)
        # host-sync: the permutation returns to the host committee views
        m = np.asarray(out)[:n].astype(np.int64)
    if len(_cache) >= _CACHE_MAX:
        _cache.pop(next(iter(_cache)))
    m.setflags(write=False)  # shared across callers; mutation would corrupt committees
    _cache[key] = m
    return m


def _source_digests(seed_words, rounds: int, blocks: int):
    """[rounds, blocks, 8] digests of sha256(seed ‖ round ‖ block_le32):
    37-byte messages, each padded into one block (0x80 at byte 37, bit
    length 296)."""
    rnd = jnp.repeat(jnp.arange(rounds, dtype=jnp.uint32), blocks)
    blk = jnp.tile(jnp.arange(blocks, dtype=jnp.uint32), rounds)
    # bytes 32..39: round, block_le32, 0x80, 0, 0
    w8 = (rnd << 24) | ((blk & 0xFF) << 16) | (((blk >> 8) & 0xFF) << 8) | ((blk >> 16) & 0xFF)
    w9 = ((blk >> 24) << 24) | jnp.uint32(0x80 << 16)
    lanes = rounds * blocks
    words = jnp.concatenate([
        jnp.broadcast_to(seed_words, (lanes, 8)),
        w8[:, None],
        w9[:, None],
        jnp.zeros((lanes, 5), dtype=jnp.uint32),
        jnp.full((lanes, 1), 37 * 8, dtype=jnp.uint32),
    ], axis=1)
    return sha256_single_block(words).reshape(rounds, blocks, 8)


def _shuffle_permutation(seed_words, pivots, n, *, lanes: int):
    """The permutation of n <= lanes indices, padded to ``lanes`` (the
    lanes at and above n hold themselves)."""
    rounds = pivots.shape[0]
    digests = _source_digests(seed_words, rounds, lanes // 256)
    shift = jnp.asarray(_BIT_SHIFT)
    idx = jnp.arange(lanes, dtype=jnp.int32)
    live = idx < n

    def body(i, arr):
        r = rounds - 1 - i
        pivot = jax.lax.dynamic_index_in_dim(pivots, r, keepdims=False)
        words = jax.lax.dynamic_index_in_dim(digests, r, keepdims=False)
        bits = ((jnp.repeat(words, 32, axis=1) >> shift) & 1).astype(jnp.int32).reshape(lanes)
        low = idx <= pivot
        flip = jnp.where(low, pivot - idx, pivot + n - idx)
        # x[flip(j)] for j < n: the reversed lanes rotated so that lane j
        # reads x[pivot - j] (j <= pivot) or x[pivot + n - j] (j > pivot)
        start_low = lanes - 1 - pivot
        start_high = (2 * lanes - 1 - pivot - n) % lanes

        def mirror(x):
            rev = jnp.concatenate([x[::-1], x[::-1]])
            return jnp.where(low,
                             jax.lax.dynamic_slice_in_dim(rev, start_low, lanes),
                             jax.lax.dynamic_slice_in_dim(rev, start_high, lanes))

        swap = live & (jnp.where(idx >= flip, bits, mirror(bits)) == 1)
        return jnp.where(swap, mirror(arr), arr)

    return jax.lax.fori_loop(0, rounds, body, idx)


# one executable per (lanes, rounds); seed, pivots and n are traced
_jit_shuffle = jax.jit(_shuffle_permutation, static_argnames=("lanes",))


def committee_bounds(n_active: int, committees_per_epoch: int) -> np.ndarray:
    """Slice boundaries of every committee of an epoch over the shuffled
    permutation: ``bounds[g] : bounds[g + 1]`` is the permutation range of
    global committee index ``g`` (``(slot % SLOTS_PER_EPOCH) *
    committees_per_slot + index``), exactly the spec's ``compute_committee``
    start/end arithmetic (beacon-chain.md:944-950) evaluated for all
    committees at once."""
    g = np.arange(committees_per_epoch + 1, dtype=np.int64)
    return (n_active * g) // committees_per_epoch
