"""Differential tests: JAX SHA-256 kernel vs hashlib."""
import hashlib
import random

import pytest

from consensus_specs_tpu.ops import sha256_jax


def test_hash_layer_matches_hashlib():
    rng = random.Random(1234)
    for n in (1, 2, 3, 7, 64, 300):
        blocks = [bytes(rng.randrange(256) for _ in range(64)) for _ in range(n)]
        expected = [hashlib.sha256(b).digest() for b in blocks]
        got = sha256_jax.hash_layer(blocks)
        assert got == expected, f"mismatch at layer size {n}"


def test_hashing_backend_swap_preserves_roots():
    from consensus_specs_tpu.ssz import hashing
    from consensus_specs_tpu.ssz.types import List, uint64

    big = List[uint64, 1 << 20](range(5000))
    root_hashlib = big.hash_tree_root()

    hashing.set_backend("jax")
    try:
        # force full rebuild under the device backend
        big2 = List[uint64, 1 << 20](range(5000))
        root_jax = big2.hash_tree_root()
    finally:
        hashing.set_backend("hashlib")

    assert root_jax == root_hashlib


def test_hash_waves_matches_hashlib():
    """The single-dispatch wave-schedule hasher must agree with hashlib
    on an arbitrary DAG schedule (deduped known children, cross-wave
    references)."""
    import numpy as np

    rng = random.Random(99)
    known = [bytes(rng.randrange(256) for _ in range(32)) for _ in range(11)]
    # wave 0: pairs of known digests
    w0 = (np.array([0, 2, 4, 10], dtype=np.int32),
          np.array([1, 3, 5, 10], dtype=np.int32))
    # wave 1: mixes known and wave-0 outputs (pool rows 11..14)
    w1 = (np.array([11, 13, 6], dtype=np.int32),
          np.array([12, 14, 7], dtype=np.int32))
    # wave 2: consumes wave-1 outputs (pool rows 15..17)
    w2 = (np.array([15], dtype=np.int32), np.array([16], dtype=np.int32))
    got = sha256_jax.hash_waves(known, [w0, w1, w2])

    pool = list(known)
    expected = []
    for left, right in (w0, w1, w2):
        outs = [hashlib.sha256(pool[le] + pool[ri]).digest()
                for le, ri in zip(left.tolist(), right.tolist())]
        expected.extend(outs)
        pool.extend(outs)
    assert got == expected


def test_wave_path_used_for_large_trees_same_roots():
    """Above MIN_DEVICE_TREE the merkle_root path switches to the
    one-dispatch wave hasher; roots must be byte-identical to hashlib."""
    from consensus_specs_tpu.ssz import hashing
    from consensus_specs_tpu.ssz.types import List, uint64

    values = list(range(40_000))  # ~10k chunks > MIN_DEVICE_TREE nodes
    big = List[uint64, 1 << 30](values)
    root_hashlib = bytes(big.hash_tree_root())

    hashing.set_backend("jax")
    try:
        assert hashing.get_wave_hasher() is not None
        big2 = List[uint64, 1 << 30](values)
        root_jax = bytes(big2.hash_tree_root())
        # dirty-subtree incremental path through the wave hasher too
        for i in range(0, 40_000, 101):
            big2[i] = uint64(i + 7)
            big[i] = uint64(i + 7)
        dirty_jax = bytes(big2.hash_tree_root())
    finally:
        hashing.set_backend("hashlib")
    dirty_hashlib = bytes(big.hash_tree_root())

    assert root_jax == root_hashlib
    assert dirty_jax == dirty_hashlib


def _single_block_words(messages):
    """Pad each message of at most 55 bytes into one 64-byte block:
    0x80, zeros, then the 64-bit big-endian bit length."""
    import numpy as np

    padded = b"".join(m + b"\x80" + bytes(55 - len(m)) + (8 * len(m)).to_bytes(8, "big")
                      for m in messages)
    return np.frombuffer(padded, dtype=">u4").reshape(len(messages), 16).astype(np.uint32)


@pytest.mark.parametrize("length, lanes", [(33, 1), (33, 300), (37, 7), (37, 1792)])
def test_single_block_matches_hashlib(length, lanes):
    """The single-block entry the shuffle's source hashes use, for its
    33-byte (seed ‖ round) and 37-byte (seed ‖ round ‖ block) messages,
    at lane counts that are not powers of two."""
    import jax
    import numpy as np

    rng = random.Random(length * 1000 + lanes)
    messages = [bytes(rng.randrange(256) for _ in range(length)) for _ in range(lanes)]
    out = np.asarray(jax.jit(sha256_jax.sha256_single_block)(_single_block_words(messages)))
    assert out.astype(">u4").tobytes() == b"".join(hashlib.sha256(m).digest() for m in messages)
