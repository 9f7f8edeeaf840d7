"""Differential test: whole-permutation swap-or-not shuffle vs the scalar spec.

``compute_shuffle_permutation`` (ops/shuffle.py) is installed into every
built spec as the committee-computation optimization (specs/builder.py),
so it must equal the spec's scalar ``compute_shuffled_index``
(reference: specs/phase0/beacon-chain.md:760-781) at every index — in
particular near the 256-index source-hash block boundaries.  Sizes the
scalar spec is too slow for (the lane-bucket edges, ~100k at 90 rounds)
are checked against the forward-form numpy shuffle kept below as the
oracle.
"""
import hashlib

import numpy as np
import pytest

from consensus_specs_tpu import tracing
from consensus_specs_tpu.ops import shuffle
from consensus_specs_tpu.ops.shuffle import compute_shuffle_permutation, lanes_for
from consensus_specs_tpu.specs.builder import get_spec

SIZES = [0, 1, 2, 3, 7, 8, 100, 255, 256, 257, 511, 512, 513, 1000]
SEEDS = [b"\x00" * 32, bytes(range(32)), b"\xff" * 32]


@pytest.fixture(scope="module")
def spec():
    return get_spec("phase0", "minimal")


def _scalar_permutation(spec, seed, n):
    return [int(spec.compute_shuffled_index(spec.uint64(i), spec.uint64(n), seed))
            for i in range(n)]


@pytest.mark.parametrize("n", SIZES)
def test_permutation_matches_scalar_minimal_rounds(spec, n):
    rounds = int(spec.SHUFFLE_ROUND_COUNT)
    seed = SEEDS[1]
    perm = compute_shuffle_permutation(seed, n, rounds)
    assert perm.tolist() == _scalar_permutation(spec, seed, n)


@pytest.mark.parametrize("seed", SEEDS)
def test_permutation_matches_scalar_all_seeds(spec, seed):
    rounds = int(spec.SHUFFLE_ROUND_COUNT)
    for n in (255, 256, 257):
        perm = compute_shuffle_permutation(seed, n, rounds)
        assert perm.tolist() == _scalar_permutation(spec, seed, n)


def test_permutation_mainnet_round_count(spec):
    """90 rounds (mainnet SHUFFLE_ROUND_COUNT) against a scalar twin that
    re-derives the per-index form directly from the spec formula."""
    import hashlib

    def scalar_shuffled_index(index, index_count, seed, rounds):
        # reference: specs/phase0/beacon-chain.md:760-781
        assert index < index_count
        for current_round in range(rounds):
            pivot = int.from_bytes(
                hashlib.sha256(seed + bytes([current_round])).digest()[:8],
                "little") % index_count
            flip = (pivot + index_count - index) % index_count
            position = max(index, flip)
            source = hashlib.sha256(
                seed + bytes([current_round])
                + (position // 256).to_bytes(4, "little")).digest()
            byte = source[(position % 256) // 8]
            bit = (byte >> (position % 8)) % 2
            index = flip if bit else index
        return index

    rounds = 90
    seed = SEEDS[2]
    for n in (257, 512):
        perm = compute_shuffle_permutation(seed, n, rounds)
        expected = [scalar_shuffled_index(i, n, seed, rounds) for i in range(n)]
        assert perm.tolist() == expected


def test_permutation_is_bijection():
    perm = compute_shuffle_permutation(SEEDS[0], 1000, 90)
    assert sorted(perm.tolist()) == list(range(1000))


def test_cache_returns_readonly():
    perm = compute_shuffle_permutation(SEEDS[0], 64, 10)
    with pytest.raises(ValueError):
        perm[0] = 99  # noqa: CC01 (probing the read-only enforcement itself)


def _forward_form(seed, n, rounds):
    """The spec's per-index form over all lanes at once: per round, one
    pivot hash, one source hash per 256-index block, and a gather of each
    lane's bit at max(index, flip)."""
    m = np.arange(n, dtype=np.int64)
    block_ids = range((n + 255) // 256)
    for rnd in range(rounds):
        rb = bytes([rnd])
        pivot = int.from_bytes(hashlib.sha256(seed + rb).digest()[:8], "little") % n
        flip = (pivot - m) % n
        position = np.maximum(m, flip)
        src = np.frombuffer(
            b"".join(hashlib.sha256(seed + rb + b.to_bytes(4, "little")).digest()
                     for b in block_ids),
            dtype=np.uint8,
        ).reshape(-1, 32)
        byte_vals = src[position // 256, (position % 256) // 8]
        bits = (byte_vals >> (position % 8).astype(np.uint8)) & 1
        m = np.where(bits.astype(bool), flip, m)
    return m


@pytest.mark.parametrize("n, rounds", [
    (1 << 12, 10), ((1 << 12) + 1, 10), (1 << 16, 10), ((1 << 16) + 1, 10),
    ((1 << 17) + 1, 10), (100_003, 90),
])
def test_permutation_matches_forward_form(n, rounds):
    seed = hashlib.sha256(n.to_bytes(4, "little")).digest()
    perm = compute_shuffle_permutation(seed, n, rounds)
    assert np.array_equal(perm, _forward_form(seed, n, rounds))


@pytest.mark.parametrize("n, lanes", [
    (1, 1 << 12), (1 << 12, 1 << 12), ((1 << 12) + 1, 1 << 13), (1 << 16, 1 << 16),
    ((1 << 16) + 1, 1 << 17), ((1 << 17) + 1, 3 << 16), (400_000, 458_752),
])
def test_lane_buckets(n, lanes):
    assert lanes_for(n) == lanes


@pytest.mark.parametrize("n", [0, 1, 4097])
def test_result_is_int64_and_readonly(n):
    seed = bytes(range(1, 33))
    for _ in range(2):  # the miss, then the cache hit
        perm = compute_shuffle_permutation(seed, n, 10)
        assert perm.dtype == np.int64 and perm.shape == (n,)
        assert not perm.flags.writeable


@pytest.fixture
def traced():
    tracing.reset()
    tracing.enable()
    yield
    tracing.reset()
    tracing.disable()


def _shuffle_spans():
    return sum(v["count"] for k, v in tracing.report()["spans"].items()
               if k.endswith("ops/shuffle"))


def test_one_miss_is_one_span(traced):
    seed = bytes(range(2, 34))
    compute_shuffle_permutation(seed, 300, 10)
    assert _shuffle_spans() == 1
    compute_shuffle_permutation(seed, 300, 10)  # hit: no span
    assert _shuffle_spans() == 1
    assert tracing.report()["counters"]["shuffle.permutation_cache_hit"] == 1


def test_program_compiles_counts_once_per_bucket(traced, monkeypatch):
    monkeypatch.setattr(shuffle, "_programs", set())  # as in a fresh process
    for n in (5, 4000, 4096, 4097, 8000, 70_000):
        compute_shuffle_permutation(bytes(32), n, 10)
    assert tracing.report()["counters"]["shuffle.program_compiles"] == 3
