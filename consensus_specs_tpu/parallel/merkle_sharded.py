"""Full sharded SSZ merkleization of packed uint64 data over a device
mesh (SURVEY §2.7 tensor-parallel merkle lanes, completed: per-shard
SUBTREE ROOTS, not just one hashed layer).

Layout: chunk lanes shard across devices; every device reduces its own
subtree bottom-up with the batched SHA-256 kernel (zero cross-device
traffic), producing one 32-byte subtree root per device.  The tiny top of
the tree — log2(n_dev) levels plus the zero-capped limit levels and the
SSZ length mixin — folds on the host, bit-identical to
``List[uint64, limit].hash_tree_root()`` (differential test:
tests/test_merkle_sharded.py).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from consensus_specs_tpu.ops.sha256_jax import sha256_block64
from consensus_specs_tpu.ssz.hashing import sha256
from consensus_specs_tpu.ssz.node import ZERO_HASHES

jax.config.update("jax_enable_x64", True)


def _bswap32(x):
    x = ((x & jnp.uint32(0x00FF00FF)) << 8) | ((x >> 8) & jnp.uint32(0x00FF00FF))
    return ((x << 16) | (x >> 16)).astype(jnp.uint32)


def _local_subtree_root(balances):
    """[local_n] int64 lanes -> [8] uint32 words: the shard's subtree root.
    local_n must be a power-of-two multiple of 8 (whole 64-byte blocks)."""
    lanes = balances.astype(jnp.uint64)
    lo = (lanes & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (lanes >> jnp.uint64(32)).astype(jnp.uint32)
    words = jnp.stack([_bswap32(lo), _bswap32(hi)], axis=-1).reshape(-1)
    digests = sha256_block64(words.reshape(-1, 16))  # chunk-pair layer
    while digests.shape[0] > 1:
        digests = sha256_block64(digests.reshape(-1, 16))
    return digests[0]


_SUBTREE_FN_CACHE: dict = {}


def make_sharded_subtree_roots(mesh: Mesh, axis: str = "v"):
    """jitted fn: sharded [n] balances -> [n_dev, 8] per-shard subtree
    roots (still device-resident; axis-sharded input, replicated output).
    Cached per (mesh, axis) so repeated roots reuse the compiled kernel."""
    key = (mesh, axis)
    fn = _SUBTREE_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(jax.shard_map(
            lambda b: _local_subtree_root(b)[None, :],
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(axis),
        ))
        if len(_SUBTREE_FN_CACHE) > 8:
            _SUBTREE_FN_CACHE.clear()
        _SUBTREE_FN_CACHE[key] = fn
    return fn


def _words_to_bytes(words: np.ndarray) -> bytes:
    return b"".join(int(w).to_bytes(4, "big") for w in words)


def sharded_uint64_list_root(mesh: Mesh, arr: np.ndarray, limit: int,
                             axis: str = "v") -> bytes:
    """hash_tree_root of ``List[uint64, limit](arr)`` with the heavy
    subtree hashed across the mesh.

    The data pads with zero lanes to (n_dev * pow2 * 8); zero-padding is
    exactly SSZ's virtual zero-extension, so no correction is needed."""
    n_dev = mesh.devices.size
    assert n_dev & (n_dev - 1) == 0, (
        "sharded merkleization needs a power-of-two device count; the "
        "pairwise host fold and the SSZ tree depth both assume it")
    n = len(arr)
    # chunks per shard must be a power of two for clean pairwise reduction
    per_shard = 8
    while per_shard * n_dev < max(n, 1):
        per_shard *= 2
    n_pad = per_shard * n_dev
    limit_chunks = (limit * 8 + 31) // 32
    if limit_chunks < n_pad // 4:
        # list too small to fill even one padded shard each: the sharded
        # reduction would hash past the limit depth — host path is right
        from consensus_specs_tpu.ssz.types import List, uint64

        return bytes(List[uint64, limit]([int(x) for x in arr]).hash_tree_root())
    padded = np.zeros(n_pad, dtype=np.int64)
    padded[:n] = arr

    sharding = NamedSharding(mesh, P(axis))
    roots = np.asarray(
        make_sharded_subtree_roots(mesh, axis)(
            jax.device_put(padded, sharding))
    )

    # top of the tree on host: log2(n_dev) levels over the shard roots
    level = [_words_to_bytes(roots[i]) for i in range(n_dev)]
    while len(level) > 1:
        level = [
            sha256(level[i] + level[i + 1]) for i in range(0, len(level), 2)
        ]
    node = level[0]

    # extend with zero-subtrees to the limit depth, then mix in the length
    chunks_hashed = n_pad // 4
    depth = (chunks_hashed - 1).bit_length()
    limit_chunks = (limit * 8 + 31) // 32
    limit_depth = max((limit_chunks - 1).bit_length(), 0)
    for d in range(depth, limit_depth):
        node = sha256(node + ZERO_HASHES[d])
    return sha256(node + len(arr).to_bytes(8, "little") + b"\x00" * 24)
