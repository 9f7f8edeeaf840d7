"""Device-resident merkleization of hot SSZ subtrees.

A device hasher that ships every dirty subtree's chunk data to the device
pays the transfer on every pass.  The TPU-native shape is residency: the
packed leaf data of a hot subtree (balances is the canonical case — every
epoch rewrites all of it) lives on the device across calls.  Mutations are
expressed as device ops on the resident buffers, the whole subtree
reduction runs as ONE jit dispatch, and only the 32-byte root comes back
to the host.  The host keeps the rest of the state tree and folds the subtree
root into the state root with a handful of hashlib hashes.

Reference seams: eth2spec/utils/ssz/ssz_impl.py:12-13 (hash_tree_root =
backing.merkle_root()); merkleization rules ssz/simple-serialize.md:210-248
(pack / merkleize / mix_in_length).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from consensus_specs_tpu.ssz.node import (
    BranchNode,
    LeafNode,
    Node,
    ZERO_HASHES,
    merkle_root,
    uint_to_leaf,
)

from .sha256_jax import sha256_block64


def _byteswap32(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 little-endian value -> big-endian word (SHA-256 reads bytes)."""
    return ((x >> 24) | ((x >> 8) & 0x0000FF00)
            | ((x << 8) & 0x00FF0000) | (x << 24))


def _reduce_to_root(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Full merkle reduction of a packed uint64 leaf array, on device.

    ``lo``/``hi`` are the 32-bit halves of the (LE) uint64 values, length a
    multiple of 4 and a power of two in chunks.  Returns the [8] uint32
    (big-endian word) root of the 2^k-chunk subtree.
    """
    # chunk words: per value the LE bytes are lo,hi; as BE words that is
    # byteswap(lo), byteswap(hi); 4 values -> 8 words -> one 32-byte chunk
    words = jnp.stack([_byteswap32(lo), _byteswap32(hi)], axis=1).reshape(-1, 8)
    level = words
    while level.shape[0] > 1:
        level = sha256_block64(level.reshape(level.shape[0] // 2, 16))
    return level[0]


_jit_reduce = jax.jit(_reduce_to_root)


def _add_u64(lo, hi, dlo, dhi):
    """(lo,hi) += (dlo,dhi) with carry, element-wise on uint32 halves."""
    new_lo = lo + dlo
    carry = (new_lo < lo).astype(jnp.uint32)
    return new_lo, hi + dhi + carry


_jit_add = jax.jit(_add_u64)


class ResidentPackedU64List:
    """A packed ``List[uint64, limit]`` whose leaves live on the device.

    upload() once; mutate via apply_add()/set_values() (device ops); root()
    runs the reduction on device and downloads 32 bytes.  ``root()`` output
    is bit-identical to ``hash_tree_root`` of the equivalent SSZ list.
    """

    def __init__(self, limit: int, device=None):
        assert limit % 4 == 0
        self.limit = limit
        self.chunk_limit = limit // 4
        self.contents_depth = max((self.chunk_limit - 1).bit_length(), 0)
        self.device = device if device is not None else jax.devices()[0]
        self.length = 0
        self._lo: Optional[jnp.ndarray] = None
        self._hi: Optional[jnp.ndarray] = None

    # -- data movement -------------------------------------------------------

    def upload(self, values: np.ndarray) -> None:
        """One-time (or rare) bulk upload of the full value array."""
        values = np.ascontiguousarray(values, dtype="<u8")
        self.length = len(values)
        n_chunks = max((self.length + 3) // 4, 1)
        n_pad = 1 << (n_chunks - 1).bit_length() if n_chunks > 1 else 1
        padded = np.zeros(n_pad * 4, dtype="<u8")
        padded[: self.length] = values
        as_u32 = padded.view("<u4").reshape(-1, 2)
        self._lo = jax.device_put(
            jnp.asarray(as_u32[:, 0].copy()), self.device)
        self._hi = jax.device_put(
            jnp.asarray(as_u32[:, 1].copy()), self.device)

    def to_numpy(self) -> np.ndarray:
        """Download the current values (verification/debug path)."""
        lo = np.asarray(self._lo)[: self.length].astype(np.uint64)
        hi = np.asarray(self._hi)[: self.length].astype(np.uint64)
        return lo | (hi << np.uint64(32))

    # -- device-side mutation ------------------------------------------------

    def apply_add(self, delta) -> None:
        """Add ``delta`` (scalar or per-element array, may be negative) to
        every live element, entirely on device.  A jnp array delta (the
        epoch-kernel-output case) never leaves the device; a scalar ships
        only its two u32 halves; a numpy vector is the one case that pays
        an upload."""
        assert self._lo is not None, "upload() before apply_add()"
        dlo = jnp.zeros_like(self._lo)
        dhi = jnp.zeros_like(self._hi)
        if isinstance(delta, jnp.ndarray):
            # >> 32 must be an arithmetic shift so negative deltas carry a
            # sign-extended high half; only int64 guarantees that here
            assert delta.dtype == jnp.int64, (
                f"jnp delta must be int64, got {delta.dtype}")
            dlo = dlo.at[: self.length].set(delta.astype(jnp.uint32))
            dhi = dhi.at[: self.length].set((delta >> 32).astype(jnp.uint32))
        elif np.isscalar(delta):
            half = np.array([delta], dtype=np.int64).view("<u4")
            dlo = dlo.at[: self.length].set(np.uint32(half[0]))
            dhi = dhi.at[: self.length].set(np.uint32(half[1]))
        else:
            halves = np.ascontiguousarray(
                np.asarray(delta, dtype=np.int64)).view("<u4").reshape(-1, 2)
            dlo = dlo.at[: self.length].set(jnp.asarray(halves[:, 0].copy()))
            dhi = dhi.at[: self.length].set(jnp.asarray(halves[:, 1].copy()))
        self._lo, self._hi = _jit_add(self._lo, self._hi, dlo, dhi)

    # -- roots ---------------------------------------------------------------

    def contents_subtree_root(self) -> bytes:
        """Root of the real-data subtree (padded to its power of two)."""
        assert self._lo is not None, "upload() before reading roots"
        # host-sync: staged view — the resident tree's single root readback
        out = np.asarray(_jit_reduce(self._lo, self._hi))
        return out.astype(">u4").tobytes()

    def as_backing_node(self) -> Node:
        """The list's backing as a fixed-root node pair (contents, length)
        — spliceable into a host-side container backing."""
        import hashlib

        node_root = self.contents_subtree_root()
        n_chunks_padded = max(len(self._lo) // 4, 1)
        level = (n_chunks_padded - 1).bit_length()
        for d in range(level, self.contents_depth):
            node_root = hashlib.sha256(node_root + ZERO_HASHES[d]).digest()
        return BranchNode(LeafNode(node_root), uint_to_leaf(self.length))

    def root(self) -> bytes:
        """Full SSZ ``hash_tree_root`` of the list (zero-hash fold up to
        the virtual depth, then mix in the length)."""
        return merkle_root(self.as_backing_node())


# ---------------------------------------------------------------------------
# Shipping-path integration: "residency composes"
# ---------------------------------------------------------------------------
# The epoch transition's process_rewards_and_penalties rewrites the WHOLE
# balances vector.  The fused program below runs the deltas kernel, the
# clipped balance update AND the full merkle reduction of the new vector as
# ONE jit dispatch — the kernel's output is consumed by the hasher on
# device, never shipped back up for hashing.  The spec substitution
# (specs/builder.py _install_phase0_epoch_kernel) then memoizes the
# device-computed subtree root into the freshly written host backing via
# memoize_packed_u64_contents_root(), so the next hash_tree_root(state) —
# the per-slot state-root cache of process_slots included — skips the
# balances subtree entirely.  Reference seam unchanged:
# eth2spec/utils/ssz/ssz_impl.py:8-13.

RESIDENT_MIN = 16_384  # below this, host hashing of the subtree is trivial


def resident_device():
    """Device for the fused epoch+merkle program, or None to stay on the
    host path.  Policy (CSTPU_RESIDENT_MERKLE): '0' = off, '1' = force on
    the default backend, 'auto' (default) = engage only when the default
    JAX backend is an accelerator (the XLA SHA-256 reduction loses to
    hashlib on the host CPU backend; its chip time is not measured yet).
    A device that fails to initialize raises: no silent host fallback."""
    import os

    mode = os.environ.get("CSTPU_RESIDENT_MERKLE", "auto")
    if mode == "0":
        return None
    dev = jax.devices()[0]
    if mode == "1":
        return dev
    return dev if dev.platform != "cpu" else None


def _fused_epoch_balances(balances, eff, eligible, source_part, target_part,
                          head_part, incl_delay, incl_proposer, scalars):
    from .epoch_jax import _deltas_kernel

    rewards, penalties = _deltas_kernel(
        eff, eligible, source_part, target_part, head_part,
        incl_delay, incl_proposer, scalars)
    increased = balances + rewards
    new_bal = jnp.where(penalties > increased, 0, increased - penalties)
    # padded lanes carry balance 0 and zero deltas, so the zero-padded
    # chunk tail the SSZ merkleization demands is preserved
    lo = new_bal.astype(jnp.uint32)
    hi = (new_bal >> 32).astype(jnp.uint32)
    return new_bal, _reduce_to_root(lo, hi)


_jit_fused = jax.jit(_fused_epoch_balances)


def fused_epoch_balance_update(inp, balances: np.ndarray, device,
                               device_cache: tuple = None):
    """DeltaInputs + current balances -> (new balances [n] int64 numpy,
    padded-subtree root bytes).  One device program; the root reduction
    reads the kernel's output vector in place.  ``device_cache`` (from
    ``epoch_jax.delta_device_cache``) serves the registry-derived inputs
    as resident device buffers — uploaded once per registry version
    (stf/columns.device_buffer), not per epoch call."""
    n = balances.shape[0]
    n_pad = max(4, 1 << (n - 1).bit_length() if n > 1 else 1)

    def pad(a, fill=0):
        if n_pad == n:
            return a
        return np.concatenate([a, np.full(n_pad - n, fill, dtype=a.dtype)])

    from .epoch_jax import delta_scalars

    scalars = delta_scalars(inp)

    put = lambda a: jax.device_put(a, device)  # noqa: E731
    if device_cache is not None:
        from consensus_specs_tpu.stf import columns

        # backend identity bound by device_buffer (appends str(device));
        # these keys deliberately match attestation_deltas' so the two
        # paths share uploads on the same backend
        root, prev_epoch = device_cache
        eff_dev = columns.device_buffer(
            (root, "eff_pad", n_pad),
            lambda: pad(inp.effective_balance), device=device)
        elig_dev = columns.device_buffer(
            (root, "eligible_pad", prev_epoch, n_pad),
            lambda: pad(inp.eligible.astype(bool)), device=device)
    else:
        eff_dev = put(pad(inp.effective_balance))
        elig_dev = put(pad(inp.eligible.astype(bool)))
    new_bal, root_words = _jit_fused(
        put(pad(balances.astype(np.int64))),
        eff_dev,
        elig_dev,
        put(pad(inp.source_part.astype(bool))),
        put(pad(inp.target_part.astype(bool))),
        put(pad(inp.head_part.astype(bool))),
        put(pad(inp.incl_delay, fill=1)),
        put(pad(inp.incl_proposer)),
        put(scalars),
    )
    stats["fused_epoch_updates"] += 1
    # host-sync: staged view — fused-update outputs (new balances + root)
    # pulled once per epoch; ROADMAP item 3 keeps balances resident
    return (np.asarray(new_bal)[:n],
            np.asarray(root_words).astype(">u4").tobytes())


def memoize_packed_u64_contents_root(view, padded_root: bytes) -> None:
    """Install a device-computed subtree root into a packed uint64 List
    view freshly rewritten by bulk.set_packed_uint64_from_numpy: fold the
    padded-power-of-two root up to the list's virtual contents depth with
    shared zero hashes (a handful of host hashes) and memoize it on the
    still-unhashed contents node.  hash_tree_root output is bit-identical
    to the host path — pinned by tests/test_merkle_resident.py."""
    import hashlib

    cls = type(view)
    backing = view.get_backing()
    contents = backing.left
    if contents._root is not None:
        return  # already hashed (nothing to save)
    n = len(view)
    n_chunks = max((n + 3) // 4, 1)
    n_chunks_pad = 1 << (n_chunks - 1).bit_length() if n_chunks > 1 else 1
    root = padded_root
    for d in range((n_chunks_pad - 1).bit_length(), cls.contents_depth()):
        root = hashlib.sha256(root + ZERO_HASHES[d]).digest()
    contents._root = root
    stats["roots_memoized"] += 1


# engagement counters (bench/tests introspection)
stats = {"fused_epoch_updates": 0, "roots_memoized": 0}


def replace_field_subtree(backing: Node, field_index: int, depth: int,
                          new_node: Node) -> Node:
    """Rebuild the spine of a container backing with one field's subtree
    replaced (everything else structurally shared)."""
    if depth == 0:
        return new_node
    bit = (field_index >> (depth - 1)) & 1
    assert isinstance(backing, BranchNode)
    if bit:
        return BranchNode(backing.left, replace_field_subtree(
            backing.right, field_index, depth - 1, new_node))
    return BranchNode(replace_field_subtree(
        backing.left, field_index, depth - 1, new_node), backing.right)
