"""Bulk numpy <-> SSZ-backing transfers.

The TPU pipeline consumes whole-registry columns (effective balances,
participation flags, epochs) and produces whole-registry balance vectors.
Feeding those through the per-element view protocol costs O(n) Python
object churn per epoch; these helpers move data between numpy arrays and
the persistent Merkle backing directly at chunk granularity.

The reference has no analogue — its spec loops per validator (e.g.
process_rewards_and_penalties, phase0/beacon-chain.md:1439-1561); this
module is the seam that lets the compiled spec keep identical semantics
while the state transfer runs at memcpy speed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from .node import (
    BranchNode,
    LeafNode,
    Node,
    PackedLazySubtree,
    ZERO_HASHES,
    pack_chunks,
    subtree_fill_to_contents,
    uint_to_leaf,
    with_updated_subtrees,
    zero_node,
)
from .types import _collect_leaf_roots


def _packed_to_numpy(view, elem_bytes: int, np_dtype: str) -> np.ndarray:
    cls = type(view)
    node = view.get_backing()  # flush pending writes
    contents = node.left if cls.IS_LIST else node
    n = len(view)
    per_chunk = 32 // elem_bytes
    n_chunks = (n + per_chunk - 1) // per_chunk
    data = b"".join(_collect_leaf_roots(contents, cls.contents_depth(), n_chunks))
    return np.frombuffer(data, dtype=np_dtype)[:n]


def packed_lazy_contents(data: bytes, contents_depth: int) -> Node:
    """Contents node for a freshly bulk-written packed subtree: the dense
    power-of-two region is a ``PackedLazySubtree`` (eager level-loop root,
    children materialized only on demand), the zero spine above carries
    eagerly folded roots — a whole-column write costs one vectorized hash
    pass instead of ~n/32 leaf nodes plus a wave re-merkleization."""
    import hashlib

    n_chunks = (len(data) + 31) // 32
    if n_chunks == 0 or not any(data):
        return zero_node(contents_depth)
    dense_depth = (n_chunks - 1).bit_length()
    if dense_depth == 0:
        node: Node = pack_chunks(data)[0]
    else:
        node = PackedLazySubtree(data, dense_depth)
    root = node._root
    for d in range(dense_depth, contents_depth):
        parent = BranchNode(node, zero_node(d))
        root = parent._root = hashlib.sha256(root + ZERO_HASHES[d]).digest()
        node = parent
    return node


def _set_packed_from_numpy(view, arr: np.ndarray, lazy: bool = False) -> None:
    cls = type(view)
    if cls.IS_LIST:
        if len(arr) > cls.LENGTH:
            raise ValueError(f"{len(arr)} exceeds list limit {cls.LENGTH}")
    elif len(arr) != cls.LENGTH:
        raise ValueError(f"vector needs exactly {cls.LENGTH} elements")
    if lazy:
        contents = packed_lazy_contents(arr.tobytes(), cls.contents_depth())
    else:
        contents = subtree_fill_to_contents(
            pack_chunks(arr.tobytes()), cls.contents_depth()
        )
    backing = (
        BranchNode(contents, uint_to_leaf(len(arr))) if cls.IS_LIST else contents
    )
    # install fresh backing; drop any materialized value cache
    view._values = None
    view._dirty_chunks = set()
    view._backing = backing
    view._length = len(arr) if cls.IS_LIST else cls.LENGTH
    view._invalidate()  # parent (e.g. the BeaconState container) sees the change


def packed_uint64_to_numpy(view) -> np.ndarray:
    """List/Vector[uint64, N] -> int64 numpy array (values < 2^63 assumed,
    which Gwei balances satisfy by orders of magnitude)."""
    return _packed_to_numpy(view, 8, "<u8").astype(np.int64)


def set_packed_uint64_from_numpy(view, arr: np.ndarray) -> None:
    """Replace the full contents of a packed uint64 List/Vector in one
    bottom-up rebuild, preserving view/parent dirty-tracking semantics."""
    _set_packed_from_numpy(view, np.ascontiguousarray(arr, dtype="<u8"))


def set_packed_uint64_at(view, arr: np.ndarray, indices) -> None:
    """Write the chunks of a packed uint64 List/Vector that hold
    ``indices``, from ``arr`` (the view's whole new contents, equal to the
    old outside those chunks): one path copy per touched chunk, the rest of
    the tree and its memoized roots kept.  For a few writes into a large
    column, where a whole rebuild would cost a node per chunk."""
    cls = type(view)
    arr = np.ascontiguousarray(arr, dtype="<u8")
    if len(arr) != len(view):
        raise ValueError(f"{len(arr)} values for a view of {len(view)}")
    updates = [(c, LeafNode(arr[4 * c:4 * c + 4].tobytes().ljust(32, b"\x00")))
               for c in np.unique(np.asarray(indices, dtype=np.int64) // 4).tolist()]
    node = view.get_backing()  # flush pending writes
    contents = with_updated_subtrees(node.left if cls.IS_LIST else node,
                                     cls.contents_depth(), updates)
    view._values = None
    view._dirty_chunks = set()
    view._backing = BranchNode(contents, node.right) if cls.IS_LIST else contents
    view._invalidate()


def packed_uint8_to_numpy(view) -> np.ndarray:
    """List/Vector[uint8, N] (e.g. altair participation flags) -> uint8."""
    return _packed_to_numpy(view, 1, np.uint8).copy()


def set_packed_uint8_from_numpy(view, arr: np.ndarray) -> None:
    """uint8 columns take the lazy-subtree write: participation flags are
    rewritten once per block and their subtree ROOT is always consumed by
    the next state-root check, while their chunk nodes are read back only
    on a resident-store miss — the eager-root/lazy-children split is
    exactly that access pattern.  (uint64 balance writes stay node-built:
    epoch processing rewrites them several times between root reads, so
    an eager root per write would hash MORE, not less.)"""
    _set_packed_from_numpy(
        view, np.ascontiguousarray(arr, dtype=np.uint8), lazy=True)


def bitlist_to_numpy(bits) -> np.ndarray:
    """Bool column of a ``Bitlist``/``Bitvector`` view (the per-bit view
    protocol costs a Python object per member; attestation batching reads
    whole aggregation-bit columns)."""
    inner = getattr(bits, "_bits", None)
    if inner is not None:  # the in-repo bit views hold a plain bool list
        return np.asarray(inner, dtype=bool)
    return np.fromiter(bits, dtype=bool, count=len(bits))


def composite_subtrees(view) -> list:
    """The backing subtree node of each element of a List/Vector of
    composites, left to right (no hashing is triggered)."""
    cls = type(view)
    node = view.get_backing()
    contents = node.left if cls.IS_LIST else node
    n = len(view)
    out: list = []
    if n == 0:
        return out
    stack = [(contents, cls.contents_depth())]
    while stack and len(out) < n:
        nd, d = stack.pop()
        if d == 0:
            out.append(nd)
            continue
        stack.append((nd.right, d - 1))
        stack.append((nd.left, d - 1))
    return out


def composite_list_tail(view, start: int):
    """A new view of ``view``'s List type holding its elements from
    ``start`` on: their backing subtrees refilled under a new length, no
    element rebuilt (the queue dequeue ``queue = queue[start:]``)."""
    cls = type(view)
    nodes = composite_subtrees(view)[start:]
    contents = subtree_fill_to_contents(nodes, cls.contents_depth())
    return cls.view_from_backing(BranchNode(contents, uint_to_leaf(len(nodes))))


def _field_path(field_index: int, depth: int):
    """Descent path (True=right) for a field at the given container depth."""
    return [bool((field_index >> (depth - 1 - b)) & 1) for b in range(depth)]


def _walk(node: Node, path) -> Node:
    for go_right in path:
        node = node.right if go_right else node.left
    return node


# --- validator registry columns ---------------------------------------------

# epoch-processing columns (phase0/beacon-chain.md Validator container; later
# forks may append fields — e.g. the early capella draft's
# fully_withdrawn_epoch — so paths are derived from the element class layout)
_V_FIELDS_U64 = (
    "effective_balance",
    "activation_eligibility_epoch",
    "activation_epoch",
    "exit_epoch",
    "withdrawable_epoch",
)

# the full-withdrawal sweep's inputs, on layouts that carry one (capella's
# early draft): this epoch column, and the credentials' first byte
_SWEEP_EPOCH = "fully_withdrawn_epoch"


def validator_pubkeys(validators) -> list:
    """One walk over the registry subtrees -> every validator's pubkey as
    raw 48-byte strings.  The per-index view path
    (``state.validators[i].pubkey``) costs a tree descent + view
    materialization per read; attestation verification reads ~25k pubkeys
    per block, which makes this column the cheap representation."""
    et = type(validators).ELEM_TYPE
    path = _field_path(et._field_index["pubkey"], et._depth)
    out = []
    for sub in composite_subtrees(validators):
        node = _walk(sub, path)
        # Bytes48 backing: Branch(chunk0, chunk1) with 16 zero tail bytes
        out.append(node.left._root + node.right._root[:16])
    return out


class RootKeyedCache:
    """LRU cache keyed by a view's tree root: any mutation produces a new
    root, so invalidation is automatic.  THE shared memoizer for derived
    registry representations (pubkey column here, numeric columns in
    ops/epoch_jax.registry_columns).  ``key`` replaces the root as the
    key (``registry_key`` for values that read no withdrawal field)."""

    def __init__(self, capacity: int, key=None):
        self.capacity = capacity
        self.key = key
        self._store: Dict[bytes, object] = {}

    def get(self, view, build, on_insert=None):
        """Cached value for ``view``; on a miss, builds and inserts.
        ``on_insert(store, root)`` fires after a fresh insert — the stf
        cache transaction uses it to record the insert for rollback."""
        root = (self.key or _root_of)(view)
        hit = self._store.pop(root, None)
        if hit is None:
            return self.put(root, build(view), on_insert)
        self._store[root] = hit  # most recent last
        return hit

    def put(self, root: bytes, value, on_insert=None):
        """Insert ``value`` under ``root`` as the most recent entry (the
        least recent evicted when the cache is full)."""
        self._store.pop(root, None)
        if len(self._store) >= self.capacity:
            self._store.pop(next(iter(self._store)))
        self._store[root] = value
        if on_insert is not None:
            on_insert(self._store, root)
        return value


def _root_of(view) -> bytes:
    return bytes(view.hash_tree_root())


# registry root -> the key of a registry that differs from it only in the
# withdrawal fields (withdrawal_credentials, fully_withdrawn_epoch).  A
# capella block rewrites credentials (BLS-to-execution changes) and the
# epoch sweep sets fully_withdrawn_epoch: neither moves a pubkey, a status
# epoch or an effective balance, so what those feed (active sets,
# committees, proposers, pubkey and affine columns, sync seats) carries
# over instead of being rebuilt for a registry root seen once.  Each entry
# is a fact about two roots, so an entry recorded by a block that then
# rolled back is still true.
_REGISTRY_ALIASES: Dict[bytes, bytes] = {}
_REGISTRY_ALIASES_MAX = 64


def registry_key(validators) -> bytes:
    """The registry's identity for values that read no withdrawal field:
    its tree root, or the key of the registry it was derived from by
    withdrawal-field writes (``note_withdrawal_writes``)."""
    root = bytes(validators.hash_tree_root())
    return _REGISTRY_ALIASES.get(root, root)


def note_withdrawal_writes(key: bytes, validators) -> None:
    """Record that ``validators`` differs from the registry keyed ``key``
    only in withdrawal fields."""
    root = bytes(validators.hash_tree_root())
    if root == key or root in _REGISTRY_ALIASES:
        return
    if len(_REGISTRY_ALIASES) >= _REGISTRY_ALIASES_MAX:
        _REGISTRY_ALIASES.pop(next(iter(_REGISTRY_ALIASES)))
    _REGISTRY_ALIASES[root] = key


# 2 entries cover the pre/post-epoch registries a transition touches
_PUBKEY_CACHE = RootKeyedCache(2, key=registry_key)


def cached_validator_pubkeys(validators) -> list:
    return _PUBKEY_CACHE.get(validators, validator_pubkeys)


_PUBKEY_INDEX_CACHE = RootKeyedCache(2, key=registry_key)


def cached_pubkey_index(validators) -> Dict[bytes, int]:
    """pubkey bytes -> FIRST validator index carrying it (list.index
    semantics, which is what the altair sync-committee reward loop's
    ``all_pubkeys.index(pubkey)`` resolves to on duplicate keys)."""

    def build(v):
        index_of: Dict[bytes, int] = {}
        for i, pk in enumerate(cached_validator_pubkeys(v)):
            index_of.setdefault(pk, i)
        return index_of

    return _PUBKEY_INDEX_CACHE.get(validators, build)


def validator_columns(validators) -> Dict[str, np.ndarray]:
    """One walk over the registry subtrees -> all epoch-processing columns:
    the uint64 fields of ``_V_FIELDS_U64`` and ``slashed``, and on a layout
    with the withdrawal sweep also ``fully_withdrawn_epoch`` and
    ``withdrawal_prefix`` (uint8, the credentials' first byte).

    Field paths come from the element class's own layout (field count sets
    the tree depth); each shared path prefix is descended once for the
    whole registry.  Saturates epochs at int64 max (FAR_FUTURE_EPOCH =
    2^64-1 would wrap)."""
    et = type(validators).ELEM_TYPE
    depth = et._depth
    findex = et._field_index
    names = _V_FIELDS_U64 + ((_SWEEP_EPOCH,) if _SWEEP_EPOCH in findex else ())
    leaves = {name: _field_path(findex[name], depth)
              for name in names + ("slashed",)}
    if _SWEEP_EPOCH in findex:
        leaves["withdrawal_prefix"] = _field_path(
            findex["withdrawal_credentials"], depth)
    chunks = _leaf_chunks(composite_subtrees(validators), leaves)
    cols: Dict[str, np.ndarray] = {}
    for name in names:
        u = np.ascontiguousarray(chunks[name][:, :8]).view("<u8").ravel()
        cols[name] = np.minimum(u, np.uint64(2**63 - 1)).astype(np.int64)
    cols["slashed"] = chunks["slashed"][:, 0] != 0
    if "withdrawal_prefix" in chunks:
        cols["withdrawal_prefix"] = chunks["withdrawal_prefix"][:, 0].copy()
    return cols


def _leaf_chunks(subs: list, paths: Dict[str, list]) -> Dict[str, np.ndarray]:
    """name -> (n, 32) uint8: the leaf chunk at ``paths[name]`` under each
    subtree, with every shared path prefix descended once."""
    level = {(): subs}
    out = {}
    for name, path in paths.items():
        key: tuple = ()
        for go_right in path:
            parent, key = level[key], key + (go_right,)
            if key not in level:
                level[key] = ([nd.right for nd in parent] if go_right
                              else [nd.left for nd in parent])
        out[name] = np.frombuffer(
            b"".join([nd._root for nd in level[key]]), np.uint8).reshape(-1, 32)
    return out
