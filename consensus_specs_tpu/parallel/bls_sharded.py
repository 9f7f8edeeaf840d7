"""Sharded BLS batch verification: the pairing-product check split over a
device mesh along the batch axis.

The block-processing workload is B independent aggregate checks (SURVEY
§2.7: "#1 TPU target"; reference workload phase0/beacon-chain.md:1807-1833
— one FastAggregateVerify per attestation).  Each item's Miller loop +
final exponentiation is a self-contained limb program with NO cross-item
data flow, so the scale-out seam is pure data parallelism: shard the [K,
B, ...] limb tensors on B, run the whole pipeline per shard, gather the
[B] verdict bits.  The only collective is the implicit output gather —
exactly the shape that rides ICI for free.

Bit-exactness vs the host oracle is pinned by tests/test_sharded_lanes.py
and executed in the driver's multichip dryrun (__graft_entry__).
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from consensus_specs_tpu.ops.bls_jax import pairing

# compiled per (mesh, axis): jit keys on callable identity, so a fresh
# wrapper per call would recompile the Miller-loop pipeline every time
_SHARDED_CHECK_CACHE: dict = {}
_SHARDED_PARTIALS_CACHE: dict = {}


def make_sharded_pairs_check(mesh: Mesh, axis: str = "v"):
    """Compile prod_k e(P_k, Q_k) == 1 per item, batch axis sharded.

    Returns fn(px, py, qx, qy) -> bool [B]; px, py are [K, B, 16] and
    qx, qy [K, B, 2, 16] Montgomery limb tensors (bls_jax marshalling),
    B divisible by the mesh size.
    """
    key = (mesh, axis)
    fn = _SHARDED_CHECK_CACHE.get(key)
    if fn is not None:
        return fn

    def body(px, py, qx, qy):
        f = pairing._miller_product(px, py, qx, qy)
        return pairing.final_exp_is_one_traced(f)

    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, axis), P(None, axis),
                      P(None, axis), P(None, axis)),
            out_specs=P(axis),
            # the Miller loop's fori_loop carries have no replication
            # rule; every in/out spec is explicit so nothing rides on the
            # checker
            check_vma=False,
        )
    )
    _SHARDED_CHECK_CACHE[key] = fn
    return fn


def sharded_batch_fast_aggregate_verify(
    mesh: Mesh,
    pubkeys_lists: Sequence[Sequence[bytes]],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> List[bool]:
    """FastAggregateVerify for B items with the pairing batch sharded over
    the mesh.  Host marshalling is the SAME code path as the single-device
    backend (bls_jax.marshal_fast_aggregate_items); infinity-carrying
    items (no affine limb form) drop to the host oracle per the bls_jax
    policy; the rest are padded with a copy of the first item up to a
    mesh-size multiple and decided in one sharded device program."""
    from consensus_specs_tpu.crypto.bls.pairing import pairings_are_identity
    from consensus_specs_tpu.ops.bls_jax import (
        _g1_coords,
        _g2_coords,
        limbs,
        marshal_fast_aggregate_items,
    )

    results, todo = marshal_fast_aggregate_items(
        pubkeys_lists, messages, signatures)
    clean = []
    for b, pairs in todo:
        if any(p.is_infinity() or q.is_infinity() for p, q in pairs):
            results[b] = bool(pairings_are_identity(pairs))
        else:
            clean.append((b, pairs))
    if not clean:
        return results

    D = int(np.prod(mesh.devices.shape))
    n = len(clean)
    padded = [pairs for _, pairs in clean]
    while len(padded) % D:
        padded.append(padded[0])
    # K comes from the marshalled pairs themselves (FastAggregateVerify
    # always yields 2 — e(pk_agg, H(m)) · e(-G1, sig) — but the device
    # program is shaped by whatever the marshaller produced, not by a
    # hardcoded constant that could silently drift from it)
    K = len(padded[0])
    assert all(len(ps) == K for ps in padded), (
        "sharded pairing batch requires a uniform pair count per item; got "
        f"{sorted({len(ps) for ps in padded})}")
    Bp = len(padded)
    px = np.zeros((K, Bp, limbs.N_LIMBS), dtype=np.int64)
    py = np.zeros_like(px)
    qx = np.zeros((K, Bp, 2, limbs.N_LIMBS), dtype=np.int64)
    qy = np.zeros_like(qx)
    for b, ps in enumerate(padded):
        for k, (p, q) in enumerate(ps):
            px[k, b], py[k, b] = _g1_coords(p)
            qx[k, b], qy[k, b] = _g2_coords(q)
    check = make_sharded_pairs_check(mesh)
    verdicts = np.asarray(check(px, py, qx, qy))  # host-sync: per-block verdicts readback
    for (b, _), v in zip(clean, verdicts[:n]):
        results[b] = bool(v)
    return results


# ---------------------------------------------------------------------------
# Pairing-lane chunks: ONE product, its lanes split over the mesh
# ---------------------------------------------------------------------------
# The batch verifier's MSM-folded interior reduces a whole block to a
# SINGLE multi-pairing — one lane per unique message plus the folded
# signature lane — so the multi-chip seam is no longer B independent
# checks but the lanes of one product.  Mirror of the native kernel's
# chunk-parallel miller_loop_product: each device runs the shared-squaring
# Miller chain of its contiguous lane chunk, the partial Fp12 products
# multiply in FIXED chunk-index order, and ONE final exponentiation
# decides the whole product.  Squaring distributes over products, so the
# chunked result is bit-identical to the one-chain product wherever the
# chunk boundaries fall.


def make_sharded_lane_partials(mesh: Mesh, axis: str = "v"):
    """Compile the per-chunk partial Miller product, chunk axis sharded.

    Returns fn(px, py, qx, qy) -> f [D, 6, 2, 16]: px, py are [C, D, 16]
    and qx, qy [C, D, 2, 16] Montgomery limb tensors where chunk d owns C
    lanes; D divisible by the mesh size.  f[d] is the conjugated Miller
    value of chunk d's lane product (conjugation is the p^6 Frobenius, a
    ring automorphism, so per-chunk conjugates compose under the merge
    multiply)."""
    key = (mesh, axis)
    fn = _SHARDED_PARTIALS_CACHE.get(key)
    if fn is not None:
        return fn

    fn = jax.jit(
        jax.shard_map(
            pairing._miller_product,
            mesh=mesh,
            in_specs=(P(None, axis), P(None, axis),
                      P(None, axis), P(None, axis)),
            out_specs=P(axis),
            # same fori_loop-carry caveat as make_sharded_pairs_check
            check_vma=False,
        )
    )
    _SHARDED_PARTIALS_CACHE[key] = fn
    return fn


def sharded_pairing_lanes_check(mesh: Mesh, pairs) -> bool:
    """prod_i e(P_i, Q_i) == 1, the lanes of ONE pairing product split
    into contiguous chunks over the mesh.

    ``pairs`` is a sequence of (G1 Point, G2 Point) lanes — the shape the
    folded batch verifier emits (unique-message lanes + the signature
    lane).  Infinity lanes contribute the identity and are dropped on the
    host.  Ragged lane counts are padded up to a chunks-times-lanes
    rectangle with self-canceling lanes (m-1 copies of e(G, H) and one
    e([-(m-1)]G, H): their product is exactly 1, so the verdict is
    untouched no matter which chunks the pads land in)."""
    from consensus_specs_tpu.crypto.bls.curve import (
        g1_generator,
        g2_generator,
    )
    from consensus_specs_tpu.ops.bls_jax import _g1_coords, _g2_coords, limbs

    lanes = [(p, q) for p, q in pairs
             if not (p.is_infinity() or q.is_infinity())]
    if not lanes:
        return True  # empty product
    D = int(np.prod(mesh.devices.shape))
    C = -(-len(lanes) // D)  # lanes per chunk
    m = C * D - len(lanes)
    if m == 1:
        # a single non-trivial lane cannot be the identity; widen the
        # chunks so the pad group has >= 2 lanes to cancel within
        C += 1
        m += D
    if m:
        G, H = g1_generator(), g2_generator()
        lanes += [(G, H)] * (m - 1) + [(-G.mul(m - 1), H)]
    px = np.zeros((C, D, limbs.N_LIMBS), dtype=np.int64)
    py = np.zeros_like(px)
    qx = np.zeros((C, D, 2, limbs.N_LIMBS), dtype=np.int64)
    qy = np.zeros_like(qx)
    for l, (p, q) in enumerate(lanes):
        d, c = divmod(l, C)  # chunk d owns lanes [d*C, (d+1)*C)
        px[c, d], py[c, d] = _g1_coords(p)
        qx[c, d], qy[c, d] = _g2_coords(q)
    partials = make_sharded_lane_partials(mesh)(px, py, qx, qy)
    # fixed chunk-index merge order, then the single shared final exp
    f = partials[0]
    for d in range(1, D):
        f = pairing._mul12(f, partials[d])
    return bool(pairing.final_exp_is_one(f[None])[0])
