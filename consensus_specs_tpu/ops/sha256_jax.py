"""Batched SHA-256 for merkle layer hashing, in JAX.

Each merkle parent is SHA-256 over exactly 64 bytes (two child roots) —
one message block plus one constant padding block (reference semantics:
eth2spec/utils/hash_function.py:8; merkleize rules
ssz/simple-serialize.md:210-248).  The kernel runs the 64-round
compression across all lanes of a layer at once: bitwise rotes/adds in
int32 lanes map directly onto the TPU VPU, and XLA fuses the whole
round chain into a few kernels.  Lanes are padded to the next power of
two to bound recompilation.

This module is also the building block for the sharded merkleization
path in ``parallel/`` (layer split across devices, no collectives
needed until the subtree roots merge).
"""
from __future__ import annotations

from typing import List

import numpy as np

import jax
import jax.numpy as jnp

# SHA-256 round constants
_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)

# Message schedule of the constant second (padding) block for a 64-byte
# message: 0x80, zeros, 64-bit bit-length (512).
_PAD_BLOCK = np.zeros(16, dtype=np.uint32)
_PAD_BLOCK[0] = 0x80000000
_PAD_BLOCK[15] = 512


def _rotr(x, n):
    return (x >> n) | (x << (32 - n))


def _compress(state, w):
    """One SHA-256 compression over a [N,16] uint32 block batch.
    ``state`` is a tuple of 8 [N] uint32 vectors.

    Rounds run under ``lax.fori_loop`` — one compiled body instead of a
    64×-unrolled graph (compile time matters: the dryrun and tests
    compile on CPU; runtime stays lane-vectorized either way).
    """
    n = w.shape[0]
    k = jnp.asarray(_K, dtype=jnp.uint32)

    # message schedule: extend [N,16] -> [N,64]
    ws0 = jnp.concatenate([w, jnp.zeros((n, 48), dtype=jnp.uint32)], axis=1)

    def sched_body(i, ws):
        w15 = ws[:, i - 15]
        w2 = ws[:, i - 2]
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> 3)
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> 10)
        return ws.at[:, i].set(ws[:, i - 16] + s0 + ws[:, i - 7] + s1)

    ws = jax.lax.fori_loop(16, 64, sched_body, ws0)

    def round_body(i, carry):
        a, b, c, d, e, f, g, h = carry
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        temp1 = h + S1 + ch + k[i] + ws[:, i]
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        temp2 = S0 + maj
        return (temp1 + temp2, a, b, c, d + temp1, e, f, g)

    out = jax.lax.fori_loop(0, 64, round_body, state)
    return tuple(x + y for x, y in zip(state, out))


def sha256_block64(blocks: jnp.ndarray) -> jnp.ndarray:
    """SHA-256 of N 64-byte messages given as [N, 16] big-endian uint32.
    Returns [N, 8] uint32 digests."""
    n = blocks.shape[0]
    # the `+ blocks[:, 0] * 0` ties the init state to the input so its
    # sharding axes (vma) match the loop carry under shard_map
    zero = blocks[:, 0] * 0
    init = tuple(jnp.full((n,), _H0[i], dtype=jnp.uint32) + zero for i in range(8))
    mid = _compress(init, blocks)
    pad = (jnp.broadcast_to(jnp.asarray(_PAD_BLOCK, dtype=jnp.uint32), (n, 16))
           + zero[:, None])
    out = _compress(mid, pad)
    return jnp.stack(out, axis=1)


def sha256_single_block(blocks: jnp.ndarray) -> jnp.ndarray:
    """SHA-256 of N messages of at most 55 bytes, each already padded into
    one 64-byte block (message, ``0x80``, zeros, 64-bit big-endian bit
    length), given as [N, 16] big-endian uint32.  One compression per
    lane.  Returns [N, 8] uint32 digests."""
    n = blocks.shape[0]
    init = tuple(jnp.full((n,), _H0[i], dtype=jnp.uint32) for i in range(8))
    return jnp.stack(_compress(init, blocks), axis=1)


# jax.jit caches one executable per input shape on this single callable
_jit_block64 = jax.jit(sha256_block64)


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _next_pow4(n: int) -> int:
    p = _next_pow2(n)
    return p if (p.bit_length() - 1) % 2 == 0 else p * 2


def hash_blocks_u32(words: np.ndarray) -> np.ndarray:
    """Hash [N,16] big-endian uint32 words to [N,8] digests (numpy in/out)."""
    n = words.shape[0]
    n_pad = _next_pow2(n)  # pad lanes to powers of two to bound recompiles
    if n_pad != n:
        words = np.vstack([words, np.zeros((n_pad - n, 16), dtype=np.uint32)])
    out = np.asarray(_jit_block64(jnp.asarray(words)))  # host-sync: digest batch returns to the byte pipeline
    return out[:n]


def hash_layer_via(hash_words, blocks: List[bytes]) -> List[bytes]:
    """Shared byte<->uint32 packing for layer-hash backends: `hash_words`
    maps [N,16] big-endian uint32 words to [N,8] digests (numpy in/out)."""
    n = len(blocks)
    if n == 0:
        return []
    words = np.frombuffer(b"".join(blocks), dtype=">u4").reshape(n, 16).astype(np.uint32)
    out = hash_words(words)
    flat = out.astype(">u4").tobytes()
    return [flat[i * 32:(i + 1) * 32] for i in range(n)]


def hash_layer(blocks: List[bytes]) -> List[bytes]:
    """Backend for ssz.hashing: list of 64-byte inputs -> 32-byte digests."""
    return hash_layer_via(hash_blocks_u32, blocks)


# -- whole-wave-schedule hashing (single device program) --------------------
#
# Per-layer dispatch pays one host<->device round trip per tree level;
# what that costs against the whole-tree program is not measured on the
# chip yet.  The TPU-native shape for a full merkle (sub)tree is ONE
# program: upload the known child digests once, run every wave as a
# gather + compress stage inside a single jit (the level loop is unrolled
# at trace time — wave sizes are static), download every produced digest
# once.


def _run_waves(known, lefts, rights):
    """known: [K,8] u32 digest pool seed.  lefts/rights: per-wave int32
    index arrays into the pool (known rows, then each prior wave's rows).
    One preallocated pool buffer; each wave writes its digests in place
    (XLA turns the dynamic_update_slice chain into in-place updates).
    Returns all wave outputs concatenated [sum(n_k), 8]."""
    total = known.shape[0] + sum(left.shape[0] for left in lefts)
    pool = jnp.zeros((total, 8), dtype=jnp.uint32)
    pool = jax.lax.dynamic_update_slice(pool, known, (0, 0))
    offset = known.shape[0]
    outs = []
    for left, right in zip(lefts, rights):
        blocks = jnp.concatenate([pool[left], pool[right]], axis=1)  # [n,16]
        digest = sha256_block64(blocks)
        outs.append(digest)
        pool = jax.lax.dynamic_update_slice(pool, digest, (offset, 0))
        offset += left.shape[0]
    return jnp.concatenate(outs, axis=0)


_jit_run_waves = jax.jit(_run_waves)


def hash_waves_u32(known: np.ndarray, waves) -> np.ndarray:
    """Run a whole wave schedule on device in one dispatch.

    ``known``: [K,8] big-endian-word digests (the already-rooted children).
    ``waves``: list of (left_idx, right_idx) int32 numpy arrays indexing
    the pool, where pool rows are ``known`` rows followed by every prior
    wave's outputs in schedule order.  Returns all outputs concatenated.

    jax.jit caches one executable per (K, wave-size...) signature; the
    byte-level wrapper pads both to powers of two so differently-sized
    dirty subtrees bucket into a bounded set of compiled shapes.
    """
    lefts = tuple(jnp.asarray(w[0]) for w in waves)
    rights = tuple(jnp.asarray(w[1]) for w in waves)
    out = _jit_run_waves(jnp.asarray(known), lefts, rights)
    return np.asarray(out)  # host-sync: wave digests return to the byte pipeline


def hash_waves(known: List[bytes], waves) -> List[bytes]:
    """Byte-level wrapper: ``known`` is 32-byte digests; ``waves`` is
    (left_idx, right_idx) pairs indexing [known | outputs-so-far].
    Returns the concatenated 32-byte outputs of every wave.

    The known pool and the first wave are padded to powers of FOUR, later
    waves follow a monotone halving envelope, and the wave count is padded
    to a multiple of four with dummy single-lane waves (padding lanes hash
    row 0 and are discarded) — so the jit signature, and therefore the
    compile count, is a small bounded set per tree magnitude rather than
    one executable per exact dirty pattern."""
    k = len(known)
    k_pad = _next_pow4(max(k, 1))
    words = np.zeros((k_pad, 8), dtype=np.uint32)
    if k:
        words[:k] = np.frombuffer(b"".join(known), dtype=">u4").reshape(k, 8)

    sizes = [len(w[0]) for w in waves]
    # Monotone halving envelope: wave k is padded to
    # max(pow2(size_k), previous_pad // 2).  Merkle wave schedules are
    # (near-)halving ladders, so the whole padded-size tuple — and hence
    # the jit signature — is determined by (first-wave pow2, wave count):
    # arbitrary dirty patterns of similar magnitude share one executable
    # instead of recompiling per exact shape.
    padded = []
    for s in sizes:
        if padded:
            p = max(_next_pow2(max(s, 1)), padded[-1] // 2)
        else:
            p = _next_pow4(max(s, 1))
        padded.append(p)
    # padded pool row of each unpadded output position: known padding sits
    # at rows k..k_pad-1, wave k's rows start where wave k-1's padded rows end
    trans = np.empty(max(sum(sizes), 1), dtype=np.int64)
    base, up = k_pad, 0
    for size, psize in zip(sizes, padded):
        trans[up:up + size] = base + np.arange(size)
        up += size
        base += psize

    padded_waves = []
    for (left, right), size, psize in zip(waves, sizes, padded):
        lp = np.zeros(psize, dtype=np.int32)
        rp = np.zeros(psize, dtype=np.int32)
        for src, dst in ((left, lp), (right, rp)):
            src = np.asarray(src, dtype=np.int64)
            dst[:size] = np.where(src < k, src, trans[np.maximum(src - k, 0)])
        padded_waves.append((lp, rp))
    # dummy single-lane waves pad the count to a multiple of 4 (their
    # outputs land after every real wave's rows and are never extracted)
    dummy = (np.zeros(1, dtype=np.int32), np.zeros(1, dtype=np.int32))
    while len(padded_waves) % 4:
        padded_waves.append(dummy)
        padded.append(1)

    out = hash_waves_u32(words, padded_waves)
    flat = out.astype(">u4").tobytes()
    result = []
    base = 0
    for size, psize in zip(sizes, padded):
        result.extend(flat[(base + i) * 32:(base + i + 1) * 32]
                      for i in range(size))
        base += psize
    return result
