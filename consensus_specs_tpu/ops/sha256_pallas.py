"""Pallas TPU kernel for batched SHA-256 merkle compression.

Layout is the TPU-native transpose of ops/sha256_jax.py: message words
live on SUBLANES (16 rows) and independent messages on LANES (128 per
program), so every round is a VPU-wide uint32 op with zero gathers.  The
grid walks lane-tiles of 128 messages; each program runs the full 64
unrolled rounds for its tile plus the padding-block compression (the
merkle case: one 64-byte message = two child roots).

On non-TPU backends the kernel runs in interpreter mode — bit-identical
but minutes-per-shape slow under this image's jax build, so its
interpret-mode tests (tests/test_sha256_pallas.py) are opt-in via
CSTPU_PALLAS_TESTS=1.  tests/test_chip_compile.py compiles the kernel for
a described TPU v5e, and chip_smoke.py phase f runs it on the chip.
Registered as the "pallas" hashing backend:
``hashing.set_backend("pallas")``.
"""
from __future__ import annotations

from typing import List

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from consensus_specs_tpu import _jaxcache
from consensus_specs_tpu.ops.sha256_jax import (
    _H0,
    _K,
    _PAD_BLOCK,
    _next_pow2,
    hash_layer_via,
)

_jaxcache.configure()

_LANES = 128


def _ror(x, n):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _compress_rows(state, w_rows):
    """One SHA-256 compression over 8 state rows given 16 message rows
    (each row shape [LANES], uint32).  Rounds fully unrolled."""
    a, b, c, d, e, f, g, h = state
    w = list(w_rows)
    for i in range(64):
        if i >= 16:
            s0 = _ror(w[i - 15], 7) ^ _ror(w[i - 15], 18) ^ (w[i - 15] >> jnp.uint32(3))
            s1 = _ror(w[i - 2], 17) ^ _ror(w[i - 2], 19) ^ (w[i - 2] >> jnp.uint32(10))
            w.append(w[i - 16] + s0 + w[i - 7] + s1)
        s1 = _ror(e, 6) ^ _ror(e, 11) ^ _ror(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + jnp.uint32(_K[i]) + w[i]
        s0 = _ror(a, 2) ^ _ror(a, 13) ^ _ror(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        a, b, c, d, e, f, g, h = t1 + t2, a, b, c, d + t1, e, f, g
    return tuple(x + y for x, y in zip(state, (a, b, c, d, e, f, g, h)))


def _kernel(in_ref, out_ref):
    w_rows = [in_ref[i, :] for i in range(16)]
    init = tuple(
        jnp.full((_LANES,), _H0[i], dtype=jnp.uint32) for i in range(8)
    )
    mid = _compress_rows(init, w_rows)
    pad_rows = [
        jnp.full((_LANES,), int(_PAD_BLOCK[i]), dtype=jnp.uint32)
        for i in range(16)
    ]
    out = _compress_rows(mid, pad_rows)
    for i in range(8):
        out_ref[i, :] = out[i]


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block64_t_impl(words_t: jnp.ndarray) -> jnp.ndarray:
    """[16, N] big-endian uint32 message words -> [8, N] digests.
    N must be a multiple of 128."""
    n = words_t.shape[1]
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.uint32),
        grid=(n // _LANES,),
        in_specs=[pl.BlockSpec((16, _LANES), _block_index)],
        out_specs=pl.BlockSpec((8, _LANES), _block_index),
        interpret=_use_interpret(),
    )(words_t)


def _block_index(i):
    # an int32 block index even under jax_enable_x64 (the epoch and BLS
    # modules turn it on): Mosaic refuses a literal 0 traced as i64
    return jnp.int32(0), i


# On real TPUs the kernel compiles natively and the jit wrapper caches the
# executable per shape.  In interpreter mode (every other backend) jitting
# would lower the op-by-op emulation into an enormous XLA graph — minutes
# of compile for zero benefit — so the interpreter runs eagerly.
_block64_t_jit = jax.jit(_block64_t_impl)


def _block64_t(words_t):
    if _use_interpret():
        return _block64_t_impl(words_t)
    return _block64_t_jit(words_t)


def sha256_block64(blocks: np.ndarray) -> np.ndarray:
    """SHA-256 of N 64-byte messages given as [N, 16] big-endian uint32
    (numpy in/out); the merkle parent-digest primitive."""
    n = blocks.shape[0]
    # pad to a power-of-two multiple of the lane tile: bounded shape set
    # (each distinct shape pays a trace/compile)
    n_pad = max(_LANES, _next_pow2(n))
    words = np.zeros((n_pad, 16), dtype=np.uint32)
    words[:n] = blocks
    out = np.asarray(_block64_t(jnp.asarray(words.T)))
    return out.T[:n]


def hash_layer(blocks: List[bytes]) -> List[bytes]:
    """Hashing-backend entry: list of 64-byte blocks -> 32-byte digests."""
    return hash_layer_via(sha256_block64, blocks)
