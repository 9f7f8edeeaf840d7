"""Fr (BLS12-381 scalar field) NTT on device limbs, shardable across a
mesh along the chunk axis.

This is the SP/CP axis of SURVEY §2.7: the DAS erasure-coding FFT
(das/das-core.md:90-128) runs over polynomial chunks; sharding splits the
chunk axis across devices with a four-step (Bailey) decomposition —
local M-point NTTs per device, a twiddle stage, then the cross-device
D-point combine over an ``all_gather`` collective (ICI traffic only).

Field arithmetic mirrors the lazy-reduction Montgomery-limb design of
``ops/bls_jax/limbs.py`` (26-bit int64 limb lanes, only ``mul`` reduces),
specialized to the 255-bit scalar modulus: 10 limbs, R = 2^260.
Differential oracle: ``crypto/fr.py`` (host python-int NTT) — parity is
bit-exact, tests/test_fr_jax.py.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from consensus_specs_tpu import _jaxcache
from consensus_specs_tpu.crypto.fr import R as FR_MOD
from consensus_specs_tpu.crypto.fr import root_of_unity

jax.config.update("jax_enable_x64", True)
_jaxcache.configure()

N_LIMBS = 10
LIMB_BITS = 26
_B = LIMB_BITS
_MASK = (1 << LIMB_BITS) - 1
R_BITS = N_LIMBS * LIMB_BITS  # 260

R_INT = (1 << R_BITS) % FR_MOD
R2_INT = (R_INT * R_INT) % FR_MOD
N0INV_INT = (-pow(FR_MOD, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)


def int_to_limbs(x: int) -> np.ndarray:
    assert 0 <= x < (1 << R_BITS)
    out = np.zeros(N_LIMBS, dtype=np.int64)
    for i in range(N_LIMBS):
        out[i] = (x >> (LIMB_BITS * i)) & _MASK
    return out


def limbs_to_int(a) -> int:
    arr = np.asarray(a, dtype=object)
    return int(sum(int(arr[..., i]) << (LIMB_BITS * i) for i in range(N_LIMBS)))


_P_LIMBS = int_to_limbs(FR_MOD)
_P_LIMBS_J = jnp.asarray(_P_LIMBS)
_N0INV = np.int64(N0INV_INT)

# REDC static tables (same construction as bls_jax/limbs.py)
_P_SHIFTED = np.zeros((N_LIMBS, 2 * N_LIMBS), dtype=np.int64)
for _i in range(N_LIMBS):
    _P_SHIFTED[_i, _i:_i + N_LIMBS] = _P_LIMBS
_P_SHIFTED_J = jnp.asarray(_P_SHIFTED)
_E = np.zeros((2 * N_LIMBS + 1, 2 * N_LIMBS), dtype=np.int64)
for _i in range(2 * N_LIMBS):
    _E[_i, _i] = 1
_E_J = jnp.asarray(_E)
_CONV_IDX = np.zeros((N_LIMBS, 2 * N_LIMBS), dtype=np.int64)
for _r in range(N_LIMBS):
    for _c in range(2 * N_LIMBS):
        _CONV_IDX[_r, _c] = (_c - _r) % (2 * N_LIMBS)
_CONV_IDX_J = jnp.asarray(_CONV_IDX)


def mul(a, b):
    """Montgomery multiply-reduce over [..., N_LIMBS] int64 lanes."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    outer = a[..., :, None] * b[..., None, :]
    padded = jnp.concatenate(
        [outer, jnp.zeros(shape[:-1] + (N_LIMBS, N_LIMBS), jnp.int64)], axis=-1)
    idx = jnp.broadcast_to(_CONV_IDX_J, shape[:-1] + (N_LIMBS, 2 * N_LIMBS))
    rolled = jnp.take_along_axis(padded, idx, axis=-1)
    T = jnp.sum(rolled, axis=-2)
    for i in range(N_LIMBS):
        m = ((T[..., i] & _MASK) * _N0INV) & _MASK
        T = T + m[..., None] * _P_SHIFTED_J[i]
        carry = T[..., i] >> _B
        T = T + carry[..., None] * _E_J[i + 1]
    r = T[..., N_LIMBS:] + _P_LIMBS_J
    digits = []
    c = jnp.zeros_like(r[..., 0])
    for i in range(N_LIMBS):
        v = r[..., i] + c
        digits.append(v & _MASK)
        c = v >> _B
    return jnp.stack(digits, axis=-1)


def host_to_mont(x: int) -> np.ndarray:
    return int_to_limbs(x * R_INT % FR_MOD)


def host_from_mont(a) -> int:
    return limbs_to_int(np.asarray(a)) * pow(R_INT, -1, FR_MOD) % FR_MOD


def canonical_int(a) -> int:
    """Host: limb array (possibly lazy/Montgomery-reduced) -> canonical
    python int mod r, leaving Montgomery form."""
    return host_from_mont(a) % FR_MOD


# ---------------------------------------------------------------------------
# NTT
# ---------------------------------------------------------------------------


def _bit_reverse_perm(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    out = np.zeros(n, dtype=np.int64)
    for i in range(n):
        out[i] = int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
    return out


def _ntt_host_precompute(n: int, w: int):
    """Index + twiddle schedule for the in-place iterative NTT."""
    perm = _bit_reverse_perm(n)
    schedule = []
    size = 2
    while size <= n:
        w_size = pow(w, n // size, FR_MOD)
        top = np.arange(n).reshape(n // size, size)[:, : size // 2].reshape(-1)
        bot = top + size // 2
        tw = np.stack([host_to_mont(pow(w_size, j, FR_MOD))
                       for j in range(size // 2)])
        tws = np.tile(tw, (n // size, 1))
        schedule.append((top, bot, tws))
        size *= 2
    return perm, schedule


def _ntt_apply(x, schedule):
    """Run the precomputed butterfly schedule over [n, N_LIMBS] limbs."""
    for top, bot, tws in schedule:
        t = mul(jnp.asarray(tws), x[jnp.asarray(bot)])
        e = x[jnp.asarray(top)]
        x = x.at[jnp.asarray(top)].set(e + t)
        x = x.at[jnp.asarray(bot)].set(e - t)
        # keep limbs in signed-lazy range; mul renormalizes next stage
    return x


# Lazy-carry magnitude bound: _ntt_apply accumulates e±t without per-stage
# renormalization while mul drops its final carry, so worst-case entry
# magnitudes grow ~2 canonical units per stage.  REDC stays exact for
# inputs above -2^260; 2^14 stages of growth keeps the worst case inside
# that window with margin (the eip4844/DAS sizes are <= 2^12, verified
# bit-exact to 2^12 in tests).  Larger transforms would need renormalizing
# lanes every few stages.
MAX_NTT_SIZE = 1 << 14


def ntt_device(values: Sequence[int], inv: bool = False) -> List[int]:
    """Single-device NTT over Fr, bit-exact vs crypto.fr.fft."""
    n = len(values)
    assert n & (n - 1) == 0
    assert n <= MAX_NTT_SIZE, (
        f"transform size {n} exceeds the lazy-carry bound {MAX_NTT_SIZE}")
    w = root_of_unity(n)
    if inv:
        w = pow(w, FR_MOD - 2, FR_MOD)
    perm, schedule = _ntt_host_precompute(n, w)
    x = np.stack([host_to_mont(int(v) % FR_MOD) for v in values])[perm]
    out = np.asarray(_ntt_apply(jnp.asarray(x), schedule))
    res = [canonical_int(out[i]) for i in range(n)]
    if inv:
        n_inv = pow(n, FR_MOD - 2, FR_MOD)
        res = [v * n_inv % FR_MOD for v in res]
    return res


# ---------------------------------------------------------------------------
# sharded four-step NTT (chunk axis across the mesh)
# ---------------------------------------------------------------------------
#
# N = D*M with device d holding the strided residue class x[M*n1 + ...].
# Decompose n = D*n1 + n2 (n2 = device), k = M*k2 + k1:
#   Y[M*k2 + k1] = sum_{n2} w_D^{n2 k2} * ( w_N^{n2 k1} * Z[n2, k1] )
#   Z[n2, k1]   = M-point NTT over n1 of x[D*n1 + n2]     (local, per device)
# Stage 3 (the D-point combine over k2) runs after an all_gather of the
# twiddled Z rows — D is the mesh size, so this is a small ICI collective.


def sharded_ntt(values: Sequence[int], mesh, axis_name: str = None) -> List[int]:
    """NTT of ``values`` sharded over ``mesh``'s devices along the chunk
    axis; returns canonical ints, bit-exact vs crypto.fr.fft."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if axis_name is None:
        axis_name = mesh.axis_names[0]

    n = len(values)
    d = mesh.devices.size
    assert n % d == 0 and n & (n - 1) == 0
    assert n <= MAX_NTT_SIZE, (
        f"transform size {n} exceeds the lazy-carry bound {MAX_NTT_SIZE}")
    m = n // d
    w_n = root_of_unity(n)
    w_d = pow(w_n, m, FR_MOD)

    perm, schedule = _ntt_host_precompute(m, pow(w_n, d, FR_MOD))

    # rows[n2] = bit-reversed x[D*n1 + n2]; the row axis is the sharded axis
    rows = np.zeros((d, m, N_LIMBS), dtype=np.int64)
    for n2 in range(d):
        strided = [host_to_mont(int(values[d * n1 + n2]) % FR_MOD)
                   for n1 in range(m)]
        rows[n2] = np.stack(strided)[perm]

    # twiddle tensor w_N^{n2*k1} and combine tensor w_D^{n2*k2}, per device
    tw = np.zeros((d, m, N_LIMBS), dtype=np.int64)
    comb = np.zeros((d, d, N_LIMBS), dtype=np.int64)
    for n2 in range(d):
        for k1 in range(m):
            tw[n2, k1] = host_to_mont(pow(w_n, n2 * k1, FR_MOD))
        for k2 in range(d):
            # device k2's combine row: w_D^{n2*k2} for every source n2
            comb[k2, n2] = host_to_mont(pow(w_d, n2 * k2, FR_MOD))

    def _shard_body(x_row, tw_row, comb_row):
        # x_row/tw_row: [1, m, NL]; comb_row: [1, d, NL]
        z = _ntt_apply(x_row[0], schedule)          # local M-point NTT
        z = mul(tw_row[0], z)                       # w_N^{n2 k1} twiddle
        allz = jax.lax.all_gather(z, axis_name)     # [d, m, NL] over ICI
        # this device's output row k2: sum_n2 w_D^{n2 k2} * allz[n2]
        acc = mul(comb_row[0][0], allz[0])
        for n2 in range(1, allz.shape[0]):
            acc = acc + mul(comb_row[0][n2], allz[n2])
        # renormalize the lazy sum so host decode sees digit-bounded limbs
        # (same signed-carry scheme as renorm in bls_jax/limbs.py)
        digits = []
        c = jnp.zeros_like(acc[..., 0])
        for i in range(N_LIMBS - 1):
            v = acc[..., i] + c
            digits.append(v & _MASK)
            c = v >> _B
        digits.append(acc[..., N_LIMBS - 1] + c)
        return jnp.stack(digits, axis=-1)[None]

    spec_sharded = NamedSharding(mesh, P(axis_name))
    fn = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name))
    out_arr = jax.jit(fn)(
        jax.device_put(jnp.asarray(rows), spec_sharded),
        jax.device_put(jnp.asarray(tw), spec_sharded),
        jax.device_put(jnp.asarray(comb), spec_sharded))
    if jax.process_count() > 1:
        # the sharded output spans processes (a DCN mesh): gather the
        # small result rows instead of materializing non-addressable shards
        from jax.experimental import multihost_utils

        # host-sync: cross-process gather of the small NTT result rows
        out = np.asarray(multihost_utils.process_allgather(
            out_arr, tiled=True))
    else:
        out = np.asarray(out_arr)  # host-sync: NTT result rows return to the int pipeline

    result = [0] * n
    for k2 in range(d):
        for k1 in range(m):
            result[m * k2 + k1] = canonical_int(out[k2, k1])
    return result
