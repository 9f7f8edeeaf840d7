"""Batched G1 scalar multiplication for KZG commitments (BASELINE config
5; reference analogue: the G1 MSM inside eip4844's blob_to_kzg,
specs/eip4844/beacon-chain.md:112-120).

Device layout: N lanes of (affine point, 255-bit scalar); a lax.scan over
bit-planes runs the double-and-add for ALL lanes at once on the Montgomery
limb representation from ops/bls_jax.  The per-lane products return to the
host, which finishes the (tiny) N-way sum on the oracle curve — the
O(N * 255) field work is the device's, the O(N) tail is not worth a
collective.  Multi-chip: shard the lane axis with shard_map (the scan body
is purely elementwise over lanes, so sharding is trivial).

Degenerate add cases (equal-x, infinity) are resolved branchlessly with
canonical-equality selects, so structured scalars cannot corrupt lanes.
Differential test vs the host oracle: tests/crypto/test_kzg.py.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from consensus_specs_tpu.crypto.bls.curve import Point, g1_infinity
from consensus_specs_tpu.crypto.fr import R as FR_ORDER

from .bls_jax import limbs

_N_BITS = 255


def _sel(mask, a, b):
    """mask [...] selecting between limb arrays [..., 16]."""
    return jnp.where(mask[..., None], a, b)


def _is_zero(a):
    return limbs.is_zero_canonical(limbs.canonical(a))


def _eq(a, b):
    return limbs.eq_canonical(limbs.canonical(a), limbs.canonical(b))


def _dbl(X, Y, Z):
    """Jacobian doubling (dbl-2009-l), lazy adds + renorm; Z=0 stays 0."""
    mul, rn = limbs.mul, limbs.renorm
    A = mul(X, X)
    B = mul(Y, Y)
    C = mul(B, B)
    D = rn(2 * (mul(rn(X + B), rn(X + B)) - A - C))
    E = rn(3 * A)
    F = mul(E, E)
    X3 = rn(F - 2 * D)
    Y3 = rn(mul(E, rn(D - X3)) - 8 * C)
    Z3 = rn(2 * mul(Y, Z))
    return X3, Y3, Z3


def _madd(X1, Y1, Z1, x2, y2):
    """Mixed add (madd-2007-bl) of jacobian (X1,Y1,Z1) + affine (x2,y2),
    with branchless handling of P1 = inf, equal-x double, and inverse."""
    mul, rn = limbs.mul, limbs.renorm
    Z1Z1 = mul(Z1, Z1)
    U2 = mul(x2, Z1Z1)
    S2 = mul(mul(y2, Z1), Z1Z1)
    H = rn(U2 - X1)
    HH = mul(H, H)
    I = rn(4 * HH)
    J = mul(H, I)
    r = rn(2 * (S2 - Y1))
    V = mul(X1, I)
    rr = mul(r, r)
    X3 = rn(rr - J - 2 * V)
    Y3 = rn(mul(r, rn(V - X3)) - 2 * mul(Y1, J))
    Z3 = rn(mul(rn(Z1 + H), rn(Z1 + H)) - Z1Z1 - HH)

    p1_inf = _is_zero(Z1)
    h_zero = _is_zero(H)
    r_zero = _is_zero(r)
    # equal-x, equal-y: the true result is double(P1)
    dX, dY, dZ = _dbl(X1, Y1, Z1)
    # equal-x, opposite-y: infinity (Z=0)
    zero = jnp.zeros_like(Z3)

    X3 = _sel(h_zero & r_zero, dX, _sel(h_zero & ~r_zero, X3, X3))
    Y3 = _sel(h_zero & r_zero, dY, Y3)
    Z3 = _sel(h_zero & r_zero, dZ, _sel(h_zero & ~r_zero, zero, Z3))

    one = jnp.broadcast_to(jnp.asarray(limbs.MONT_ONE_LIMBS), x2.shape)
    X3 = _sel(p1_inf, x2, X3)
    Y3 = _sel(p1_inf, y2, Y3)
    Z3 = _sel(p1_inf, one, Z3)
    return X3, Y3, Z3


# Device choice: the host CPU XLA backend by default.  The scan is int64
# limb arithmetic, which a TPU emulates on 32-bit lanes; whether the chip
# runs it faster is not measured yet (ROADMAP Speed item 3).
# CSTPU_KZG_BACKEND=tpu opts into the accelerator; a backend that does not
# exist raises.
import os as _os


def _msm_device():
    return jax.local_devices(
        backend=_os.environ.get("CSTPU_KZG_BACKEND", "cpu"))[0]


@jax.jit
def _msm_lanes(px, py, bits):
    """Per-lane scalar multiplication.

    px, py: [N, 16] affine Montgomery limbs; bits: [255, N] int32
    (MSB-first).  Returns jacobian [N, 16] triples."""
    # derive the carry from the inputs (px * 0, not jnp.zeros): under
    # shard_map the scan carry must share the inputs' varying-axes type
    X = px * 0
    Y = px * 0 + jnp.asarray(limbs.MONT_ONE_LIMBS)
    Z = px * 0  # infinity

    def step(carry, bit_row):
        X, Y, Z = carry
        X, Y, Z = _dbl(X, Y, Z)
        aX, aY, aZ = _madd(X, Y, Z, px, py)
        m = bit_row > 0
        return (_sel(m, aX, X), _sel(m, aY, Y), _sel(m, aZ, Z)), None

    (X, Y, Z), _ = jax.lax.scan(step, (X, Y, Z), bits)
    return limbs.canonical(X), limbs.canonical(Y), limbs.canonical(Z)


def _to_bits(scalars: Sequence[int]) -> np.ndarray:
    out = np.zeros((_N_BITS, len(scalars)), dtype=np.int32)
    for lane, s in enumerate(scalars):
        s %= FR_ORDER
        for b in range(_N_BITS):
            out[_N_BITS - 1 - b, lane] = (s >> b) & 1
    return out


def _points_to_limbs(points: Sequence[Point]) -> tuple:
    px = np.zeros((len(points), limbs.N_LIMBS), dtype=np.int64)
    py = np.zeros_like(px)
    for i, p in enumerate(points):
        x, y = p.to_affine()
        px[i] = limbs.host_to_mont(x.n)
        py[i] = limbs.host_to_mont(y.n)
    return px, py


def _limbs_to_points(X: np.ndarray, Y: np.ndarray, Z: np.ndarray) -> List[Point]:
    """Jacobian Montgomery limb triples -> host curve points (shared by the
    single-device and mesh-sharded lanes)."""
    from consensus_specs_tpu.crypto.bls.curve import B_G1
    from consensus_specs_tpu.crypto.bls.fields import Fq

    out = []
    for i in range(X.shape[0]):
        z = limbs.host_from_mont(Z[i])
        if z == 0:
            out.append(g1_infinity())
            continue
        out.append(Point(
            Fq(limbs.host_from_mont(X[i])),
            Fq(limbs.host_from_mont(Y[i])),
            Fq(z),
            B_G1,
        ))
    return out


def batch_scalar_mul(points: Sequence[Point], scalars: Sequence[int]) -> List[Point]:
    """[k_i * P_i] for all lanes in one device dispatch."""
    assert len(points) == len(scalars)
    px, py = _points_to_limbs(points)
    bits = _to_bits(scalars)
    dev = _msm_device()
    put = lambda a: jax.device_put(a, dev)  # noqa: E731
    X, Y, Z = (np.asarray(a) for a in _msm_lanes(put(px), put(py), put(bits)))
    return _limbs_to_points(X, Y, Z)


def msm(points: Sequence[Point], scalars: Sequence[int]) -> Point:
    """sum_i k_i * P_i: device per-lane products, host tail sum."""
    acc = g1_infinity()
    for p in batch_scalar_mul(points, scalars):
        acc = acc + p
    return acc


# --- mesh-sharded lane (the TP axis of SURVEY §2.7: one large MSM split
# over cores) ----------------------------------------------------------------


# jitted shard_map wrappers cached per (mesh, axis): jit keys on callable
# identity, so rebuilding the wrapper per call would recompile the 255-step
# scan every time
_SHARDED_MSM_CACHE: dict = {}


def _sharded_msm_fn(mesh, axis: str):
    key = (mesh, axis)
    fn = _SHARDED_MSM_CACHE.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        fn = jax.jit(jax.shard_map(
            _msm_lanes,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(None, axis)),
            out_specs=(P(axis), P(axis), P(axis)),
        ))
        _SHARDED_MSM_CACHE[key] = fn
    return fn


def sharded_batch_scalar_mul(mesh, points: Sequence[Point],
                             scalars: Sequence[int],
                             axis: str = "v") -> List[Point]:
    """[k_i * P_i] with the lane axis sharded over a device mesh.

    The scan body is purely elementwise over lanes, so the shard_map needs
    no collectives — each device runs its lanes' double-and-add chains;
    the host gathers and tail-sums.  Lane count must divide by the mesh
    size.  Bit-exact vs batch_scalar_mul/host (tests/test_sharded_lanes.py;
    executed in the driver's multichip dryrun)."""
    assert len(points) == len(scalars)
    D = int(np.prod(mesh.devices.shape))
    assert len(points) % D == 0, f"{len(points)} lanes over {D} devices"
    px, py = _points_to_limbs(points)
    bits = _to_bits(scalars)
    X, Y, Z = (np.asarray(a) for a in _sharded_msm_fn(mesh, axis)(px, py, bits))
    return _limbs_to_points(X, Y, Z)


def sharded_msm(mesh, points: Sequence[Point], scalars: Sequence[int]) -> Point:
    """Mesh-sharded MSM: per-device lane products + host tail sum."""
    acc = g1_infinity()
    for p in sharded_batch_scalar_mul(mesh, points, scalars):
        acc = acc + p
    return acc
