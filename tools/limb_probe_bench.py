"""Race the Fq-multiply radices on the real chip (VERDICT item 8).

Measures steady-state batched Montgomery-multiply throughput for:
  * 26-bit limbs in int64 lanes (the shipping bls_jax design), and
  * 13-bit limbs in int32 lanes (the densest radix whose schoolbook
    accumulation fits a 32-bit accumulator; "16-bit products in int32"
    is arithmetically impossible — a 16x16 product is already 32 bits).

Also splits the int64 path into upload / compute / download so the pairing
loss can be attributed.  Writes LIMB_PROBE.json and prints it.
"""
from __future__ import annotations

import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from consensus_specs_tpu.ops.bls_jax import limb_probe, limbs

BATCH = 4096
ROUNDS = 8       # int64 chain length (graph stays small)
ROUNDS32 = 1     # the int32 kernel's interleaved-carry trace is ~25x larger
                 # per mul; a chained graph fails to compile over this link
                 # in reasonable time — itself part of the measured finding


def _chain64(a, b):
    for _ in range(ROUNDS):
        a = limbs.mul(a, b)
    return a


def _single64(a, b):
    return limbs.mul(a, b)


def _chain32(a, b):
    for _ in range(ROUNDS32):
        a = limb_probe.mul32(a, b)
    return a


def main() -> None:
    rng = np.random.default_rng(11)
    vals_a = [int(x) ** 7 % limbs.P_INT for x in rng.integers(2, 2**63, BATCH)]
    vals_b = [int(x) ** 7 % limbs.P_INT for x in rng.integers(2, 2**63, BATCH)]

    report = {"batch": BATCH, "chained_muls_per_dispatch": ROUNDS,
              "device": str(jax.devices()[0])}
    print("starting int64 leg", flush=True)

    # -- int64 / 26-bit limbs
    a64 = np.stack([limbs.host_to_mont(v) for v in vals_a])
    b64 = np.stack([limbs.host_to_mont(v) for v in vals_b])
    t0 = time.perf_counter()
    da, db = jnp.asarray(a64), jnp.asarray(b64)
    da.block_until_ready()
    report["int64_upload_s"] = round(time.perf_counter() - t0, 4)
    fn64 = jax.jit(_chain64)
    t0 = time.perf_counter()
    out = fn64(da, db)
    out.block_until_ready()
    report["int64_cold_s"] = round(time.perf_counter() - t0, 3)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn64(da, db)
        out.block_until_ready()
        t = time.perf_counter() - t0
        best = t if best is None else min(best, t)
    report["int64_warm_s"] = round(best, 4)
    report["int64_mulls_per_s"] = round(BATCH * ROUNDS / best)
    t0 = time.perf_counter()
    np.asarray(out)
    report["int64_download_s"] = round(time.perf_counter() - t0, 4)
    # sanity: the chain result decodes to a field element
    assert 0 <= limbs.host_from_mont(np.asarray(out)[0]) < limbs.P_INT

    # single-mul dispatch row: apples-to-apples with the int32 leg
    fn64s = jax.jit(_single64)
    fn64s(da, db).block_until_ready()
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        fn64s(da, db).block_until_ready()
        t = time.perf_counter() - t0
        best = t if best is None else min(best, t)
    report["int64_single_mul_dispatch_s"] = round(best, 4)
    report["int64_single_mulls_per_s"] = round(BATCH / best)
    print("int64 leg done:", report["int64_warm_s"], flush=True)

    # -- int32 / 13-bit limbs
    a32 = np.stack([limb_probe.host_to_mont32(v) for v in vals_a])
    b32 = np.stack([limb_probe.host_to_mont32(v) for v in vals_b])
    da, db = jnp.asarray(a32), jnp.asarray(b32)
    fn32 = jax.jit(_chain32)
    t0 = time.perf_counter()
    out = fn32(da, db)
    out.block_until_ready()
    report["int32_cold_s"] = round(time.perf_counter() - t0, 3)
    print("int32 cold done:", report["int32_cold_s"], flush=True)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn32(da, db)
        out.block_until_ready()
        t = time.perf_counter() - t0
        best = t if best is None else min(best, t)
    report["int32_warm_s"] = round(best, 4)
    report["int32_mulls_per_s"] = round(BATCH * ROUNDS32 / best)
    # correctness of the raced kernel: same product both radices
    report["int32_spot_check_ok"] = bool(
        limb_probe.host_from_mont32(np.asarray(out)[0]) ==
        (limbs.host_from_mont(a64[0]) * limbs.host_from_mont(b64[0])) % limbs.P_INT)

    report["int32_vs_int64_chained"] = round(
        report["int32_mulls_per_s"] / report["int64_mulls_per_s"], 3)
    report["int32_vs_int64_single_dispatch"] = round(
        report["int32_mulls_per_s"] / report["int64_single_mulls_per_s"], 3)

    # -- MXU / int8 6-bit limbs (round-4 VERDICT item 3)
    try:
        _mxu_leg(report, vals_a, vals_b)
    except Exception as exc:  # probe resilience: record, don't lose the rest
        report["mxu_error"] = repr(exc)[:300]

    if jax.devices()[0].platform == "tpu":
        # achieved-vs-peak accounting regenerates with every chip run
        import mfu

        mfu.annotate_limb_probe(report, jax.devices()[0].device_kind)

    with open("LIMB_PROBE.json", "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))


MXU_ROUNDS = 4  # chain length: the scan tail keeps traces moderate


def _chain_mxu(a, b):
    from consensus_specs_tpu.ops.bls_jax import mxu_probe

    for _ in range(MXU_ROUNDS):
        a = mxu_probe.mxu_mont_mul(a, b)
    return a


def _mxu_leg(report, vals_a, vals_b) -> None:
    """Race the int8/MXU phrasing: the a*b im2col conv plus two genuinely
    MXU-shaped fixed-Toeplitz matmuls (t_low*N0INV and m*P), with one
    exact carry scan per multiply."""
    from consensus_specs_tpu.ops.bls_jax import mxu_probe

    print("starting mxu leg", flush=True)
    a8 = np.stack([mxu_probe.host_to_mont(v) for v in vals_a])
    b8 = np.stack([mxu_probe.host_to_mont(v) for v in vals_b])
    da = jnp.asarray(a8, dtype=jnp.int8)
    db = jnp.asarray(b8, dtype=jnp.int8)

    fn = jax.jit(_chain_mxu)
    t0 = time.perf_counter()
    out = fn(da, db)
    out.block_until_ready()
    report["mxu_cold_s"] = round(time.perf_counter() - t0, 3)
    print("mxu cold done:", report["mxu_cold_s"], flush=True)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(da, db)
        out.block_until_ready()
        t = time.perf_counter() - t0
        best = t if best is None else min(best, t)
    report["mxu_chain_rounds"] = MXU_ROUNDS
    report["mxu_warm_s"] = round(best, 4)
    report["mxu_mulls_per_s"] = round(BATCH * MXU_ROUNDS / best)

    # single-dispatch row
    fns = jax.jit(mxu_probe.mxu_mont_mul)
    fns(da, db).block_until_ready()
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        fns(da, db).block_until_ready()
        t = time.perf_counter() - t0
        best = t if best is None else min(best, t)
    report["mxu_single_mul_dispatch_s"] = round(best, 4)
    report["mxu_single_mulls_per_s"] = round(BATCH / best)

    # correctness of the raced kernel against python ints
    got = mxu_probe.host_from_mont(np.asarray(out)[0]) % mxu_probe.P_INT
    want = vals_a[0]
    for _ in range(MXU_ROUNDS):
        want = want * vals_b[0] % mxu_probe.P_INT
    report["mxu_spot_check_ok"] = bool(got == want)
    report["mxu_vs_int64_chained"] = round(
        report["mxu_mulls_per_s"] / report["int64_mulls_per_s"], 3)


if __name__ == "__main__":
    main()
