"""Achieved-vs-peak (MFU-style) accounting for the device benchmark rows.

Absolute throughputs ("191k muls/s") say nothing about how much of the
chip they use.  This module attaches, to every device row in the bench
details, (a) the peak of the chip for that row's op mix, (b) the achieved
fraction, and (c) a note on what bounds it.  The op-mix models are static
counts derived from the kernels' own structure; each is documented inline
so a reviewer can re-derive them.

Peaks are keyed by the ``device_kind`` JAX reports, each with its source.
A device kind missing from the table is an error, never a default: a row
scored against another chip's peak is wrong.  Rows from a host run
(``JAX_PLATFORMS=cpu``) are not scored at all (bench.py calls ``annotate``
on a TPU only).
"""
from __future__ import annotations

import json
import os

# Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
# v5e"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The VPU
# int32 figure is an estimate (8 ops/cycle x 8x128 lanes x ~0.94 GHz x 4
# subcores); Google does not publish a VPU peak.
PEAKS = {
    "TPU v5 lite": {  # what JAX reports for a v5e (chip_smoke.py, PR 21)
        "source": "Google Cloud documentation, TPU v5e; VPU int32 estimated",
        "mxu_int8_ops_s": 393e12,
        "mxu_bf16_flops_s": 197e12,
        "vpu_int32_ops_s": 4.0e12,
        "hbm_bytes_s": 819e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table for device kind {device_kind!r}; add it to "
            f"tools/mfu.py PEAKS with its source") from None


# --- op-mix models ---------------------------------------------------------

# SHA-256 compression of one 64-byte block on the VPU (ops/sha256_jax.py):
# 48 schedule steps (~10 uint32 ALU ops: 2 sigmas at 3 ops + 3 adds) plus
# 64 rounds (~12 ops: 2 sigmas, ch, maj, 7 adds) ~= 1250 uint32 ops.
SHA256_OPS_PER_BLOCK = 1250
SHA256_BYTES_PER_BLOCK = 64 + 32  # read two child digests, write one

# MXU int8 Montgomery Fq multiply (ops/bls_jax/mxu_probe.py): one im2col
# conv [64]x[64]->128 (8192 MACs) + t_low*N0INV Toeplitz [N,64]x[64,64]
# (4096 MACs) + m*P Toeplitz [N,64]x[64,129] (8256 MACs) ~= 20.5k MACs
# = 41k int8 ops per 381-bit multiply.
MXU_OPS_PER_FQ_MUL = 41_000

# Vectorized epoch deltas kernel (ops/epoch_jax.py): per validator ~37
# bytes read (eff 8, five flags 5, delay 8, proposer 8, balance 8), 8
# written; ~40 int64 ALU ops (3 component deltas + inclusion + leak).
EPOCH_BYTES_PER_VALIDATOR = 45
EPOCH_OPS_PER_VALIDATOR = 40

# Device pairing batch (ops/bls_jax/pairing.py), per item: 2 Miller loops
# sharing the squaring chain + 1/B of a shared final exponentiation
# ~= 1.2e4 Fq muls; each Fq mul is a lazy 16x16 limb conv (~512 MACs) plus
# renormalization ~= 600 int64 ops -> ~7e6 int64 ALU ops per verification.
PAIRING_OPS_PER_VERIFY = 7e6


def _frac(achieved, peak):
    return round(achieved / peak, 6) if peak else None


def _mfu(peaks, achieved_ops_s, peak_key, bytes_s=None, note=""):
    out = {
        "peak_basis": peak_key,
        "peak_ops_s": peaks[peak_key],
        "achieved_ops_s": round(achieved_ops_s, 1),
        "achieved_fraction": _frac(achieved_ops_s, peaks[peak_key]),
    }
    if bytes_s is not None:
        out["hbm_bytes_s"] = round(bytes_s, 1)
        out["hbm_fraction"] = _frac(bytes_s, peaks["hbm_bytes_s"])
    if note:
        out["binding_limit"] = note
    return out


def annotate(details: dict, device_kind: str) -> dict:
    """Attach an ``mfu`` sub-dict to every device row of a run on a chip of
    ``device_kind`` (unknown kinds raise, see ``peaks_for``)."""
    peaks = peaks_for(device_kind)

    def attach(row_key: str, mfu: dict):
        row = details.get(row_key)
        if isinstance(row, dict):
            row["mfu"] = mfu

    # config 4: full-state root with balances dirty, device path.  Work =
    # one SHA-256 block per branch node of the 2^ceil(log2(N/4))-chunk
    # subtree (+ spine, negligible).
    r = details.get("hash_tree_root_state", {})
    n = details.get("_load_context", {}).get("bench_validators", 400_000)
    chunks = max((n + 3) // 4, 1)
    n_chunks = 1 << (chunks - 1).bit_length() if chunks > 1 else 1
    blocks = n_chunks  # ~n_chunks-1 branches + spine
    t = r.get("jax_resident")
    if t:
        ops_s = blocks * SHA256_OPS_PER_BLOCK / t
        attach("hash_tree_root_state", _mfu(
            peaks, ops_s, "vpu_int32_ops_s",
            bytes_s=blocks * SHA256_BYTES_PER_BLOCK / t,
            note=("one device program per reduction; the 32-byte root "
                  "and the per-call dispatch are host round trips")))

    # configs 2+3: device pairing batches
    for key in ("sync_aggregate_512", "attestation_batch"):
        r = details.get(key, {})
        v = r.get("device_jax")
        if v:
            attach(key, _mfu(
                peaks, v * PAIRING_OPS_PER_VERIFY, "vpu_int32_ops_s",
                note=("int64 limb lanes, emulated on 32-bit VPU lanes")))

    # north star kernel: memory-bound elementwise pass
    r = details.get("north_star_epoch", {})
    t = r.get("value")
    if t:
        nv = details.get("_load_context", {}).get("bench_validators", 400_000)
        attach("north_star_epoch", _mfu(
            peaks, nv * EPOCH_OPS_PER_VALIDATOR / t, "vpu_int32_ops_s",
            bytes_s=nv * EPOCH_BYTES_PER_VALIDATOR / t,
            note=("the kernel touches ~45 B and ~40 int64 ops per "
                  "validator; the row's seconds include the host's "
                  "committee flattening and tree rebuilds")))
    return details


def annotate_limb_probe(probe: dict, device_kind: str) -> dict:
    """LIMB_PROBE.json: the MXU int8 Montgomery-multiply probe.  Called by
    tools/limb_probe_bench.py before it writes the artifact, so the
    accounting regenerates with every probe run."""
    peaks = peaks_for(device_kind)
    muls_s = probe.get("mxu_mulls_per_s")
    if muls_s:
        achieved = muls_s * MXU_OPS_PER_FQ_MUL
        roofline_muls = peaks["mxu_int8_ops_s"] / MXU_OPS_PER_FQ_MUL
        probe["mxu_mfu"] = _mfu(
            peaks, achieved, "mxu_int8_ops_s",
            note=(f"{MXU_OPS_PER_FQ_MUL / 1e3:.0f}k int8 ops per mul; the "
                  f"op mix could sustain ~{roofline_muls:.1e} muls/s "
                  f"compute-bound on a {probe.get('batch', '?')}-lane "
                  f"batch"))
    return probe


def main():
    """Annotate the bench's details file in place, against the device
    kind the run recorded (bench.py writes ``_device``)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    dpath = os.path.join(repo, "BENCH_DETAILS.json")
    with open(dpath) as f:
        details = json.load(f)
    annotate(details, details["_device"]["kind"])
    with open(dpath, "w") as f:
        json.dump(details, f, indent=2)
    print("MFU annotations attached")


if __name__ == "__main__":
    main()
