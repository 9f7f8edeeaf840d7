"""DCN dryrun: the sharded kernels over a mesh SPANNING TWO PROCESSES.

Round-3 verdict item 7: `docs/multihost.md` designed the jax.distributed
deployment but nothing ever initialized it — cross-host was a claim.  This
tool converts it into a demonstrated capability on localhost: two OS
processes, each owning 4 virtual CPU devices, joined by
``jax.distributed.initialize`` into one 8-device mesh.  XLA routes the
same collectives the single-process dryrun exercises (psum, all_gather)
across the process boundary — exactly the ICI/DCN split a real multi-host
pod sees, minus the wire.

Three programs run over the spanning mesh, each cross-checked bit-for-bit
against a host oracle computed independently in both processes:

  1. the sharded epoch step (validator-axis DP: psum attesting balances,
     all_gather proposer credits) — `parallel/epoch_sharded.py`, the SAME
     code the single-process dryrun jits;
  2. sharded merkleization (chunk-axis TP): per-shard subtree roots on
     device, 32-byte roots allgathered across processes, host top fold ==
     SSZ root;
  3. the four-step DAS NTT (chunk axis) == host Fr oracle.

Usage:  python tools/dcn_dryrun.py           (parent: spawns 2 workers)
        writes DCN_DRYRUN.json {ok, n_processes, n_devices, checks}
CI hook: tests/test_dcn_dryrun.py runs this end-to-end.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_PROC = 2
DEV_PER_PROC = 4


# --------------------------------------------------------------------------
# worker
# --------------------------------------------------------------------------

def worker(process_id: int, port: int) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=N_PROC,
        process_id=process_id,
    )
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert jax.process_count() == N_PROC
    assert len(jax.local_devices()) == DEV_PER_PROC
    assert len(jax.devices()) == N_PROC * DEV_PER_PROC

    from consensus_specs_tpu.parallel import build_mesh

    mesh = build_mesh(N_PROC * DEV_PER_PROC, devices=jax.devices())
    sharding = NamedSharding(mesh, P("v"))
    checks = {}

    # ---- 1. sharded epoch step across the process boundary ----
    sys.path.insert(0, REPO)
    import __graft_entry__ as graft
    from consensus_specs_tpu.parallel.epoch_sharded import (
        make_sharded_epoch_step,
        shard_delta_inputs,
    )

    n = 8 * N_PROC * DEV_PER_PROC * 2
    inp, balances = graft._example_inputs(n)
    step = make_sharded_epoch_step(mesh)
    args, n_orig = shard_delta_inputs(mesh, inp, balances)
    new_balances, digests = step(*args)
    new_balances.block_until_ready()

    # oracle: single-device kernel, computed identically in each process
    from consensus_specs_tpu.ops.epoch_jax import attestation_deltas

    rewards, penalties = attestation_deltas(inp)
    expected = balances + rewards
    expected = np.where(penalties > expected, 0, expected - penalties)

    # each process can read only its addressable shards; compare those
    # against the matching slice of the oracle, then AND across processes
    local_ok = True
    for shard in new_balances.addressable_shards:
        start = shard.index[0].start or 0
        got = np.asarray(shard.data)
        want = expected[start:start + got.shape[0]]
        if got.shape[0] > want.shape[0]:  # padding tail
            got = got[:want.shape[0]]
        local_ok &= bool(np.array_equal(got, want))
    from jax.experimental import multihost_utils

    all_ok = multihost_utils.process_allgather(
        np.array([local_ok], dtype=np.bool_))
    checks["epoch_step_bitexact"] = bool(all_ok.all())

    # ---- 2. sharded merkleization: device subtrees, DCN root exchange ----
    from consensus_specs_tpu.parallel.merkle_sharded import (
        _words_to_bytes,
        make_sharded_subtree_roots,
    )
    from consensus_specs_tpu.ssz.types import List, uint64
    import hashlib

    vals = expected[:n]  # the epoch step's output, recomputed on host
    n_dev = N_PROC * DEV_PER_PROC
    per_shard = 8
    while per_shard * n_dev < n:
        per_shard *= 2
    padded = np.zeros(per_shard * n_dev, dtype=np.int64)
    padded[:n] = vals
    roots_arr = make_sharded_subtree_roots(mesh)(
        jax.device_put(padded, sharding))
    roots_arr.block_until_ready()
    # only the 32-byte per-shard roots cross the process boundary
    gathered = multihost_utils.process_allgather(
        np.stack([np.asarray(s.data)[0] for s in
                  sorted(roots_arr.addressable_shards,
                         key=lambda s: s.index[0].start or 0)]))
    gathered = gathered.reshape(n_dev, 8)
    level = [_words_to_bytes(gathered[i]) for i in range(n_dev)]
    while len(level) > 1:
        level = [hashlib.sha256(level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    # fold up to the SSZ limit depth + mix in length (host, both procs)
    limit = 2**40
    limit_chunks = (limit * 8 + 31) // 32
    depth = max((limit_chunks - 1).bit_length(), 0)
    from consensus_specs_tpu.ssz.node import ZERO_HASHES

    node = level[0]
    cur = max((per_shard * n_dev // 4 - 1).bit_length(), 0)
    for d in range(cur, depth):
        node = hashlib.sha256(node + ZERO_HASHES[d]).digest()
    root = hashlib.sha256(node + n.to_bytes(32, "little")).digest()
    ssz_root = bytes(List[uint64, limit]([int(x) for x in vals]).hash_tree_root())
    checks["merkle_root_matches_ssz"] = bool(root == ssz_root)

    # ---- 3. sharded DAS NTT over the spanning mesh ----
    from consensus_specs_tpu.crypto import fr
    from consensus_specs_tpu.ops import fr_jax

    m = 16 * n_dev  # power-of-two total, chunk axis across both processes
    vals_fr = [(i * 0x9E3779B9 + 7) % fr.R for i in range(m)]
    host = fr.fft(vals_fr)
    # sharded_ntt materializes the gathered result (replicated out-spec),
    # which is addressable in every process
    got = fr_jax.sharded_ntt(vals_fr, mesh)
    checks["das_ntt_matches_host_oracle"] = bool(list(got) == list(host))

    ok = all(checks.values())
    if process_id == 0:
        print(json.dumps({"checks": checks, "ok": ok}), flush=True)
    assert ok, f"DCN dryrun checks failed: {checks}"


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------

def main() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={DEV_PER_PROC}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    # pick a free coordinator port so concurrent runs on one host can't
    # collide or cross-join each other's cluster
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", str(i),
             str(port)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(N_PROC)
    ]
    outs = []
    deadline = time.time() + 600
    for p in procs:
        try:
            out, err = p.communicate(timeout=max(10.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
        outs.append((p.returncode, out, err))

    ok = all(rc == 0 for rc, _, _ in outs)
    checks = {}
    for rc, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("{"):
                checks = json.loads(line).get("checks", checks)
    report = {
        "ok": ok,
        "n_processes": N_PROC,
        "devices_per_process": DEV_PER_PROC,
        "n_devices": N_PROC * DEV_PER_PROC,
        "checks": checks,
        "rc": [rc for rc, _, _ in outs],
    }
    if not ok:
        report["stderr_tail"] = [err[-2000:] for _, _, err in outs]
    with open(os.path.join(REPO, "DCN_DRYRUN.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--worker":
        worker(int(sys.argv[2]), int(sys.argv[3]))
        sys.exit(0)
    report = main()
    sys.exit(0 if report["ok"] else 1)
