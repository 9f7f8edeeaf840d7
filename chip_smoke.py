"""Chip smoke test: the executable spec's main path on one TPU, at mainnet
width (400,000 validators, phase0, mainnet preset).

    python chip_smoke.py             # one chip: phases a-f
    python chip_smoke.py --chips 4   # four chips: the mesh-sharded epoch
                                     # step and sharded merkle root only

Everything runs in this one process, which owns the chip; nothing here
starts another process that touches JAX.  The script reads nothing outside
the repository.  Each phase prints one line with its cold wall seconds
(compile included) and its warm wall seconds, the seconds XLA spent
compiling, and the device it ran on.  Any failed check raises, so the
script exits non-zero and never prints the last line; on success the last
line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (one chip):
  a. device      — a TPU, or exit 2 before any other work
  b. state       — 400k-validator phase0 mainnet state with real pubkeys,
                   through the checkpoint-sync seam (.bench_cache/)
  c. epoch       — spec.process_epoch through the fused epoch+balances
                   merkle program on the chip, byte-equal to the host path;
                   one slot advance through the resident slot root
  d. blocks      — signed mainnet blocks, BLS on, across an epoch
                   boundary, through stf.apply_signed_blocks; no literal
                   replay, root equal to spec.state_transition on the host
  e. device BLS  — ops/bls_jax batch verification of 8 aggregates (one
                   invalid), verdicts equal to the native backend's
  f. hashing     — balances and registry roots through the compiled
                   Pallas kernel and the wave-schedule program, equal to
                   hashlib
"""
import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
N_VALIDATORS = 400_000
N_BLS_ITEMS = 8


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def _require_tpu(devices, count: int) -> None:
    if devices[0].platform != "tpu":
        _fail(f"JAX's default device is {devices[0].platform} "
              f"({devices[0].device_kind}), not a TPU")
    if len(devices) < count:
        _fail(f"{count} chips wanted, {len(devices)} found")


# -- per-phase timing ----------------------------------------------------------

# seconds summed over the run: XLA backend compiles, cold and warm passes
_clock = {"compile": 0.0, "cold": 0.0, "warm": 0.0}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _clock["compile"] += duration


def _timed(fn):
    """(seconds, XLA compile seconds, result) of ``fn()``."""
    c0 = _clock["compile"]
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, _clock["compile"] - c0, out


def _report(phase: str, cold, warm, device: str, extra: str = "") -> None:
    _clock["cold"] += cold[0]
    _clock["warm"] += warm[0]
    print(f"phase {phase}: cold {cold[0]:.3f} s (compile {cold[1]:.3f} s) "
          f"warm {warm[0]:.3f} s (compile {warm[1]:.3f} s) "
          f"device {device}{extra}", flush=True)


@contextlib.contextmanager
def _resident(mode: str):
    """CSTPU_RESIDENT_MERKLE for the block: '1' the fused/resident path on
    the default device, '0' the host numpy/hashlib path."""
    prev = os.environ.get("CSTPU_RESIDENT_MERKLE")
    os.environ["CSTPU_RESIDENT_MERKLE"] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("CSTPU_RESIDENT_MERKLE", None)
        else:
            os.environ["CSTPU_RESIDENT_MERKLE"] = prev


def _dev(d) -> str:
    return f"{d.platform}:{d.id} ({d.device_kind})"


# -- phase b: state ------------------------------------------------------------

def phase_state(spec, n):
    import bench
    from consensus_specs_tpu.crypto import bls

    bls.use_fastest()
    assert bls.backend_name() == "native", (
        f"host BLS is {bls.backend_name()!r}, not the native backend")

    def build():
        _, state = bench._state_through_snapshot(spec, n)
        bench._install_real_pubkeys(spec, state, n)
        return state

    cold = _timed(build)
    warm = _timed(build)
    state = warm[2]
    assert len(state.validators) == n and int(state.slot) == 2 * int(
        spec.SLOTS_PER_EPOCH)
    _report("b state", cold, warm, "host",
            f"; {n} validators, preset {spec.preset_name}, bls native")
    return state


# -- phase c: epoch on the chip ------------------------------------------------

def phase_epoch(spec, pre, device):
    from consensus_specs_tpu import tracing
    from consensus_specs_tpu.ops import merkle_resident
    from consensus_specs_tpu.ssz import bulk
    from consensus_specs_tpu.stf import slot_roots

    def fused_epoch():
        state = pre.copy()
        before = merkle_resident.stats["fused_epoch_updates"]
        with _resident("auto"):
            spec.process_epoch(state)
        assert merkle_resident.stats["fused_epoch_updates"] == before + 1, \
            "the fused epoch program did not engage"
        return state

    cold = _timed(fused_epoch)
    warm = _timed(fused_epoch)

    def host_epoch():
        state = pre.copy()
        spec.process_epoch(state)
        return state

    with _resident("0"):
        host = _timed(host_epoch)
    host_state = host[2]
    host_root = bytes(spec.hash_tree_root(host_state))
    chip_roots = {bytes(spec.hash_tree_root(s)) for s in (cold[2], warm[2])}
    assert chip_roots == {host_root}, "chip epoch root != host epoch root"
    _report("c epoch", cold, warm, _dev(device),
            f"; host path {host[0]:.3f} s; root 0x{host_root.hex()[:16]} "
            f"equal to host")

    # one slot advance over freshly bulk-written balances: the per-slot
    # state root reduces the balances subtree on the chip
    post_balances = bulk.packed_uint64_to_numpy(host_state.balances)

    def fresh():
        state = pre.copy()
        bulk.set_packed_uint64_from_numpy(state.balances, post_balances)
        return state

    def advance():
        state = fresh()
        before = tracing.report()["counters"].get("stf.resident_slot_root", 0)
        with _resident("auto"):
            slot_roots.process_slots(spec, state, state.slot + 1)
        after = tracing.report()["counters"]
        assert after.get("stf.resident_slot_root", 0) == before + 1, \
            "the resident slot root did not engage"
        assert after.get("stf.resident_slot_root_failed", 0) == 0
        return state

    cold = _timed(advance)
    warm = _timed(advance)
    with _resident("0"):
        ref = fresh()
        slot_roots.process_slots(spec, ref, ref.slot + 1)
        ref_root = bytes(spec.hash_tree_root(ref))
    assert bytes(spec.hash_tree_root(cold[2])) == ref_root
    assert bytes(spec.hash_tree_root(warm[2])) == ref_root
    counters = tracing.report()["counters"]
    _report("c slot", cold, warm, _dev(device),
            f"; stf.resident_slot_root="
            f"{counters.get('stf.resident_slot_root', 0)} "
            f"stf.resident_slot_root_failed="
            f"{counters.get('stf.resident_slot_root_failed', 0)}; "
            f"root equal to host")


# -- phase d: signed blocks, BLS on ---------------------------------------------

def phase_blocks(spec, pre, device):
    import bench
    from consensus_specs_tpu import stf
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.ops import merkle_resident

    spe = int(spec.SLOTS_PER_EPOCH)
    base = pre.copy()
    # three blocks at the last two slots of the epoch and the first of the
    # next: two carry full attestation sets (every committee of the two
    # slots before them), and the third crosses the epoch boundary
    spec.process_slots(base, (int(base.slot) // spe + 1) * spe - 3)
    t0 = time.perf_counter()
    blocks = bench._build_epoch_blocks(spec, base, n_slots=3)
    t_build = time.perf_counter() - t0
    n_atts = [len(b.message.body.attestations) for b in blocks]
    full = int(spec.get_committee_count_per_slot(
        base, spec.get_current_epoch(base))) * 2
    assert n_atts[:2] == [full, full], n_atts
    bls.bls_active = True

    def apply():
        state = base.copy()
        stf.reset_stats()
        fused = merkle_resident.stats["fused_epoch_updates"]
        with _resident("auto"):
            stf.apply_signed_blocks(spec, state, blocks, True)
        assert stf.stats["fast_blocks"] == len(blocks), stf.stats
        assert stf.stats["replayed_blocks"] == 0, stf.stats["replay_reasons"]
        assert merkle_resident.stats["fused_epoch_updates"] == fused + 1, \
            "the fused epoch program did not run inside the blocks"
        return state

    cold = _timed(apply)
    warm = _timed(apply)
    with _resident("0"):
        ref = base.copy()
        t0 = time.perf_counter()
        for sb in blocks:
            spec.state_transition(ref, sb, True)
        t_spec = time.perf_counter() - t0
        ref_root = bytes(spec.hash_tree_root(ref))
    for state in (cold[2], warm[2]):
        assert bytes(spec.hash_tree_root(state)) == ref_root, \
            "engine post-state != spec.state_transition on the host"
    assert int(cold[2].slot) % spe == 0  # the epoch boundary was crossed
    _report("d blocks", cold, warm, _dev(device),
            f"; {len(blocks)} blocks at slots "
            f"{[int(b.message.slot) for b in blocks]}, attestations {n_atts}, "
            f"build {t_build:.3f} s, fast_blocks {len(blocks)}, "
            f"replayed_blocks 0, spec.state_transition {t_spec:.3f} s, "
            f"root 0x{ref_root.hex()[:16]} equal to spec")


# -- phase e: device BLS ------------------------------------------------------------

def _bls_items(state, n_items):
    import bench

    pks, msgs, sigs = [], [], []
    for i in range(n_items):
        members = [(97 * i + 13 * j) % len(state.validators) for j in range(4)]
        msg = bytes([i + 1]) * 32
        pks.append([bytes(state.validators[m].pubkey) for m in members])
        sigs.append(bench._aggregate_sign(
            [bench._sk_for(m) for m in members], msg))
        msgs.append(msg)
    msgs[-1] = b"\xee" * 32  # the signature no longer matches: invalid
    return pks, msgs, sigs


def phase_device_bls(state, device):
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.crypto.bls import native
    from consensus_specs_tpu.ops import bls_jax

    pks, msgs, sigs = _bls_items(state, N_BLS_ITEMS)
    want = [native.FastAggregateVerify(p, m, s)  # noqa: ST01 oracle
            for p, m, s in zip(pks, msgs, sigs)]
    assert want == [True] * (N_BLS_ITEMS - 1) + [False], want
    bls.use_jax()
    try:
        assert bls.backend_name() == "jax"
        cold = _timed(lambda: bls_jax.batch_fast_aggregate_verify(
            pks, msgs, sigs))
        warm = _timed(lambda: bls_jax.batch_fast_aggregate_verify(
            pks, msgs, sigs))
    finally:
        bls.use_fastest()
    for got in (cold[2], warm[2]):
        assert got == want, f"device verdicts {got} != native {want}"
    print(f"pairing compile: {cold[1]:.3f} s (batch {N_BLS_ITEMS})",
          flush=True)
    _report("e device-bls", cold, warm, _dev(device),
            f"; {N_BLS_ITEMS} items, verdicts {cold[2]} equal to native")


# -- phase f: Pallas and wave-schedule hashing --------------------------------------

def fresh_balances(spec, values):
    """A balances list view over ``values`` whose branch nodes are all
    unhashed (nothing memoized from earlier hashing)."""
    from consensus_specs_tpu.ssz.node import (
        BranchNode,
        pack_chunks,
        subtree_fill_to_contents,
        uint_to_leaf,
    )

    typ = type(spec.BeaconState().balances)
    data = np.ascontiguousarray(values, dtype="<u8").tobytes()
    contents = subtree_fill_to_contents(pack_chunks(data),
                                        typ.contents_depth())
    return typ.view_from_backing(BranchNode(contents, uint_to_leaf(len(values))))


def _pallas_differential():
    """Single and multi lane-tile batches, the merkle parent, the empty
    layer and a small list root, through the compiled kernel."""
    import hashlib
    import random

    from consensus_specs_tpu.ops import sha256_pallas
    from consensus_specs_tpu.ssz import hashing
    from consensus_specs_tpu.ssz.types import List, uint64

    rng = random.Random(9)
    for n in (1, 127, 129):
        msgs = [bytes(rng.getrandbits(8) for _ in range(64)) for _ in range(n)]
        got = sha256_pallas.hash_layer(msgs)
        assert got == [hashlib.sha256(m).digest() for m in msgs], n
    left, right = hashlib.sha256(b"left").digest(), hashlib.sha256(b"right").digest()
    assert sha256_pallas.hash_layer([left + right]) == [
        hashlib.sha256(left + right).digest()]
    assert sha256_pallas.hash_layer([]) == []
    expected = List[uint64, 2**40](list(range(1500))).hash_tree_root()
    hashing.set_backend("pallas")
    try:
        assert List[uint64, 2**40](list(range(1500))).hash_tree_root() == expected
    finally:
        hashing.set_backend("hashlib")


def _assert_pallas_compiled():
    import jax
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import sha256_pallas

    assert sha256_pallas._use_interpret() is False, "Pallas would interpret"
    lowered = sha256_pallas._block64_t_jit.lower(
        jax.ShapeDtypeStruct((16, 65536), jnp.uint32)).as_text()
    assert "tpu_custom_call" in lowered, "no Mosaic kernel in the lowering"


def phase_hashing(spec, state, device):
    from consensus_specs_tpu.ssz import bulk, hashing

    _assert_pallas_compiled()
    _pallas_differential()

    import bench

    values = bulk.packed_uint64_to_numpy(state.balances)
    n = len(state.validators)
    makers = {"balances": lambda: fresh_balances(spec, values),
              "registry": lambda: bench.real_pubkey_registry(spec, n)}
    want = {what: bytes(make().hash_tree_root())
            for what, make in makers.items()}
    for backend in ("pallas", "jax"):
        for what, make in makers.items():
            def root(view):
                hashing.set_backend(backend)
                try:
                    return bytes(view.hash_tree_root())
                finally:
                    hashing.set_backend("hashlib")

            view = make()
            cold = _timed(lambda: root(view))
            view = make()
            warm = _timed(lambda: root(view))
            assert cold[2] == want[what] and warm[2] == want[what], \
                f"{backend} {what} root != hashlib"
            _report(f"f {backend}-{what}", cold, warm, _dev(device),
                    f"; root 0x{want[what].hex()[:16]} equal to hashlib")
    print("pallas: compiled (interpret False, tpu_custom_call in lowering)",
          flush=True)


# -- --chips 4: the mesh-sharded epoch step ------------------------------------------

def four_chips(devices, n):
    import jax

    from __graft_entry__ import _example_inputs
    from consensus_specs_tpu.ops.epoch_jax import attestation_deltas
    from consensus_specs_tpu.parallel import build_mesh
    from consensus_specs_tpu.parallel.epoch_sharded import (
        make_sharded_epoch_step,
        shard_delta_inputs,
    )
    from consensus_specs_tpu.parallel.merkle_sharded import (
        sharded_uint64_list_root,
    )
    from consensus_specs_tpu.ssz.types import List, uint64

    mesh = build_mesh(4, devices=devices[:4])
    inp, balances = _example_inputs(n)
    step = make_sharded_epoch_step(mesh)
    args, n_orig = shard_delta_inputs(mesh, inp, balances)
    for name, arr in zip(("balances", "eff", "eligible", "src", "tgt",
                          "head", "delay", "proposer", "scalars"), args):
        place = sorted((s.device.id, s.data.shape[0] if s.data.ndim else 0)
                       for s in arr.addressable_shards)
        print(f"placement {name}: {place}", flush=True)
    balances_place = {s.device.id for s in args[0].addressable_shards}
    assert balances_place == {d.id for d in devices[:4]}, balances_place

    def run():
        new_balances, digests = step(*args)
        new_balances.block_until_ready()
        return np.asarray(new_balances)[:n_orig], digests

    cold = _timed(run)
    warm = _timed(run)
    rewards, penalties = attestation_deltas(inp)
    increased = balances + rewards
    expected = np.where(penalties > increased, 0, increased - penalties)
    for got in (cold[2][0], warm[2][0]):
        assert np.array_equal(got, expected), \
            "sharded balances != single-device attestation_deltas"
    _report("4chip epoch-step", cold, warm,
            ",".join(_dev(d) for d in devices[:4]),
            f"; {n_orig} validators, balances equal to single-device kernel")

    limit = 2**40
    ssz_root = bytes(List[uint64, limit](*map(int, expected)).hash_tree_root())
    cold = _timed(lambda: sharded_uint64_list_root(mesh, expected, limit))
    warm = _timed(lambda: sharded_uint64_list_root(mesh, expected, limit))
    assert cold[2] == ssz_root and warm[2] == ssz_root, \
        "sharded root != SSZ List[uint64] root"
    _report("4chip merkle-root", cold, warm,
            ",".join(_dev(d) for d in devices[:4]),
            f"; root 0x{ssz_root.hex()[:16]} equal to SSZ")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    if not __debug__:
        _fail("the checks are asserts: run without python -O")

    # -- phase a: device
    from consensus_specs_tpu import _jaxcache

    _jaxcache.keep_host_backend()
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    _require_tpu(devices, args.chips)
    pkg = os.path.dirname(os.path.abspath(_jaxcache.__file__))
    assert os.path.dirname(pkg) == REPO, f"package outside the repo: {pkg}"
    from consensus_specs_tpu import tracing

    _jaxcache.configure()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    tracing.enable()
    device = devices[0]
    print(f"phase a device: platform {device.platform} kind "
          f"{device.device_kind} count {len(devices)}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}; JAX_PLATFORMS "
          f"{os.environ.get('JAX_PLATFORMS')}", flush=True)

    if args.chips == 4:
        four_chips(devices, N_VALIDATORS)
    else:
        from consensus_specs_tpu.specs.builder import get_spec

        spec = get_spec("phase0", "mainnet")
        state = phase_state(spec, N_VALIDATORS)
        phase_epoch(spec, state, device)
        phase_blocks(spec, state, device)
        phase_device_bls(state, device)
        phase_hashing(spec, state, device)
    print(f"total: cold {_clock['cold']:.3f} s warm {_clock['warm']:.3f} s "
          f"XLA compile {_clock['compile']:.3f} s wall "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
