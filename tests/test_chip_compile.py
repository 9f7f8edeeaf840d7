"""AOT compiles of the main path's device programs for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2).  Each test
compiles one program of chip_smoke.py at its 400,000-validator shape, so
what the chip's compiler refuses (tiling, VMEM, s64 emulation, HBM) fails
here at no chip time.  Nothing runs: these say nothing about results or
times.

The topology is described inside a module fixture, never at import: only
one process may hold libtpu, and under pytest-xdist only the worker given
this file loads it.  Keep every such compile in this one file.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_VALIDATORS = 400_000
N_PAD = 1 << 19  # the epoch programs pad the registry to a power of two


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's executable cannot be read back without the chip:
    # keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    # x64 on, as on the main path: the epoch and BLS modules enable it
    # when imported, and the int64 shapes below need it
    from consensus_specs_tpu.ops import epoch_jax  # noqa: F401

    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("lanes", [65536, 128])
def test_pallas_sha256_kernel_compiles_to_mosaic(one_chip, monkeypatch,
                                                 lanes):
    import jax
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import sha256_pallas

    # under x64 a literal block index traces as i64, which Mosaic refuses
    assert jax.config.jax_enable_x64
    # the test process runs JAX on the CPU, where the kernel interprets
    monkeypatch.setattr(sha256_pallas, "_use_interpret", lambda: False)
    compiled = sha256_pallas._block64_t_jit.lower(
        _shape(one_chip, (16, lanes), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_epoch_balances_program_compiles(one_chip):
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import merkle_resident

    i64 = _shape(one_chip, (N_PAD,), jnp.int64)
    flag = _shape(one_chip, (N_PAD,), jnp.bool_)
    compiled = merkle_resident._jit_fused.lower(
        i64, i64, flag, flag, flag, flag, i64, i64,
        _shape(one_chip, (9,), jnp.int64)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30  # one v5e chip's HBM


def test_balances_wave_schedule_compiles(one_chip, monkeypatch):
    import jax.numpy as jnp

    import chip_smoke
    from consensus_specs_tpu.ops import sha256_jax
    from consensus_specs_tpu.specs.builder import get_spec
    from consensus_specs_tpu.ssz import hashing

    class Captured(Exception):
        pass

    def capture(known, waves):
        raise Captured(known.shape, [len(left) for left, _ in waves])

    # the schedule hash_waves pads and hands to the device program, for
    # a freshly written 400k balances list
    spec = get_spec("phase0", "mainnet")
    view = chip_smoke.fresh_balances(
        spec, np.arange(N_VALIDATORS, dtype=np.uint64))
    monkeypatch.setattr(sha256_jax, "hash_waves_u32", capture)
    hashing.set_backend("jax")
    try:
        with pytest.raises(Captured) as got:
            view.hash_tree_root()
    finally:
        hashing.set_backend("hashlib")
    known, sizes = got.value.args
    assert sizes[0] == 65536 and len(sizes) % 4 == 0
    idx = tuple(_shape(one_chip, (s,), jnp.int32) for s in sizes)
    sha256_jax._jit_run_waves.lower(
        _shape(one_chip, known, jnp.uint32), idx, idx).compile()


def test_shuffle_permutation_program_compiles(one_chip):
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import shuffle

    lanes = shuffle.lanes_for(N_VALIDATORS)
    compiled = shuffle._jit_shuffle.lower(
        _shape(one_chip, (8,), jnp.uint32), _shape(one_chip, (90,), jnp.int32),
        _shape(one_chip, (), jnp.int32), lanes=lanes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
