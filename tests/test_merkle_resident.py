"""Device-resident merkleization parity: the resident subtree root and the
spliced full-state root must be bit-identical to the SSZ host path
(ops/merkle_resident.py; reference seam: ssz_impl.hash_tree_root)."""
import numpy as np
import pytest

from consensus_specs_tpu.ops.merkle_resident import (
    ResidentPackedU64List,
    replace_field_subtree,
)
from consensus_specs_tpu.ssz.impl import hash_tree_root
from consensus_specs_tpu.ssz.node import merkle_root
from consensus_specs_tpu.ssz.types import List, uint64

LIMIT = 2**40


@pytest.mark.parametrize("n", [1, 3, 4, 5, 63, 1024])
def test_resident_root_matches_ssz(n):
    rng = np.random.default_rng(n)
    values = rng.integers(0, 2**63, n, dtype=np.uint64)
    resident = ResidentPackedU64List(LIMIT)
    resident.upload(values)
    expected = bytes(hash_tree_root(List[uint64, LIMIT](*map(int, values))))
    assert resident.root() == expected


def test_resident_apply_add_scalar_and_vector():
    rng = np.random.default_rng(99)
    values = rng.integers(0, 2**62, 200, dtype=np.uint64)
    resident = ResidentPackedU64List(LIMIT)
    resident.upload(values)

    resident.apply_add(7)
    values = values + np.uint64(7)
    assert (resident.to_numpy() == values).all()

    deltas = rng.integers(-1000, 1000, 200)
    resident.apply_add(deltas)
    values = (values.astype(np.int64) + deltas).astype(np.uint64)
    assert (resident.to_numpy() == values).all()
    assert resident.root() == bytes(
        hash_tree_root(List[uint64, LIMIT](*map(int, values))))


@pytest.mark.parametrize("n", [5, 64, 100, 256])
def test_memoize_contents_root_matches_host(n):
    """memoize_packed_u64_contents_root installs a root the host hasher
    would have produced — pinned across pow2 and ragged lengths."""
    from consensus_specs_tpu.ops import merkle_resident
    from consensus_specs_tpu.ssz import bulk

    rng = np.random.default_rng(n)
    values = rng.integers(0, 2**63, n, dtype=np.uint64)
    resident = ResidentPackedU64List(LIMIT)
    resident.upload(values)
    padded_root = resident.contents_subtree_root()

    lst = List[uint64, LIMIT]()
    bulk.set_packed_uint64_from_numpy(lst, values)
    merkle_resident.memoize_packed_u64_contents_root(lst, padded_root)
    backing = lst.get_backing()
    assert backing.left._root is not None, "root was not memoized"
    expected = bytes(hash_tree_root(List[uint64, LIMIT](*map(int, values))))
    assert bytes(hash_tree_root(lst)) == expected


def test_fused_epoch_update_is_root_identical_to_host_path(monkeypatch):
    """The SHIPPING integration: process_rewards_and_penalties routed
    through the fused deltas+merkle program (forced on, threshold lowered)
    must leave a state whose full hash_tree_root is bit-identical to the
    host kernel path — the VERDICT 'residency composes' contract."""
    import jax

    from consensus_specs_tpu.ops import merkle_resident
    from consensus_specs_tpu.specs.builder import get_spec
    from consensus_specs_tpu.testing.context import (
        default_activation_threshold,
        default_balances,
    )
    from consensus_specs_tpu.testing.helpers.attestations import (
        next_epoch_with_attestations,
    )
    from consensus_specs_tpu.testing.helpers.genesis import create_genesis_state

    spec = get_spec("phase0", "minimal")
    state = create_genesis_state(
        spec, default_balances(spec), default_activation_threshold(spec))
    # a previous epoch of attestations so the deltas kernel has real work
    _, _, state = next_epoch_with_attestations(spec, state, True, False)

    host_state = state.copy()
    dev_state = state.copy()

    monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", "0")
    spec.process_rewards_and_penalties(host_state)

    monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", "1")
    monkeypatch.setattr(merkle_resident, "RESIDENT_MIN", 1)
    before = merkle_resident.stats["fused_epoch_updates"]
    spec.process_rewards_and_penalties(dev_state)
    assert merkle_resident.stats["fused_epoch_updates"] == before + 1, \
        "fused path did not engage"
    assert merkle_resident.stats["roots_memoized"] > 0

    assert bytes(dev_state.hash_tree_root()) == bytes(host_state.hash_tree_root())
    # values identical too, not just roots
    from consensus_specs_tpu.ssz import bulk

    assert (bulk.packed_uint64_to_numpy(dev_state.balances)
            == bulk.packed_uint64_to_numpy(host_state.balances)).all()


def test_resident_device_policy(monkeypatch):
    from consensus_specs_tpu.ops import merkle_resident

    monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", "0")
    assert merkle_resident.resident_device() is None
    monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", "1")
    assert merkle_resident.resident_device() is not None
    # auto on the CPU test backend: host hashing wins, stay off
    monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", "auto")
    import jax

    expected_off = jax.devices()[0].platform == "cpu"
    assert (merkle_resident.resident_device() is None) == expected_off


def test_resident_splice_into_state_root():
    from consensus_specs_tpu.specs.builder import get_spec
    from consensus_specs_tpu.ssz import bulk
    from consensus_specs_tpu.testing.context import (
        default_activation_threshold,
        default_balances,
    )
    from consensus_specs_tpu.testing.helpers.genesis import create_genesis_state

    spec = get_spec("phase0", "minimal")
    state = create_genesis_state(
        spec, default_balances(spec), default_activation_threshold(spec))
    cls = type(state)

    balances = bulk.packed_uint64_to_numpy(state.balances).astype(np.uint64)
    resident = ResidentPackedU64List(type(state.balances).LENGTH)
    resident.upload(balances)
    resident.apply_add(5)

    clean = state.get_backing()
    spliced = replace_field_subtree(
        clean, cls._field_index["balances"], cls._depth,
        resident.as_backing_node())

    host = state.copy()
    bulk.set_packed_uint64_from_numpy(host.balances, balances + np.uint64(5))
    assert merkle_root(spliced) == bytes(host.hash_tree_root())


def test_resident_device_raises_when_device_init_fails(monkeypatch):
    # no silent host fallback: a device that fails to come up is an error
    import jax

    from consensus_specs_tpu.ops import merkle_resident

    def broken():
        raise RuntimeError("device init failed")

    monkeypatch.setattr(jax, "devices", broken)
    for mode in ("auto", "1"):
        monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", mode)
        with pytest.raises(RuntimeError, match="device init failed"):
            merkle_resident.resident_device()


@pytest.mark.parametrize("module, pick, var", [
    ("consensus_specs_tpu.ops.epoch_jax", "_kernel_device",
     "CSTPU_EPOCH_BACKEND"),
    ("consensus_specs_tpu.ops.kzg_jax", "_msm_device", "CSTPU_KZG_BACKEND"),
])
def test_backend_choice_raises_on_missing_backend(monkeypatch, module, pick,
                                                  var):
    import importlib

    pick_device = getattr(importlib.import_module(module), pick)
    monkeypatch.delenv(var, raising=False)
    assert pick_device().platform == "cpu"  # the unmeasured default
    monkeypatch.setenv(var, "no_such_backend")
    with pytest.raises(RuntimeError, match="no_such_backend"):
        pick_device()


def test_slot_root_device_error_propagates(monkeypatch):
    # a failing resident upload raises out of the per-slot root, so the
    # block engine's rollback + counted literal replay handles it
    from consensus_specs_tpu.ops import merkle_resident
    from consensus_specs_tpu.specs.builder import get_spec
    from consensus_specs_tpu.ssz import bulk
    from consensus_specs_tpu.stf import slot_roots
    from consensus_specs_tpu.testing.context import (
        default_activation_threshold,
        default_balances,
    )
    from consensus_specs_tpu.testing.helpers.genesis import create_genesis_state

    spec = get_spec("phase0", "minimal")
    state = create_genesis_state(
        spec, default_balances(spec), default_activation_threshold(spec))
    values = bulk.packed_uint64_to_numpy(state.balances) + 1
    monkeypatch.setenv("CSTPU_RESIDENT_MERKLE", "1")
    monkeypatch.setattr(merkle_resident, "RESIDENT_MIN", 1)

    def broken(self, values):
        raise RuntimeError("device lost")

    monkeypatch.setattr(merkle_resident.ResidentPackedU64List, "upload",
                        broken)
    bulk.set_packed_uint64_from_numpy(state.balances, values)
    assert state.balances.get_backing().left._root is None
    with pytest.raises(RuntimeError, match="device lost"):
        slot_roots.state_root(spec, state)
