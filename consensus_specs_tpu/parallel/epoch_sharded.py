"""Sharded epoch step: attestation deltas + balance update + merkle lanes
over a device mesh.

The validator axis shards across devices (``axis "v"``); the only
cross-shard traffic is:

  * psum of the three component attesting balances (scalars),
  * all_gather of (proposer-index, credit) pairs for the inclusion-delay
    proposer rewards — proposers live on arbitrary shards,
  * the SHA-256 chunk lanes hash locally (tensor-parallel) and the layer
    digests stay sharded for the next tree level.

Collectives ride ICI on a real pod; the same code runs on the test
harness's 8-device virtual CPU mesh (tests/conftest.py) and via the
driver's ``dryrun_multichip``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from consensus_specs_tpu.ops.sha256_jax import sha256_block64

jax.config.update("jax_enable_x64", True)


def _local_deltas(eff, eligible, src, tgt, head, delay, att_bal, scalars):
    """Per-shard deltas given globally-reduced attesting balances.
    ``att_bal`` is [3] (source, target, head)."""
    (total_balance, sqrt_total, finality_delay, brf, brpe, prq, ipq,
     min_leak, ebi) = [scalars[i] for i in range(9)]

    base_reward = eff * brf // sqrt_total // brpe
    proposer_reward = base_reward // prq
    is_leak = finality_delay > min_leak

    rewards = jnp.zeros_like(eff)
    penalties = jnp.zeros_like(eff)
    total_incr = total_balance // ebi
    for k, part in enumerate((src, tgt, head)):
        att_incr = jnp.maximum(att_bal[k], ebi) // ebi
        comp_reward = jnp.where(
            is_leak, base_reward, base_reward * att_incr // total_incr)
        rewards = rewards + jnp.where(eligible & part, comp_reward, 0)
        penalties = penalties + jnp.where(eligible & ~part, base_reward, 0)

    max_attester_reward = base_reward - proposer_reward
    rewards = rewards + jnp.where(src, max_attester_reward // delay, 0)

    leak_base = brpe * base_reward - proposer_reward
    leak_extra = eff * finality_delay // ipq
    penalties = penalties + jnp.where(
        is_leak & eligible, leak_base + jnp.where(~tgt, leak_extra, 0), 0)

    return rewards, penalties, jnp.where(src, proposer_reward, 0)


def make_sharded_epoch_step(mesh: Mesh, axis: str = "v"):
    """Build the jitted, mesh-sharded epoch step.

    Step signature (all arrays sharded over ``axis`` except scalars):
      (balances, eff, eligible, src, tgt, head, delay, proposer, scalars)
        -> (new_balances, layer_digests)
    """
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis)),
    )
    def step(balances, eff, eligible, src, tgt, head, delay, proposer, scalars):
        # ---- global attesting balances: local partial sums -> psum ----
        local_bal = jnp.stack([
            jnp.sum(jnp.where(src, eff, 0)),
            jnp.sum(jnp.where(tgt, eff, 0)),
            jnp.sum(jnp.where(head, eff, 0)),
        ])
        att_bal = jax.lax.psum(local_bal, axis_name=axis)

        rewards, penalties, prop_credit = _local_deltas(
            eff, eligible, src, tgt, head, delay, att_bal=att_bal, scalars=scalars)

        # ---- proposer rewards: gather (global index, credit) pairs ----
        shard_idx = jax.lax.axis_index(axis)
        local_n = eff.shape[0]
        global_idx_base = shard_idx * local_n
        all_prop = jax.lax.all_gather(proposer, axis_name=axis)       # [D, n]
        all_credit = jax.lax.all_gather(prop_credit, axis_name=axis)  # [D, n]
        flat_prop = all_prop.reshape(-1)
        flat_credit = all_credit.reshape(-1)
        in_shard = (flat_prop >= global_idx_base) & (flat_prop < global_idx_base + local_n)
        local_slot = jnp.where(in_shard, flat_prop - global_idx_base, 0)
        rewards = rewards.at[local_slot].add(jnp.where(in_shard, flat_credit, 0))

        # ---- apply balance update (spec: increase/decrease_balance) ----
        new_balances = balances + rewards
        new_balances = jnp.where(
            penalties > new_balances, 0, new_balances - penalties)

        # ---- merkleize the local balance lanes (packed uint64 chunks) ----
        # 4 balances per 32-byte chunk; pairs of chunks -> 64-byte blocks.
        # Each device hashes its own lanes; digests stay sharded.
        lanes = new_balances.astype(jnp.uint64)
        assert local_n % 8 == 0, (
            "per-shard lane count must be a multiple of 8 (whole 64-byte "
            "merkle blocks); use shard_delta_inputs to pad")
        n_blocks = local_n // 8
        lo = (lanes & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (lanes >> jnp.uint64(32)).astype(jnp.uint32)
        # little-endian uint64 serialization -> big-endian word view
        words = jnp.stack([_bswap32(lo), _bswap32(hi)], axis=-1).reshape(-1)
        words = words[: n_blocks * 16].reshape(n_blocks, 16)
        digests = sha256_block64(words)  # [n_blocks, 8] uint32

        return new_balances, digests.reshape(-1)

    return jax.jit(step)


def _bswap32(x):
    x = ((x & jnp.uint32(0x00FF00FF)) << 8) | ((x >> 8) & jnp.uint32(0x00FF00FF))
    return ((x << 16) | (x >> 16)).astype(jnp.uint32)


def shard_delta_inputs(mesh: Mesh, inp, balances: np.ndarray, axis: str = "v"):
    """Pad arrays to a multiple of the mesh size and device_put with the
    sharding the step expects.  Returns (args tuple, original n)."""
    n_dev = mesh.devices.size
    n = inp.effective_balance.shape[0]
    # lanes must be a multiple of 8*n_dev so each shard hashes whole blocks
    mult = 8 * n_dev
    n_pad = ((n + mult - 1) // mult) * mult

    def pad(a, fill=0):
        if n_pad == a.shape[0]:
            return a
        return np.concatenate([a, np.full(n_pad - a.shape[0], fill, dtype=a.dtype)])

    sharding = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())

    scalars = np.array([
        inp.total_balance, inp.sqrt_total, inp.finality_delay,
        inp.base_reward_factor, inp.base_rewards_per_epoch,
        inp.proposer_reward_quotient, inp.inactivity_penalty_quotient,
        inp.min_epochs_to_inactivity_penalty, inp.effective_balance_increment,
    ], dtype=np.int64)

    args = (
        jax.device_put(pad(balances.astype(np.int64)), sharding),
        jax.device_put(pad(inp.effective_balance), sharding),
        jax.device_put(pad(inp.eligible), sharding),
        jax.device_put(pad(inp.source_part), sharding),
        jax.device_put(pad(inp.target_part), sharding),
        jax.device_put(pad(inp.head_part), sharding),
        jax.device_put(pad(inp.incl_delay, fill=1), sharding),
        jax.device_put(pad(inp.incl_proposer), sharding),
        jax.device_put(scalars, rep),
    )
    return args, n
