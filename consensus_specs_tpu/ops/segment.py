"""Segment-sum primitive for per-node vote aggregation.

The batched fork-choice engine reduces hundreds of thousands of
``(validator_index, target_node, effective_balance)`` vote rows into one
weight delta per proto-array node.  That reduction is a segment sum over
the node axis — the same shape as the participation scatters in
``ops/epoch_jax.py`` (``np.add.at`` over dense arrays) and
``jax.ops.segment_sum`` on device.

The host path is the default: vote batches are memory-light (int64
triples) and arrive host-side, and which side runs the reduction faster
is not measured on the chip yet (ROADMAP Speed item 3).
``CSTPU_SEGMENT_BACKEND=jax`` flips the reduction onto the accelerator
unchanged; the differential test
(tests/spec/phase0/fork_choice/test_engine_differential.py) pins the two
backends element-identical.
"""
from __future__ import annotations

import os

import numpy as np


def segment_sum(values: np.ndarray, segment_ids: np.ndarray,
                num_segments: int, backend: str | None = None) -> np.ndarray:
    """``out[s] = sum(values[segment_ids == s])`` as int64 [num_segments].

    ``segment_ids`` must be in ``[0, num_segments)``; callers filter
    negative ids (the proto-array's "no node" sentinel) beforehand.
    """
    if backend is None:
        backend = os.environ.get("CSTPU_SEGMENT_BACKEND", "numpy")
    values = np.asarray(values, dtype=np.int64)
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    if backend == "jax":
        import jax
        import jax.numpy as jnp

        from consensus_specs_tpu import _jaxcache

        jax.config.update("jax_enable_x64", True)
        _jaxcache.configure()
        # host-sync: staged view — callers consume segment counts on host
        return np.asarray(jax.ops.segment_sum(
            jnp.asarray(values), jnp.asarray(segment_ids),
            num_segments=num_segments))
    out = np.zeros(num_segments, dtype=np.int64)
    np.add.at(out, segment_ids, values)
    return out
