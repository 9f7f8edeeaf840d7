"""Differential tests for the Pallas SHA-256 merkle kernel, in interpret
mode.

tests/conftest.py pins the test process to the virtual CPU mesh, where
the kernel runs through Pallas interpret mode — bit-identical but
minutes-slow under this image's jax build, hence opt-in with
CSTPU_PALLAS_TESTS=1.  The compiled kernel is covered twice elsewhere:
tests/test_chip_compile.py compiles it for a described TPU v5e, and
chip_smoke.py phase f runs it on the chip against hashlib.
"""
import hashlib
import os
import random

import pytest


# ---- interpreter-mode in-process tests (opt-in: minutes-slow) ------------

interp = pytest.mark.skipif(
    os.environ.get("CSTPU_PALLAS_TESTS") != "1",
    reason="pallas interpret mode is minutes-slow off-TPU; set CSTPU_PALLAS_TESTS=1",
)


@interp
def test_interpret_differential():
    from consensus_specs_tpu.ops import sha256_pallas

    rng = random.Random(9)
    msgs = [bytes(rng.getrandbits(8) for _ in range(64)) for _ in range(3)]
    got = sha256_pallas.hash_layer(msgs)
    assert all(d == hashlib.sha256(m).digest() for m, d in zip(msgs, got))


@interp
def test_interpret_empty_layer():
    from consensus_specs_tpu.ops import sha256_pallas

    assert sha256_pallas.hash_layer([]) == []
