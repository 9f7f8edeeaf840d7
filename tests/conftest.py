"""Test-session configuration (reference: pyspec test/conftest.py).

Device setup: tests run on a virtual 8-device CPU mesh so multi-chip
sharding is validated without real chips (the driver's dryrun_multichip
does the same).  Must be set before jax is imported anywhere.

CLI flags mirror the reference:
  --preset=minimal|mainnet   preset for spec tests
  --fork=phase0[,altair...]  forks to run
  --disable-bls              run with BLS stubbed (fast)
"""
import os

# Force CPU even when the ambient env points at a TPU — unit tests run on
# the virtual 8-device mesh, and a chip belongs to one process.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Some TPU platform plugins override JAX_PLATFORMS via jax config at
# import; pin the config itself so tests always see the CPU mesh.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_addoption(parser):
    parser.addoption(
        "--preset", action="store", type=str, default="minimal",
        help="preset for spec tests: minimal or mainnet",
    )
    parser.addoption(
        "--fork", action="store", type=str, default=None,
        help="comma-separated forks to run spec tests against",
    )
    parser.addoption(
        "--disable-bls", action="store_true", default=False,
        help="bypass BLS operations in spec tests (massively faster)",
    )


def pytest_configure(config):
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.testing import context

    # fast host BLS (native C++) when the toolchain can build it, like the
    # reference's CI running under the milagro backend; pointless when BLS
    # is stubbed out
    if not config.getoption("--disable-bls"):
        bls.use_fastest()

    context.DEFAULT_TEST_PRESET = config.getoption("--preset")
    forks = config.getoption("--fork")
    if forks:
        context.DEFAULT_PYTEST_FORKS = tuple(f.strip() for f in forks.split(","))
    if config.getoption("--disable-bls"):
        context.DEFAULT_BLS_ACTIVE = False
