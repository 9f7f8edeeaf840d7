"""Cross-fork transition vector generator (reference capability:
tests/generators/transition/main.py): scenarios straddling a fork
boundary, tests run under the pre-fork spec with the post fork in
phases."""
from __future__ import annotations

from consensus_specs_tpu.gen import gen_runner
from consensus_specs_tpu.gen.runners.forks import make_cross_fork_provider


def main(argv=None):
    from consensus_specs_tpu.gen.runners import ensure_vector_sources_importable

    ensure_vector_sources_importable()
    from consensus_specs_tpu.testing.helpers.constants import ALL_PRE_POST_FORKS

    # Reference classification (tests/generators/transition/main.py): EVERY
    # module emits under handler "core", for every pre/post fork pair.
    modules = (
        "tests.spec.altair.test_transition",
        "tests.spec.altair.transition.test_activations_and_exits",
        "tests.spec.altair.transition.test_leaking",
        "tests.spec.altair.transition.test_operations",
        "tests.spec.altair.transition.test_slashing",
    )
    providers = [
        make_cross_fork_provider(
            mod, preset, pre_fork, post_fork,
            runner_name="transition", handler_name="core")
        for preset in ("minimal", "mainnet")
        for mod in modules
        for pre_fork, post_fork in ALL_PRE_POST_FORKS
    ]
    gen_runner.run_generator("transition", providers, argv=argv)


if __name__ == "__main__":
    main()
