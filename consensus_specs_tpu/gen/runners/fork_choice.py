"""Fork-choice vector generator (reference capability:
tests/generators/fork_choice/main.py): step-scripted tick/block/
attestation/attester_slashing scenarios with store checks, generated
from the fork-choice test module across forks."""
from __future__ import annotations

from consensus_specs_tpu.gen.gen_from_tests import run_state_test_generators


def main(argv=None):
    from consensus_specs_tpu.gen.runners import ensure_vector_sources_importable

    ensure_vector_sources_importable()
    # reference handler classification (tests/generators/fork_choice/main.py):
    # get_head / on_block / ex_ante, plus on_merge_block from bellatrix
    mods = {
        "get_head": ["tests.spec.phase0.test_fork_choice",
                     "tests.spec.phase0.fork_choice.test_get_head"],
        "ex_ante": "tests.spec.phase0.fork_choice.test_ex_ante",
        "on_block": "tests.spec.phase0.fork_choice.test_on_block",
    }
    all_mods = {
        "phase0": mods,
        "altair": mods,
        "bellatrix": {**mods,
                      "on_merge_block":
                          "tests.spec.bellatrix.fork_choice.test_on_merge_block"},
        "capella": mods,
    }
    run_state_test_generators(
        runner_name="fork_choice", all_mods=all_mods, argv=argv)


if __name__ == "__main__":
    main()
