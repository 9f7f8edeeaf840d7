"""Structural audit of the spec-mirror registry: every declaration
resolves against the live tree (mirrors exist, guards present, pins match
the extracted digests) and the extraction pass's redeclared fork ladder
stays in lockstep with ``specs/builder.py``."""

import ast

from analysis import REPO_ROOT, mirror_registry, spec_extract


def test_registry_is_structurally_valid():
    assert mirror_registry.registry_errors() == []


def test_registry_scale_matches_the_fast_paths():
    # the tentpole floor: every production fast-path mirror is declared
    assert len(mirror_registry.MIRRORS) >= 25
    assert len(mirror_registry.mirror_files()) >= 6


def test_fork_chains_lockstep_with_builder_fork_parents():
    # spec_extract redeclares the ladder (importing builder pulls in jax);
    # pin it AST-for-AST against the authoritative FORK_PARENTS
    src = (REPO_ROOT / "consensus_specs_tpu/specs/builder.py").read_text()
    parents = None
    for node in ast.walk(ast.parse(src)):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "FORK_PARENTS"
                        for t in node.targets)):
            parents = ast.literal_eval(node.value)
    assert parents is not None
    for fork, chain in spec_extract.FORK_CHAINS.items():
        rebuilt, cur = [], fork
        while cur is not None:
            rebuilt.append(cur)
            cur = parents[cur]
        assert tuple(reversed(rebuilt)) == chain, fork


def test_every_mirror_resolves_with_its_guards_present():
    for m in mirror_registry.MIRRORS:
        path = REPO_ROOT / mirror_registry.mirror_display(m)
        assert path.exists(), m.name
        text = path.read_text()
        node = mirror_registry.find_def(ast.parse(text), m.qualname)
        assert node is not None, (m.name, m.qualname)
        seg = ast.get_source_segment(text, node)
        for pin in m.pins:
            for guard in pin.guards:
                if guard is not None:
                    assert guard in seg, (m.name, pin.fn, guard)


def test_live_pins_match_extracted_spec_facts():
    texts = {d: (REPO_ROOT / d).read_text()
             for d in spec_extract.spec_source_displays()}
    snap = spec_extract.snapshot(texts)
    for m in mirror_registry.MIRRORS:
        for pin in m.pins:
            for fork in pin.forks:
                fn = snap.get(fork, pin.fn)
                assert fn is not None, (m.name, pin.fn, fork)
                assert fn.digest == pin.digest, (m.name, pin.fn, fork)
                assert fn.raise_count == pin.raise_count, (m.name, pin.fn)
                assert fn.raise_digest == pin.raise_digest, (m.name, pin.fn)
                assert len(pin.guards) == pin.raise_count, (m.name, pin.fn)


def test_coverage_queries():
    assert mirror_registry.coverage(
        "process_slots", "phase0") == "mirror:slot-advance"
    assert mirror_registry.coverage("process_deposit", "phase0") == "literal"
    # capella is on the fast path: its new operations are declared mirrors
    assert mirror_registry.coverage(
        "process_withdrawals", "capella") == "mirror:withdrawals-dequeue"
    assert mirror_registry.coverage(
        "process_full_withdrawals", "capella") == "mirror:full-withdrawals-sweep"
    assert mirror_registry.coverage("process_withdrawals", "eip4844") is None


def test_extra_file_deps_cover_pinned_chains():
    deps = mirror_registry.extra_file_deps()
    # SP02 reads every chain: the engine depends on all spec sources
    assert set(deps[mirror_registry.ENGINE_DISPLAY]) == set(
        spec_extract.spec_source_displays())
    # chain closure: an altair-pinned mirror also depends on phase0 (an
    # earlier-fork edit can move the later fork's effective definition)
    epoch = deps["consensus_specs_tpu/ops/epoch_altair.py"]
    assert "consensus_specs_tpu/specs/src/altair.py" in epoch
    assert "consensus_specs_tpu/specs/src/phase0.py" in epoch
    # every mirror file appears
    assert set(mirror_registry.mirror_files()) <= set(deps)


def test_find_def_resolves_nested_paths():
    tree = ast.parse(
        "class _Outer:\n"
        "    def inner(self):\n"
        "        pass\n"
        "def top():\n"
        "    pass\n")
    assert mirror_registry.find_def(tree, "top").name == "top"
    assert mirror_registry.find_def(tree, "_Outer.inner").name == "inner"
    assert mirror_registry.find_def(tree, "_Outer.gone") is None
    assert mirror_registry.find_def(tree, "missing") is None
