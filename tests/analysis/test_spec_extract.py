"""Unit tests for the spec-source extraction pass (SP01–SP03 substrate):
AST-normalized digests, raise-site facts, fork-chain layering, and the
bare-name reachability walk."""

from analysis import spec_extract

P0 = spec_extract.fork_display("phase0")
AL = spec_extract.fork_display("altair")
BE = spec_extract.fork_display("bellatrix")
CA = spec_extract.fork_display("capella")
SSZ = spec_extract.fork_display("ssz")


def _snap(phase0, altair="", bellatrix="", capella="", ssz=""):
    return spec_extract.snapshot({
        P0: phase0, AL: altair, BE: bellatrix, CA: capella, SSZ: ssz})


def test_digest_ignores_comments_docstrings_and_whitespace():
    a = _snap("def f(x):\n    return x + 1\n")
    b = _snap(
        "# leading comment\n"
        "def f(x):\n"
        '    """docstring."""\n'
        "    # inline comment\n"
        "    return x + 1\n"
    )
    fa, fb = a.get("phase0", "f"), b.get("phase0", "f")
    assert fa is not None and fb is not None
    assert fa.digest == fb.digest
    assert fa.raise_digest == fb.raise_digest


def test_digest_changes_on_semantic_edit():
    a = _snap("def f(x):\n    return x + 1\n")
    b = _snap("def f(x):\n    return x + 2\n")
    assert a.get("phase0", "f").digest != b.get("phase0", "f").digest


_NESTED = ("def f(x):\n    def g(y):\n        assert y > 0\n        return y\n"
           "    return g(x)\n")


def test_digest_of_nested_def_is_pinned_across_interpreters():
    # the nested def carries 3.12's empty ``type_params``; the digest is the
    # one every interpreter must produce (the live pins depend on it)
    fn = _snap(_NESTED).get("phase0", "f")
    assert fn.digest == (
        "4f6f03539deae190afb98f84f9292ff5337953694e29b2922e05b7f959ba5d5f")
    assert fn.raise_digest == (
        "46d30ef3ca3610b8a390e7bbf268c7e22840d29a4c615a89691f3e0823c7da46")


def test_canonical_dump_omits_empty_schema_fields():
    import ast

    node = ast.parse(_NESTED).body[0].body[0]
    with_field = spec_extract._canonical(node)
    node.type_params = []
    assert spec_extract._canonical(node) == with_field
    del node.type_params
    assert spec_extract._canonical(node) == with_field
    assert "type_params" not in with_field


def test_raise_sites_are_ordered_and_digested():
    snap = _snap(
        "def f(x):\n"
        "    assert x > 0\n"
        "    if x > 9:\n"
        "        raise ValueError('big')\n"
        "    assert x < 5, 'small'\n"
    )
    fn = snap.get("phase0", "f")
    assert fn.raise_count == 3
    kinds = [s.kind for s in fn.raise_sites]
    assert kinds == ["assert", "raise", "assert"]
    assert fn.raise_sites[0].source == "assert x > 0"
    # the raise digest covers conditions, not line numbers: shifting the
    # function down a line keeps it stable
    shifted = _snap(
        "# shim\n"
        "def f(x):\n"
        "    assert x > 0\n"
        "    if x > 9:\n"
        "        raise ValueError('big')\n"
        "    assert x < 5, 'small'\n"
    )
    assert shifted.get("phase0", "f").raise_digest == fn.raise_digest
    # ...while editing one condition moves it
    edited = _snap(
        "def f(x):\n"
        "    assert x >= 0\n"
        "    if x > 9:\n"
        "        raise ValueError('big')\n"
        "    assert x < 5, 'small'\n"
    )
    assert edited.get("phase0", "f").raise_digest != fn.raise_digest


def test_fork_chain_layering_latest_definition_wins():
    snap = _snap(
        phase0="def f():\n    return 0\n\ndef g():\n    return f()\n",
        altair="def f():\n    return 1\n",
    )
    assert snap.get("phase0", "f").fork == "phase0"
    assert snap.get("altair", "f").fork == "altair"
    assert snap.get("bellatrix", "f").fork == "altair"  # inherited
    # unredefined names flow through the whole chain
    assert snap.get("capella", "g").fork == "phase0"
    # per-fork digests differ exactly when the effective defs differ
    assert snap.fork_digests["phase0"] != snap.fork_digests["altair"]
    assert snap.fork_digests["altair"] == snap.fork_digests["bellatrix"]


def test_missing_source_is_recorded_not_fatal():
    snap = spec_extract.snapshot({P0: "def f():\n    return 0\n", AL: None,
                                  BE: None, CA: None, SSZ: None})
    assert AL in snap.missing
    assert snap.get("altair", "f") is not None  # phase0 layer still applies


def test_reachable_walks_bare_name_calls_only():
    snap = _snap(
        "def process_a():\n    helper()\n"
        "def helper():\n    return 1\n"
        "def process_b():\n    spec.process_a()\n"  # attribute call: opaque
        "def orphan():\n    return 2\n"
        "def entry():\n    process_a()\n    process_b()\n"
    )
    seen = spec_extract.reachable(snap, "phase0", ("entry",))
    assert set(seen) == {"entry", "process_a", "process_b", "helper"}
    assert "orphan" not in seen


def test_live_spec_sources_extract_cleanly():
    from analysis import REPO_ROOT

    texts = {d: (REPO_ROOT / d).read_text()
             for d in spec_extract.spec_source_displays()}
    snap = spec_extract.snapshot(texts)
    assert snap.missing == ()
    assert set(snap.fork_digests) == {
        "phase0", "altair", "bellatrix", "capella", "ssz"}
    st = snap.get("phase0", "state_transition")
    assert st is not None and st.raise_count >= 1
    reach = spec_extract.reachable(snap, "phase0", ("state_transition",))
    assert "process_block_header" in reach
