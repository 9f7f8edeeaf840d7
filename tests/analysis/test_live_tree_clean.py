"""Tier-1 gate: the live tree carries ZERO unbaselined analyzer findings
— the engine invariants (cache coherence, rollback safety, jit purity,
Gwei dtype safety, host-sync boundaries, sharding contracts, effect
safety) plus the hygiene codes hold on every PR by construction.

The seeded-mutation tests prove the gate has teeth: re-introducing each
class of bug the semantic rules exist for (a stray ``store.latest_messages``
write, a dropped ``dtype=np.uint64``, a cache poke from outside the
owner, a state write outside the rollback region, a ``print`` in a jitted
kernel, an undeclared device pull-back, a spec-less ``shard_map``, an
unrouted cache insert next to a fault probe) turns the same analysis red
— via ``overrides``, which analyze hypothetical file contents at their
real tree paths without touching disk.  The battery runs full-tree (the
interprocedural rules need the project graph) against the gate's warm
cache, so each mutation only re-analyzes the mutated files plus their
call-graph dependents.
"""
import pytest

from analysis import REPO_ROOT, run
from analysis.core import REGISTRY


@pytest.fixture(scope="module")
def gate(tmp_path_factory):
    cache = tmp_path_factory.mktemp("analysis") / "cache.json"
    result = run(cache_path=cache)
    result._cache_path = cache
    return result


def test_live_tree_has_zero_unbaselined_findings(gate):
    assert gate.findings == [], [f.render() for f in gate.findings]


def test_no_stale_baseline_entries(gate):
    assert gate.stale_baseline == [], gate.stale_baseline


def test_baselined_findings_still_fire(gate):
    # the baseline holds reviewed findings, not dead entries: every one
    # matched a live finding this run (the CC01 resident-merkle install)
    assert {f.code for f in gate.baselined} == {"CC01"}


def test_full_tree_scale_and_budget(gate):
    assert gate.n_files > 250  # the whole tree, not a subset
    # acceptance: cold two-pass run on the 1 vCPU CI box with headroom
    assert gate.duration_s < 20, f"cold run took {gate.duration_s:.1f}s"


def test_warm_run_is_cached_and_fast(gate):
    warm = run(cache_path=gate._cache_path)
    assert warm.cache_hits == warm.n_files
    assert warm.findings == []
    # acceptance: <= 2 s warm on 1 vCPU; allow CI-noise headroom
    assert warm.duration_s < 2, f"warm run took {warm.duration_s:.1f}s"


def test_per_rule_budget_and_observability(gate):
    # every registered rule reports stats, and no single rule eats the
    # whole cold-run budget on the live tree (self-observability gate)
    from analysis import all_rules

    assert set(gate.rule_stats) == {r.code for r in all_rules()}
    for code, s in gate.rule_stats.items():
        assert s["time_s"] < 8.0, f"{code} took {s['time_s']:.2f}s"
        assert s["findings"] >= 0
    # the stats survive into the JSON report (make analyze -> ANALYSIS.json)
    report = gate.to_json()["rule_stats"]
    assert set(report) == set(gate.rule_stats)
    assert all("time_s" in v and "findings" in v for v in report.values())
    # the thread-role fixed point (ISSUE 15) reports its own wall time
    # and stays a rounding error of the run — it executes warm AND cold
    assert gate.to_json()["role_pass_s"] == round(gate.role_pass_s, 4)
    assert 0.0 <= gate.role_pass_s < 1.0, gate.role_pass_s


def test_warm_run_keeps_the_role_pass_cheap(gate):
    # the role pass is the one project-level pass a warm run cannot
    # skip; its budget is what keeps `make analyze` interactive
    warm = run(cache_path=gate._cache_path)
    assert warm.role_pass_s < 0.5, warm.role_pass_s


# -- seeded mutations: the gate must turn red --------------------------------

def _mutated(gate, mutations):
    """Analyze the live tree with ``mutations`` ({rel: mutate(text)})
    applied, full gate config (baseline included, project graph built,
    warm cache consulted read-only), returning unbaselined findings."""
    overrides = {}
    for rel, mutate in mutations.items():
        text = (REPO_ROOT / rel).read_text()
        mutated = mutate(text)
        assert mutated != text, f"mutation did not apply to {rel}"
        overrides[rel] = mutated
    return run(cache_path=gate._cache_path, overrides=overrides).findings


def test_fc01_mutation_turns_red(gate):
    rel = "consensus_specs_tpu/testing/helpers/fork_choice.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef fast_vote(store, i, message):\n"
        "    store.latest_messages[i] = message\n")})
    assert any(f.code == "FC01" for f in found), found


def test_dt01_mutation_turns_red(gate):
    rel = "consensus_specs_tpu/ops/epoch_jax.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        ",\n                       dtype=np.uint64", "")})
    assert sum(f.code == "DT01" for f in found) == 2, found


def test_cc01_mutation_turns_red(gate):
    rel = "consensus_specs_tpu/stf/attestations.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef _prime_permutation(seed, n, rounds):\n"
        "    perm = compute_shuffle_permutation(seed, n, rounds)\n"
        "    perm[0] = 0\n"
        "    return perm\n")})
    assert any(f.code == "CC01" for f in found), found


def test_rb01_mutation_turns_red(gate):
    rel = "consensus_specs_tpu/stf/verify.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef settle_and_advance(state, slot):\n"
        "    state.slot = slot\n")})
    assert any(f.code == "RB01" for f in found), found


def test_jx01_mutation_turns_red(gate):
    rel = "consensus_specs_tpu/ops/sha256_jax.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\n@jax.jit\n"
        "def _traced_debug(words):\n"
        "    print(words.shape)\n"
        "    return words\n")})
    assert any(f.code == "JX01" for f in found), found


def test_st01_mutation_turns_red(gate):
    rel = "consensus_specs_tpu/testing/helpers/block_processing.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef verify_each(bls, atts):\n"
        "    return [bls.FastAggregateVerify(a.pks, a.msg, a.sig)\n"
        "            for a in atts]\n")})
    assert any(f.code == "ST01" for f in found), found


def test_hd01_mutation_turns_red(gate):
    # un-declare the epoch kernel's staged-view boundary: the pull-back
    # the issue names (ops/epoch_jax.py) must be flagged again
    rel = "consensus_specs_tpu/ops/epoch_jax.py"
    found = _mutated(gate, {rel: lambda t: t.replace("# host-sync:",
                                                     "# host-off:")})
    assert sum(f.code == "HD01" for f in found) == 2, found


def test_sh01_mutation_turns_red(gate):
    # drop out_specs from the sharded pairing check's shard_map callsite
    rel = "consensus_specs_tpu/parallel/bls_sharded.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        "            out_specs=P(axis),\n", "")})
    assert any(f.code == "SH01" and "out_specs" in f.message
               for f in found), found


def test_ef01_mutation_turns_red(gate):
    # an unrouted insert into a registered memo right next to a fault
    # probe: PR 5's transactional discipline, machine-checked
    rel = "consensus_specs_tpu/stf/attestations.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef _poke_ctx(key, value):\n"
        "    _SITE_RESOLVE()\n"
        "    _CTX_CACHE[key] = value\n")})
    assert any(f.code == "EF01" for f in found), found


def test_ob01_unclosed_span_mutation_turns_red(gate):
    # a leaky raw timeline.begin next to the pipeline's real probe sites
    # (ISSUE 11): no finally-end, no escape — the span-leak check fires
    rel = "consensus_specs_tpu/stf/pipeline.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef leaky_probe(entries):\n"
        "    sid = timeline.begin('probe')\n"
        "    return verify.first_invalid(entries)\n")})
    assert any(f.code == "OB01" and "finally" in f.message
               for f in found), found


def test_io01_mutation_turns_red(gate):
    # a hand-rolled artifact promotion next to the real engine code
    # (ISSUE 14): the torn-write discipline lives in persist/atomic.py
    # ONLY — a bespoke os.replace outside it is gate-red
    rel = "consensus_specs_tpu/stf/columns.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\nimport os\n"
        "def _spill_column(tmp, path, col):\n"
        "    with open(tmp, 'wb') as f:\n"
        "        f.write(col.tobytes())\n"
        "    os.replace(tmp, path)\n")})
    assert any(f.code == "IO01" and "os.replace" in f.message
               for f in found), found
    assert any(f.code == "IO01" and "'wb'" in f.message
               for f in found), found


def test_cc01_cross_file_passthrough_mutation_turns_red(gate):
    # the call-graph-aware half of CC01: a helper in ANOTHER file passes
    # the registry-columns producer's cached dict through; mutating its
    # return value is flagged at the mutation site
    wrapper = "consensus_specs_tpu/ops/segment.py"
    user = "consensus_specs_tpu/stf/slot_roots.py"
    found = _mutated(gate, {
        wrapper: lambda t: t + (
            "\n\nfrom consensus_specs_tpu.ops.epoch_jax import "
            "registry_columns\n"
            "def cols_view(spec, state):\n"
            "    return registry_columns(spec, state)\n"),
        user: lambda t: t + (
            "\n\nfrom consensus_specs_tpu.ops.segment import cols_view\n"
            "def _corrupt(spec, state):\n"
            "    cols = cols_view(spec, state)\n"
            "    cols[\"effective_balance\"][0] = 0\n"
            "    return cols\n")})
    assert any(f.code == "CC01" and f.file == user for f in found), found


def test_dt01_cross_file_callsite_mutation_turns_red(gate):
    # the call-graph-aware half of DT01: the reducing helper carries no
    # hint in its own file; the hinted callsite lives a file away
    helper = "consensus_specs_tpu/ops/segment.py"
    user = "consensus_specs_tpu/forkchoice/batch.py"
    found = _mutated(gate, {
        helper: lambda t: t + (
            "\n\ndef total_of(values):\n"
            "    return np.sum(values)\n"),
        user: lambda t: t + (
            "\n\nfrom consensus_specs_tpu.ops.segment import total_of\n"
            "def _total_balance(balances):\n"
            "    return total_of(balances)\n")})
    assert any(f.code == "DT01" and f.file == user
               and "total_of" in f.message for f in found), found


def test_th01_pr9_span_stack_race_mutation_turns_red(gate):
    # PR 9's historical race, reintroduced: the span nesting stack as a
    # shared module global instead of thread-local — TH01 must flag the
    # mutation with the spawned roles that reach span() named
    rel = "consensus_specs_tpu/telemetry/metrics.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        "_tls = threading.local()  # per-thread span nesting stack",
        "_NEST: list = []\n"
        "_tls = threading.local()  # per-thread span nesting stack",
    ).replace("    stack = _stack()\n", "    stack = _NEST\n")})
    hits = [f for f in found if f.code == "TH01"]
    assert hits, found
    assert any("_NEST" in f.message and "pipeline-worker" in f.message
               and "metrics.span" in f.message for f in hits), hits


def test_th01_pr14_writer_staging_leak_mutation_turns_red(gate):
    # PR 14's historical race, reintroduced: the background checkpoint
    # writer riding the apply thread's open block transaction (the
    # _WRITER_THREAD gate and its justification removed)
    rel = "consensus_specs_tpu/persist/store.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        "    if not getattr(_WRITER_THREAD, \"active\", False):\n"
        "        # thread-safe: the _WRITER_THREAD.active flag above gates this\n"
        "        # off the background writer — only same-thread (synchronous)\n"
        "        # callers ride the apply thread's own open transaction\n"
        "        staging.note_insert(_INDEX, path)",
        "    staging.note_insert(_INDEX, path)")})
    hits = [f for f in found if f.code == "TH01"]
    assert hits, found
    assert any("block cache transaction" in f.message
               and "persist-writer" in f.message
               and "CheckpointStore._drain -> "
                   "persist.store.CheckpointStore.write_checkpoint"
                   in f.message for f in hits), hits


def test_th01_lock_free_requeue_front_mutation_turns_red(gate):
    # the ingest deque's registered lock dropped from requeue_front
    rel = "consensus_specs_tpu/node/ingest.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        "        with self._lock:\n"
        "            if len(self._items) >= self._cap:",
        "        if True:\n"
        "            if len(self._items) >= self._cap:")})
    assert any(f.code == "TH01" and "ingest queue deque" in f.message
               and "IngestQueue._lock" in f.message for f in found), found


def test_th01_unguarded_aggregation_buffer_write_mutation_turns_red(gate):
    # ISSUE 19's cross-role staging buffer: gossip producers write it,
    # the apply loop drains it — the admission lock dropped from the
    # producer-side staging write must turn the gate red
    rel = "consensus_specs_tpu/node/admission.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        '    item = WorkItem("attestations", payload, link, producer)\n'
        "    with _LOCK:\n"
        "        if producer in _QUARANTINED:",
        '    item = WorkItem("attestations", payload, link, producer)\n'
        "    if True:\n"
        "        if producer in _QUARANTINED:")})
    hits = [f for f in found if f.code == "TH01"]
    assert hits, found
    assert any("admission aggregation buffer" in f.message
               for f in hits), hits


def test_th01_undeclared_spawn_site_mutation_turns_red(gate):
    # registry completeness: a new production thread without a declared
    # role turns the gate red (the chaos COVERED_SITES pattern)
    rel = "consensus_specs_tpu/node/firehose.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\ndef _orphan_worker():\n"
        "    pass\n"
        "def _spawn_orphan():\n"
        "    threading.Thread(target=_orphan_worker).start()\n")})
    assert any(f.code == "TH01" and "no declared role" in f.message
               for f in found), found


def test_lk01_undeclared_lock_mutation_turns_red(gate):
    # registry completeness: a new production lock without a LockSpec
    rel = "consensus_specs_tpu/stf/pipeline.py"
    found = _mutated(gate, {rel: lambda t: t + (
        "\n\nimport threading\n"
        "_SIDE_LOCK = threading.Lock()\n")})
    assert any(f.code == "LK01" and "_SIDE_LOCK" in f.message
               for f in found), found


# -- spec-mirror parity (ISSUE 18): SP01/SP02/SP03 ---------------------------

def test_sp02_capella_fast_forks_mutation_turns_red(gate):
    # capella's obligations are declared (withdrawals, BLS changes, the
    # sweep), so widening FAST_FORKS past it — to eip4844, which no
    # declared spec chain covers — is what must turn the gate red now
    rel = "consensus_specs_tpu/stf/engine.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        'FAST_FORKS = ("phase0", "altair", "bellatrix", "capella")',
        'FAST_FORKS = ("phase0", "altair", "bellatrix", "capella", "eip4844")')})
    hits = [f for f in found if f.code == "SP02"]
    assert hits, found
    assert all(f.file == rel for f in hits)
    assert all("'eip4844'" in f.message for f in hits), hits
    assert not [f for f in found if f.code == "SP02" and "'capella'" in f.message]


def test_sp01_spec_body_edit_mutation_turns_red(gate):
    # a semantic edit to a pinned spec function names the mirror + fork
    rel = "consensus_specs_tpu/specs/src/phase0.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        "assert block.slot == state.slot",
        "assert block.slot >= state.slot", 1)})
    hits = [f for f in found if f.code == "SP01"]
    assert any(f.file == "consensus_specs_tpu/stf/engine.py"
               and "'_header'" in f.message
               and "process_block_header" in f.message
               and "phase0" in f.message for f in hits), found


def test_sp01_spec_comment_churn_stays_green(gate):
    # AST normalization: comment/docstring churn is not drift
    rel = "consensus_specs_tpu/specs/src/phase0.py"
    found = _mutated(gate, {rel: lambda t: t + "\n# annotated, no-op\n"})
    assert not [f for f in found if f.code.startswith("SP")], found


def test_sp03_guard_deletion_mutation_turns_red(gate):
    # deleting a mapped guard from a live mirror is red with the guard,
    # the spec twin, and the mirror named
    rel = "consensus_specs_tpu/stf/slot_roots.py"
    found = _mutated(gate, {rel: lambda t: t.replace(
        "    assert state.slot < slot", "    pass")})
    hits = [f for f in found if f.code == "SP03"]
    assert any("assert state.slot < slot" in f.message
               and "process_slots" in f.message for f in hits), found


def test_mirror_pass_budget_and_snapshot_report(gate):
    # the extraction pass reports its own wall time (ANALYSIS.json) and
    # stays within the warm per-rule budget; the per-fork snapshot
    # digests are the rows a pin bump is audited against
    js = gate.to_json()
    assert js["mirror_pass_s"] == round(gate.mirror_pass_s, 4)
    assert 0.0 <= gate.mirror_pass_s < 0.5, gate.mirror_pass_s
    assert set(js["spec_snapshot"]) == {
        "phase0", "altair", "bellatrix", "capella", "ssz"}
    assert all(len(d) == 64 for d in js["spec_snapshot"].values())


def test_warm_run_keeps_the_mirror_pass_cheap(gate):
    warm = run(cache_path=gate._cache_path)
    assert warm.mirror_pass_s < 0.5, warm.mirror_pass_s


def test_spec_edit_rederives_exactly_the_pinned_mirrors(gate):
    # cache correctness: a (semantically inert) spec-source edit shifts
    # the dependency digest of exactly the files whose registry pins
    # reach that source — every mirror file re-analyzes, nothing else
    from analysis import mirror_registry

    rel = "consensus_specs_tpu/specs/src/bellatrix.py"
    text = (REPO_ROOT / rel).read_text() + "\n# churn\n"
    res = run(cache_path=gate._cache_path, overrides={rel: text},
              changed_only=True)
    assert res.findings == [], [f.render() for f in res.findings]
    expected = {rel}
    for display, deps in mirror_registry.extra_file_deps().items():
        if rel in deps:
            expected.add(display)
    assert set(res.analyzed) == expected, (
        sorted(set(res.analyzed) ^ expected))


def test_changed_only_leaf_edit_stays_scoped(gate):
    # make analyze-changed: an untouched tree re-analyzes nothing; a
    # leaf edit re-analyzes the leaf (+ dependents — this leaf has none)
    clean = run(cache_path=gate._cache_path, changed_only=True)
    assert clean.analyzed == [] and clean.findings == []
    assert clean.stale_baseline == []
    leaf = "tests/analysis/test_noqa.py"
    text = (REPO_ROOT / leaf).read_text() + "\n# touched\n"
    res = run(cache_path=gate._cache_path, overrides={leaf: text},
              changed_only=True)
    assert set(res.analyzed) == {leaf}, sorted(res.analyzed)


def test_registry_covers_every_mutation_code():
    # every rule family proven red above is a registered plugin
    for code in ("FC01", "DT01", "CC01", "RB01", "JX01", "ST01",
                 "HD01", "SH01", "EF01", "OB01", "IO01", "TH01", "LK01",
                 "SP01", "SP02", "SP03"):
        assert code in REGISTRY, code
