"""Phase-attribution regression doctor (ISSUE 11): a synthetically
regressed snapshot pair must name the injected phase as the top
contributor, telemetry drift must ride the attribution, and the CLI path
must survive missing/uncomparable snapshots."""
import json
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import perf_doctor


def _row(value, **overrides):
    row = {
        "metric": "mainnet_epoch_e2e_bls_on_400000", "value": value,
        "unit": "s",
        "sig_verify_s": 0.60, "attestation_apply_s": 0.80,
        "sync_apply_s": 0.0, "slot_roots_s": 0.57, "other_s": 0.29,
        "resolve_s": 0.12, "apply_s": 0.42, "mirror_flush_s": 0.26,
        "hash_to_g2_s": 0.29, "msm_s": 0.44, "miller_s": 0.38,
        "marshal_s": 0.27, "overlap_s": 0.85,
        "telemetry": {"plan_hit_ratio": 0.49, "memo_hit_ratio": 0.46,
                      "h2c_hit_ratio": 0.01, "overlap_ratio": 0.55,
                      "replayed_blocks": 0, "breaker_trips": 0,
                      "native_degraded": 0, "pipeline_drains": 0},
    }
    tel = overrides.pop("telemetry", None)
    row.update(overrides)
    if tel:
        row["telemetry"] = {**row["telemetry"], **tel}
    return row


def test_injected_phase_is_the_top_contributor():
    # the acceptance case: +0.9 s injected into attestation_apply_s (with
    # a matching plan-cache collapse) on a +1.1 s total regression
    prev = _row(2.38)
    cur = _row(3.48, attestation_apply_s=1.70, apply_s=1.32,
               telemetry={"plan_hit_ratio": 0.22})
    diag = perf_doctor.diagnose_row(cur, prev)
    assert diag is not None and diag["regressed"]
    top = diag["contributors"][0]
    assert top["phase"] == "attestation_apply_s"
    assert abs(top["delta_s"] - 0.90) < 1e-6
    assert 0.7 <= top["share"] <= 0.9  # ~81% of the 1.10 s move
    # the sub-phase detail names apply_s as the interior mover
    assert top["sub_phases"][0]["phase"] == "apply_s"
    # and the telemetry drift carries the WHY
    drift = {d["key"]: d for d in diag["telemetry_drift"]}
    assert drift["plan_hit_ratio"]["prev"] == 0.49
    assert drift["plan_hit_ratio"]["cur"] == 0.22


def test_attribution_line_reads_like_the_issue_example():
    prev = _row(2.38)
    cur = _row(3.48, attestation_apply_s=1.70,
               telemetry={"plan_hit_ratio": 0.22})
    line = perf_doctor.attribution_line(cur, prev)
    assert line is not None
    assert "attestation_apply_s +0.90 s" in line
    assert "of the regression" in line
    assert "plan_hit_ratio fell 0.49 -> 0.22" in line


def test_regressed_phase_outranks_a_larger_improvement():
    # a regressed run whose largest-|delta| phase actually IMPROVED must
    # still name a regressed phase as the suspect (direction-aware rank)
    prev = _row(4.60, sig_verify_s=2.0, attestation_apply_s=1.0,
                slot_roots_s=1.0)
    cur = _row(4.90, sig_verify_s=1.5, attestation_apply_s=1.4,
               slot_roots_s=1.4)
    diag = perf_doctor.diagnose_row(cur, prev)
    assert diag["regressed"]
    assert diag["contributors"][0]["phase"] in ("attestation_apply_s",
                                                "slot_roots_s")
    assert diag["contributors"][0]["delta_s"] > 0
    line = perf_doctor.attribution_line(cur, prev)
    assert "+0.40 s" in line and "sig_verify_s" not in line.split(";")[0]


def test_improvement_attributes_without_regression_claim():
    prev = _row(3.48, sig_verify_s=1.58)
    cur = _row(2.38, sig_verify_s=0.48)
    diag = perf_doctor.diagnose_row(cur, prev)
    assert not diag["regressed"]
    assert diag["contributors"][0]["phase"] == "sig_verify_s"
    line = perf_doctor.attribution_line(cur, prev)
    assert "of the regression" not in line
    # render never crashes on either direction
    assert "sig_verify_s" in perf_doctor.render(diag)


def test_not_comparable_rows_return_none():
    assert perf_doctor.diagnose_row(None, _row(2.0)) is None
    assert perf_doctor.diagnose_row(_row(2.0), {"error": "x"}) is None
    other = _row(2.0, metric="mainnet_epoch_e2e_bls_on_1048576")
    assert perf_doctor.diagnose_row(_row(3.0), other) is None
    # a row with no phase keys (pre-PR-2 shape) is not attributable
    bare = {"metric": "m", "value": 2.0}
    assert not perf_doctor.is_e2e_row(bare)
    assert perf_doctor.diagnose_row(bare, bare) is None
    assert perf_doctor.attribution_line(_row(3.0), other) is None


def test_counter_appearance_is_drift():
    prev = _row(2.38)
    cur = _row(2.90, other_s=0.81,
               telemetry={"replayed_blocks": 3, "pipeline_drains": 3})
    diag = perf_doctor.diagnose_row(cur, prev)
    keys = {d["key"] for d in diag["telemetry_drift"]}
    assert {"replayed_blocks", "pipeline_drains"} <= keys


def test_histogram_tail_shifts_are_reported():
    prev = _row(2.38, phase_histograms={
        "slot_roots": {"count": 32, "p50_ms": 15.0, "p99_ms": 30.0}})
    cur = _row(2.50, phase_histograms={
        "slot_roots": {"count": 32, "p50_ms": 15.0, "p99_ms": 80.0}})
    diag = perf_doctor.diagnose_row(cur, prev)
    assert diag["histogram_shifts"] == [
        {"phase": "slot_roots", "prev_p99_ms": 30.0, "cur_p99_ms": 80.0}]
    assert "p99" in perf_doctor.render(diag)


def test_cli_on_snapshot_files(tmp_path, capsys):
    cur = {"epoch_e2e_bls": _row(3.48, attestation_apply_s=1.70),
           "unrelated": {"metric": "x", "value": 1}}
    prev = {"epoch_e2e_bls": _row(2.38)}
    a, b = tmp_path / "cur.json", tmp_path / "prev.json"
    a.write_text(json.dumps(cur))
    b.write_text(json.dumps(prev))
    assert perf_doctor.main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "attestation_apply_s" in out and "REGRESSED" in out
    assert "verdict:" in out


def test_cli_single_arg_is_an_error(capsys):
    assert perf_doctor.main(["only-one.json"]) == 2


def test_newest_snapshot_pair_prefers_prev_file(tmp_path):
    (tmp_path / "BENCH_DETAILS.json").write_text(
        json.dumps({"epoch_e2e_bls": _row(3.0)}))
    (tmp_path / "BENCH_DETAILS_PREV.json").write_text(
        json.dumps({"epoch_e2e_bls": _row(2.0)}))
    cur, prev, label = perf_doctor.newest_snapshot_pair(str(tmp_path))
    assert label == "BENCH_DETAILS_PREV.json"
    assert prev["epoch_e2e_bls"]["value"] == 2.0


def test_newest_snapshot_pair_falls_back_to_git_history(tmp_path):
    # a repo whose BENCH_DETAILS.json has committed history and no PREV
    # file: the fallback finds the newest differing committed version
    import subprocess

    def git(*args):
        subprocess.run(["git", "-c", "user.name=t", "-c", "user.email=t@t",
                        *args], cwd=tmp_path, check=True,
                       capture_output=True)

    details = tmp_path / "BENCH_DETAILS.json"
    git("init", "-q")
    details.write_text(json.dumps({"epoch_e2e_bls": _row(2.0)}))
    git("add", "BENCH_DETAILS.json")
    git("commit", "-q", "-m", "first")
    details.write_text(json.dumps({"epoch_e2e_bls": _row(3.0)}))
    cur, prev, label = perf_doctor.newest_snapshot_pair(str(tmp_path))
    assert label == "git history"
    assert cur["epoch_e2e_bls"]["value"] == 3.0
    assert prev["epoch_e2e_bls"]["value"] == 2.0
