"""Perf-trend gate of the bench driver (ISSUE 7 satellite / ROADMAP item
5): the headline row diffs against the newest previous ``BENCH_r0N.json``
driver snapshot and the run exits non-zero on a >15% regression, so a
PR's wins can't silently erode."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def _snapshot(tmp_path, n, parsed):
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(
        json.dumps({"n": n, "rc": 0, "parsed": parsed}))


_ROW = {"metric": "mainnet_epoch_e2e_bls_on_400000", "value": 10.0,
        "unit": "s", "vs_baseline": 100.0}


def test_newest_snapshot_picks_highest_usable(tmp_path):
    _snapshot(tmp_path, 1, dict(_ROW, value=30.0))
    _snapshot(tmp_path, 2, dict(_ROW, value=20.0))
    # newest file is corrupt: the gate must fall back to the newest USABLE
    (tmp_path / "BENCH_r03.json").write_text("{not json")
    row = bench.newest_bench_snapshot(str(tmp_path))
    assert row["value"] == 20.0


def test_newest_snapshot_skips_unparsed_rows(tmp_path):
    _snapshot(tmp_path, 1, dict(_ROW, value=30.0))
    _snapshot(tmp_path, 2, None)  # failed run: no parsed headline
    assert bench.newest_bench_snapshot(str(tmp_path))["value"] == 30.0


def test_newest_snapshot_none_when_empty(tmp_path):
    assert bench.newest_bench_snapshot(str(tmp_path)) is None


def test_trend_within_budget_passes():
    cur = dict(_ROW, value=11.4)  # +14% of 10.0: inside the 15% budget
    assert bench.check_perf_trend(cur, _ROW) is None
    assert bench.check_perf_trend(dict(_ROW, value=6.0), _ROW) is None


def test_trend_regression_flagged():
    cur = dict(_ROW, value=11.6)  # +16%
    msg = bench.check_perf_trend(cur, _ROW)
    assert msg is not None and "perf-trend regression" in msg
    assert _ROW["metric"] in msg


def test_trend_not_comparable_is_silent():
    # different metric (e.g. a BENCH_VALIDATORS override), missing
    # snapshot, or garbled values must not block the run
    other = dict(_ROW, metric="mainnet_epoch_e2e_bls_on_1000")
    assert bench.check_perf_trend(dict(_ROW, value=99.0), other) is None
    assert bench.check_perf_trend(dict(_ROW, value=99.0), None) is None
    assert bench.check_perf_trend(dict(_ROW, value="nan?"), _ROW) is None
    assert bench.check_perf_trend(
        dict(_ROW, value=99.0), dict(_ROW, value=0.0)) is None


# -- forkchoice_batch_ingest row gate (ISSUE 8) ------------------------------

_FC_ROW = {"metric": "forkchoice_batch_ingest_100000_attestations_400000_validators",
           "value": 50_000.0, "unit": "attestations/s", "vs_baseline": 12.0}


def test_fc_trend_error_row_blocks():
    msg = bench.check_forkchoice_trend({"error": "AssertionError('6.3x')"}, None)
    assert msg is not None and "errored" in msg


def test_fc_trend_margin_floor_blocks():
    msg = bench.check_forkchoice_trend(dict(_FC_ROW, vs_baseline=9.9), None)
    assert msg is not None and "10x floor" in msg
    assert bench.check_forkchoice_trend(dict(_FC_ROW, vs_baseline=10.0),
                                        None) is None


def test_fc_trend_throughput_regression_flagged():
    # value is attestations/s: SMALLER is the regression direction
    cur = dict(_FC_ROW, value=40_000.0)  # -20% vs 50k
    msg = bench.check_forkchoice_trend(cur, _FC_ROW)
    assert msg is not None and "perf-trend regression" in msg
    assert bench.check_forkchoice_trend(dict(_FC_ROW, value=44_000.0),
                                        _FC_ROW) is None  # -12%: in budget


def test_fc_trend_not_comparable_is_silent():
    assert bench.check_forkchoice_trend(None, _FC_ROW) is None  # QUICK skip
    assert bench.check_forkchoice_trend(_FC_ROW, None) is None
    assert bench.check_forkchoice_trend(_FC_ROW, {"error": "x"}) is None
    other = dict(_FC_ROW, metric="forkchoice_batch_ingest_other")
    assert bench.check_forkchoice_trend(dict(_FC_ROW, value=1.0), other) is None


# -- counter-invariant gate (ISSUE 9) -----------------------------------------

_TEL = {"plan_hits": 1952, "plan_misses": 2144, "plan_hit_ratio": 0.476,
        "memo_hits": 1952, "memo_hit_ratio": 0.465,
        "h2c_hits": 31, "h2c_misses": 4128, "h2c_hit_ratio": 0.007,
        "column_hits": 0, "column_misses": 0,
        "replayed_blocks": 0, "breaker_state": "closed",
        "breaker_trips": 0, "native_degraded": 0}


def _e2e_row(**tel_overrides):
    return {"metric": "mainnet_epoch_e2e_bls_on_400000", "value": 3.4,
            "unit": "s", "telemetry": dict(_TEL, **tel_overrides)}


def test_counters_healthy_row_passes():
    assert bench.check_counter_invariants(_e2e_row()) is None
    assert bench.check_counter_invariants(_e2e_row(), _e2e_row()) is None


def test_counters_replayed_blocks_block():
    msg = bench.check_counter_invariants(_e2e_row(replayed_blocks=2))
    assert msg is not None and "replayed 2 blocks" in msg


def test_counters_open_breaker_and_degradation_block():
    msg = bench.check_counter_invariants(_e2e_row(breaker_state="open"))
    assert msg is not None and "breaker open" in msg
    msg = bench.check_counter_invariants(_e2e_row(native_degraded=1))
    assert msg is not None and "degraded" in msg


def test_counters_quarantined_items_block():
    # ISSUE 13: a dead-lettered item in a fault-free bench run means the
    # apply path broke and containment absorbed it — refuse the headline
    msg = bench.check_counter_invariants(_e2e_row(quarantined_items=1))
    assert msg is not None and "quarantined 1 items" in msg
    # a row that doesn't report the counter (pre-ISSUE-13) stays silent
    assert bench.check_counter_invariants(_e2e_row()) is None


def test_counters_store_corruptions_block():
    # ISSUE 14: a corrupt checkpoint artifact in a fault-free bench run
    # means the write path tore or the codec drifted — the degradation
    # ladder absorbs it silently, so the counter gate must not
    msg = bench.check_counter_invariants(_e2e_row(store_corruptions=2))
    assert msg is not None and "2 corrupt checkpoint" in msg
    msg = bench.check_counter_invariants(_e2e_row(restore_fallbacks=1))
    assert msg is not None and "full journal replay" in msg
    # zero counters (the healthy recovery row) stay silent
    assert bench.check_counter_invariants(
        _e2e_row(store_corruptions=0, restore_fallbacks=0)) is None


def test_counters_hit_rate_floor_breach_blocks():
    # the exit-4 path the driver sees: a keying regression zeroes the
    # plan hit ratio while wall-time may still look fine
    msg = bench.check_counter_invariants(_e2e_row(plan_hit_ratio=0.1))
    assert msg is not None and "plan_hit_ratio" in msg and "floor" in msg
    msg = bench.check_counter_invariants(_e2e_row(memo_hit_ratio=0.2))
    assert msg is not None and "memo_hit_ratio" in msg
    # exactly at the floor passes
    assert bench.check_counter_invariants(
        _e2e_row(plan_hit_ratio=0.25, memo_hit_ratio=0.25)) is None


def test_counters_h2c_drift_vs_previous():
    prev = _e2e_row(h2c_hit_ratio=0.4)
    assert bench.check_counter_invariants(
        _e2e_row(h2c_hit_ratio=0.3), prev) is None  # within 0.15 drift
    msg = bench.check_counter_invariants(
        _e2e_row(h2c_hit_ratio=0.2), prev)
    assert msg is not None and "h2c_hit_ratio" in msg
    # no previous telemetry -> no absolute h2c floor (corpus-dependent)
    assert bench.check_counter_invariants(
        _e2e_row(h2c_hit_ratio=0.0)) is None


def test_counters_not_comparable_is_silent():
    # pre-telemetry rows, errored rows, skipped rows: never block
    assert bench.check_counter_invariants(None) is None
    assert bench.check_counter_invariants({"error": "x"}) is None
    assert bench.check_counter_invariants(
        {"metric": "m", "value": 1.0}) is None  # PR-8-era row, no telemetry
    row = _e2e_row()
    del row["telemetry"]["plan_hit_ratio"]  # ratio absent (zero total)
    assert bench.check_counter_invariants(row) is None


# -- overlap-ratio floor + scale rows (ISSUE 10) ------------------------------


def test_counters_overlap_floor_breach_blocks():
    # a pipelined row whose overlap collapsed (e.g. every block silently
    # drained the speculation window) refuses the headline even when
    # wall-clock noise hides the slowdown
    row = _e2e_row(pipeline_dispatched=32, overlap_ratio=0.1,
                   overlap_s=0.05)
    msg = bench.check_counter_invariants(row)
    assert msg is not None and "overlap_ratio" in msg and "floor" in msg
    # at the floor passes
    assert bench.check_counter_invariants(
        _e2e_row(pipeline_dispatched=32, overlap_ratio=0.25)) is None


def test_counters_overlap_floor_skips_pipeline_off_rows():
    # CSTPU_PIPELINE=0 runs (and pre-pipeline rows) dispatch nothing:
    # no overlap requirement applies
    assert bench.check_counter_invariants(
        _e2e_row(pipeline_dispatched=0, overlap_ratio=None)) is None
    assert bench.check_counter_invariants(
        _e2e_row(pipeline_dispatched=0, overlap_ratio=0.0)) is None
    # dispatched but ratio unavailable (no worker time recorded): silent
    assert bench.check_counter_invariants(
        _e2e_row(pipeline_dispatched=32, overlap_ratio=None)) is None


# -- perf-doctor attribution in the refusal (ISSUE 11) ------------------------


def _details_row(value, **overrides):
    """A BENCH_DETAILS-shaped headline row (phase subtree included)."""
    row = {"metric": _ROW["metric"], "value": value, "unit": "s",
           "sig_verify_s": 0.60, "attestation_apply_s": 0.80,
           "sync_apply_s": 0.0, "slot_roots_s": 0.57, "other_s": 0.29,
           "telemetry": {"plan_hit_ratio": 0.49}}
    row.update(overrides)
    return row


def test_trend_refusal_includes_doctor_attribution():
    # the exit-4 path names its suspect: the refusal message carries the
    # perf-doctor line when the previous DETAILS row is comparable
    cur = _details_row(11.6, attestation_apply_s=1.90)   # +16% vs 10.0
    msg = bench.check_perf_trend(cur, _ROW,
                                 previous_details=_details_row(10.0))
    assert msg is not None and "perf-trend regression" in msg
    assert "doctor:" in msg
    assert "attestation_apply_s +1.10 s" in msg


def test_trend_refusal_attribution_carries_telemetry_drift():
    cur = _details_row(
        11.6, attestation_apply_s=1.90,
        telemetry={"plan_hit_ratio": 0.22})
    msg = bench.check_perf_trend(cur, _ROW,
                                 previous_details=_details_row(10.0))
    assert msg is not None
    assert "plan_hit_ratio fell 0.49 -> 0.22" in msg


def test_trend_refusal_without_details_stays_plain():
    # no previous details (first post-ISSUE-11 run) -> the plain refusal
    msg = bench.check_perf_trend(dict(_ROW, value=11.6), _ROW)
    assert msg is not None and "doctor:" not in msg


def test_trend_refusal_with_uncomparable_details_stays_plain():
    # errored / phase-free previous rows must never break the gate
    for prev_details in ({"error": "x"}, {"metric": _ROW["metric"],
                                          "value": 10.0}, None):
        msg = bench.check_perf_trend(dict(_ROW, value=11.6), _ROW,
                                     previous_details=prev_details)
        assert msg is not None and "doctor:" not in msg


def test_within_budget_never_invokes_the_doctor():
    cur = _details_row(11.4, attestation_apply_s=1.90)  # +14%: in budget
    assert bench.check_perf_trend(cur, _ROW,
                                  previous_details=_details_row(10.0)) is None


def _scale_row(n, value, **tel_overrides):
    return {"metric": f"mainnet_epoch_e2e_bls_on_{n}", "value": value,
            "unit": "s", "telemetry": dict(_TEL, **tel_overrides)}


def test_scale_rows_gate_counters_and_trend():
    # the 1M/2M rows ride the SAME counter-invariant gate as the 400k
    # rows (bench.main wires them through check_counter_invariants)...
    two_m = _scale_row(1 << 21, 14.0, replayed_blocks=1)
    msg = bench.check_counter_invariants(two_m)
    assert msg is not None and "replayed 1 blocks" in msg
    assert bench.check_counter_invariants(_scale_row(1 << 21, 14.0)) is None
    # ...and their wall time rides check_perf_trend vs the previous
    # BENCH_DETAILS row (preserved rows compare equal and pass)
    prev = _scale_row(1 << 21, 10.0)
    assert bench.check_perf_trend(_scale_row(1 << 21, 11.4), prev) is None
    msg = bench.check_perf_trend(_scale_row(1 << 21, 11.6), prev)
    assert msg is not None and "perf-trend regression" in msg
    assert bench.check_perf_trend(prev, prev) is None
    # a 1M row never compares against a 2M row (metric mismatch)
    assert bench.check_perf_trend(
        _scale_row(1 << 20, 99.0), _scale_row(1 << 21, 10.0)) is None


# -- cold-start + query-load row gates (ISSUE 16) -----------------------------

_CS_ROW = {"metric": "cold_start_checkpoint_400000_validators", "value": 1.2,
           "unit": "s", "vs_baseline": 25.0}


def test_cold_start_error_row_blocks():
    msg = bench.check_cold_start_trend({"error": "AssertionError('7x')"}, None)
    assert msg is not None and "errored" in msg


def test_cold_start_margin_floor_blocks():
    msg = bench.check_cold_start_trend(dict(_CS_ROW, vs_baseline=9.9), None)
    assert msg is not None and "10x floor" in msg
    assert bench.check_cold_start_trend(dict(_CS_ROW, vs_baseline=10.0),
                                        None) is None
    # a row that lost its margin field entirely is refused, not ignored
    row = dict(_CS_ROW)
    del row["vs_baseline"]
    msg = bench.check_cold_start_trend(row, None)
    assert msg is not None and "vs_baseline" in msg


def test_cold_start_restore_time_regression_flagged():
    # value is restore seconds: LARGER is the regression direction
    cur = dict(_CS_ROW, value=1.4)  # +16.7% vs 1.2
    msg = bench.check_cold_start_trend(cur, _CS_ROW)
    assert msg is not None and "perf-trend regression" in msg
    assert _CS_ROW["metric"] in msg
    assert bench.check_cold_start_trend(dict(_CS_ROW, value=1.35),
                                        _CS_ROW) is None  # +12.5%: in budget


def test_cold_start_not_comparable_is_silent():
    assert bench.check_cold_start_trend(None, _CS_ROW) is None  # QUICK skip
    assert bench.check_cold_start_trend(_CS_ROW, None) is None
    assert bench.check_cold_start_trend(_CS_ROW, {"error": "x"}) is None
    other = dict(_CS_ROW, metric="cold_start_checkpoint_1000_validators")
    assert bench.check_cold_start_trend(dict(_CS_ROW, value=99.0),
                                        other) is None
    assert bench.check_cold_start_trend(
        dict(_CS_ROW, value=99.0), dict(_CS_ROW, value=0.0)) is None


_QL_ROW = {"metric": "node_query_load_2readers_400000_validators",
           "value": 40.0, "unit": "ms", "query_errors": 0, "served": 5000}


def test_query_trend_error_row_blocks():
    msg = bench.check_query_trend({"error": "RuntimeError('no engine')"},
                                  None)
    assert msg is not None and "errored" in msg


def test_query_trend_reader_errors_block():
    # a fault-free bench run where readers errored means the read path
    # broke under the firehose — refuse the headline
    msg = bench.check_query_trend(dict(_QL_ROW, query_errors=3), None)
    assert msg is not None and "3" in msg and "errors" in msg


def test_query_trend_zero_served_blocks():
    msg = bench.check_query_trend(dict(_QL_ROW, served=0), None)
    assert msg is not None and "zero queries" in msg


def test_query_trend_p99_regression_flagged():
    # value is p99 ms: LARGER is the regression direction
    cur = dict(_QL_ROW, value=47.0)  # +17.5% vs 40.0
    msg = bench.check_query_trend(cur, _QL_ROW)
    assert msg is not None and "perf-trend regression" in msg
    assert _QL_ROW["metric"] in msg
    assert bench.check_query_trend(dict(_QL_ROW, value=45.0),
                                   _QL_ROW) is None  # +12.5%: in budget


def test_query_trend_not_comparable_is_silent():
    assert bench.check_query_trend(None, _QL_ROW) is None  # QUICK skip
    assert bench.check_query_trend(_QL_ROW, None) is None
    assert bench.check_query_trend(_QL_ROW, {"error": "x"}) is None
    other = dict(_QL_ROW, metric="node_query_load_4readers_400000_validators")
    assert bench.check_query_trend(dict(_QL_ROW, value=99.0), other) is None
    assert bench.check_query_trend(
        dict(_QL_ROW, value=99.0), dict(_QL_ROW, value=0.0)) is None


# -- node_firehose serving gate (ISSUE 19) -----------------------------------

_FH_ROW = {"metric": ("node_firehose_2epochs_100032_gossip_atts_"
                      "400000_validators"),
           "value": 4.0, "unit": "s", "atts_per_s": 55_000.0,
           "queue_blocked_s": 0.012}


def test_firehose_trend_error_row_blocks():
    msg = bench.check_firehose_trend({"error": "TimeoutError('starved')"},
                                     None)
    assert msg is not None and "errored" in msg


def test_firehose_throughput_regression_flagged():
    # atts_per_s is the serving claim: SMALLER is the regression
    # direction, independent of the wall-time `value`
    cur = dict(_FH_ROW, atts_per_s=44_000.0)  # -20% vs 55k
    msg = bench.check_firehose_trend(cur, _FH_ROW)
    assert msg is not None and "perf-trend regression" in msg
    assert "att/s" in msg
    assert bench.check_firehose_trend(dict(_FH_ROW, atts_per_s=48_000.0),
                                      _FH_ROW) is None  # -12.7%: in budget


def test_firehose_blocked_time_growth_flagged():
    # the tentpole turned 37.8s of blocked puts into near-zero: the gate
    # refuses when blocked time climbs back over the previous run
    cur = dict(_FH_ROW, queue_blocked_s=5.2)
    msg = bench.check_firehose_trend(cur, _FH_ROW)
    assert msg is not None and "blocked" in msg
    # millisecond noise under the 1s floor never refuses...
    assert bench.check_firehose_trend(dict(_FH_ROW, queue_blocked_s=0.9),
                                      _FH_ROW) is None
    # ...and a large-but-shrinking value passes (recovery round)
    assert bench.check_firehose_trend(
        dict(_FH_ROW, queue_blocked_s=5.0),
        dict(_FH_ROW, queue_blocked_s=37.8)) is None


def test_firehose_adversarial_slowdown_cap():
    # the adversarial row embeds honest-atts/s ÷ adversarial-atts/s:
    # over the 1.3x cap refuses even with no previous row to diff
    row = dict(_FH_ROW, vs_honest_slowdown=1.42)
    msg = bench.check_firehose_trend(row, None)
    assert msg is not None and "1.42x" in msg and "1.3x cap" in msg
    assert bench.check_firehose_trend(
        dict(_FH_ROW, vs_honest_slowdown=1.3), None) is None
    # honest rows carry no ratio (None when the honest row errored):
    # the cap check stays silent
    assert bench.check_firehose_trend(
        dict(_FH_ROW, vs_honest_slowdown=None), None) is None


def test_firehose_not_comparable_is_silent():
    assert bench.check_firehose_trend(None, _FH_ROW) is None  # skipped row
    assert bench.check_firehose_trend(_FH_ROW, None) is None
    assert bench.check_firehose_trend(_FH_ROW, {"error": "x"}) is None
    # the 4-producer row never diffs against the 16-producer row
    other = dict(_FH_ROW, metric=("node_firehose_16p_2epochs_100032_"
                                  "gossip_atts_400000_validators"))
    assert bench.check_firehose_trend(dict(_FH_ROW, atts_per_s=1.0),
                                      other) is None
    # pre-ISSUE-19 previous rows (no atts_per_s / queue_blocked_s keys)
    prev = {"metric": _FH_ROW["metric"], "value": 4.0}
    assert bench.check_firehose_trend(_FH_ROW, prev) is None


def test_counters_batch_bisections_block():
    # ISSUE 19: the honest firehose corpus is all-valid — a bisected
    # gossip run in a fault-free bench means the batching layer broke
    msg = bench.check_counter_invariants(_e2e_row(batch_bisections=1))
    assert msg is not None and "bisected 1 gossip runs" in msg
    assert bench.check_counter_invariants(
        _e2e_row(batch_bisections=0)) is None


def _dist_row(**tel_overrides):
    tel = {"tasks": 12, "dispatched": 12, "replies": 12,
           "redispatched_chunks": 0, "hedged_tasks": 0,
           "fallback_runs": 0, "fabric_runs": 3,
           "workers_lost": 0, "corrupt_replies": 0,
           "breaker_state": "closed"}
    return {"metric": "dist_verify_fabric_2workers_512x128_400000",
            "value": 0.61, "unit": "s",
            "telemetry": dict(tel, **tel_overrides)}


def test_counters_dist_redispatch_blocks():
    # ISSUE 20: a fault-free fabric run re-dispatches nothing — a
    # nonzero count means workers are dying under zero injected faults,
    # and first-valid-reply-wins keeps the wall time looking healthy
    msg = bench.check_counter_invariants(_dist_row(redispatched_chunks=2))
    assert msg is not None and "re-dispatched 2 chunks" in msg
    assert bench.check_counter_invariants(_dist_row()) is None


def test_counters_dist_fallback_and_losses_block():
    # the ladder silently demoting to in-process (or losing workers /
    # corrupting replies) is behavioral rot wall-time never shows
    msg = bench.check_counter_invariants(_dist_row(fallback_runs=1))
    assert msg is not None and "demoted 1 runs to in-process" in msg
    msg = bench.check_counter_invariants(_dist_row(workers_lost=1))
    assert msg is not None and "lost 1 workers" in msg
    msg = bench.check_counter_invariants(_dist_row(corrupt_replies=3))
    assert msg is not None and "3 corrupt replies" in msg
    # the dist breaker rides the generic breaker-state check
    msg = bench.check_counter_invariants(_dist_row(breaker_state="open"))
    assert msg is not None and "breaker open" in msg


def test_dist_row_rides_the_perf_trend_gate():
    cur, prev = _dist_row(), _dist_row()
    assert bench.check_perf_trend(cur, prev) is None
    cur = dict(cur, value=prev["value"] * 1.5)
    msg = bench.check_perf_trend(cur, prev)
    assert msg is not None and "dist_verify_fabric" in msg


# -- analyzer-gate refusal line (ISSUE 18 satellite) -------------------------

class _F:
    def __init__(self, code, file, line, message):
        self.code, self.file, self.line, self.message = (
            code, file, line, message)


def test_analyzer_refusal_surfaces_sp_mirror_and_fork():
    # an SP finding carries the drifted mirror + fork in its message:
    # the refusal must print it even when a hygiene finding sorts first
    sp = _F("SP01", "consensus_specs_tpu/stf/engine.py", 725,
            "mirror '_header' drifted from spec twin 'process_block_header'"
            " at fork(s) phase0: pinned dda1eb99d09b..., now 1f2e3d4c5b6a...")
    other = _F("DT01", "consensus_specs_tpu/ops/epoch.py", 3, "raw int")
    line = bench.analyzer_refusal_line([other, sp], [])
    assert "2 unbaselined" in line
    assert "SP01 in consensus_specs_tpu/stf/engine.py:725" in line
    assert "'_header'" in line and "phase0" in line
    assert "exit" not in line  # the exit code is the caller's contract


def test_analyzer_refusal_plain_first_offender():
    f = _F("DT01", "x.py", 3, "raw int where Gwei is required")
    line = bench.analyzer_refusal_line([f], [])
    assert "1 unbaselined" in line
    assert "first: DT01 in x.py:3" in line
    # non-SP messages stay out of the one-liner (no mirror/fork payload)
    assert "raw int" not in line


def test_analyzer_refusal_stale_only():
    line = bench.analyzer_refusal_line(
        [], [{"file": "y.py", "code": "F401", "snippet": "import os",
              "justification": "gone"}])
    assert "1 unbaselined" in line
    assert "stale baseline entry in y.py" in line


def test_bench_start_up_requires_a_tpu_unless_pinned_to_cpu(monkeypatch):
    # the test process runs JAX on the CPU: no chip, so the benchmark
    # refuses to start — unless the caller pinned JAX_PLATFORMS=cpu
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench._require_chip()
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="not a TPU"):
        bench._require_chip()


@pytest.mark.parametrize("given, kept", [
    ("tpu", "tpu,cpu"),          # a chip machine's pin: the host backend joins
    ("tpu,cpu", "tpu,cpu"),
    ("cpu", "cpu"),              # a host rehearsal stays on the host
    (None, None),                # unset: JAX brings up every backend itself
])
def test_keep_host_backend(monkeypatch, given, kept):
    from consensus_specs_tpu import _jaxcache

    if given is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", given)
    _jaxcache.keep_host_backend()
    assert os.environ.get("JAX_PLATFORMS") == kept
