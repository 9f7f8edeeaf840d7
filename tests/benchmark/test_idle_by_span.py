"""Device-idle time attributed to the program's ``cstpu.*`` spans on the
profiler's clock (``benchmark/attribution.py``), on the built trace of
``test_trace_reduction`` with program annotations added: on the window's
line, nested, and on a second thread's line, which is left out.  The
annotations leave every key of ``trace.reduce`` as it was."""
from types import SimpleNamespace as NS

import pytest

from benchmark import attribution, trace

from .test_trace_reduction import built_trace, ev


def annotated_trace():
    planes = built_trace()
    host = planes[0]
    host.lines[0].events += [
        ev("cstpu.host/operations", 2000, 3000),
        ev("cstpu.stf/committees", 2200, 600),      # nested in host/operations
        ev("cstpu.stf/state_root", 8000, 2000),     # in the second step
        ev("cstpu.stf/state_root", 20000, 500),     # after the window
    ]
    host.lines.append(NS(name="cstpu-sigpipe_0", events=[
        ev("cstpu.native/verify", 1500, 7500),     # another thread: not the window's line
    ]))
    return planes


def test_program_spans_are_the_window_lines_annotations():
    spans = attribution.program_spans(annotated_trace())
    assert sorted(name for _, _, name in spans) == [
        "cstpu.host/operations", "cstpu.stf/committees", "cstpu.stf/state_root",
        "cstpu.stf/state_root"]


def test_idle_is_split_at_span_boundaries_and_given_to_the_innermost():
    # idle: 1500..3000, 6000..9000, 9500..11000 (6000 ns)
    assert attribution.idle_seconds(annotated_trace()) == {
        "bench.reset_caches": pytest.approx(500e-9),         # 1500..2000
        "cstpu.host/operations": pytest.approx(400e-9),      # 2000..2200, 2800..3000
        "cstpu.stf/committees": pytest.approx(600e-9),       # 2200..2800
        "bench.apply_signed_blocks": pytest.approx(1000e-9),  # 6000..7000
        "bench.step": pytest.approx(2000e-9),                # 7000..8000, 10000..11000
        "cstpu.stf/state_root": pytest.approx(1500e-9),      # 8000..9000, 9500..10000
    }


def test_idle_by_span_is_longest_first_and_sums_to_the_idle_time():
    planes = annotated_trace()
    ranked = attribution.idle_by_span(planes)
    assert [name for name, _ in ranked] == [
        "bench.step", "cstpu.stf/state_root", "bench.apply_signed_blocks",
        "cstpu.stf/committees", "bench.reset_caches", "cstpu.host/operations"]
    reduced = trace.reduce(planes)
    assert sum(s for _, s in ranked) == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_without_program_spans_idle_goes_to_the_benchmarks_spans():
    ranked = dict(attribution.idle_by_span(built_trace()))
    assert ranked == {"bench.reset_caches": pytest.approx(500e-9),
                      "bench.apply_signed_blocks": pytest.approx(2000e-9),
                      "bench.step": pytest.approx(3500e-9)}


def test_idle_seconds_are_averaged_over_devices():
    planes = annotated_trace()
    planes.append(NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[])]))
    seconds = attribution.idle_seconds(planes)
    # the second device is idle all window: 10000 ns, 2000 of it in state_root
    assert seconds["cstpu.stf/state_root"] == pytest.approx((1500 + 2000) * 1e-9 / 2)
    assert sum(seconds.values()) == pytest.approx((6000 + 10000) * 1e-9 / 2)


def test_annotations_leave_the_reduction_unchanged():
    assert trace.reduce(annotated_trace()) == trace.reduce(built_trace())


def test_no_device_plane_attributes_nothing():
    assert attribution.idle_by_span([annotated_trace()[0]]) == []
