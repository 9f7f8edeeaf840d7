"""The one span seam, ``tracing.span``: a span feeds the metrics report,
the causal timeline (same name, link and fields) and, while tracing is
enabled, the profiler as a ``cstpu.<name>`` annotation; disabled, it emits
nothing; and importing telemetry does not import ``jax``."""
import os
import subprocess
import sys

import pytest

from consensus_specs_tpu import tracing
from consensus_specs_tpu.telemetry import metrics, timeline

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class StubAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the order in
    which annotations open and close."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture(autouse=True)
def _clean():
    was = timeline.enabled()
    tracing.reset()
    tracing.disable()
    timeline.reset()
    StubAnnotation.log = []
    yield
    tracing.reset()
    tracing.disable()
    timeline.reset()
    timeline.enable() if was else timeline.disable()


def test_span_feeds_report_timeline_and_profiler(monkeypatch):
    tracing.enable()
    timeline.enable()
    monkeypatch.setattr(metrics, "_annotation", StubAnnotation)
    with tracing.span("host/operations", link=7, slot=3):
        with tracing.span("stf/committees"):
            pass
    spans = tracing.report()["spans"]
    assert spans["host/operations"]["count"] == 1
    assert spans["host/operations/stf/committees"]["count"] == 1
    begins = [e for e in timeline.events() if e["ph"] == "B"]
    assert [e["name"] for e in begins] == ["host/operations", "host/operations/stf/committees"]
    assert begins[0]["link"] == 7 and begins[0]["slot"] == 3
    assert "link" not in begins[1]
    # the profiler leg carries each span's own name; the profiler nests them
    assert StubAnnotation.log == [("enter", "cstpu.host/operations"),
                                  ("enter", "cstpu.stf/committees"),
                                  ("exit", "cstpu.stf/committees"),
                                  ("exit", "cstpu.host/operations")]


def test_span_closes_its_annotation_when_the_body_raises(monkeypatch):
    tracing.enable()
    monkeypatch.setattr(metrics, "_annotation", StubAnnotation)
    with pytest.raises(ValueError):
        with tracing.span("stf/state_root"):
            raise ValueError("refused")
    assert StubAnnotation.log == [("enter", "cstpu.stf/state_root"),
                                  ("exit", "cstpu.stf/state_root")]
    assert tracing.report()["spans"]["stf/state_root"]["count"] == 1


def test_disabled_emits_nothing(monkeypatch):
    monkeypatch.setattr(metrics, "_annotation", StubAnnotation)
    with tracing.span("host/operations", link=1, slot=2):
        pass
    assert tracing.report() == {"spans": {}, "counters": {}}
    assert timeline.events() == []
    assert StubAnnotation.log == []


def test_timeline_alone_keeps_the_profiler_leg_off(monkeypatch):
    timeline.enable()
    monkeypatch.setattr(metrics, "_annotation", StubAnnotation)
    with tracing.span("native/verify", link=4, entries=9):
        pass
    (begin,) = [e for e in timeline.events() if e["ph"] == "B"]
    assert (begin["name"], begin["link"], begin["entries"]) == ("native/verify", 4, 9)
    assert tracing.report()["spans"] == {}
    assert StubAnnotation.log == []


def test_enable_resolves_the_profiler_annotation():
    import jax

    tracing.enable()
    assert metrics._annotation is jax.profiler.TraceAnnotation


def test_importing_telemetry_does_not_import_jax():
    code = ("import sys\n"
            "import consensus_specs_tpu.telemetry, consensus_specs_tpu.tracing\n"
            "print('jax' in sys.modules)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"
