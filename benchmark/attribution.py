"""Device-idle time of a trace's window, attributed to the program's own
spans on the profiler's clock.

While tracing is enabled, every ``tracing.span`` of the program is also a
``jax.profiler.TraceAnnotation`` named ``cstpu.<name>``, on the host line
of the thread that ran it.  ``program_spans`` collects those on the line
that holds ``bench.window`` (the thread that drives the cell); spans of
other threads, such as the signature pipeline's worker, run beside that
thread and are left out.  ``idle_by_span`` splits the window's device-idle
time (as ``trace.reduce`` computes it) at the boundaries of those spans and
of the benchmark's own ``bench.*`` spans, and gives each piece to the
innermost program span open over it, else to the innermost ``bench.*``
span (``bench.other`` where none is).
"""
from __future__ import annotations

from benchmark import trace

PREFIX = "cstpu."
TOP = 10


def _window_line(planes):
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace.WINDOW:
                    return line, (e.start_ns, e.start_ns + e.duration_ns)
    raise ValueError(f"no {trace.WINDOW} span in the trace")


def program_spans(planes) -> list:
    """[(start_ns, end_ns, name)] of the ``cstpu.*`` annotations on the
    host line that holds ``bench.window``."""
    line, _ = _window_line(planes)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events if e.name.startswith(PREFIX)]


def _innermost(spans, points) -> list:
    """For each piece ``[points[i], points[i + 1]]``, the name of the
    latest-started span of ``spans`` open over it, or None."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, j = [], [], 0
    for p in points[:-1]:
        while j < len(spans) and spans[j][0] <= p:
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def _idle_gaps(plane, lo, hi) -> list:
    """The stretches of ``[lo, hi]`` in which ``plane`` ran no operation."""
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
           for e in trace._events(plane, "XLA Ops")]
    busy = trace._union([iv for iv in ops if iv[0] < iv[1]])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


def idle_by_span(planes) -> list:
    """[[span name, idle seconds]] of the window, longest first, the top
    10."""
    ranked = sorted(idle_seconds(planes).items(), key=lambda kv: -kv[1])
    return [list(kv) for kv in ranked[:TOP]]


def idle_seconds(planes) -> dict:
    """{span name: idle seconds} of the window, every span that holds
    some; seconds are averaged over the devices, as ``busy_s`` is."""
    _, (lo, hi) = _window_line(planes)
    clip = [(max(s, lo), min(e, hi), n) for s, e, n in program_spans(planes)]
    program = [s for s in clip if s[0] < s[1]]
    bench = [s for s in trace.host_spans(planes) if s[2] != trace.WINDOW]
    devices = trace._device_planes(planes)
    if not devices:
        return {}
    gaps = [_idle_gaps(plane, lo, hi) for plane in devices]
    points = {lo, hi}
    for s, e, _ in program + bench:
        points.update(t for t in (s, e) if lo < t < hi)
    for device in gaps:
        points.update(t for g in device for t in g)
    points = sorted(points)
    labels = [p or b or "bench.other"
              for p, b in zip(_innermost(program, points), _innermost(bench, points))]
    totals = {}
    for device in gaps:
        k = 0
        for i, label in enumerate(labels):
            a, b = points[i], points[i + 1]
            while k < len(device) and device[k][1] <= a:
                k += 1
            if k < len(device) and device[k][0] <= a:  # the piece lies inside this gap
                totals[label] = totals.get(label, 0) + b - a
    return {name: ns / 1e9 / len(devices) for name, ns in totals.items()}
