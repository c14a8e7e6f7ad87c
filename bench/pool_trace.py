"""The program's own host spans in a profiler trace, and where the device
sat idle among them.

``ServePool.step`` writes one span ``pool.<phase>`` per phase of a step
(``src/repro/pipeline/spans.py``), on the host timeline and on the clock of
the device planes.  ``bench.trace.load`` keeps only the benchmark's
``bench.*`` spans; ``load`` here reads the ``pool.*`` ones from the same
``.xplane.pb`` as ``(start_ns, end_ns, name, attrs)``.  A program without
them (an older commit) gives an empty list, and the readers built on it
report nothing.

The reduction works on plain lists, so it can be checked on synthetic
traces, as ``bench.trace``'s is.
"""

from __future__ import annotations

import glob
import os

from bench import harness
from bench.trace import WINDOW_SPAN, Trace, clip, union

PREFIX = "pool."
# where bench/run.py profiles a traced run; it reads the metrics before it
# removes the directory
TRACE_DIR = str(harness.ROOT / ".cache" / "bench" / "trace")


def load(directory: str) -> list[tuple[int, int, str, dict]]:
    """The ``pool.*`` host spans of the newest ``.xplane.pb`` under
    ``directory``, with their attributes."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns), ev.name,
                                {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda sp: sp[0])


def spans_of(obs: dict) -> list[tuple[int, int, str, dict]]:
    """The program spans of the traced run ``obs`` describes:
    ``obs["program_spans"]`` where given, else read from ``TRACE_DIR`` once
    and kept in ``obs`` for the next reader.  Empty without a trace."""
    if obs.get("trace") is None:
        return []
    if obs.get("program_spans") is None:
        obs["program_spans"] = load(TRACE_DIR)
    return obs["program_spans"]


def in_window(trace: Trace, spans, name: str) -> list:
    """The spans called ``name`` that lie wholly inside the window."""
    lo, hi = trace.window
    return [sp for sp in spans if sp[2] == name and lo <= sp[0]
            and sp[1] <= hi]


def self_times(spans, parent: str, children) -> list[float]:
    """Seconds of each span called ``parent``, less the part of it that
    spans named in ``children`` cover."""
    kids = [sp[:3] for sp in spans if sp[2] in children]
    out = []
    for s, e, name, *_ in spans:
        if name == parent:
            covered = sum(b - a for a, b in union(clip(kids, (s, e))))
            out.append((e - s - covered) * 1e-9)
    return out


def idle_gaps(trace: Trace) -> list[tuple[int, int]]:
    """Device 0's idle intervals inside the window."""
    lo, hi = trace.window
    busy = union(clip(trace.ops[0], trace.window)) if trace.ops else []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_inside(trace: Trace, spans, name: str) -> float:
    """Seconds inside the window in which device 0 runs no operation while
    the host is inside a span called ``name``."""
    host = union(clip([sp[:3] for sp in spans if sp[2] == name],
                      trace.window))
    gaps, out, i, j = idle_gaps(trace), 0, 0, 0
    while i < len(gaps) and j < len(host):
        lo = max(gaps[i][0], host[j][0])
        hi = min(gaps[i][1], host[j][1])
        out += max(hi - lo, 0)
        if gaps[i][1] < host[j][1]:
            i += 1
        else:
            j += 1
    return out * 1e-9


def _innermost(active: dict) -> str:
    """The innermost open ``pool.*`` span, else the innermost ``bench.*``
    one, else ``host``."""
    for prefix in (PREFIX, "bench."):
        opened = [(s, i, n) for i, (s, n) in active.items()
                  if n.startswith(prefix)]
        if opened:
            return max(opened)[2]
    return "host"


def idle_by_phase(trace: Trace, spans) -> tuple[dict, list]:
    """Device 0's idle seconds in the window, by the innermost host span
    that covered them (``pool.*`` before ``bench.*``, ``host`` where none
    did), and the window's longest idle gap as ``[name, seconds]``, named
    by the span that covered most of it."""
    named = [sp[:3] for sp in spans] + [
        sp for sp in trace.spans if sp[2] != WINDOW_SPAN]
    named = clip(named, trace.window)
    gaps = idle_gaps(trace)
    # sweep over every edge; at one instant, ends come before starts
    edges = [(s, 1, -1) for s, _ in gaps] + [(e, 0, -1) for _, e in gaps]
    for k, (s, e, _) in enumerate(named):
        edges += [(s, 1, k), (e, 0, k)]
    edges.sort()
    by_phase: dict[str, float] = {}
    per_gap: dict[int, dict] = {}
    active: dict[int, tuple] = {}
    gap, prev = None, None
    for t, opens, k in edges:
        if gap is not None and t > prev:
            name = _innermost(active)
            by_phase[name] = by_phase.get(name, 0.0) + (t - prev) * 1e-9
            cover = per_gap.setdefault(gap, {})
            cover[name] = cover.get(name, 0) + (t - prev)
        prev = t
        if k < 0:
            gap = t if opens else None
        elif opens:
            active[k] = (named[k][0], named[k][2])
        else:
            active.pop(k, None)
    longest = []
    if gaps:
        s, e = max(gaps, key=lambda g: g[1] - g[0])
        cover = per_gap.get(s, {})
        longest = [max(cover, key=cover.get) if cover else "host",
                   (e - s) * 1e-9]
    return by_phase, longest

