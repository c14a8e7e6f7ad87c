"""Profiler capture and the reduction from a trace to numbers.

The reduction works on plain lists of ``(start_ns, end_ns, name)`` events,
so it can be checked on synthetic traces.  ``load`` reads an ``.xplane.pb``
written by ``jax.profiler``:

* device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per device operation, their ``XLA Modules`` line one event per
  call of a compiled program (``jit_<function>(<id>)``);
* the benchmark's own host spans are events named ``bench.*`` on the host
  plane (``jax.profiler.TraceAnnotation``), on the same clock.

The traced window is the span ``bench.window``.
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    """One traced window: per-device op and module events, host spans."""

    window: tuple[int, int]
    ops: list[list[tuple[int, int, str]]]       # per device
    modules: list[list[tuple[int, int, str]]]   # per device
    spans: list[tuple[int, int, str]]           # host, bench.* only

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def clip(events, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def union(events) -> list[tuple[int, int]]:
    """Merged ``(start, end)`` intervals covered by any event."""
    out: list[list[int]] = []
    for s, e, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    tot = [sum(e - s for s, e in union(clip(ops, trace.window)))
           for ops in trace.ops]
    return sum(tot) / max(len(tot), 1) * 1e-9


def idle_share(trace: Trace) -> float:
    """1 - busy / window, as a fraction."""
    return 1.0 - busy_s(trace) / trace.window_s


def program_calls(trace: Trace, function: str) -> list[float]:
    """Device seconds of each call of the program jitted from ``function``
    (module events named ``jit_<function>`` or ``jit_<function>(<id>)``),
    inside the window, over all devices."""
    names = (f"jit_{function}", f"jit_{function}(")
    out = []
    for mods in trace.modules:
        for s, e, n in clip(mods, trace.window):
            if n == names[0] or n.startswith(names[1]):
                out.append((e - s) * 1e-9)
    return out


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` operation names with the most device time (averaged over
    devices), as ``[name, seconds]``."""
    acc: dict[str, int] = {}
    for ops in trace.ops:
        for s, e, name in clip(ops, trace.window):
            acc[name] = acc.get(name, 0) + (e - s)
    d = max(len(trace.ops), 1)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / d * 1e-9] for n, t in best]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps of device 0 inside the window, each named
    by the host span that covered most of it (``host`` where none did), as
    ``[name, seconds]``."""
    if not trace.ops:
        return []
    busy = union(clip(trace.ops[0], trace.window))
    lo, hi = trace.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [sp for sp in trace.spans if sp[2] != WINDOW_SPAN]
    out = []
    for s, e in gaps[:k]:
        cover: dict[str, int] = {}
        for ss, se, name in spans:
            ov = min(e, se) - max(s, ss)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        name = max(cover, key=cover.get) if cover else "host"
        out.append([name, (e - s) * 1e-9])
    return out


# --------------------------------------------------------------------------
# reading an xplane file
# --------------------------------------------------------------------------


def _events(line):
    return [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
             short_name(ev.name)) for ev in line.events]


def short_name(name: str) -> str:
    """A device op's HLO text cut to its name and instruction:
    ``%fusion.12 = bf16[..] fusion(...)`` -> ``%fusion.12 fusion``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    depth, i = 0, 0
    for i, ch in enumerate(rhs):           # skip the (possibly tuple) type
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    op = rhs[i + 1:].split("(", 1)[0]
    return f"{lhs} {op}".strip()


def load(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {line.name: line for line in plane.lines}
            ops.append(_events(lines["XLA Ops"]) if "XLA Ops" in lines else [])
            modules.append(_events(lines["XLA Modules"])
                           if "XLA Modules" in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[2].startswith("bench.")]
    windows = [sp for sp in spans if sp[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    window = windows[-1][:2]
    return Trace(window, ops, modules, spans)
