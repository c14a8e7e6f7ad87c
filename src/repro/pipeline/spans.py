"""Named phases of a serving loop: a profiler span and an always-on counter
for each.

``Phases(prefix)`` is owned by the loop that it times (``ServePool`` keeps
one under the prefix ``pool``).  ``with phases("decode"):`` does two things:

* enters ``jax.profiler.TraceAnnotation("pool.decode", **attrs)``, which
  writes the span to the profiler's host timeline, on the clock the device
  planes use; keyword attributes become the event's stats (``rid=3`` reads
  back as the stat ``("rid", 3)``).  With the profiler off it records
  nothing;
* adds the phase's ``time.perf_counter()`` duration to the counter of that
  name: count, total seconds and longest single occurrence
  (``snapshot()``; the longest is taken since the previous snapshot, so a
  warm-up's compiles do not hide a later stall).

``with phases("emit") as span:`` gives the open annotation;
``span.set_metadata(finished=2)`` attaches an attribute known only at the
end.
"""

from __future__ import annotations

import contextlib
import time

import jax


class Phases:
    """Profiler spans ``<prefix>.<name>`` and per-name counters."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self._acc: dict[str, list] = {}     # name -> [n, seconds, max]

    @contextlib.contextmanager
    def __call__(self, name: str, **attrs):
        acc = self._acc.setdefault(name, [0, 0.0, 0.0])
        with jax.profiler.TraceAnnotation(f"{self.prefix}.{name}",
                                          **attrs) as span:
            t0 = time.perf_counter()
            try:
                yield span
            finally:
                dt = time.perf_counter() - t0
                acc[0] += 1
                acc[1] += dt
                acc[2] = max(acc[2], dt)

    def snapshot(self) -> dict[str, dict]:
        """``{name: {"n", "s", "max_s"}}`` for every phase entered so far:
        entries and seconds in all, and the longest single entry since the
        previous snapshot (0.0 if none)."""
        out = {k: {"n": n, "s": s, "max_s": m}
               for k, (n, s, m) in self._acc.items()}
        for acc in self._acc.values():
            acc[2] = 0.0
        return out
