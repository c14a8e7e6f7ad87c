"""Pool: share of the traced window in which device 0 runs no operation
while the host is inside ``pool.step``, in percent (at most
``idle_share.chat``; the rest of the idle time lies outside the pool).
Nothing without the program's spans or a device plane.

Also prints the earlier line ``[idle-by-phase]``: the window's idle
seconds by the innermost host span that covered them, and the longest idle
gap with the span that covered most of it."""

from bench import pool_trace as P
from bench.harness import log


def read(obs):
    tr = obs.get("trace")
    spans = P.spans_of(obs)
    if not spans or not tr.ops:
        return None
    by_phase, longest = P.idle_by_phase(tr, spans)
    log("idle-by-phase", window_s=tr.window_s, idle_s=by_phase,
        longest_gap=longest)
    return 100.0 * P.idle_inside(tr, spans, "pool.step") / tr.window_s
