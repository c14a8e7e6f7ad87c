"""Pool: the host's own time per step, in ms: the mean over the
``pool.step`` spans inside the traced window of each span less the part
its ``pool.decode_wait`` and ``pool.first_token`` phases cover (there the
host waits on the device).  Nothing without the program's spans."""

from bench import pool_trace as P

WAITS = ("pool.decode_wait", "pool.first_token")


def read(obs):
    tr = obs.get("trace")
    spans = P.spans_of(obs)
    steps = P.in_window(tr, spans, "pool.step") if spans else []
    if not steps:
        return None
    waits = [sp for sp in spans if sp[2] in WAITS]
    own = P.self_times(steps + waits, "pool.step", WAITS)
    return 1e3 * sum(own) / len(own)
