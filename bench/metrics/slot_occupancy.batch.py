"""Pool: mean share of slots live per decode step over the window, from
``ServePool.stats()`` (``occupancy`` x ``decode_steps`` x ``slots`` is the
live-slot-step count) taken at the window's open and close."""


def read(obs):
    s0, s1 = obs["counters"].get("stats_open"), obs["counters"].get(
        "stats_close")
    if not s0 or not s1:
        return None
    steps = s1["decode_steps"] - s0["decode_steps"]
    if steps <= 0:
        return None
    live = (s1["occupancy"] * s1["decode_steps"]
            - s0["occupancy"] * s0["decode_steps"])
    return 100.0 * live / steps
