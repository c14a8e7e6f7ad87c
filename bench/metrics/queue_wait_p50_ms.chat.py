"""Pool: median time from a request's due time to the step at which it left
the queue (``ServePool`` status no longer ``queued``), observed by the
benchmark after each ``pool.step()``."""

import statistics


def read(obs):
    waits = obs["counters"].get("queue_wait_s")
    return statistics.median(waits) * 1e3 if waits else None
