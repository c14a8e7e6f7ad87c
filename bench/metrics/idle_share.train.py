"""Device: share of the traced window in which no operation ran on the
device (1 - union of the op intervals / window), in percent."""

from bench.trace import idle_share


def read(obs):
    tr = obs.get("trace")
    return 100.0 * idle_share(tr) if tr else None
