"""The one traffic generator: turns a mix's parameters (``traffic/<mix>.json``)
and a seed into concrete requests.

Every seed gets the same multiset of sizes and gaps, in another order:
each length or gap is the distribution's quantile at ``(k + 0.5) / n``, and
the seed shuffles them and draws the token ids.  Runs on different seeds
then do the same amount of work, so their spread is the system's and not
the draw's.

Distributions (``{"dist": ..., "min": lo, "max": hi}``, clipped to
``[min, max]``):

* ``lognormal`` with ``median`` and ``sigma`` (of the underlying normal);
* ``uniform`` over the integers ``[min, max]``.

Arrivals of an open loop are a Poisson process at ``rate_rps``: ``n =
round(rate * seconds)`` exponential gaps of mean ``1 / rate``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request: when it is due (seconds from the window's start; 0 for
    closed-loop clients), its prompt and its output budget."""

    at_s: float
    prompt: np.ndarray
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed; any
    non-negative whole seed, however large, is accepted."""
    return np.random.default_rng([int(seed), int(stream)])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths: the stratified quantiles of ``spec``, shuffled."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = np.clip(np.rint(vals), lo, hi).astype(np.int64)
    return rng.permutation(out)


def arrivals(rate_rps: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Poisson arrival times in ``[0, seconds)``: the first at 0, then
    stratified exponential gaps of mean ``1 / rate_rps``, shuffled."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / rate_rps
    t = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    return t[t < seconds]


def prompts(lens: np.ndarray, vocab: int,
            rng: np.random.Generator) -> list[np.ndarray]:
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """The requests of one open-loop window, in due order.

    With ``"schedule_seed"`` in the mix, the arrival gaps and the lengths,
    in their order, come from that fixed seed, as a replayed trace would,
    and the run's seed draws only the token ids: a tail of queueing then
    measures the system and not where a seed happened to put the bursts."""
    sched = mix.get("schedule_seed", seed)
    at = arrivals(mix["rate_rps"], seconds, rng_for(sched, 1))
    n = len(at)
    p = lengths(mix["prompt_len"], n, rng_for(sched, 2))
    o = lengths(mix["output_len"], n, rng_for(sched, 3))
    toks = prompts(p, vocab, rng_for(seed, 4))
    return [Req(float(a), t, int(m)) for a, t, m in zip(at, toks, o)]


def closed_loop(mix: dict, seed: int, vocab: int) -> list[list[Req]]:
    """Each client's queue of requests (``per_client`` of them), submitted
    one after another as the previous one finishes.

    With ``"stagger": true`` each client joins in the middle of its first
    request: that request keeps a share ``(r + 0.5) / clients`` of its
    output budget, ``r`` a seeded rank of the client, so first completions
    spread evenly over one request's length instead of all falling due
    together."""
    c, k = int(mix["clients"]), int(mix["per_client"])
    p = lengths(mix["prompt_len"], c * k, rng_for(seed, 2))
    o = lengths(mix["output_len"], c * k, rng_for(seed, 3))
    toks = prompts(p, vocab, rng_for(seed, 4))
    if mix.get("stagger"):
        share = (rng_for(seed, 5).permutation(c) + 0.5) / c
        o[::k] = np.maximum(1, np.ceil(o[::k] * share)).astype(o.dtype)
    reqs = [Req(0.0, t, int(m)) for t, m in zip(toks, o)]
    return [reqs[i * k:(i + 1) * k] for i in range(c)]


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words of a seed, for ``jax.random`` keys."""
    s = int(seed)
    return s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF
