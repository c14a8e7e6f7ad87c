"""Serving windows over the program's ``ServePool``: an open loop (requests
due on a Poisson schedule, whether or not the pool kept up) and a closed
loop (each client submits its next request when its last one ends).

The window drives ``ServePool.step()``; after each step the benchmark reads
every active request's token count and stamps new tokens with the host
clock.  Time to first token is measured from the request's due time, so a
stall delays every request due behind it; inter-token gaps are the gaps
between successive observations of a request's tokens (tokens that appear
together count a gap of 0).

After the window, a sample of finished requests drawn from the seed (the
longest among them, and others from across the slots) goes to the plain
reference: for each served token,
the gap by which its reference logit lies below the reference's best at
that position.  The widest gap is compared with the cell's limit.
"""

from __future__ import annotations

import dataclasses
import statistics
import time

import numpy as np

from bench import generator
from bench.harness import log, now

TERMINAL = ("done", "failed")


@dataclasses.dataclass
class Obs:
    """What the benchmark saw of one request."""

    req: generator.Req
    due: float                    # host time it was due
    rid: int | None = None
    submitted: float | None = None
    left_queue: float | None = None
    stamps: list = dataclasses.field(default_factory=list)   # (t, count)
    status: str = "queued"
    served: np.ndarray | None = None   # kept for the reference check

    @property
    def count(self) -> int:
        return self.stamps[-1][1] if self.stamps else 0

    def ttft(self) -> float | None:
        return self.stamps[0][0] - self.due if self.stamps else None

    def gaps(self) -> list[float]:
        out, prev = [], None
        for t, n in self.stamps:
            if prev is not None:
                out.append(t - prev[0])
                out += [0.0] * (n - prev[1] - 1)
            else:
                out += [0.0] * (n - 1)
            prev = (t, n)
        return out


class HostClock:
    """The host's clock; tests pass a virtual one with the same methods."""

    now = staticmethod(now)

    def sleep_until(self, t: float, annotate) -> None:
        with annotate("bench.idle_wait"):
            while True:
                left = t - now()
                if left <= 0:
                    return
                time.sleep(min(left, 0.002))


class Recorder:
    """Observes the pool after each step."""

    def __init__(self, pool, clock):
        self.pool = pool
        self.clock = clock
        self.active: list[Obs] = []
        self.steps: list[tuple] = []    # (t, live rows, sum of live context)

    def submit(self, ob: Obs, annotate):
        with annotate("bench.submit"):
            ob.rid = self.pool.submit(ob.req.prompt, ob.req.max_new)
        ob.submitted = self.clock.now()
        self.active.append(ob)

    def step(self, annotate) -> None:
        with annotate("bench.pool_step"):
            self.pool.step()
        t = self.clock.now()
        live, ctx, keep = 0, 0, []
        for ob in self.active:
            r = self.pool.request(ob.rid)
            if ob.left_queue is None and r.status != "queued":
                ob.left_queue = t
            n = len(r.tokens)
            if n != ob.count:
                ob.stamps.append((t, n))
            ob.status = r.status
            if r.status == "live":
                live += 1
                ctx += r.prompt.size + n
            if r.status not in TERMINAL:
                keep.append(ob)
        self.active = keep
        self.steps.append((t, live, ctx))

    @property
    def busy(self) -> bool:
        p = self.pool
        return bool(p.live or p.pending or p.admitting)


def warm(pool, mix: dict, vocab: int) -> None:
    """Run one request of every prefill shape the mix can produce (each
    bucket, and a prompt long enough to be chunked), through to its end:
    every program the window uses is compiled before it opens."""
    opts = mix["pool"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    lens, b = [], max(opts.get("bucket_min", 8), 1)
    while b < hi * 2:
        n = min(max(lo, b), hi)
        if n not in lens:
            lens.append(n)
        b *= 2
    rng = np.random.default_rng(0)
    rids = [pool.submit(rng.integers(0, vocab, n).astype(np.int32), 2)
            for n in lens]
    pool.run()
    bad = [pool.request(r).status for r in rids
           if pool.request(r).status != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests did not finish: {bad}")
    log("warm", prompt_lens=lens, **{k: pool.stats()[k] for k in (
        "decode_steps", "prefill_traces")})


def open_loop(pool, mix: dict, seed: int, seconds: float, vocab: int,
              annotate, on_window, clock=None) -> dict:
    """One open-loop window; ``on_window(phase, t)`` is called with
    ``"open"``, ``"tick"`` before each step and ``"close"`` (the traced
    sub-window hooks in there)."""
    clock = clock or HostClock()
    now = clock.now
    reqs = generator.open_loop(mix, seed, seconds, vocab)
    rec = Recorder(pool, clock)
    t0 = now()
    obs = [Obs(r, t0 + r.at_s) for r in reqs]
    on_window("open", t0)
    i, late = 0, []
    while True:
        t = now()
        if t - t0 >= seconds:
            break
        on_window("tick", t)
        while i < len(obs) and obs[i].due <= t:
            rec.submit(obs[i], annotate)
            late.append(obs[i].submitted - obs[i].due)
            i += 1
        if rec.busy:
            rec.step(annotate)
        else:
            nxt = obs[i].due if i < len(obs) else t0 + seconds
            clock.sleep_until(min(nxt, t0 + seconds), annotate)
    t_close = now()
    on_window("close", t_close)
    due = obs[:i]
    drain_end = t_close + mix["drain_s"]
    while rec.busy and now() < drain_end:
        rec.step(annotate)
    return {"obs": due, "t0": t0, "t_close": t_close, "t_end": now(),
            "late": late, "steps": rec.steps, "pool": pool}


def closed_loop(pool, mix: dict, seed: int, seconds: float, vocab: int,
                annotate, on_window, clock=None) -> dict:
    """Prime every client's first request (set-up: the window opens on a
    full pool), then keep each client's next request in the pool as soon as
    its last one ends (a client that reaches the end of its queue starts it
    again)."""
    clock = clock or HostClock()
    now = clock.now
    queues = generator.closed_loop(mix, seed, vocab)
    rec = Recorder(pool, clock)
    cur = [Obs(q[0], now()) for q in queues]
    for ob in cur:
        rec.submit(ob, annotate)
    while pool.pending or pool.admitting:
        rec.step(annotate)
    nxt = [1] * len(queues)
    t0 = now()
    on_window("open", t0)
    done_in_window: list[Obs] = []
    tokens0 = {id(ob): ob.count for ob in cur}
    while now() - t0 < seconds:
        on_window("tick", now())
        rec.step(annotate)
        for c, ob in enumerate(cur):
            if ob.status in TERMINAL:
                done_in_window.append(ob)
                q = queues[c]
                new = Obs(q[nxt[c] % len(q)], now())
                nxt[c] += 1
                rec.submit(new, annotate)
                tokens0[id(new)] = 0
                cur[c] = new
    t_close = now()
    on_window("close", t_close)
    return {"obs": done_in_window + cur, "t0": t0, "t_close": t_close,
            "t_end": t_close, "late": [], "steps": rec.steps, "pool": pool,
            "tokens0": tokens0}


LOOPS = {"open_loop": open_loop, "closed_loop": closed_loop}


# --------------------------------------------------------------------------
# end-to-end numbers
# --------------------------------------------------------------------------


def p95(values: list[float]) -> float:
    """95th percentile (``statistics.quantiles``, exclusive method)."""
    if len(values) < 2:
        return values[0] if values else float("nan")
    return statistics.quantiles(values, n=20)[-1]


def end_to_end(run: dict) -> dict:
    """An open loop's requests count as failed unless done by the end of the
    drain (their time to first token is then taken at the drain's end); a
    closed loop's requests still running at the close are not failures."""
    obs, t0, tc = run["obs"], run["t0"], run["t_close"]
    missing_at = run["t_end"]
    closed = "tokens0" in run
    ttft, gaps, failed = [], [], 0
    for ob in obs:
        ok = ob.status == "done" or (closed and ob.status != "failed")
        if not ok:
            failed += 1
        t = ob.ttft()
        ttft.append(t if (ok and t is not None) else missing_at - ob.due)
        gaps += ob.gaps()
    tokens = 0
    for ob in obs:
        before = run.get("tokens0", {}).get(id(ob), 0)
        inside = [n for t, n in ob.stamps if t0 <= t <= tc]
        earlier = [n for t, n in ob.stamps if t < t0]
        start = max([before] + earlier)
        if inside:
            tokens += inside[-1] - start
    return {"ttft_p95_ms": p95(ttft) * 1e3,
            "itl_p95_ms": p95(gaps) * 1e3 if gaps else float("nan"),
            "decode_tok_s": tokens / (tc - t0),
            "attempted": len(obs), "failed": failed}


def window_counters(run: dict) -> dict:
    """Counters the per-layer readers take from a serving window."""
    t0, tc = run["t0"], run["t_close"]
    waits = [ob.left_queue - ob.due for ob in run["obs"]
             if ob.left_queue is not None]
    late = run["late"]
    steps = [s for s in run["steps"] if t0 <= s[0] <= tc]
    return {"queue_wait_s": waits,
            "late_s": late,
            "steps": steps,
            "slots": run["pool"].slots}


# --------------------------------------------------------------------------
# correctness against the plain reference
# --------------------------------------------------------------------------


def sample(run: dict, seed: int, requests: int) -> list[Obs]:
    """Up to ``requests`` finished requests: the longest, and the others
    drawn from the seed among every request finished (many slots' worth,
    not one); their tokens are copied out of the pool."""
    done = [ob for ob in run["obs"] if ob.status == "done"]
    if not done:
        return []
    longest = max(done, key=lambda ob: ob.req.prompt.size + ob.count)
    rest = [ob for ob in done if ob is not longest]
    order = generator.rng_for(seed, 9).permutation(len(rest))
    out = [longest] + [rest[k] for k in order[:requests - 1]]
    for ob in out:
        ob.served = np.asarray(run["pool"].request(ob.rid).tokens, np.int64)
    return out


def _bucket(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def ref_logits(ref, rcfg: dict, params, w_head, prompt, served,
               quant=None) -> np.ndarray:
    """Reference logits at each position that produced a served token, over
    the prompt followed by the served tokens (lengths padded to a power of
    two at the end, which a causal model never attends)."""
    import jax.numpy as jnp
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = np.arange(prompt.size - 1, seq.size)
    toks = np.zeros(_bucket(seq.size), np.int32)
    toks[:seq.size] = seq
    rws = np.full(_bucket(rows.size), rows[-1], np.int32)
    rws[:rows.size] = rows
    lg = ref.logits(rcfg, params, w_head, jnp.asarray(toks),
                    jnp.asarray(rws), quant)
    return np.asarray(lg, np.float64)[:rows.size]


def gap(lg: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far below the row's best each chosen token's logit lies."""
    return lg.max(-1) - lg[np.arange(len(chosen)), chosen]


def check(ref, rcfg: dict, params, picked: list, seed: int,
          control: bool = False) -> dict:
    """Readings of the served tokens of ``picked`` against the reference.
    ``max_logit_gap`` and ``mean_logit_gap`` decide ``correct``; with
    ``control`` also the gaps of the token a float8 reference puts first
    (the control) and of a served token replaced at random (a fault)."""
    import jax
    w_head = jax.jit(ref.head)(params)
    rng = generator.rng_for(seed, 10)
    out = {"requests": len(picked), "tokens": 0, "max_logit_gap": 0.0}
    ctl, fault, total, ctl_total = 0.0, 0.0, 0.0, 0.0
    for ob in picked:
        served = np.asarray(ob.served, np.int64)
        lg = ref_logits(ref, rcfg, params, w_head, ob.req.prompt, served)
        out["tokens"] += served.size
        g = gap(lg, served)
        out["max_logit_gap"] = max(out["max_logit_gap"], float(g.max()))
        total += float(g.sum())
        if control:
            lq = ref_logits(ref, rcfg, params, w_head, ob.req.prompt, served,
                            "fp8")
            gq = gap(lg, lq.argmax(-1))
            ctl = max(ctl, float(gq.max()))
            ctl_total += float(gq.sum())
            bad = served.copy()
            k = int(rng.integers(0, bad.size))
            bad[k] = (bad[k] + 1 + int(rng.integers(0, lg.shape[1] - 1))
                      ) % lg.shape[1]
            fault = max(fault, float(gap(lg, bad).max()))
    # the widest gap catches one wrong token; the mean separates a lower
    # precision, whose widest gap lies within 3x of the program's
    out["mean_logit_gap"] = total / max(out["tokens"], 1)
    if control:
        out["control_fp8_max_logit_gap"] = ctl
        out["control_fp8_mean_logit_gap"] = ctl_total / max(out["tokens"], 1)
        out["fault_token_altered_max_logit_gap"] = fault
    return out
