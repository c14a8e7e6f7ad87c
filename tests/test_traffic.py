"""Open-loop traffic replay + continuous-batching admission tests.

The randomized stress draws arrivals, prompt lengths, token budgets, EOS
ids, and deadlines from a seeded rng (via the hypothesis-or-fixed-seed shim
in ``tests/conftest.py`` for the property-style case) and checks the two
invariants that make the continuous frontend trustworthy:

* TOKEN PARITY — every completed request's tokens equal a dedicated
  batch-1 serial generation, no matter how admissions chunked, bucketed,
  or interleaved with decode;
* CLEAN PAGE ACCOUNTING — after the replay drains, the paged pool holds
  zero used and zero reserved pages (nothing leaked across ~hundreds of
  adopt/recycle cycles).

Requests are drawn from a small combo grid (prompt length x budget x EOS x
deadline), so serial verification costs O(distinct combos) while the pool
serves 1000+ requests.  ``REPRO_TRAFFIC_N`` scales the per-case request
count (the nightly traffic-stress CI job raises it).

The compile-count regression pins the bucketing contract: heterogeneous
prompt lengths collapse to <= log2(max_len) distinct prefill shapes with
``bucket_prompts=True``, and the seeded violation (bucketing off) shows the
per-length retraces the bucket bound removes.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st
from repro import Session
from repro.pipeline import traffic

MAX_LEN = 32
# per-pool-config request count: 4 configs x 260 = 1040 requests by
# default; the nightly traffic-stress job raises REPRO_TRAFFIC_N
N_PER_CASE = int(os.environ.get("REPRO_TRAFFIC_N", "260"))
VOCAB = 50          # small vocab so EOS ids actually fire mid-stream

_SESSION = None
_SERIAL_CACHE: dict = {}


def _get_session():
    # memoized module global, NOT a fixture: the shim's ``given`` wrapper
    # takes no pytest fixtures (see tests/conftest.py)
    global _SESSION
    if _SESSION is None:
        _SESSION = Session.init("qwen3-14b")
    return _SESSION


def _prompt(plen: int) -> np.ndarray:
    rng = np.random.default_rng(1000 + plen)
    return rng.integers(1, VOCAB, size=plen).astype(np.int32)


def _serial_full(plen: int, n: int = 8) -> np.ndarray:
    """Greedy serial generation for the canonical ``plen`` prompt; greedy
    decoding is prefix-stable, so one n=8 run serves every budget <= 8."""
    key = (plen, n)
    if key not in _SERIAL_CACHE:
        s = _get_session()
        if not hasattr(s, "_serial_handle"):
            s._serial_handle = s.serve(1, MAX_LEN)
        out = s._serial_handle.generate(
            {"tokens": jnp.asarray(_prompt(plen))[None, :]}, n)
        _SERIAL_CACHE[key] = np.asarray(out)[0]
    return _SERIAL_CACHE[key]


def _expected(plen: int, budget: int, eos_id: int | None) -> np.ndarray:
    """Serial-truth tokens for one combo: budget-truncated, EOS-stopped."""
    toks = _serial_full(plen)[:budget]
    if eos_id is not None:
        hits = np.nonzero(toks == eos_id)[0]
        if hits.size:
            toks = toks[:hits[0] + 1]
    return toks


def _combo_trace(n: int, rate_rps: float, rng: np.random.Generator):
    """n arrivals drawn from the combo grid, Poisson-spaced.  Deadlines are
    generous (never expire) — they exercise the deadline bookkeeping, not
    expiry (expiry chaos lives in test_resilience.py)."""
    plens = (3, 5, 8, 13, 16)
    budgets = (1, 2, 4, 8)
    eoses = (None, 7, 11)           # vocab 50: these fire mid-stream often
    at = np.cumsum(rng.exponential(1.0 / rate_rps, size=n))
    out = []
    for i in range(n):
        plen = int(rng.choice(plens))
        budget = int(rng.choice(budgets))
        eos = eoses[int(rng.integers(len(eoses)))]
        deadline = 120.0 if rng.integers(2) else None
        out.append(traffic.TrafficRequest(float(at[i]), _prompt(plen),
                                          budget, eos, deadline))
    return out


def test_trace_deterministic():
    a = traffic.make_trace(50, 25.0, seed=9)
    b = traffic.make_trace(50, 25.0, seed=9)
    c = traffic.make_trace(50, 25.0, seed=10)
    assert all(x.at_s == y.at_s and np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) or x.at_s != y.at_s
               for x, y in zip(a, c))
    assert all(x.at_s < y.at_s for x, y in zip(a, a[1:]))  # strictly ordered


@pytest.mark.parametrize("kw", [
    dict(bucket_prompts=True),
    dict(prefill_chunk=4),
    dict(prefill_chunk=8, bucket_prompts=True),
    dict(prefill_chunk=4, bucket_prompts=True, paged=True, page_size=8),
], ids=["bucket", "chunk", "chunk+bucket", "chunk+bucket+paged"])
def test_traffic_stress_parity_and_page_accounting(kw):
    """The headline stress: N_PER_CASE open-loop arrivals per pool config
    (>= 1k requests across the parametrized cases at the default), every
    completion token-equal to serial, zero pages leaked."""
    session = _get_session()
    rng = np.random.default_rng(sum(map(ord, str(sorted(kw.items())))))
    trace = _combo_trace(N_PER_CASE, rate_rps=200.0, rng=rng)
    pool = session.serve_pool(slots=4, max_len=MAX_LEN, **kw)
    report = traffic.replay(pool, trace,
                            clock=traffic.VirtualClock(step_s=0.005),
                            max_steps=400 * N_PER_CASE)
    assert report.summary["completed"] == N_PER_CASE
    assert report.summary["failed"] == 0
    for req, rec in zip(trace, report.records):
        want = _expected(req.prompt.size, req.max_new_tokens, req.eos_id)
        np.testing.assert_array_equal(
            rec["tokens"], want,
            err_msg=f"rid {rec['rid']} (plen={req.prompt.size}, "
                    f"budget={req.max_new_tokens}, eos={req.eos_id})")
    st = pool.stats()
    assert not pool.admitting and pool.pending == 0 and pool.live == 0
    if st["page_pool"] is not None:
        assert st["page_pool"]["used"] == 0, "leaked pages after drain"
        assert st["page_pool"]["reserved"] == 0, "leaked reservations"
    # host time per phase: one first-token read per request, at least one
    # prefill chunk each, one decode wait per decode step
    ph = st["phases"]
    assert ph["first_token"]["n"] == N_PER_CASE
    assert ph["prefill_chunk"]["n"] >= N_PER_CASE
    assert ph["decode_wait"]["n"] == st["decode_steps"]
    assert all(0 <= p["max_s"] <= p["s"] and p["s"] > 0 for p in ph.values())
    assert st["prefill_tokens"] == sum(r.prompt.size for r in trace)
    # each request's FIRST token comes from the admission prefill, the
    # rest from batched decode
    assert st["decode_tokens"] == st["tokens_generated"] - N_PER_CASE


def test_fleet_traffic_stress_kill_pool_parity(tmp_path):
    """Fleet-scale stress (the CI traffic-stress job's "fleet" step): a
    3-replica router serves N_PER_CASE open-loop arrivals on the full
    continuous-admission config while chaos kills replica 1 mid-replay.
    Every request still completes token-equal to serial (failovers and
    rebuild included), the killed replica rejoins closed, and no replica
    leaks a page."""
    from repro.resilience import faults
    session = _get_session()
    rng = np.random.default_rng(20260808)
    trace = _combo_trace(N_PER_CASE, rate_rps=200.0, rng=rng)
    kill_step = max(10, N_PER_CASE // 6)    # mid-replay, tenants live
    with faults.fault_scope(faults.FaultPlan(kill_pool=(1, kill_step))):
        router = session.serve_fleet(
            3, slots=2, max_len=MAX_LEN, prefill_chunk=4,
            bucket_prompts=True, paged=True, page_size=8,
            session_dir=str(tmp_path / "fleet"),
            router=dict(breaker_cooldown_s=0.05))
        report = traffic.replay(router, trace,
                                clock=traffic.VirtualClock(step_s=0.005),
                                max_steps=400 * N_PER_CASE)
    assert report.summary["completed"] == N_PER_CASE
    assert report.summary["failed"] == 0 and report.summary["shed"] == 0
    for req, rec in zip(trace, report.records):
        want = _expected(req.prompt.size, req.max_new_tokens, req.eos_id)
        np.testing.assert_array_equal(
            rec["tokens"], want,
            err_msg=f"rid {rec['rid']} (plen={req.prompt.size}, "
                    f"budget={req.max_new_tokens}, eos={req.eos_id})")
    st = router.stats()
    assert st["trips"] == 1 and st["rebuilds"] == 1
    assert [r["state"] for r in st["replicas"]] == ["closed"] * 3
    assert st["outstanding"] == 0 and st["backlog"] == 0
    for rep in st["replicas"]:
        pp = rep["pool"]["page_pool"]
        assert pp["used"] == 0, f"replica {rep['idx']} leaked pages"
        assert pp["reserved"] == 0, f"replica {rep['idx']} leaked reservations"


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_replay_property_randomized_seeds(seed):
    """Property-style randomized replay (hypothesis when installed, the
    fixed-seed conftest shim otherwise): any seed's open-loop schedule
    yields serial-parity completions on the chunked+bucketed pool."""
    session = _get_session()
    rng = np.random.default_rng(seed)
    trace = _combo_trace(40, rate_rps=float(rng.integers(20, 400)), rng=rng)
    pool = session.serve_pool(slots=3, max_len=MAX_LEN,
                              prefill_chunk=4, bucket_prompts=True)
    report = traffic.replay(pool, trace,
                            clock=traffic.VirtualClock(step_s=0.005),
                            max_steps=40_000)
    assert report.summary["completed"] == len(trace)
    for req, rec in zip(trace, report.records):
        np.testing.assert_array_equal(
            rec["tokens"],
            _expected(req.prompt.size, req.max_new_tokens, req.eos_id))


def test_bucketed_admission_bounds_prefill_traces():
    """Compile-count regression: 14 distinct prompt lengths through a
    bucketed pool stay within the log2(max_len) trace budget; the pinned
    violation (bucketing off) retraces once per distinct length."""
    import math
    session = _get_session()
    lengths = list(range(3, 17))            # 14 distinct lengths
    bound = int(math.log2(MAX_LEN))         # 5 for MAX_LEN=32

    pool = session.serve_pool(slots=2, max_len=MAX_LEN, bucket_prompts=True)
    for n in lengths:
        pool.submit(_prompt(n), max_new_tokens=2)
    pool.run()
    st = pool.stats()
    assert st["prefill_traces"] <= bound, (
        f"bucketing leaked {st['prefill_traces']} distinct prefill shapes "
        f"(budget {bound})")
    # the jit cache agrees when the runtime exposes it
    cache_size = getattr(pool._chunk1, "_cache_size", None)
    if callable(cache_size):
        assert cache_size() <= bound

    # pinned seeded violation: same workload, bucketing disabled
    legacy = session.serve_pool(slots=2, max_len=MAX_LEN)
    for n in lengths:
        legacy.submit(_prompt(n), max_new_tokens=2)
    legacy.run()
    assert legacy.stats()["prefill_traces"] == len(lengths) > bound


def test_chunked_admission_interleaves_with_decode():
    """A long admission must not stall live tenants: while a 16-token
    prompt streams in 2-token chunks, the live tenant keeps producing a
    token per step.  (The legacy whole-prompt path stalls everyone for the
    full prefill + its jit trace.)"""
    session = _get_session()
    pool = session.serve_pool(slots=2, max_len=MAX_LEN, prefill_chunk=2)
    r1 = pool.submit(_prompt(3), max_new_tokens=8)
    pool.step()                             # admit r1 (now live)
    assert pool.request(r1).status == "live"
    r2 = pool.submit(_prompt(16), max_new_tokens=4)   # 8 chunks of 2
    interleaved = 0
    while pool.admitting or pool.pending:
        before = len(pool.request(r1).tokens)
        pool.step()
        if pool.admitting and len(pool.request(r1).tokens) > before:
            interleaved += 1
    assert interleaved >= 4, (
        f"decode advanced only {interleaved} times during the 8-chunk "
        "admission — chunked prefill is stalling live tenants")
    pool.run()
    np.testing.assert_array_equal(pool.request(r1).output,
                                  _expected(3, 8, None))
    np.testing.assert_array_equal(pool.request(r2).output,
                                  _expected(16, 4, None))


def test_continuous_knobs_validation():
    session = _get_session()
    with pytest.raises(ValueError, match="prefill_chunk"):
        session.serve_pool(slots=1, max_len=MAX_LEN, prefill_chunk=0)
    with pytest.raises(ValueError, match="bucket_min"):
        session.serve_pool(slots=1, max_len=MAX_LEN, bucket_prompts=True,
                           bucket_min=0)


def test_continuous_rejects_family_without_chunk_prefill():
    """SSM states have no KV sequence to continue a prefill into — the
    knobs must fail loudly at construction, not mid-admission."""
    s = Session.init("mamba2-130m")
    with pytest.raises(ValueError, match="prefill_chunk"):
        s.serve_pool(slots=1, max_len=MAX_LEN, prefill_chunk=4)
    with pytest.raises(ValueError, match="prefill_chunk"):
        s.serve_pool(slots=1, max_len=MAX_LEN, bucket_prompts=True)
