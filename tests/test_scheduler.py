"""``pipeline.scheduler.ServePool`` unit tests (single device): slot
packing, per-slot EOS/budget tracking, recycling parity with serial
generation, admission validation, and stats/report plumbing.  The
multi-device (forced CPU mesh) pool tests live in ``test_serve_mesh.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import Session
from repro.pipeline.scheduler import ServePool


MAX_LEN = 32


def _prompts(sizes, seed=0, vocab=500):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=p).astype(np.int32) for p in sizes]


@pytest.fixture(scope="module")
def session():
    return Session.init("qwen3-14b")


@pytest.fixture(scope="module")
def serial_handle(session):
    return session.serve(1, MAX_LEN)


def _serial(handle, prompt, n):
    out = handle.generate({"tokens": jnp.asarray(prompt)[None, :]}, n)
    return np.asarray(out)[0]


def test_pool_recycling_matches_serial_generation(session, serial_handle):
    """6 requests with mixed prompt lengths and budgets through 2 slots:
    every tenant's tokens equal a dedicated batch-1 generation, even though
    slots were recycled mid-run and rows decoded at different offsets."""
    prompts = _prompts((8, 5, 8, 11, 5, 8))
    budgets = [6, 9, 4, 7, 5, 8]
    serial = [_serial(serial_handle, p, n) for p, n in zip(prompts, budgets)]

    pool = session.serve_pool(slots=2, max_len=MAX_LEN)
    rids = [pool.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = pool.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(outs[rid], serial[i],
                                      err_msg=f"request {i}")
    st = pool.stats()
    assert st["submitted"] == st["completed"] == 6
    assert st["tokens_generated"] == sum(budgets)
    # 2 slots, uneven budgets: recycling must have happened (more decode
    # steps than the longest single request, fewer than the serial sum)
    assert max(budgets) - 1 < st["decode_steps"] < sum(budgets)
    assert 0 < st["occupancy"] <= 1


def test_pool_more_slots_than_requests(session, serial_handle):
    prompts = _prompts((6, 9), seed=1)
    pool = session.serve_pool(slots=4, max_len=MAX_LEN)
    rids = [pool.submit(p, max_new_tokens=5) for p in prompts]
    outs = pool.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid],
                                      _serial(serial_handle, p, 5))
    assert pool.stats()["occupancy"] <= 0.5 + 1e-9  # 2 live of 4 slots


def test_pool_eos_frees_slot_early(session, serial_handle):
    """A tenant whose EOS appears mid-budget stops there (output includes
    the EOS token) and its slot admits the next pending request."""
    [p] = _prompts((8,), seed=1)
    full = _serial(serial_handle, p, 10)
    # EOS = the first generated token past the 2nd that did not occur
    # before it (random weights may repeat a token from the start)
    k = next(i for i in range(2, len(full)) if full[i] not in full[:i])
    pool = session.serve_pool(slots=1, max_len=MAX_LEN)
    r1 = pool.submit(p, max_new_tokens=10, eos_id=int(full[k]))
    [q] = _prompts((6,), seed=2)
    r2 = pool.submit(q, max_new_tokens=3)
    outs = pool.run()
    np.testing.assert_array_equal(outs[r1], full[:k + 1])
    np.testing.assert_array_equal(outs[r2], _serial(serial_handle, q, 3))
    assert pool.stats()["completed"] == 2


def test_pool_single_token_budget_never_occupies_slot(session, serial_handle):
    [p] = _prompts((5,), seed=3)
    pool = session.serve_pool(slots=1, max_len=MAX_LEN)
    rid = pool.submit(p, max_new_tokens=1)
    outs = pool.run()
    np.testing.assert_array_equal(outs[rid], _serial(serial_handle, p, 1))
    assert pool.stats()["decode_steps"] == 0  # prefill-only request


def test_pool_submit_validation(session):
    pool = session.serve_pool(slots=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds the pool max_len"):
        pool.submit(np.zeros(10, np.int32), max_new_tokens=10)
    with pytest.raises(ValueError, match="empty prompt"):
        pool.submit(np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        pool.submit(np.zeros(4, np.int32), max_new_tokens=0)


def test_pool_rejects_unsupported_family(session):
    from repro import configs
    from repro.models import model as M
    import jax
    cfg = configs.smoke_config("zamba2-7b")  # hybrid: shared-position cache
    model = M.build(cfg)
    params, _ = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="ServePool supports"):
        ServePool(model, params, 2, MAX_LEN)


def test_pool_ssm_family_supported():
    """Position-free SSM states recycle per-slot too (no KV positions to
    track) — mamba2 decode through the pool matches serial."""
    s = Session.init("mamba2-130m")
    h1 = s.serve(1, MAX_LEN)
    prompts = _prompts((7, 4, 9), seed=4)
    serial = [_serial(h1, p, 5) for p in prompts]
    pool = s.serve_pool(slots=2, max_len=MAX_LEN)
    rids = [pool.submit(p, max_new_tokens=5) for p in prompts]
    outs = pool.run()
    for rid, want in zip(rids, serial):
        np.testing.assert_array_equal(outs[rid], want)


def test_session_report_surfaces_pool_stats(session):
    """report() lists stats for pools the caller still holds; pools are
    weakly referenced, so a dropped pool stops pinning its snapshots and
    disappears from the report."""
    import gc
    [p] = _prompts((5,), seed=7)
    pool = session.serve_pool(slots=1, max_len=MAX_LEN)
    pool.submit(p, max_new_tokens=2)
    pool.run()
    rep = session.report()
    assert "serve_pools" in rep and len(rep["serve_pools"]) >= 1
    st = rep["serve_pools"][-1]
    assert {"slots", "occupancy", "phases", "completed"} <= set(st)
    assert st["completed"] == 1
    # one admission prefill token + one decoded token; every decode step
    # waited on the device once
    assert st["tokens_generated"] == 2 and st["decode_tokens"] == 1
    assert st["phases"]["decode_wait"]["n"] == st["decode_steps"]
    n_live = len(rep["serve_pools"])
    del pool, st, rep
    gc.collect()
    after = session.report().get("serve_pools", [])
    assert len(after) == n_live - 1  # dropped pool no longer pinned/reported


def test_pool_incremental_stepping_and_late_submit(session, serial_handle):
    """Requests submitted AFTER the pool started decoding are admitted into
    recycled slots; step() drives the pool one batched decode at a time."""
    prompts = _prompts((6, 8), seed=5)
    pool = session.serve_pool(slots=1, max_len=MAX_LEN)
    r1 = pool.submit(prompts[0], max_new_tokens=4)
    pool.step()
    pool.step()
    r2 = pool.submit(prompts[1], max_new_tokens=3)  # while r1 is live
    outs = pool.run()
    np.testing.assert_array_equal(outs[r1],
                                  _serial(serial_handle, prompts[0], 4))
    np.testing.assert_array_equal(outs[r2],
                                  _serial(serial_handle, prompts[1], 3))


def test_pool_phase_counters_count_each_step(session):
    """stats()["phases"] counts one ``step`` per step() call; the longest
    entry never exceeds the total and restarts at each stats() call."""
    pool = session.serve_pool(slots=2, max_len=MAX_LEN)
    for p in _prompts((5, 8, 6), seed=11):
        pool.submit(p, max_new_tokens=3)
    calls = 0
    while pool.live or pool.pending:
        pool.step()
        calls += 1
    ph = pool.stats()["phases"]
    assert ph["step"]["n"] == calls
    assert ph["decode_wait"]["n"] == ph["emit"]["n"] == (
        pool.stats()["decode_steps"])
    assert ph["first_token"]["n"] == ph["prefill_chunk"]["n"] == 3
    assert all(0 <= p["max_s"] <= p["s"] for p in ph.values())
    again = pool.stats()["phases"]
    assert all(again[k]["max_s"] == 0.0 and again[k]["n"] == ph[k]["n"]
               for k in ph)


@pytest.mark.parametrize("kw", [{}, {"prefill_chunk": 4,
                                    "bucket_prompts": True}],
                         ids=["whole-prompt", "chunked"])
def test_request_stamps_ordered_on_virtual_clock(session, kw):
    """admitted_at (left the queue) and first_token_at (first token
    appended) are stamped on the pool's clock, after submitted_at."""
    from repro.pipeline.clock import VirtualClock
    pool = session.serve_pool(slots=2, max_len=MAX_LEN,
                              clock=VirtualClock(step_s=0.01), **kw)
    rids = [pool.submit(p, max_new_tokens=3)
            for p in _prompts((11, 9, 13), seed=12)]
    pool.run()
    reqs = [pool.request(r) for r in rids]
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    # two slots: the third request waited in the queue
    assert reqs[2].admitted_at > reqs[2].submitted_at
    if kw:
        # chunked: an admission beside a live tenant takes one chunk a
        # step, so its first token lands steps after admission began
        assert reqs[1].first_token_at > reqs[1].admitted_at
