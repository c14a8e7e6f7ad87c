"""Measured-autotuner tests: cache determinism, warm-cache zero-timing,
corruption/staleness tolerance, and measured block_m threading into plans.

``REPRO_AUTOTUNE_MEASURE=1`` forces the measured path on this CPU container
(kernel candidates run in interpret mode over tiny shapes); the disk cache
is pointed at a per-test tmp path via ``REPRO_AUTOTUNE_CACHE``."""

import json

import pytest

from repro.core import engine
from repro.core import layers as L
from repro.kernels import autotune

CFG = L.MPOConfig()
# tiny but kernel-eligible shapes: I=32 (i_tile 16 % 8), J=512 (j_tile 128)
SHAPES = ((1, 2, 4, 4), (4, 4, 4, 4), (4, 4, 32, 1))
TOKENS = 16


@pytest.fixture
def tuned_env(tmp_path, monkeypatch):
    """Fresh tuner + plan memo against a tmp on-disk cache; restores the
    process-global tuner/planner state afterwards."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(autotune.ENV_CACHE, path)
    monkeypatch.setenv(autotune.ENV_MEASURE, "1")
    engine.clear_plan_cache()
    autotune.reset_tuner()
    yield path
    engine.clear_plan_cache()
    autotune.reset_tuner()


def _fresh_engine():
    """New engine AND new tuner/plan memo — simulates a new process that
    still sees the same on-disk cache."""
    engine.clear_plan_cache()
    tuner = autotune.reset_tuner()
    return engine.MPOEngine(CFG, interpret=True), tuner


def test_warm_cache_same_plan_zero_timing_runs(tuned_env):
    """Determinism + zero re-tuning cost: two fresh ``MPOEngine`` instances
    resolve the same key to the same plan, and the second (warm disk cache)
    performs ZERO timing runs."""
    eng1, tuner1 = _fresh_engine()
    p1 = eng1.plan(SHAPES, TOKENS, "train")
    assert p1.tuned
    assert tuner1.timing_runs > 0          # cold: candidates were timed
    assert "(measured)" in p1.reason

    eng2, tuner2 = _fresh_engine()
    p2 = eng2.plan(SHAPES, TOKENS, "train")
    assert tuner2.timing_runs == 0         # warm: answered from disk
    assert "(disk)" in p2.reason
    assert (p2.mode, p2.block_m) == (p1.mode, p1.block_m)

    # the persisted file is valid, versioned JSON with the tuned key
    raw = json.load(open(tuned_env))
    assert raw["version"] == autotune.CACHE_VERSION
    key = autotune.make_key(SHAPES, TOKENS, "train", "float32")
    assert raw["entries"][key]["mode"] == p1.mode


def test_corrupted_cache_is_ignored_and_retuned(tuned_env):
    with open(tuned_env, "w") as f:
        f.write("{this is not json")
    eng, tuner = _fresh_engine()
    plan = eng.plan(SHAPES, TOKENS, "prefill")
    assert plan.tuned and tuner.timing_runs > 0
    # the corrupted file was replaced by a valid one
    raw = json.load(open(tuned_env))
    assert autotune.make_key(SHAPES, TOKENS, "prefill", "float32") \
        in raw["entries"]


def test_stale_or_malformed_entries_are_ignored(tuned_env):
    key = autotune.make_key(SHAPES, TOKENS, "prefill", "float32")
    stale = {"version": autotune.CACHE_VERSION + 999,
             "entries": {key: {"mode": "kernel", "block_m": 64}}}
    with open(tuned_env, "w") as f:
        json.dump(stale, f)
    eng, tuner = _fresh_engine()
    assert eng.plan(SHAPES, TOKENS, "prefill").tuned
    assert tuner.timing_runs > 0           # version mismatch -> re-tuned

    # right version, garbage entry (unaligned block_m) -> also re-tuned
    bad = {"version": autotune.CACHE_VERSION,
           "entries": {key: {"mode": "kernel", "block_m": 7}}}
    with open(tuned_env, "w") as f:
        json.dump(bad, f)
    eng, tuner = _fresh_engine()
    plan = eng.plan(SHAPES, TOKENS, "prefill")
    assert tuner.timing_runs > 0
    assert plan.block_m % 8 == 0


def test_measured_block_m_threads_into_plan_and_execution(tuned_env):
    """A disk verdict of kernel@64 lands in ``ExecutionPlan.block_m`` and
    the engine executes it (interpret mode) with correct results."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mpo

    key = autotune.make_key(SHAPES, TOKENS, "train", "float32")
    with open(tuned_env, "w") as f:
        json.dump({"version": autotune.CACHE_VERSION,
                   "entries": {key: {"mode": "kernel", "block_m": 64,
                                     "timings": {"kernel@64": 1e-6}}}}, f)
    eng, tuner = _fresh_engine()
    plan = eng.plan(SHAPES, TOKENS, "train")
    assert (plan.mode, plan.block_m, plan.tuned) == ("kernel", 64, True)
    assert tuner.timing_runs == 0

    # execute through the engine with exactly these core shapes
    cores = [jax.random.normal(jax.random.PRNGKey(k), s)
             for k, s in enumerate(SHAPES)]
    params = {"cores": {n: c for n, c in
                        zip(L.core_names(len(cores)), cores)}}
    x = jax.random.normal(jax.random.PRNGKey(9), (TOKENS, 32))
    y = eng.linear(params, x, phase="train")
    w = mpo.reconstruct(cores)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)
    # grads flow through the tuned kernel plan
    g = jax.grad(lambda p: jnp.sum(
        eng.linear(p, x, phase="train") ** 2))(params)
    assert all(float(jnp.abs(v).max()) > 0 for v in
               jax.tree.leaves(g)), "no gradient reached the cores"


def test_interpret_mode_defaults_to_analytic(tmp_path, monkeypatch):
    """Without REPRO_AUTOTUNE_MEASURE, interpret mode (this container) keeps
    the analytic FLOPs heuristic: no timing, no cache file."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(autotune.ENV_CACHE, path)
    monkeypatch.delenv(autotune.ENV_MEASURE, raising=False)
    assert not autotune.should_measure(interpret=True)
    eng, tuner = _fresh_engine()
    plan = eng.plan(SHAPES, 4096, "train")
    assert not plan.tuned and tuner.timing_runs == 0
    assert "FLOPs" in plan.reason
    import os
    assert not os.path.exists(path)
    engine.clear_plan_cache()
    autotune.reset_tuner()


def test_measure_disable_env_wins(monkeypatch):
    monkeypatch.setenv(autotune.ENV_MEASURE, "0")
    assert not autotune.should_measure(interpret=False)


def test_long_prefill_candidates_past_512(tuned_env):
    """Carried-over ROADMAP gap: long-prefill shapes (4k+ tokens) must race
    tile heights past 512, and a >512 disk verdict must round-trip into the
    plan without a re-tune (i.e. the cache accepts the new candidates)."""
    assert {1024, 2048} <= set(autotune.CANDIDATE_BLOCK_MS)
    assert {1024, 2048} <= set(autotune._block_m_candidates(4096))
    # short calls dedupe the tall tiles away by effective tile height
    assert 2048 not in autotune._block_m_candidates(600)
    assert autotune._parse_label("kernel@2048") == ("kernel", 2048)
    # the cache key separates the long-prefill entry from the short one,
    # so a 512-token verdict can never answer a 4096-token lookup
    assert autotune.make_key(SHAPES, 4096, "prefill", "float32") != \
        autotune.make_key(SHAPES, 512, "prefill", "float32")
    key = autotune.make_key(SHAPES, 4096, "prefill", "float32")
    with open(tuned_env, "w") as f:
        json.dump({"version": autotune.CACHE_VERSION,
                   "entries": {key: {"mode": "kernel", "block_m": 2048,
                                     "timings": {"kernel@2048": 1e-6,
                                                 "kernel@1024": 2e-6}}}}, f)
    eng, tuner = _fresh_engine()
    plan = eng.plan(SHAPES, 4096, "prefill")
    assert (plan.mode, plan.block_m, plan.tuned) == ("kernel", 2048, True)
    assert tuner.timing_runs == 0          # disk verdict accepted as-is


def test_key_distinguishes_dtype_phase_and_substrate():
    k = autotune.make_key(SHAPES, TOKENS, "train", "float32")
    assert k != autotune.make_key(SHAPES, TOKENS, "train", "bfloat16")
    assert k != autotune.make_key(SHAPES, TOKENS, "prefill", "float32")
    assert k != autotune.make_key(SHAPES, TOKENS + 1, "train", "float32")
    # interpret-mode (CPU bring-up) verdicts must never answer a compiled
    # real-hardware lookup: the measurement substrate is part of the key
    assert k != autotune.make_key(SHAPES, TOKENS, "train", "float32",
                                  interpret=False)
    assert "backend=" in k


def test_key_includes_jax_version(tuned_env, monkeypatch):
    """A verdict measured under an older JAX must never answer lookups
    after an upgrade — compiler changes reshuffle the candidate rankings."""
    import jax
    k = autotune.make_key(SHAPES, TOKENS, "prefill", "float32")
    assert f"jax={jax.__version__}" in k
    monkeypatch.setattr(jax, "__version__", "0.0.0-preupgrade")
    old_key = autotune.make_key(SHAPES, TOKENS, "prefill", "float32")
    assert old_key != k
    # seed a disk verdict under the old version, then "upgrade" back:
    # the lookup must MISS (re-measure), not serve the stale ranking
    with open(tuned_env, "w") as f:
        json.dump({"version": autotune.CACHE_VERSION,
                   "entries": {old_key: {"mode": "kernel", "block_m": 64,
                                         "timings": {}}}}, f)
    monkeypatch.undo()
    monkeypatch.setenv(autotune.ENV_CACHE, tuned_env)
    monkeypatch.setenv(autotune.ENV_MEASURE, "1")
    eng, tuner = _fresh_engine()
    plan = eng.plan(SHAPES, TOKENS, "prefill")
    assert plan.tuned and tuner.timing_runs > 0  # stale entry not consulted
    # both substrate generations coexist in the rewritten file
    entries = json.load(open(tuned_env))["entries"]
    assert old_key in entries
    assert autotune.make_key(SHAPES, TOKENS, "prefill", "float32") in entries


# qwen3-14b's vocabulary head at published widths (5120 -> 152064)
HEAD = ((1, 5, 16, 64), (64, 8, 11, 64), (64, 8, 12, 64), (64, 4, 12, 24),
        (24, 4, 6, 1))


def test_chain_raced_only_where_its_flops_allow():
    """The factorized chain is a race candidate only where its FLOPs do not
    exceed rebuild + dense: at a 512-token train step the head's chain
    (3x the dense FLOPs/token) is not timed; at one token it is."""
    assert not autotune.chain_in_race(HEAD, 512)
    assert autotune.chain_in_race(HEAD, 1)
    labels = [lbl for lbl, _ in autotune._candidates(
        HEAD, 512, "train", "bfloat16", True)]
    assert labels == ["reconstruct"]
    labels = [lbl for lbl, _ in autotune._candidates(
        SHAPES, 1, "prefill", "float32", True)]
    assert "factorized" in labels
