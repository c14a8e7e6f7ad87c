"""Paged-KV decode attention: flash-kernel vs XLA-reference parity
(interpret mode), paged-vs-dense token parity through serving, pool slot
recycling with page accounting, and the autotuner race.

All kernel executions here run ``interpret=True`` (this container is CPU);
the kernel-vs-fallback *choice* is forced via ``REPRO_DECODE_ATTN`` where a
specific path is under test, and measured via ``REPRO_AUTOTUNE_MEASURE=1``
where the race itself is."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import Session
from repro.kernels import autotune
from repro.kernels import decode_attention as DA

MAX_LEN = 32
PAGE = 8


# --------------------------------------------------------------------------
# unit parity: flash kernel vs a plain-jnp paged reference
# --------------------------------------------------------------------------


def _ref_paged_attention(q, kp, vp, table, lens, bias):
    """Gather-pages + masked softmax oracle (mirrors nn.attention_scores
    math for a single decoded token)."""
    dh = q.shape[-1]
    k = DA.gather_pages(kp, table)
    v = DA.gather_pages(vp, table)
    s = jnp.einsum("bkgd,bskd->bkgs", q, k) / math.sqrt(dh)
    s = s + bias[:, None, None, :]
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", w.astype(v.dtype), v)


def _rand_paged(key, b, kv, g, dh, ps, mp, ragged=True):
    ks = jax.random.split(key, 4)
    p = b * mp
    q = jax.random.normal(ks[0], (b, kv, g, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (p, ps, kv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (p, ps, kv, dh), jnp.float32)
    if ragged:  # every slot at a different context length
        lens = jax.random.randint(ks[3], (b,), 1, mp * ps + 1)
    else:
        lens = jnp.full((b,), mp * ps)
    lens = lens.astype(jnp.int32)
    # each slot maps a distinct page range, shuffled so physical order
    # differs from logical order
    perm = jax.random.permutation(ks[3], p).astype(jnp.int32)
    table = perm.reshape(b, mp)
    bias = jnp.where(jnp.arange(mp * ps)[None, :] < lens[:, None],
                     0.0, DA.MASK_VALUE).astype(jnp.float32)
    return q, kp, vp, table, lens, bias


@pytest.mark.parametrize("kv,g", [(2, 2), (1, 4), (4, 1)],
                         ids=["gqa", "mqa", "mha"])
def test_flash_matches_reference_across_head_configs(kv, g):
    """GQA / MQA / MHA head groupings, ragged per-slot lengths: the flash
    kernel's online softmax matches the gathered full-softmax oracle."""
    args = _rand_paged(jax.random.PRNGKey(0), b=3, kv=kv, g=g, dh=16,
                      ps=4, mp=3)
    out = DA.flash_decode_attention(*args, interpret=True)
    ref = _ref_paged_attention(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_softcap_matches_reference():
    q, kp, vp, table, lens, bias = _rand_paged(
        jax.random.PRNGKey(1), b=2, kv=2, g=2, dh=8, ps=4, mp=2)
    cap = 5.0
    out = DA.flash_decode_attention(q, kp, vp, table, lens, bias,
                                    softcap=cap, interpret=True)
    dh = q.shape[-1]
    k = DA.gather_pages(kp, table)
    v = DA.gather_pages(vp, table)
    s = jnp.einsum("bkgd,bskd->bkgs", q, k) / math.sqrt(dh)
    s = cap * jnp.tanh(s / cap) + bias[:, None, None, :]
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    ref = jnp.einsum("bkgs,bskd->bkgd", w.astype(v.dtype), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_single_key_slot():
    """A slot with length 1 (just admitted) reduces over exactly one key:
    output equals that key's V row regardless of page-pool garbage."""
    q, kp, vp, table, lens, bias = _rand_paged(
        jax.random.PRNGKey(2), b=2, kv=1, g=2, dh=8, ps=4, mp=2)
    lens = jnp.array([1, 5], jnp.int32)
    bias = jnp.where(jnp.arange(8)[None, :] < lens[:, None],
                     0.0, DA.MASK_VALUE).astype(jnp.float32)
    out = DA.flash_decode_attention(q, kp, vp, table, lens, bias,
                                    interpret=True)
    first_page = table[0, 0]
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]),
                               np.asarray(vp[first_page, 0, 0]), atol=1e-5)


# --------------------------------------------------------------------------
# serving-level token parity (session fixtures shared across tests)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def session():
    return Session.init("qwen3-14b")


def _prompts(sizes, seed=0, vocab=500):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=p).astype(np.int32) for p in sizes]


def test_paged_generation_token_identical_to_dense(session):
    """Interpret mode keeps the XLA reference path: paged serving must be
    token-identical to the dense cache (masked-out keys contribute exact
    zeros; the decode windows change only the f32 reduction order)."""
    from repro.configs.base import ShapeConfig
    from repro.models import model as M
    batch = M.make_batch(session.cfg, ShapeConfig("t", "prefill", 8, 4))
    out_d = session.serve(4, MAX_LEN).generate(batch, 10)
    out_p = session.serve(4, MAX_LEN, paged=True,
                          page_size=PAGE).generate(batch, 10)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))


def test_flash_forced_generation_matches_xla(session, monkeypatch):
    """REPRO_DECODE_ATTN=flash routes every decode step through the Pallas
    kernel (interpret); tokens must match the XLA gather path."""
    from repro.configs.base import ShapeConfig
    from repro.models import model as M
    batch = M.make_batch(session.cfg, ShapeConfig("t", "prefill", 8, 2))
    monkeypatch.setenv(DA.ENV_IMPL, "xla")
    out_x = session.serve(2, MAX_LEN, paged=True, page_size=PAGE,
                          weight_cache=False).generate(batch, 8)
    monkeypatch.setenv(DA.ENV_IMPL, "flash")
    s2 = Session.init("qwen3-14b")
    out_f = s2.serve(2, MAX_LEN, paged=True, page_size=PAGE,
                     weight_cache=False).generate(batch, 8)
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_f))


def test_paged_pool_recycling_matches_serial(session):
    """Slot recycling mid-run over the paged pool: every tenant's tokens
    equal dedicated batch-1 dense generation, pages are freed on recycle
    (pool fully drained -> zero pages in use)."""
    prompts = _prompts((8, 5, 8, 11, 5), seed=3)
    budgets = [6, 9, 4, 7, 5]
    h1 = session.serve(1, MAX_LEN)
    serial = [np.asarray(h1.generate(
        {"tokens": jnp.asarray(p)[None, :]}, n))[0]
        for p, n in zip(prompts, budgets)]
    pool = session.serve_pool(slots=2, max_len=MAX_LEN, paged=True,
                              page_size=PAGE)
    rids = [pool.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = pool.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(outs[rid], serial[i],
                                      err_msg=f"request {i}")
    st = pool.stats()
    assert st["completed"] == 5
    pp = st["page_pool"]
    assert pp["pages"] == 2 * (MAX_LEN // PAGE) and pp["used"] == 0
    assert pp["page_size"] == PAGE and pp["occupancy"] == 0.0


def test_paged_pool_occupancy_while_live(session):
    """Mid-run, the page pool reports exactly the pages the live tenants'
    contexts need (ceil(context / page_size) each)."""
    [p] = _prompts((9,), seed=4)
    pool = session.serve_pool(slots=2, max_len=MAX_LEN, paged=True,
                              page_size=PAGE)
    pool.submit(p, max_new_tokens=8)
    pool.step()   # admit (prefill 9 tokens) + decode 1
    pp = pool.stats()["page_pool"]
    # context = 9 prompt + 1 decoded = 10 tokens -> 2 pages of 8
    assert pp["used"] == 2, pp
    pool.run()
    assert pool.stats()["page_pool"]["used"] == 0


def test_paged_rejected_for_ssm_family():
    s = Session.init("mamba2-130m")
    with pytest.raises(ValueError, match="paged"):
        s.serve_pool(slots=2, max_len=MAX_LEN, paged=True)


def test_paged_cache_rejects_indivisible_page_size():
    """page_size must divide max_len: the page-clamped index maps assume
    full pages, so a partial tail page would read garbage.  The error must
    be actionable (suggest a working page_size / rounded max_len)."""
    from repro import configs
    from repro.models import model as M
    model = M.build(configs.smoke_config("qwen3-14b"))
    with pytest.raises(ValueError) as ei:
        model.init_cache(2, 24, paged=True, page_size=16)
    msg = str(ei.value)
    assert "page_size=16" in msg and "max_len=24" in msg
    assert "8" in msg and "32" in msg    # gcd suggestion + rounded max_len
    with pytest.raises(ValueError, match="positive"):
        model.init_cache(2, 24, paged=True, page_size=0)
    # divisible sizes construct fine, tail page included
    cache = model.init_cache(2, 32, paged=True, page_size=16)
    assert cache["page_table"].shape[-1] == 2


# --------------------------------------------------------------------------
# autotuner race
# --------------------------------------------------------------------------


def test_choose_impl_races_and_persists(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE_MEASURE=1: choose_impl times flash vs xla once per
    (head-config, context-bucket, dtype, backend) key, persists the verdict,
    and answers the next process from disk with zero timing runs."""
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "autotune.json"))
    monkeypatch.setenv(autotune.ENV_MEASURE, "1")
    monkeypatch.delenv(DA.ENV_IMPL, raising=False)
    tuner = autotune.reset_tuner()
    impl = DA.choose_impl(2, 2, 16, 8, 4, "float32", interpret=True)
    assert impl in ("flash", "xla")
    assert tuner.timing_runs > 0
    raw = json.load(open(tmp_path / "autotune.json"))
    keys = [k for k in raw["entries"] if "phase=decode_attn" in k]
    assert len(keys) == 1 and raw["entries"][keys[0]]["mode"] == impl
    # both candidates were actually timed
    assert set(raw["entries"][keys[0]]["timings"]) == {"flash", "xla"}
    # warm lookup: fresh tuner, same verdict, zero timing runs
    tuner2 = autotune.reset_tuner()
    assert DA.choose_impl(2, 2, 16, 8, 4, "float32", interpret=True) == impl
    assert tuner2.timing_runs == 0
    autotune.reset_tuner()


def test_choose_impl_defaults(monkeypatch):
    """No measurement, no force: interpret keeps the XLA reference (the
    kernel interprets slowly), compiled defaults to flash."""
    monkeypatch.setenv(autotune.ENV_MEASURE, "0")
    monkeypatch.delenv(DA.ENV_IMPL, raising=False)
    assert DA.choose_impl(2, 2, 16, 8, 4, "float32", interpret=True) == "xla"
    assert DA.choose_impl(2, 2, 16, 8, 4, "float32",
                          interpret=False) == "flash"
    monkeypatch.setenv(DA.ENV_IMPL, "flash")
    assert DA.choose_impl(2, 2, 16, 8, 4, "float32", interpret=True) == "flash"


def test_context_bucket_is_next_pow2():
    assert DA._context_bucket(32) == 32
    assert DA._context_bucket(33) == 64
    assert DA._context_bucket(1) == 2
