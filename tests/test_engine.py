"""Tests for the unified MPO execution engine (core/engine.py).

Covers: mode parity (factorized / reconstruct / kernel / cached agree on the
same cores — forward, transpose, and aux-core gradients under
``freeze_central_grads``), pinned phase -> mode plan decisions, and the
serving-time weight cache (structure + zero per-step contractions in the
decode path)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layers as L
from repro.core import mpo
from repro.core.engine import (MPOEngine, choose_mode, engine_for,
                               flops_dense_per_token,
                               flops_factorized_per_token)

AUTO = L.MPOConfig(bond_embed=8, bond_attn=8, bond_ffn=8, n=3)


def _linear_params(cfg=AUTO, i=48, j=96, seed=0):
    lin = L.init_linear(jax.random.PRNGKey(seed), i, j, cfg=cfg)
    params, _ = L.split_annotations(lin)
    return params


# ------------------------------------------------------------- mode parity


MODES = ["factorized", "reconstruct", "kernel", "cached"]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("transpose", [False, True])
def test_mode_parity(mode, transpose):
    """All four execution modes compute the same y = x @ W (or x @ W^T)."""
    params = _linear_params()
    eng = engine_for(dataclasses.replace(AUTO, mode=mode))
    d = 96 if transpose else 48
    x = jax.random.normal(jax.random.PRNGKey(1), (5, d))
    if mode == "cached":
        # cached parity is exercised through the densified serving tree
        params = eng.cache_weights(params)
        assert "w" in params
    y = eng.linear(params, x, transpose=transpose, phase="decode")
    w = mpo.reconstruct(L.cores_to_list(_linear_params()["cores"]))
    ref = x @ (w.T if transpose else w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("mode", ["factorized", "reconstruct", "kernel"])
def test_cached_fallback_matches_mode(mode):
    """A 'cached' plan over raw (un-densified) cores degrades gracefully to
    an equivalent contraction — same math, no crash."""
    params = _linear_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 48))
    y_cached = engine_for(dataclasses.replace(AUTO, mode="cached")).linear(
        params, x)
    y_mode = engine_for(dataclasses.replace(AUTO, mode=mode)).linear(params, x)
    np.testing.assert_allclose(np.asarray(y_cached), np.asarray(y_mode),
                               atol=1e-4)


def test_auto_decode_raw_cores_does_not_rebuild_per_step():
    """Auto-mode decode over raw (un-densified) cores must NOT pay the
    cores->W rebuild per call: the engine re-prices the call as a forward-
    only one-shot, which at decode token counts picks the factorized chain
    (the pre-engine behavior)."""
    cfg = L.MPOConfig()
    ffn = tuple(mpo.MPOSpec.make(1024, 1024, n=5, bond_dim=16).core_shapes())
    eng = engine_for(cfg)
    assert eng.plan(ffn, 8, "decode").mode == "cached"
    # the fallback decision the engine takes for raw cores at 8 tokens:
    assert eng.plan(ffn, 8, "prefill").mode == "factorized"
    # parity: raw-cores decode output == factorized output
    params = _linear_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 48))
    y = engine_for(AUTO).linear(params, x, phase="decode")
    y_f = engine_for(dataclasses.replace(AUTO, mode="factorized")).linear(
        params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_f), atol=1e-4)


@pytest.mark.parametrize("mode", ["factorized", "reconstruct", "kernel"])
def test_grad_parity_freeze_central(mode):
    """Gradients w.r.t. auxiliary cores agree across differentiable modes
    under freeze_central_grads; the central core's gradient is exactly 0."""
    cfg = dataclasses.replace(AUTO, mode=mode, freeze_central_grads=True)
    ref_cfg = dataclasses.replace(AUTO, mode="reconstruct",
                                  freeze_central_grads=True)
    params = _linear_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 48))

    def loss(cfg):
        return lambda p: jnp.sum(
            jnp.sin(engine_for(cfg).linear(p, x, phase="train")))

    g = jax.grad(loss(cfg))(params)
    g_ref = jax.grad(loss(ref_cfg))(params)
    assert float(jnp.abs(g["cores"]["central"]).max()) == 0.0
    assert float(jnp.abs(g_ref["cores"]["central"]).max()) == 0.0
    # reconstruct's custom VJP intentionally reduces dW in bf16 (the 2x
    # traffic saving) -> parity at bf16 precision, like the mpo-core grad test
    for name in ("c0", "c2"):
        assert float(jnp.abs(g["cores"][name]).max()) > 0.0
        np.testing.assert_allclose(np.asarray(g["cores"][name]),
                                   np.asarray(g_ref["cores"][name]),
                                   atol=5e-2, rtol=5e-2)


def test_embedding_parity_cached_vs_factorized():
    cfg = AUTO
    emb = L.init_embedding(jax.random.PRNGKey(0), 512, 64, cfg=cfg)
    params, _ = L.split_annotations(emb)
    ids = jnp.array([[0, 1, 7], [510, 100, 3]])
    eng = engine_for(cfg)
    y_fact = eng.embedding(params, ids)
    w = mpo.reconstruct(L.cores_to_list(params["cores"]))
    np.testing.assert_allclose(np.asarray(y_fact), np.asarray(w[ids]),
                               atol=1e-4)
    dense = eng.cache_weights(params)
    if "w" in dense:  # tiny smoke table densifies; parity must hold
        y_dense = eng.embedding(dense, ids)
        np.testing.assert_allclose(np.asarray(y_fact), np.asarray(y_dense),
                                   atol=1e-4)


# ------------------------------------------------- pinned plan decisions


# qwen3-14b's attention cores at published widths: the K/V projection
# (5120 -> 1024) and the query projection (5120 -> 5120)
WK = ((1, 5, 4, 20), (20, 8, 4, 128), (128, 8, 4, 128), (128, 4, 4, 16),
      (16, 4, 4, 1))
WQ = ((1, 5, 5, 25), (25, 8, 8, 128), (128, 8, 8, 128), (128, 4, 4, 16),
      (16, 4, 4, 1))


def test_plan_phase_decisions_pinned():
    """Phase -> mode decisions for representative shapes (the contract the
    models/serving layers rely on)."""
    cfg = L.MPOConfig()
    ffn = tuple(mpo.MPOSpec.make(1024, 1024, n=5, bond_dim=16).core_shapes())
    vocab = tuple(mpo.MPOSpec.make(32768, 256, n=3, bond_dim=8).core_shapes())

    # train on TPU: dense-favored with a tile the compiler accepts in both
    # orientations and in the cores-backward -> the kernel, at the largest
    # such tile
    plan = MPOEngine(cfg, interpret=False).plan(WK, 4096, "train")
    assert (plan.mode, plan.block_m) == ("kernel", 64)
    # the query projection's cores-backward exceeds VMEM at every tile
    assert choose_mode(cfg, WQ, 4096, "train", interpret=False)[0] \
        == "reconstruct"
    # train in interpret mode: kernel never a perf candidate -> reconstruct
    # (matmul_reconstruct's core-space backward)
    assert choose_mode(cfg, WK, 4096, "train", interpret=True)[0] \
        == "reconstruct"
    # prefill on TPU (interpret=False) with a compilable tile -> fused kernel
    assert choose_mode(cfg, WQ, 4096, "prefill", interpret=False)[0] \
        == "kernel"
    assert choose_mode(cfg, WK, 4096, "prefill", interpret=False)[0] \
        == "kernel"
    # interpreter mode is never a perf candidate -> falls back to reconstruct
    assert choose_mode(cfg, WQ, 4096, "prefill", interpret=True)[0] \
        == "reconstruct"
    # bonds narrower than the 128 lanes make the in-kernel tile rebuild
    # unlowerable: no kernel at any tile
    assert choose_mode(cfg, ffn, 4096, "prefill", interpret=False)[0] \
        == "reconstruct"
    # decode: dense/token beats the chain for ffn-like shapes -> cached
    assert choose_mode(cfg, ffn, 8, "decode", interpret=True)[0] == "cached"
    assert flops_dense_per_token(ffn) < flops_factorized_per_token(ffn)
    # heavily compressed vocab-sized matrix: chain beats dense per token ->
    # stays factorized (densifying would also resurrect the [V, D] table)
    assert choose_mode(cfg, vocab, 8, "decode", interpret=True)[0] \
        == "factorized"
    assert flops_factorized_per_token(vocab) < flops_dense_per_token(vocab)
    # factorized-favored shapes stay factorized in every phase
    assert choose_mode(cfg, vocab, 8, "train")[0] == "factorized"
    assert choose_mode(cfg, vocab, 100_000, "prefill",
                       interpret=False)[0] == "factorized"


def test_plan_respects_forced_mode_and_rejects_bad_phase():
    cfg = dataclasses.replace(L.MPOConfig(), mode="factorized")
    ffn = tuple(mpo.MPOSpec.make(1024, 1024, n=5, bond_dim=16).core_shapes())
    for phase in ("train", "prefill", "decode"):
        assert choose_mode(cfg, ffn, 4096, phase)[0] == "factorized"
    with pytest.raises(ValueError, match="phase"):
        choose_mode(L.MPOConfig(), ffn, 4096, "serve")


def test_plans_are_memoized():
    eng = engine_for(AUTO)
    p1 = eng.plan(WQ, 4096, "prefill")
    p2 = eng.plan([list(s) for s in WQ], 4096, "prefill")
    assert p1 is p2  # same plan object: planned once per signature
    assert engine_for(AUTO) is eng


# ------------------------------------------------- serving weight cache


def test_cache_weights_densifies_selected_matrices():
    params = _linear_params()
    eng = engine_for(AUTO)
    dense = eng.cache_weights(params)
    assert set(dense.keys()) == {"w"}
    np.testing.assert_allclose(
        np.asarray(dense["w"]),
        np.asarray(mpo.reconstruct(L.cores_to_list(params["cores"]))),
        atol=1e-5)
    # factorized-favored matrices pass through untouched (same objects)
    vocab_lin = L.init_linear(jax.random.PRNGKey(0), 32768, 256,
                              cfg=L.MPOConfig(bond_embed=8, n=3),
                              kind="embed")
    vp, _ = L.split_annotations(vocab_lin)
    out = MPOEngine(L.MPOConfig(bond_embed=8, n=3)).cache_weights(vp)
    assert "cores" in out and out["cores"] is vp["cores"]


def test_cache_weights_handles_stacked_layer_dims():
    """Scan-stacked cores (leading layers/expert dims) densify per slice."""
    def one(k):
        return L.init_linear(k, 48, 96, cfg=AUTO)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    stacked = jax.vmap(lambda k: L.split_annotations(one(k))[0])(keys)
    dense = engine_for(AUTO).cache_weights({"lin": stacked})
    assert set(dense["lin"].keys()) == {"w"}
    assert dense["lin"]["w"].shape == (3, 48, 96)
    for i in range(3):
        sl = jax.tree.map(lambda a: a[i], stacked)
        np.testing.assert_allclose(
            np.asarray(dense["lin"]["w"][i]),
            np.asarray(mpo.reconstruct(L.cores_to_list(sl["cores"]))),
            atol=1e-5)


def test_serve_decode_zero_per_step_contractions():
    """The serving path: init_serve densifies every decode-``cached`` matrix
    once; the jitted decode step over the serving tree contains no einsum
    (chain contraction) ops — only dense dots — and its logits match the
    un-cached decode step."""
    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.models import model as M
    from repro.train.steps import make_serve_steps

    cfg = configs.smoke_config("qwen3-14b")
    model = M.build(cfg)
    params, _ = model.init_params(jax.random.PRNGKey(0))
    prefill_step, decode_step, init_serve, _ = make_serve_steps(model)
    sparams, cache = init_serve(params, 2, 24)

    # every attention/mlp matrix in the serving tree is dense
    flat = jax.tree_util.tree_flatten_with_path(sparams)[0]
    keys = {"/".join(str(getattr(p, "key", "")) for p in path)
            for path, _ in flat}
    assert any(k.endswith("wq/w") for k in keys), sorted(keys)
    # at smoke scale EVERY matrix (incl. embed / tied logits) is decode-
    # cached, so no cores survive anywhere: the jitted decode step over this
    # tree cannot contain a chain contraction
    assert not any("cores" in k for k in keys), sorted(keys)

    batch = M.make_batch(cfg, ShapeConfig("p", "prefill", 8, 2))
    logits_c, cache_c = prefill_step(sparams, batch, cache)
    tok = jnp.argmax(logits_c[:, -1], -1)[:, None].astype(jnp.int32)

    # reference: same weights, no weight cache
    _, decode_raw, init_raw, _ = make_serve_steps(model, weight_cache=False)
    rparams, rcache = init_raw(params, 2, 24)
    logits_r, rcache = prefill_step(rparams, batch, rcache)
    np.testing.assert_allclose(np.asarray(logits_c, np.float32),
                               np.asarray(logits_r, np.float32), atol=2e-3)
    for _ in range(3):
        tok_c, logits_c, cache_c = decode_step(sparams, tok, cache_c)
        tok_r, logits_r, rcache = decode_raw(rparams, tok, rcache)
        np.testing.assert_allclose(np.asarray(logits_c, np.float32),
                                   np.asarray(logits_r, np.float32),
                                   atol=2e-3)
        assert bool(jnp.all(tok_c == tok_r))
        tok = tok_c
