"""Sharding rules, mesh ctx, SP layout, and optimizer-transform unit tests."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.optim.schedule import constant, cosine_warmup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- schedules


def test_cosine_warmup_shape():
    f = cosine_warmup(1.0, warmup=10, total=100)
    assert float(f(0)) == 0.0
    assert float(f(10)) == pytest.approx(1.0)
    assert float(f(100)) == pytest.approx(0.0, abs=1e-6)
    assert float(f(55)) < float(f(20))


def test_constant_schedule():
    assert float(constant(0.5)(123)) == 0.5


# --------------------------------------------------------- EF compression


def test_ef_int8_error_feedback_is_unbiased_over_time():
    from repro.optim.compress import ef_int8
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    err = jnp.zeros_like(g)
    total_sent = jnp.zeros_like(g)
    for _ in range(50):
        sent, err = ef_int8(g, err)
        total_sent = total_sent + sent
    # average transmitted gradient converges to the true gradient
    np.testing.assert_allclose(np.asarray(total_sent / 50), np.asarray(g),
                               atol=0.02)


def test_ef_topk_sparsity():
    from repro.optim.compress import ef_topk
    g = jnp.arange(100, dtype=jnp.float32)
    sent, err = ef_topk(g, jnp.zeros_like(g), frac=0.1)
    assert int((sent != 0).sum()) == 10
    np.testing.assert_allclose(np.asarray(sent + err), np.asarray(g),
                               atol=1e-6)


# ----------------------------------------------------- rules / divisibility


def _subproc(code: str, timeout=560):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=ROOT, timeout=timeout, env=env)


def test_rules_divisibility_fallback():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel import sharding as S
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             (jax.sharding.AxisType.Auto,) * 2)
        rules = S.make_rules(mesh, fsdp=False)
        # divisible dim -> sharded; non-divisible -> replicated
        assert S.spec_for(("ffn",), (16,), rules, mesh) == P("model")
        assert S.spec_for(("ffn",), (10,), rules, mesh) == P()
        assert S.spec_for((None, "ffn"), (3, 8), rules, mesh) == P(None, "model")
        # sp mode replicates weights, keeps expert EP
        sp = S.make_rules(mesh, sp=True)
        assert sp["ffn"] is None and sp["expert"] == ("model",)
        print("RULES_OK")
    """)
    r = _subproc(code)
    assert "RULES_OK" in r.stdout, r.stdout + r.stderr


def test_head_safe_rules_mqa_and_exact_boundary():
    """Edge cases of the head-splitting guard: MQA (kv_heads=1) must drop
    the KV TP rule on any model axis > 1 (1 head cannot shard), and a mesh
    whose model axis EQUALS the head count keeps the rule (each device gets
    exactly one head — legal, no head_dim split)."""
    import dataclasses
    from repro import configs
    from repro.analysis import MeshSpec
    from repro.parallel import sharding as S

    base = configs.get_config("qwen3-14b")
    mqa = dataclasses.replace(base, num_heads=8, num_kv_heads=1)
    mesh4 = MeshSpec({"data": 1, "model": 4})
    rules = S.head_safe_rules(S.make_rules(mesh4), mqa, mesh4)
    assert rules["kv_qkv"] is None          # 1 % 4 != 0: replicate KV
    assert rules["qkv"] == ("model",)       # 8 % 4 == 0: Q stays sharded

    exact = dataclasses.replace(base, num_heads=8, num_kv_heads=8)
    mesh8 = MeshSpec({"data": 1, "model": 8})
    rules = S.head_safe_rules(S.make_rules(mesh8), exact, mesh8)
    assert rules["qkv"] == ("model",)       # one head per device: legal
    assert rules["kv_qkv"] == ("model",)

    # one past the boundary: 8 heads over model=16 would split head_dim
    mesh16 = MeshSpec({"data": 1, "model": 16})
    rules = S.head_safe_rules(S.make_rules(mesh16), exact, mesh16)
    assert rules["qkv"] is None and rules["kv_qkv"] is None

    # trivial mesh never drops anything
    mesh1 = MeshSpec({"data": 1, "model": 1})
    rules = S.head_safe_rules(S.make_rules(mesh1), mqa, mesh1)
    assert rules["qkv"] == ("model",) and rules["kv_qkv"] == ("model",)


def test_sp_lowering_small_mesh():
    """SP-mode qwen3 smoke train step lowers with seq-sharded activations."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro import configs, optim
        from repro.configs.base import ShapeConfig
        from repro.core import lightweight
        from repro.data.pipeline import make_batch_fn
        from repro.models import model as M
        from repro.parallel import sharding as S
        from repro.parallel.ctx import current_mesh, sequence_parallel
        from repro.train.steps import TrainState, make_train_step

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             (jax.sharding.AxisType.Auto,) * 2)
        cfg = configs.smoke_config("qwen3-14b", d_model=64, num_heads=4,
                                   num_kv_heads=2, parallelism="sp")
        shape = ShapeConfig("t", "train", 32, 4)
        model = M.build(cfg)
        params, axes = model.init_params(jax.random.PRNGKey(0))
        rules = S.make_rules(mesh, fsdp=False, sp=True)
        with mesh, current_mesh(mesh), sequence_parallel(True):
            sh = S.tree_shardings(
                axes, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
                mesh, rules)
            params = jax.tree.map(jax.device_put, params, sh)
            mask = lightweight.trainable_mask(params, mode="lfa")
            opt = optim.adamw(1e-3, mask=mask)
            state = TrainState(params, opt.init(params))
            step = jax.jit(make_train_step(model, opt))
            bf = make_batch_fn(cfg, shape)
            batch = {k: jnp.asarray(v) for k, v in bf(0).items()}
            state, m = step(state, batch)
            assert bool(jnp.isfinite(m["loss"])), m
        print("SP_OK", float(m["loss"]))
    """)
    r = _subproc(code)
    assert "SP_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


def test_elastic_checkpoint_reshard():
    """Checkpoint saved on one layout restores onto a different mesh."""
    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh1 = jax.make_mesh((8,), ("data",),
                              (jax.sharding.AxisType.Auto,))
        t1 = jax.tree.map(lambda a: jax.device_put(
            a, NamedSharding(mesh1, P("data"))), tree)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(1, t1)
            mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                                  (jax.sharding.AxisType.Auto,) * 2)
            sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
            t2, meta = mgr.restore(1, tree, shardings=sh2)
            assert t2["w"].sharding == sh2["w"]
            np.testing.assert_array_equal(np.asarray(t2["w"]),
                                          np.asarray(tree["w"]))
        print("RESHARD_OK")
    """)
    r = _subproc(code)
    assert "RESHARD_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


def test_freeze_central_grads_graph_level():
    import dataclasses
    from repro.core import layers as L
    cfg = L.MPOConfig(bond_ffn=8, n=3)
    cfgf = dataclasses.replace(cfg, freeze_central_grads=True)
    lin = L.init_linear(jax.random.PRNGKey(0), 48, 96, cfg=cfg)
    params, _ = L.split_annotations(lin)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 48))
    gf = jax.grad(lambda p: jnp.sum(L.apply_linear(p, x, cfg=cfgf) ** 2))(params)
    gn = jax.grad(lambda p: jnp.sum(L.apply_linear(p, x, cfg=cfg) ** 2))(params)
    assert float(jnp.abs(gf["cores"]["central"]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(gf["cores"]["c0"]),
                               np.asarray(gn["cores"]["c0"]), atol=1e-4)
