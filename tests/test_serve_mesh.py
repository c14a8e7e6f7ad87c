"""Mesh-sharded serving tests on a forced 8-device CPU mesh.

Each test runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the parent pytest
process is pinned to one CPU device by conftest).  Asserted invariants, per
the sharded-serving design (docs/serving.md):

(a) ``Session.serve(mesh=...)`` output matches the single-device path
    token-for-token, and the dense cached Ws carry non-replicated
    ``NamedSharding``s;
(b) heavily compressed factorized tables are NEVER re-materialized as a
    replicated dense W — they stay factorized with per-core placements;
(c) ``ServePool`` slot recycling over the mesh produces tokens identical
    to serial single-tenant generation;
(d) ``make_host_mesh`` rejects a model-axis size that doesn't divide the
    device count with an actionable error.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys; sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import Session
    from repro.launch.mesh import make_host_mesh
"""


def _subproc(code: str, timeout=560):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, cwd=ROOT,
                          timeout=timeout, env=env)


def test_mesh_serve_parity_and_dense_w_shardings():
    """(a): 8-way mesh generate == single-device generate, with dense serve
    params actually distributed (non-trivial PartitionSpecs)."""
    code = _PRELUDE + """
    from repro.configs.base import ShapeConfig
    from repro.models import model as M

    mesh = make_host_mesh(model=4)
    s = Session.init("qwen3-14b")
    h_mesh = s.serve(4, 24, mesh=mesh)
    h_one = s.serve(4, 24)
    batch = M.make_batch(s.cfg, ShapeConfig("t", "prefill", 8, 4))
    out_mesh = h_mesh.generate(batch, 8)
    out_one = h_one.generate(batch, 8)
    assert bool(jnp.all(out_mesh == out_one)), (np.asarray(out_mesh),
                                                np.asarray(out_one))

    # dense cached Ws carry non-replicated NamedShardings on the mesh
    flat = jax.tree_util.tree_flatten_with_path(h_mesh.params)[0]
    dense_specs = {
        "/".join(str(getattr(p, "key", "")) for p in path):
            leaf.sharding.spec
        for path, leaf in flat
        if str(getattr(path[-1], "key", "")) == "w"}
    sharded = {k: s for k, s in dense_specs.items() if s != P()}
    assert len(sharded) >= 4, dense_specs
    assert any("model" in str(s) for s in sharded.values()), sharded
    # the KV cache sits in the flash-decoding layout: batch over data,
    # cache seq dim over model; per-slot positions replicated
    assert h_mesh.cache["k"].sharding.spec == P(None, "data", "model",
                                                None, None)
    assert h_mesh.cache["pos"].sharding.spec == P()
    print("MESH_PARITY_OK")
    """
    r = _subproc(code)
    assert "MESH_PARITY_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_mesh_factorized_tables_stay_factorized():
    """(b): a heavily compressed embedding (decode plan: factorized) must
    keep its cores on the mesh — no replicated dense [vocab, d] W anywhere
    in the serve params — and the cores get their own per-core specs."""
    code = _PRELUDE + """
    import dataclasses
    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.models import model as M

    mesh = make_host_mesh(model=4)
    cfg = configs.smoke_config("qwen3-14b", vocab_size=2048)
    cfg = dataclasses.replace(
        cfg, mpo=dataclasses.replace(cfg.mpo, bond_embed=4))
    s = Session.init(cfg)
    h = s.serve(4, 24, mesh=mesh)

    # the embedding stayed factorized: cores present, dense "w" absent
    embed = h.params["embed"]
    assert "cores" in embed and "w" not in embed, list(embed)
    vocab, d = s.cfg.vocab_size, s.cfg.d_model
    for leaf in jax.tree.leaves(h.params):
        assert leaf.shape[-2:] != (vocab, d), \\
            "a dense [vocab, d] table materialized on the mesh"
    # every core was placed individually (committed NamedShardings)
    for name, core in embed["cores"].items():
        assert core.sharding.mesh.shape == dict(data=2, model=4), name
    # and the factorized serving path still matches single-device output
    batch = M.make_batch(s.cfg, ShapeConfig("t", "prefill", 8, 4))
    out_mesh = h.generate(batch, 6)
    out_one = s.serve(4, 24).generate(batch, 6)
    assert bool(jnp.all(out_mesh == out_one))
    print("FACTORIZED_OK")
    """
    r = _subproc(code)
    assert "FACTORIZED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_mesh_pool_recycling_matches_serial():
    """(c): multi-tenant ServePool over the mesh — slot recycling with
    mixed budgets produces exactly the serial batch-1 tokens."""
    code = _PRELUDE + """
    s = Session.init("qwen3-14b")
    mesh = make_host_mesh(model=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, size=p).astype(np.int32)
               for p in (8, 5, 8, 11)]
    budgets = [6, 9, 4, 7]
    h1 = s.serve(1, 32)
    serial = [np.asarray(h1.generate(
        {"tokens": jnp.asarray(p)[None, :]}, n))[0]
        for p, n in zip(prompts, budgets)]
    pool = s.serve_pool(slots=2, max_len=32, mesh=mesh)
    rids = [pool.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = pool.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(outs[rid], serial[i],
                                      err_msg=f"request {i}")
    st = pool.stats()
    assert st["completed"] == 4 and st["mesh"] == dict(data=2, model=4)
    print("MESH_POOL_OK")
    """
    r = _subproc(code)
    assert "MESH_POOL_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.parametrize("model,impl,pages_spec", [
    # 2 KV heads over model=4: in-page seq over model, the XLA gather
    (4, "", (None, None, "model")),
    # 2 KV heads over model=2: heads over model, the flash kernel per device
    (2, "flash", (None, None, None, "model")),
])
def test_mesh_pool_paged_matches_serial(model, impl, pages_spec):
    """Paged KV over the 8-device mesh: the pool decodes through the paged
    cache (page data in the paged flash layout — KV heads over model where
    the axis divides them, else in-page seq; table/free-list leaves
    replicated) and still produces exactly the serial batch-1 tokens, with
    every page returned on drain and no flash fallback."""
    code = _PRELUDE + f"""
    model, impl, pages_spec = {model}, {impl!r}, {pages_spec!r}
    """ + """
    os.environ["REPRO_DECODE_ATTN"] = impl
    s = Session.init("qwen3-14b")
    mesh = make_host_mesh(model=model)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, size=p).astype(np.int32)
               for p in (8, 5, 11)]
    budgets = [6, 9, 7]
    h1 = s.serve(1, 32)
    serial = [np.asarray(h1.generate(
        {"tokens": jnp.asarray(p)[None, :]}, n))[0]
        for p, n in zip(prompts, budgets)]
    pool = s.serve_pool(slots=2, max_len=32, mesh=mesh, paged=True,
                        page_size=8)
    rids = [pool.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = pool.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(outs[rid], serial[i],
                                      err_msg=f"request {i}")
    st = pool.stats()
    assert st["completed"] == 3 and st["page_pool"]["used"] == 0
    assert st["flash_fallbacks"] == 0
    # paged flash layout on the mesh; page table / free list / positions
    # replicated
    kp = pool._cache["k_pages"]
    assert kp.sharding.spec == P(*pages_spec), kp.sharding.spec
    assert pool._cache["page_table"].sharding.spec == P()
    assert pool._cache["free_list"].sharding.spec == P()
    print("MESH_PAGED_OK")
    """
    r = _subproc(code)
    assert "MESH_PAGED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_make_host_mesh_rejects_nondividing_model_axis():
    """(d): the clear error replaces mesh_utils' obscure failure."""
    import jax
    from repro.launch.mesh import make_host_mesh
    n = jax.device_count()
    with pytest.raises(ValueError, match="does not divide"):
        make_host_mesh(model=n + 1)
    with pytest.raises(ValueError, match="must be >= 1"):
        make_host_mesh(model=0)


def test_serve_mesh_without_axes_raises():
    """Session built raw (no axes tree) must fail serve(mesh=) loudly."""
    import jax
    from repro import Session, configs
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    cfg = configs.smoke_config("qwen3-14b")
    params, _ = M.build(cfg).init_params(jax.random.PRNGKey(0))
    s = Session(cfg, params)  # axes=None
    mesh = make_host_mesh(model=1)
    with pytest.raises(ValueError, match="logical-axis tree"):
        s.serve(2, 16, mesh=mesh)
