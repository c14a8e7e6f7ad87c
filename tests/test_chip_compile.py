"""The main path's Pallas kernels compile for a TPU v5e at qwen3-14b widths.

Interpret mode (the rest of the suite) never applies Mosaic's lowering
rules, so these tests ask the TPU compiler itself, for a described
``v5e:2x2`` topology (no chip attached): the fused ``mpo_linear`` forward
at the query-projection cores and forward+backward at the K/V-projection
cores, at every tile height the eligibility gate admits, the flash
decode-attention kernel at qwen3-14b's head geometry, and the XLA path's
windowed decode scan at the chat pool's geometry (no copy of the page
stack into its conditionals).

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and collection runs in every test
worker).  The persistent compilation cache is off around these compiles:
an executable for a described device is written but cannot be read back
without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import decode_attention as DA
from repro.kernels.mpo_linear import kernel_eligible, mpo_linear
from repro.models import nn

# qwen3-14b cores (configs/qwen3_14b.py): wq 5120 -> 5120, wk 5120 -> 1024
WQ = ((1, 5, 5, 25), (25, 8, 8, 128), (128, 8, 8, 128), (128, 4, 4, 16),
      (16, 4, 4, 1))
WK = ((1, 5, 4, 20), (20, 8, 4, 128), (128, 8, 4, 128), (128, 4, 4, 16),
      (16, 4, 4, 1))
TOKENS = 512                 # the LFA fine-tune step's 16 x 32 tokens


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is in there
    return compiled


def _admitted(shapes, train):
    tiles = [bm for bm in autotune.CANDIDATE_BLOCK_MS
             if kernel_eligible(shapes, bm, train=train)]
    assert tiles, f"no tile admitted for {shapes} (train={train})"
    return tiles


def test_mpo_linear_forward_compiles_at_wq(one_chip):
    cores = tuple(_sds(s, jnp.bfloat16, one_chip) for s in WQ)
    x = _sds((TOKENS, 5120), jnp.bfloat16, one_chip)
    for bm in _admitted(WQ, train=False):
        _compile(lambda c, x, bm=bm: mpo_linear(c, x, block_m=bm,
                                                interpret=False), cores, x)


def test_mpo_linear_fwd_bwd_compiles_at_wk(one_chip):
    cores = tuple(_sds(s, jnp.bfloat16, one_chip) for s in WK)
    x = _sds((TOKENS, 5120), jnp.bfloat16, one_chip)
    for bm in _admitted(WK, train=True):
        def loss(c, x, bm=bm):
            y = mpo_linear(c, x, block_m=bm, interpret=False)
            return jnp.sum(y.astype(jnp.float32))
        _compile(jax.grad(loss, argnums=(0, 1)), cores, x)


def test_flash_decode_attention_compiles(one_chip):
    slots, kv, g, dh, ps, mp = 8, 8, 5, 128, 16, 16
    args = (_sds((slots, kv, g, dh), jnp.bfloat16, one_chip),
            _sds((slots * mp, ps, kv, dh), jnp.bfloat16, one_chip),
            _sds((slots * mp, ps, kv, dh), jnp.bfloat16, one_chip),
            _sds((slots, mp), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip),
            _sds((slots, mp * ps), jnp.float32, one_chip))
    _compile(lambda *a: DA._flash_jit(*a, interpret=False), *args)


def test_windowed_decode_scan_keeps_the_page_stack(one_chip):
    """The chat pool's decode attention (32 slots x 4096 keys in pages of
    16, 8 KV heads of 128, 4 layers): a layer scan that appends to the
    donated page stacks and attends through ``nn._windowed_decode``.  The
    stacks enter the window conditionals without a copy, a slice or a
    write-back of a stack or of one layer's pool."""
    layers, slots, mp, ps, kv, g, dh = 4, 32, 256, 16, 8, 5, 128
    pool = slots * mp
    cfg = nn.AttnCfg(d_model=kv * g * dh, num_heads=kv * g, num_kv_heads=kv,
                     head_dim=dh)

    def decode(kp, vp, table, pos, free, count, q, k):
        kj = jnp.arange(mp * ps)[None, :]
        mask = (kj <= pos[0][:, None])[:, None, None, :]

        def body(carry, xs):
            kp, vp, acc = carry
            table, pos, free, count, i = xs
            cache = nn._paged_decode_append(
                dict(k_pages=kp, v_pages=vp, page_table=table, pos=pos,
                     free_list=free, free_count=count), k, k, (i,))
            kp, vp = cache["k_pages"], cache["v_pages"]
            y = nn._windowed_decode(q, kp, vp, cache["page_table"],
                                    cache["pos"], cfg, mask, i)
            return (kp, vp, acc + y.astype(jnp.float32).sum()), None

        (kp, vp, acc), _ = jax.lax.scan(
            body, (kp, vp, jnp.float32(0)),
            (table, pos, free, count, jnp.arange(layers)))
        return kp, vp, acc

    stack = (layers, pool, ps, kv, dh)
    args = (_sds(stack, jnp.bfloat16, one_chip),
            _sds(stack, jnp.bfloat16, one_chip),
            _sds((layers, slots, mp), jnp.int32, one_chip),
            _sds((layers, slots), jnp.int32, one_chip),
            _sds((layers, pool), jnp.int32, one_chip),
            _sds((layers,), jnp.int32, one_chip),
            _sds((slots, 1, kv * g, dh), jnp.bfloat16, one_chip),
            _sds((slots, 1, kv, dh), jnp.bfloat16, one_chip))
    hlo = jax.jit(decode, donate_argnums=(0, 1)).lower(
        *args).compile().as_text()
    assert hlo.count(" conditional(") == slots // DA.DECODE_GROUP
    pools = {stack, stack[1:], (1,) + stack[1:]}
    moves = [(op, dims) for dims, op in re.findall(
        r"= \w+\[([0-9,]*)\]\S*\s+(dynamic-slice|dynamic-update-slice|copy)\(",
        hlo) if tuple(int(d) for d in dims.split(",") if d) in pools]
    assert not moves, moves
