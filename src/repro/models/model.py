"""Unified model API: family dispatch + ShapeDtypeStruct input specs.

``build(cfg)`` returns a ``Model`` whose methods close over the config.  The
``input_specs`` / ``cache_specs`` functions return ``jax.ShapeDtypeStruct``
stand-ins (no allocation) — the dry-run lowers against these.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import layers as L
from repro.models import mamba, transformer, whisper, zamba


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable           # key -> Annot tree
    forward: Callable        # (params, batch[, phase]) -> (logits, aux)
    forward_hidden: Callable  # (params, batch[, phase]) -> (hidden, aux)
    logits_head: Callable    # (params, hidden[, phase]) -> logits
    init_cache: Callable     # (batch, max_len) -> cache pytree
    prefill: Callable        # (params, batch, cache[, phase]) -> (logits, cache)
    decode_step: Callable    # (params, tokens, cache[, phase]) -> (logits, cache)
    # incremental prefill: one chunk at the cache's current offset ->
    # (all-position logits, cache); None for families without a KV-sequence
    # cache to continue (ssm/hybrid/encdec)
    prefill_chunk: Callable | None = None

    def init_params(self, key):
        """(params, axes) — values split from logical-axis annotations."""
        return L.split_annotations(self.init(key))

    def cache_weights(self, params, *, axes=None):
        """Serving-time weight cache: contract decode-``cached`` matrices to
        dense W once (done at serving init, next to the KV cache).  With
        ``axes`` returns ``(params, axes)`` — the dense W inherits the cores'
        TP layout (see ``MPOEngine.cache_weights``).

        W is stored in the activation dtype: every use casts it there
        anyway (``MPOEngine.linear`` / ``embedding``), so a wider copy
        would only double the snapshot's device memory."""
        from repro.core.engine import engine_for
        return engine_for(self.cfg.mpo).cache_weights(
            params, axes=axes, dtype=self.cfg.jnp_dtype)


def build(cfg: ModelConfig) -> Model:
    fam = cfg.family
    mod = {"dense": transformer, "moe": transformer, "vlm": transformer,
           "ssm": mamba, "hybrid": zamba, "encdec": whisper}.get(fam)
    if mod is None:
        raise ValueError(f"unknown family {fam}")
    def init_cache(b, m, **kw):
        # paged KV (kw: paged=, page_size=) exists for the transformer
        # families only — SSM states and the hybrid/encdec caches have no
        # per-slot KV sequence to page
        if fam == "ssm":
            if kw.get("paged"):
                raise ValueError(
                    "paged KV cache requires an attention KV cache; "
                    f"family {fam!r} has none")
            return mamba.init_ssm_state(cfg, b)
        if fam not in ("dense", "moe", "vlm") and kw.get("paged"):
            raise ValueError(
                f"paged KV cache is not supported for family {fam!r}")
        return mod.init_cache(cfg, b, m, **kw) \
            if fam in ("dense", "moe", "vlm") else mod.init_cache(cfg, b, m)
    return Model(
        cfg=cfg,
        init=lambda key: mod.init(key, cfg),
        forward=lambda p, b, phase="train": mod.forward(p, b, cfg,
                                                        phase=phase),
        forward_hidden=lambda p, b, phase="train": mod.forward_hidden(
            p, b, cfg, phase=phase),
        logits_head=lambda p, h, phase="train": mod.logits_head(
            p, h, cfg, phase=phase),
        init_cache=init_cache,
        prefill=lambda p, b, c, phase="prefill": mod.prefill(
            p, b, c, cfg, phase=phase),
        decode_step=lambda p, t, c, phase="decode": mod.decode_step(
            p, t, c, cfg, phase=phase),
        prefill_chunk=(
            (lambda p, b, c, phase="prefill": mod.prefill_chunk(
                p, b, c, cfg, phase=phase))
            if fam in ("dense", "moe", "vlm") else None),
    )


# --------------------------------------------------------------------------
# shape-struct inputs for the dry-run
# --------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """Model inputs for one (arch x shape) cell, as ShapeDtypeStructs."""
    b, s = shape.global_batch, shape.seq_len
    i32, bf16 = jnp.int32, jnp.bfloat16
    if shape.kind == "train":
        if cfg.family == "vlm":
            text = s - cfg.frontend_len
            return {"tokens": _sds((b, text), i32),
                    "patches": _sds((b, cfg.frontend_len, cfg.frontend_dim), bf16),
                    "labels": _sds((b, s), i32)}
        if cfg.family == "encdec":
            return {"frames": _sds((b, cfg.frontend_len, cfg.d_model), bf16),
                    "tokens": _sds((b, s), i32),
                    "labels": _sds((b, s), i32)}
        return {"tokens": _sds((b, s), i32), "labels": _sds((b, s), i32)}
    if shape.kind == "prefill":
        if cfg.family == "vlm":
            text = s - cfg.frontend_len
            return {"tokens": _sds((b, text), i32),
                    "patches": _sds((b, cfg.frontend_len, cfg.frontend_dim), bf16)}
        if cfg.family == "encdec":
            return {"frames": _sds((b, cfg.frontend_len, cfg.d_model), bf16),
                    "tokens": _sds((b, s), i32)}
        return {"tokens": _sds((b, s), i32)}
    # decode: one new token against a seq_len cache
    return {"tokens": _sds((b, 1), i32)}


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Decode-cache ShapeDtypeStructs (mirrors each family's init_cache)."""
    b, s = shape.global_batch, shape.seq_len
    model = build(cfg)
    return jax.eval_shape(lambda: model.init_cache(b, s))


def make_batch(cfg: ModelConfig, shape: ShapeConfig, key=None):
    """Concrete (small-scale) batch matching input_specs — for smoke tests."""
    key = key if key is not None else jax.random.PRNGKey(0)
    specs = input_specs(cfg, shape)
    out = {}
    for name, sd in specs.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(sd.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, sd.shape, 0,
                                           min(cfg.vocab_size, 1000), sd.dtype)
        else:
            out[name] = jax.random.normal(sub, sd.shape, jnp.float32).astype(sd.dtype)
    return out
