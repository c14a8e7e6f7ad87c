"""Decoder-only transformer LM — covers the dense / MoE / VLM families.

Layer stack is ``lax.scan``-compiled (compile time + HLO size at 48L/400B
scale); per-layer variation (gemma2 local/global alternation) rides in as a
scanned ``is_local`` flag.  VLM configs prepend projected patch embeddings
(the modality frontend itself is a stub per the assignment).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import layers as L
from repro.models import nn, paged_cache
from repro.models.moe import apply_moe, init_moe


def attn_cfg(cfg: ModelConfig) -> nn.AttnCfg:
    return nn.AttnCfg(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
        attn_softcap=cfg.attn_softcap)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig):
    ka, km, _ = jax.random.split(key, 3)
    p = {"ln1": nn.init_rmsnorm(cfg.d_model),
         "ln2": nn.init_rmsnorm(cfg.d_model),
         "attn": nn.init_attention(ka, attn_cfg(cfg), cfg.mpo)}
    if cfg.num_experts:
        p["moe"] = init_moe(km, cfg.d_model, cfg.d_ff, cfg.num_experts,
                            cfg.mlp_act, cfg.mpo)
    else:
        p["mlp"] = nn.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.mlp_act, cfg.mpo)
    return p


def init(key, cfg: ModelConfig):
    k_emb, k_layers, k_proj = jax.random.split(key, 3)
    params = {
        "embed": L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model,
                                  cfg=cfg.mpo),
        "layers": nn.stack_layers(lambda k: init_layer(k, cfg), k_layers,
                                  cfg.num_layers),
        "final_norm": nn.init_rmsnorm(cfg.d_model),
    }
    if cfg.family == "vlm":
        params["projector"] = L.init_linear(
            k_proj, cfg.frontend_dim, cfg.d_model, cfg=L.DENSE,
            in_axis=None, out_axis=None)
    if cfg.share_layers:  # ALBERT-style: one layer scanned num_layers times
        params["layers"] = nn.stack_layers(lambda k: init_layer(k, cfg),
                                           k_layers, 1)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(
            k_proj, cfg.d_model, cfg.vocab_size, cfg=cfg.mpo, kind="embed",
            out_axis="vocab", sharded_out=True)
    if cfg.num_classes:
        params["cls_head"] = L.init_linear(
            k_proj, cfg.d_model, cfg.num_classes, cfg=L.DENSE)
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _is_local_flags(cfg: ModelConfig) -> jax.Array:
    if cfg.local_window is None:
        return jnp.zeros((cfg.num_layers,), bool)
    return (jnp.arange(cfg.num_layers) % 2) == 0  # even layers local


def _layer_fwd(cfg: ModelConfig, x, layer, *, positions, mask, mask_local,
               cache=None, phase="train", chunk=False, layer_idx=None):
    acfg = attn_cfg(cfg)
    is_local = layer.pop("_is_local") if "_is_local" in layer else None
    m = mask if is_local is None else jnp.where(is_local, mask_local, mask)
    from repro.parallel import ctx
    h = nn.apply_rmsnorm(layer["ln1"], x)
    a, new_cache = nn.apply_attention(layer["attn"], h, acfg, cfg.mpo,
                                      positions=positions, mask=m, cache=cache,
                                      phase=phase, chunk=chunk,
                                      layer_idx=layer_idx)
    x = ctx.shard_activation(x + a)
    h = nn.apply_rmsnorm(layer["ln2"], x)
    if cfg.num_experts:
        f, aux = apply_moe(layer["moe"], h, act=cfg.mlp_act, mpo=cfg.mpo,
                           top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor, phase=phase)
    else:
        f, aux = nn.apply_mlp(layer["mlp"], h, cfg.mlp_act, cfg.mpo,
                              phase=phase), 0.0
    return ctx.shard_activation(x + f), new_cache, aux


def _run_stack(cfg: ModelConfig, params, x, *, positions, mask, mask_local,
               caches=None, phase="train", chunk=False):
    """Scan the layer stack; returns (x, new_caches, aux_loss_sum).

    With ``caches``, the cache rides in the scan CARRY, with the layer
    index scanned alongside: each layer appends into its own slice of the
    K/V stacks in place and reads it back, so no layer's K/V is sliced out
    of the stack and stacked back each step.  Positions, page bookkeeping
    and a paged cache's write plan are the same for every layer: they pass
    through the scan unchanged (``_serve_stack`` advances them once)."""
    flags = _is_local_flags(cfg)
    idx = None if caches is None else jnp.arange(cfg.num_layers)

    def body(carry, scanned):
        x, aux_sum, cache = carry
        layer, flag, i = scanned
        layer = dict(layer)
        if cfg.local_window is not None:
            layer["_is_local"] = flag
        y, cache, aux = _layer_fwd(cfg, x, layer, positions=positions,
                                   mask=mask, mask_local=mask_local,
                                   cache=cache, phase=phase, chunk=chunk,
                                   layer_idx=i)
        return (y, aux_sum + aux, cache), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    layer_params = params["layers"]
    if cfg.share_layers:  # broadcast the single shared layer across the scan
        layer_params = jax.tree.map(
            lambda a: jnp.broadcast_to(a[0], (cfg.num_layers,) + a.shape[1:]),
            layer_params)
    (x, aux, caches), _ = jax.lax.scan(
        body, (x, jnp.array(0.0, jnp.float32), caches),
        (layer_params, flags, idx))
    return x, caches, aux


def _logits(cfg: ModelConfig, params, x, phase="train"):
    if cfg.tie_embeddings:
        logits = L.apply_logits(params["embed"], x, cfg=cfg.mpo, phase=phase)
    else:
        logits = L.apply_linear(params["lm_head"], x, cfg=cfg.mpo,
                                phase=phase)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def _embed_inputs(cfg: ModelConfig, params, batch, phase="train"):
    """Token (+ optional patch) embeddings -> (B, S, D)."""
    x = L.apply_embedding(params["embed"], batch["tokens"], cfg=cfg.mpo,
                          dtype=cfg.jnp_dtype, phase=phase)
    x = x * (cfg.d_model ** 0.5) if cfg.name.startswith("gemma") else x
    if cfg.family == "vlm" and "patches" in batch:
        p = batch["patches"] @ params["projector"]["w"]
        x = jnp.concatenate([p.astype(x.dtype), x], axis=1)
    from repro.parallel import ctx
    return ctx.shard_activation(x.astype(cfg.jnp_dtype))


def forward_hidden(params, batch, cfg: ModelConfig, *, phase="train"):
    """Teacher-forced forward up to the final norm -> (hidden, aux_loss)."""
    x = _embed_inputs(cfg, params, batch, phase)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    if cfg.causal:
        mask = nn.causal_mask(s, s)
    else:  # encoder (BERT/ALBERT analog): full bidirectional attention
        mask = jnp.ones((1, 1, s, s), bool)
    mask_local = nn.causal_mask(s, s, window=cfg.local_window)
    x, _, aux = _run_stack(cfg, params, x, positions=positions, mask=mask,
                           mask_local=mask_local, caches=None, phase=phase)
    return nn.apply_rmsnorm(params["final_norm"], x), aux


def logits_head(params, hidden, cfg: ModelConfig, *, phase="train"):
    return _logits(cfg, params, hidden, phase)


def forward(params, batch, cfg: ModelConfig, *, phase="train"):
    """Teacher-forced forward -> (logits, aux_loss)."""
    hidden, aux = forward_hidden(params, batch, cfg, phase=phase)
    return _logits(cfg, params, hidden, phase), aux


def forward_cls(params, batch, cfg: ModelConfig):
    """Sequence classification (paper's GLUE-analog): pool first token."""
    x = _embed_inputs(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    mask = nn.causal_mask(s, s) if cfg.causal else jnp.ones((1, 1, s, s), bool)
    mask_local = nn.causal_mask(s, s, window=cfg.local_window)
    x, _, aux = _run_stack(cfg, params, x, positions=positions, mask=mask,
                           mask_local=mask_local, caches=None)
    x = nn.apply_rmsnorm(params["final_norm"], x)
    pooled = x[:, 0]
    return L.apply_linear(params["cls_head"], pooled, cfg=L.DENSE), aux


# --------------------------------------------------------------------------
# serving (prefill / decode against a KV cache)
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None, *,
               paged: bool = False, page_size: int = 16,
               pool_pages: int | None = None):
    """KV cache with PER-SLOT positions: ``pos`` is (batch,), so each
    batch row ("slot") can sit at its own decode offset — the substrate for
    multi-tenant batched decode (``pipeline.scheduler.ServePool``), where
    finished slots are recycled mid-generation without disturbing the
    positions of live tenants.  The K/V stacks keep the leading layers dim
    the layer scan indexes; positions are held once for every layer.

    ``paged=True`` swaps the dense ``(layers, B, max_len)`` layout for a
    paged one (vLLM-style, ``models.paged_cache``): K/V live in a pool of
    fixed-size pages, each slot maps logical pages to physical ones
    through its page-table row, and pages are allocated lazily as a slot's
    context grows — so decode attention bandwidth scales with a slot's own
    length (``kernels.decode_attention``), and ``ServePool`` returns a
    finished slot's pages to the pool at recycle.  ``pool_pages`` below
    ``batch * max_len / page_size`` oversubscribes the pool."""
    dtype = dtype or cfg.jnp_dtype
    acfg = attn_cfg(cfg)
    if paged:
        return paged_cache.init(cfg.num_layers, batch, max_len,
                                acfg.num_kv_heads, acfg.head_dim, dtype,
                                page_size=page_size, pool_pages=pool_pages)
    shape = (cfg.num_layers, batch, max_len, acfg.num_kv_heads, acfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


def cache_kv_len(cache) -> int:
    """Key span the decode masks cover: ``max_len`` for dense caches, page
    capacity (``paged_cache.span``, >= max_len) for paged ones."""
    if "k_pages" in cache:
        return paged_cache.span(cache)
    return cache["k"].shape[2]


def _serve_stack(cfg: ModelConfig, params, x, cache, *, positions, mask,
                 mask_local, phase, chunk=False):
    """Run the layer stack over ``x.shape[1]`` new tokens per slot and
    advance the cache's bookkeeping once: a paged cache plans its append
    (``paged_cache.plan``) before the scan and every layer writes at the
    planned indices; a dense cache writes at ``pos``.  Returns (x, cache)."""
    s = x.shape[1]
    if "k_pages" in cache:
        cache, at = paged_cache.plan(cache, s)
        x, out, _ = _run_stack(cfg, params, x, positions=positions, mask=mask,
                               mask_local=mask_local, caches=dict(cache, at=at),
                               phase=phase, chunk=chunk)
        return x, {n: out[n] for n in cache}
    x, out, _ = _run_stack(cfg, params, x, positions=positions, mask=mask,
                           mask_local=mask_local, caches=cache, phase=phase,
                           chunk=chunk)
    return x, dict(out, pos=cache["pos"] + s)


def prefill(params, batch, cache, cfg: ModelConfig, *, phase="prefill"):
    """Fill KV caches with the prompt; returns (last_logits, cache)."""
    x = _embed_inputs(cfg, params, batch, phase)
    s = x.shape[1]
    max_len = cache_kv_len(cache)
    positions = jnp.arange(s)[None, :]
    mask = nn.causal_mask(s, max_len)
    mask_local = nn.causal_mask(s, max_len, window=cfg.local_window)
    x, cache = _serve_stack(cfg, params, x, cache, positions=positions,
                            mask=mask, mask_local=mask_local, phase=phase)
    x = nn.apply_rmsnorm(params["final_norm"], x)
    return _logits(cfg, params, x[:, -1:], phase), cache


def prefill_chunk(params, batch, cache, cfg: ModelConfig, *, phase="prefill"):
    """One CHUNK of an incremental prefill: run ``s`` prompt tokens at each
    slot's CURRENT cache offset (``cache["pos"]``), appending their K/V.

    The substrate for chunked prefill (``pipeline.scheduler.ServePool``
    ``prefill_chunk=``): a long prompt is split into fixed-size chunks and
    fed through this step between live decode steps, so admission never
    stalls live tenants for the whole prompt's forward.  Chunk ``c``'s
    queries apply RoPE at their global offsets and attend every key at or
    before them (earlier chunks included), which makes the concatenation of
    chunks token-identical to one unchunked ``prefill``.

    Returns ``(logits, cache)`` with logits for ALL ``s`` chunk positions —
    the caller picks the row of the real last prompt token (under padded /
    length-bucketed admission that is generally not the last chunk row).
    Multi-row batches must sit at one shared offset (admission is batch-1;
    the dense cache write uses row 0's position for the slice start)."""
    x = _embed_inputs(cfg, params, batch, phase)
    s = x.shape[1]
    max_len = cache_kv_len(cache)
    start = cache["pos"]                           # (B,) per-slot offsets
    positions = start[:, None] + jnp.arange(s)[None, :]      # (B, s)
    kj = jnp.arange(max_len)[None, None, :]
    qi = positions[:, :, None]                     # (B, s, 1)
    mask = (kj <= qi)[:, None]                     # (B, 1, s, max_len)
    if cfg.local_window is not None:
        mask_local = mask & (kj > qi - cfg.local_window)[:, None]
    else:
        mask_local = mask
    x, cache = _serve_stack(cfg, params, x, cache, positions=positions,
                            mask=mask, mask_local=mask_local, phase=phase,
                            chunk=True)
    x = nn.apply_rmsnorm(params["final_norm"], x)
    return _logits(cfg, params, x, phase), cache


def decode_step(params, tokens, cache, cfg: ModelConfig, *, phase="decode"):
    """One-token decode against a filled cache.  tokens: (B, 1).

    Positions are per slot (``cache["pos"]``: (batch,)): each batch
    row applies RoPE at its own offset and masks keys beyond its own
    position, so rows admitted at different times decode correctly side by
    side in one batched step."""
    x = _embed_inputs(cfg, params, {"tokens": tokens}, phase)
    max_len = cache_kv_len(cache)
    pos = cache["pos"]                             # (B,) per-slot positions
    positions = pos[:, None]                       # (B, 1) for rope
    kj = jnp.arange(max_len)[None, :]
    mask = (kj <= pos[:, None])[:, None, None, :]  # (B, 1, 1, S)
    if cfg.local_window is not None:
        mask_local = mask & \
            (kj > pos[:, None] - cfg.local_window)[:, None, None, :]
    else:
        mask_local = mask
    x, cache = _serve_stack(cfg, params, x, cache, positions=positions,
                            mask=mask, mask_local=mask_local, phase=phase)
    x = nn.apply_rmsnorm(params["final_norm"], x)
    return _logits(cfg, params, x, phase), cache
