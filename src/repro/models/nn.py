"""Shared neural building blocks (MPO-aware) for the architecture zoo.

All init functions return ``Annot``-leaf trees (value + logical axes); apply
functions consume plain value trees (post ``split_annotations``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import layers as L
from repro.core.layers import Annot, MPOConfig


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------


def init_rmsnorm(dim: int, axis: str | None = "embed"):
    return {"scale": Annot(jnp.ones((dim,), jnp.float32), (axis,))}


def apply_rmsnorm(params, x, eps: float = 1e-6):
    # variance reduction in f32, normalize/scale muls in the compute dtype —
    # keeps the (all-reduced) activation gradients bf16 (EXPERIMENTS §Perf A)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * params["scale"].astype(x.dtype)


def init_layernorm(dim: int):
    return {"scale": Annot(jnp.ones((dim,), jnp.float32), ("embed",)),
            "bias": Annot(jnp.zeros((dim,), jnp.float32), ("embed",))}


def apply_layernorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return ((x - mu.astype(x.dtype)) * inv * params["scale"].astype(x.dtype)
            + params["bias"].astype(x.dtype))


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (GQA + local windows + softcap + qk-norm)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    qk_norm: bool = False
    attn_softcap: float | None = None
    causal: bool = True
    use_rope: bool = True


def init_attention(key, cfg: AttnCfg, mpo: MPOConfig, *, cross: bool = False):
    kq, kk, kv, ko, _ = jax.random.split(key, 5)
    d, h, kvh, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # TP-shard a projection only if its HEAD count divides the model axis —
    # sharding the flattened (H*Dh) dim otherwise splits head_dim after the
    # reshape and GSPMD all-reduces the (Sq x Sk) attention scores
    # (observed 300 GiB/step on qwen3 with 40 heads over 16; §Perf it.13).
    q_ok = mpo.shard_multiple <= 1 or h % mpo.shard_multiple == 0
    kv_ok = mpo.shard_multiple <= 1 or kvh % mpo.shard_multiple == 0
    p = {
        "wq": L.init_linear(kq, d, h * dh, cfg=mpo, kind="attn",
                            out_axis="qkv", sharded_out=q_ok),
        "wk": L.init_linear(kk, d, kvh * dh, cfg=mpo, kind="attn",
                            out_axis="kv_qkv", sharded_out=kv_ok),
        "wv": L.init_linear(kv, d, kvh * dh, cfg=mpo, kind="attn",
                            out_axis="kv_qkv", sharded_out=kv_ok),
        "wo": L.init_linear(ko, h * dh, d, cfg=mpo, kind="attn",
                            in_axis="qkv", sharded_in=q_ok,
                            scale=(h * dh) ** -0.5),
    }
    if cfg.qk_norm:
        # head_dim-sized scales: NOT an embed dim, so no FSDP ("embed" ->
        # data) annotation — sharding a Dh-element broadcast scale saves
        # nothing and has produced numerically wrong GSPMD output on
        # forced-CPU meshes (mesh-serving bring-up)
        p["q_norm"] = init_rmsnorm(dh, axis=None)
        p["k_norm"] = init_rmsnorm(dh, axis=None)
    return p


def _split_heads(x, n, dh):
    return x.reshape(x.shape[:-1] + (n, dh))


def attention_scores(q, k, cfg: AttnCfg, mask):
    """Grouped-query scores without materializing repeated K.

    q: (B,Sq,H,Dh), k: (B,Sk,KV,Dh) -> (B,KV,G,Sq,Sk) softmax weights
    (H = KV*G).  Avoiding ``jnp.repeat`` keeps the KV tensors in whatever
    layout the cache uses (seq-sharded under flash-decoding; §Perf it.10)
    and skips a (B,S,H,Dh)-sized materialization.
    """
    b, sq, h, dh = q.shape
    g = h // cfg.num_kv_heads
    qg = q.reshape(b, sq, cfg.num_kv_heads, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / math.sqrt(cfg.head_dim)
    if cfg.attn_softcap:
        c = cfg.attn_softcap
        scores = c * jnp.tanh(scores / c)
    scores = jnp.where(mask[:, :, None], scores, -2.3819763e38)
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


def causal_mask(sq: int, sk: int, *, window: int | None = None,
                offset: int = 0) -> jax.Array:
    """(1,1,Sq,Sk) boolean; query i attends key j iff j <= i+offset
    (and i+offset-j < window for local attention)."""
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window is not None:
        m = m & (qi - kj < window)
    return m[None, None]


def _paged_prefill_append(cache, k, v, lead=()):
    """Write a start-0 prompt's K/V into freshly allocated pages.

    Prefill always begins at position 0 (its masks/positions assume it), so
    allocation is a vectorized pop of ``ceil(s / ps)`` pages per slot off
    the free-list stack.  Returns the updated paged-cache leaves.

    ``lead`` indexes the page pools ahead of the page dim: ``(layer,)``
    when they are the whole layer stack (written in place, see
    ``apply_attention``), ``()`` for one layer's pool."""
    b, s = k.shape[0], k.shape[1]
    kp, vp = cache["k_pages"], cache["v_pages"]
    table, fl, fc = cache["page_table"], cache["free_list"], cache["free_count"]
    ps = kp.shape[-3]
    npg = -(-s // ps)                              # pages per slot (static)
    pad = npg * ps - s
    kq = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(kp.dtype)
    vq = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(vp.dtype)
    kq = kq.reshape(b, npg, ps, *k.shape[2:])
    vq = vq.reshape(b, npg, ps, *v.shape[2:])
    pids = fl[fc - 1 - jnp.arange(b * npg)].reshape(b, npg)
    at = (*lead, pids.reshape(-1))
    kp = kp.at[at].set(kq.reshape(b * npg, ps, *k.shape[2:]))
    vp = vp.at[at].set(vq.reshape(b * npg, ps, *v.shape[2:]))
    table = table.at[:, :npg].set(pids)
    return dict(cache, k_pages=kp, v_pages=vp, page_table=table,
                free_count=fc - b * npg, pos=cache["pos"] + s)


def _paged_chunk_append(cache, k, v, lead=()):
    """Append an ``s``-token prefill CHUNK at each slot's current position,
    allocating pages lazily for every page boundary the chunk crosses.

    The general form of ``_paged_prefill_append`` (start 0, whole prompt)
    and ``_paged_decode_append`` (one token): chunked prefill interleaves a
    long prompt's admission with live decode steps, so chunk ``c`` starts
    at ``pos = c * chunk_len`` with the first touched page possibly half
    filled by the previous chunk.  Positions past capacity are redirected
    out of bounds (dropped), mirroring the decode append."""
    b, s = k.shape[0], k.shape[1]
    kp, vp = cache["k_pages"], cache["v_pages"]
    table, fl, fc = cache["page_table"], cache["free_list"], cache["free_count"]
    pos = cache["pos"]                             # (B,)
    p_total, ps = kp.shape[-4], kp.shape[-3]
    mp = table.shape[1]
    # map every logical page the chunk touches that has no physical page yet
    pages = jnp.arange(mp)[None, :]                # (1, MP)
    lo = pos[:, None] // ps
    hi = jnp.minimum((pos[:, None] + s - 1) // ps, mp - 1)
    need = (pages >= lo) & (pages <= hi) & (table < 0)   # (B, MP)
    flat = need.reshape(-1)
    rank = jnp.cumsum(flat.astype(jnp.int32)) - 1
    fresh = fl[fc - 1 - rank].reshape(b, mp)
    table = jnp.where(need, fresh, table)
    # scatter the chunk's rows at their global positions
    g = pos[:, None] + jnp.arange(s)[None, :]      # (B, s) global positions
    oob = g >= mp * ps
    lp = jnp.minimum(g // ps, mp - 1)
    phys = jnp.take_along_axis(table, lp, axis=1)  # (B, s)
    phys_w = jnp.where(oob, p_total, phys).reshape(-1)
    off_w = jnp.where(oob, ps, g % ps).reshape(-1)
    kp = kp.at[(*lead, phys_w, off_w)].set(
        k.reshape(b * s, *k.shape[2:]).astype(kp.dtype))
    vp = vp.at[(*lead, phys_w, off_w)].set(
        v.reshape(b * s, *v.shape[2:]).astype(vp.dtype))
    return dict(cache, k_pages=kp, v_pages=vp, page_table=table,
                free_count=fc - jnp.sum(flat.astype(jnp.int32)),
                pos=pos + s)


def _paged_decode_append(cache, k, v, lead=()):
    """Append one (KV, Dh) row per slot at its own position, allocating a
    fresh page lazily when a slot crosses a page boundary.

    Slots past capacity (the freed-slot sentinel, or an idle row that ran
    off the end) neither allocate nor write — their scatter indices are
    redirected out of bounds, which JAX drops."""
    b = k.shape[0]
    kp, vp = cache["k_pages"], cache["v_pages"]
    table, fl, fc = cache["page_table"], cache["free_list"], cache["free_count"]
    pos = cache["pos"]                             # (B,)
    p_total, ps = kp.shape[-4], kp.shape[-3]
    mp = table.shape[1]
    oob = pos >= mp * ps
    lp = jnp.minimum(pos // ps, mp - 1)            # logical page (clamped)
    off = pos % ps
    need = (off == 0) & ~oob                       # page-boundary slots
    # distinct stack entries per allocating slot: pool size is B * MP, so
    # the stack can never underflow while any slot still has room
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    fresh = fl[fc - 1 - rank]
    rows = jnp.arange(b)
    table = jnp.where(need[:, None],
                      table.at[rows, lp].set(fresh), table)
    phys = table[rows, lp]                         # (B,) now mapped
    phys_w = jnp.where(oob, p_total, phys)         # dropped when oob
    off_w = jnp.where(oob, ps, off)
    kp = kp.at[(*lead, phys_w, off_w)].set(k[:, 0].astype(kp.dtype))
    vp = vp.at[(*lead, phys_w, off_w)].set(v[:, 0].astype(vp.dtype))
    return dict(cache, k_pages=kp, v_pages=vp, page_table=table,
                free_count=fc - jnp.sum(need.astype(jnp.int32)),
                pos=pos + 1)


def _windowed_decode(q, kp, vp, table, pos, cfg: AttnCfg, mask, layer_idx):
    """Single-token decode attention over each slot group's own window
    (``kernels.decode_attention.decode_windows``): slots sorted longest
    first, each group of ``DECODE_GROUP`` gathers only the first pages of
    its window and attends them under the same mask prefix; a group with
    no live slot returns zeros.  ``pos`` is after the append, so a parked
    slot (appended at the capacity sentinel) reads past capacity: length
    0.  Returns (B, 1, KV, G, Dh) in slot order."""
    from repro.kernels import decode_attention as DA
    b = q.shape[0]
    ps, mp = kp.shape[-3], table.shape[1]
    lengths = jnp.where(pos > mp * ps, 0, pos)
    order, branch, _ = DA.decode_windows(lengths, ps, mp)
    mask = jnp.broadcast_to(mask, (b,) + mask.shape[1:])
    q, mask, table = q[order], mask[order], table[order]
    group = min(DA.DECODE_GROUP, b)

    def attend(pages, q, mask, table):
        kc = DA.gather_pages(kp, table[:, :pages], layer_idx)
        vc = DA.gather_pages(vp, table[:, :pages], layer_idx)
        w = attention_scores(q, kc, cfg, mask[..., :pages * ps])
        return jnp.einsum("bkgqs,bskd->bqkgd", w.astype(vc.dtype), vc)

    def empty(q, mask, table):
        return jnp.zeros((q.shape[0], 1, cfg.num_kv_heads,
                          cfg.num_heads // cfg.num_kv_heads, cfg.head_dim),
                         vp.dtype)

    branches = [empty] + [functools.partial(attend, w)
                          for w in DA.window_pages(mp)]
    ys = [jax.lax.switch(branch[i], branches, q[lo:lo + group],
                         mask[lo:lo + group], table[lo:lo + group])
          for i, lo in enumerate(range(0, b, group))]
    return jnp.concatenate(ys)[jnp.argsort(order)]


def _paged_attention(params, q, k, v, cache, cfg: AttnCfg, mpo: MPOConfig,
                     mask, phase: str, chunk: bool = False, layer_idx=None):
    """Self-attention over a paged KV cache (see ``transformer.init_cache``
    ``paged=True``).  Prefill attends over the in-hand prompt K/V; decode
    appends one row per slot and dispatches to the flash kernel or the
    XLA gather fallback (``kernels.decode_attention.choose_impl``).  The
    fallback gathers each group of length-sorted slots over its own page
    window (``_windowed_decode``); with a single window, or on a mesh that
    splits the model, it gathers every slot's whole span.  Either way it
    is the same softmax as the full span, equal up to f32 reduction order:
    every key it leaves out is masked.

    ``chunk=True`` marks a prefill CHUNK starting at the slot's current
    (nonzero) position: the chunk is appended via ``_paged_chunk_append``
    and its queries attend the whole mapped span (earlier chunks included)
    through the ``gather_pages`` contiguous view, masked by the caller's
    offset-aware mask — token-identical to an unchunked prefill.

    ``layer_idx`` (see ``apply_attention``): the page pools are the whole
    layer stack, written and read at that layer."""
    from repro.kernels import decode_attention as DA
    from repro.parallel.ctx import get_mesh, shard_dims
    b, s = q.shape[0], q.shape[1]
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    lead = () if layer_idx is None else (layer_idx,)
    if s > 1 and chunk:                            # prefill chunk (start >= 0)
        new_cache = _paged_chunk_append(cache, k, v, lead)
        kc = DA.gather_pages(new_cache["k_pages"], new_cache["page_table"],
                             layer_idx)
        vc = DA.gather_pages(new_cache["v_pages"], new_cache["page_table"],
                             layer_idx)
        w = attention_scores(q, kc, cfg, mask)
        y = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(vc.dtype), vc)
    elif s > 1:                                    # prefill (start == 0)
        new_cache = _paged_prefill_append(cache, k, v, lead)
        w = attention_scores(q, k, cfg, mask[..., :s])
        y = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v)
    else:                                          # single-token decode
        new_cache = _paged_decode_append(cache, k, v, lead)
        kp, vp = new_cache["k_pages"], new_cache["v_pages"]
        # pin the paged layout (``sharding.cache_sharding``) so GSPMD never
        # reshards the pool per layer: KV heads over model where the axis
        # divides them (each device attends its own heads), else the
        # in-page sequence dim
        mesh = get_mesh()
        model = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
            "model", 1) if mesh is not None else 1
        pin = ({len(lead) + 2: "model"} if kvh % model == 0
               else {len(lead) + 1: "model"})
        kp, vp = shard_dims(kp, pin), shard_dims(vp, pin)
        new_cache = dict(new_cache, k_pages=kp, v_pages=vp)
        table = new_cache["page_table"]
        ps, mp = kp.shape[-3], table.shape[1]
        # the kernel runs per device over its own KV heads (a Mosaic kernel
        # is never partitioned automatically); with heads the model axis
        # does not divide, the XLA gather is the only path
        impl = (DA.choose_impl(kvh, g, dh, ps, mp, str(q.dtype))
                if kvh % model == 0 else "xla")
        y = None
        if impl == "flash":
            lengths = jnp.minimum(new_cache["pos"], mp * ps).astype(jnp.int32)
            bias = jnp.where(mask[:, 0, 0], 0.0, DA.MASK_VALUE
                             ).astype(jnp.float32)
            flash = functools.partial(DA.flash_decode_attention,
                                      softcap=cfg.attn_softcap)
            if model > 1:
                heads = P(None, "model", None, None)
                pages = P(None, None, "model", None)
                flash = jax.shard_map(
                    flash, mesh=mesh, in_specs=(heads, pages, pages, P(),
                                                P(), P()),
                    out_specs=heads, check_vma=False)
            try:
                # the kernel takes one layer's pool: a read-only slice
                y = flash(q[:, 0].reshape(b, kvh, g, dh), kp[lead], vp[lead],
                          table, lengths, bias)
                y = y[:, None]                     # (B, 1, KV, G, Dh)
            except Exception as e:                 # noqa: BLE001
                # Pallas failures surface at trace/lowering time; degrade
                # to the gather path (same maths) rather than dying.
                # (A compiled-runtime fault is not catchable here — see
                # docs/resilience.md for the limitation.)
                DA.note_fallback(e)
                y = None
        if y is None and DA.windowed(mp, model):
            y = _windowed_decode(q, kp, vp, table, new_cache["pos"], cfg,
                                 mask, layer_idx)
        if y is None:
            kc = DA.gather_pages(kp, table, layer_idx)
            vc = DA.gather_pages(vp, table, layer_idx)
            w = attention_scores(q, kc, cfg, mask)
            y = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(vc.dtype), vc)
    y = y.reshape(b, s, h * dh)
    return L.apply_linear(params["wo"], y, cfg=mpo, phase=phase), new_cache


def apply_attention(params, x, cfg: AttnCfg, mpo: MPOConfig, *,
                    positions, mask, kv_x=None, cache=None,
                    phase: str = "train", chunk: bool = False,
                    layer_idx=None):
    """Returns (y, new_cache).

    ``cache``: dict(k, v, pos) for incremental decode — or the paged form
    (k_pages / v_pages / page_table / free_list / free_count / pos, see
    ``transformer.init_cache(paged=True)``), which appends into fixed-size
    pages and dispatches decode to ``kernels.decode_attention``.  ``kv_x``
    for cross-attention (ignores cache k/v writes when provided with
    cache — cross k/v are precomputed in the cache by prefill).  ``phase``
    feeds the execution engine's per-matrix planning.  ``chunk=True`` marks
    a multi-token prefill CHUNK continuing at the cache's current position
    (``transformer.prefill_chunk``): the caller supplies offset-aware
    positions/mask; the dense cache path already appends at ``pos`` for
    multi-token writes, the paged path switches to the chunked append.

    ``layer_idx`` (self-attention caches only): the cache's K/V buffers
    (``k``/``v`` or ``k_pages``/``v_pages``) are the WHOLE layer stack,
    and this layer appends at index ``layer_idx`` in one scatter and reads
    its own slice back — the layer scan carries the stack and updates it in
    place instead of slicing each layer out and stacking it back
    (``transformer._run_stack``).  The returned cache holds the stacks."""
    b = x.shape[0]
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _split_heads(L.apply_linear(params["wq"], x, cfg=mpo, phase=phase),
                     h, dh)
    src = x if kv_x is None else kv_x
    k = _split_heads(L.apply_linear(params["wk"], src, cfg=mpo, phase=phase),
                     kvh, dh)
    v = _split_heads(L.apply_linear(params["wv"], src, cfg=mpo, phase=phase),
                     kvh, dh)
    if cfg.qk_norm:
        q = apply_rmsnorm(params["q_norm"], q)
        k = apply_rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        if kv_x is None:
            k = rope(k, positions, cfg.rope_theta)
    if cache is None:
        # sequence-parallel: Q stays seq-sharded; K/V are gathered across
        # the model axis (the one AG sequence parallelism pays per layer)
        from repro.parallel.ctx import gather_seq
        k = gather_seq(k)
        v = gather_seq(v)
    if cache is not None and kv_x is None and "k_pages" in cache:
        return _paged_attention(params, q, k, v, cache, cfg, mpo, mask,
                                phase, chunk=chunk, layer_idx=layer_idx)
    new_cache = None
    if cache is not None:
        if kv_x is None:  # self-attention decode: append to ring buffer
            from repro.parallel.ctx import shard_dims  # lazy: avoid cycle
            lead = () if layer_idx is None else (layer_idx,)
            idx = cache["pos"]
            per_slot = getattr(idx, "ndim", 0) >= 1
            if per_slot and x.shape[1] == 1:
                # multi-tenant decode: each batch row sits at its OWN
                # position (``pos``: (B,)) — scatter one (KV, Dh) row per
                # slot.  Out-of-bounds writes (an idle slot past max_len)
                # are dropped by the scatter, never clobber a live tenant.
                rows = jnp.arange(b)
                kc = cache["k"].at[(*lead, rows, idx)].set(
                    k[:, 0].astype(cache["k"].dtype))
                vc = cache["v"].at[(*lead, rows, idx)].set(
                    v[:, 0].astype(cache["v"].dtype))
            else:
                # prefill (all rows start at the same offset) or a legacy
                # scalar-pos cache: one contiguous slice write
                start = (*lead, 0, idx[0] if per_slot else idx, 0, 0)
                one = (1,) * len(lead)
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype).reshape(
                        one + k.shape), start)
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype).reshape(
                        one + v.shape), start)
            # pin the flash-decoding layout: cache seq dim model-sharded,
            # batch data-sharded (GSPMD otherwise reshards the whole cache
            # to kv-head sharding per layer — §Perf it.10)
            spec = {len(lead): "batch", len(lead) + 1: "model"}
            kc = shard_dims(kc, spec)
            vc = shard_dims(vc, spec)
            k, v = kc[lead], vc[lead]
            new_cache = {"k": kc, "v": vc, "pos": idx + x.shape[1]}
        else:  # cross-attention: cache holds precomputed enc k/v
            k, v = cache["k"], cache["v"]
            new_cache = cache
    w = attention_scores(q, k, cfg, mask)     # (B,KV,G,Sq,Sk)
    y = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v)
    y = y.reshape(b, y.shape[1], h * dh)
    return L.apply_linear(params["wo"], y, cfg=mpo, phase=phase), new_cache


def init_kv_cache(batch: int, max_len: int, cfg: AttnCfg, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.array(0, jnp.int32)}


# --------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / squared-ReLU)
# --------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, act: str, mpo: MPOConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": L.init_linear(k1, d_model, d_ff, cfg=mpo, kind="ffn",
                               out_axis="ffn", sharded_out=True),
         "w_down": L.init_linear(k2, d_ff, d_model, cfg=mpo, kind="ffn",
                                 in_axis="ffn", sharded_in=True,
                                 scale=d_ff ** -0.5)}
    if act in ("silu", "gelu"):  # gated variants (SwiGLU / GeGLU)
        p["w_gate"] = L.init_linear(k3, d_model, d_ff, cfg=mpo, kind="ffn",
                                    out_axis="ffn", sharded_out=True)
    return p


def apply_mlp(params, x, act: str, mpo: MPOConfig, phase: str = "train"):
    up = L.apply_linear(params["w_up"], x, cfg=mpo, phase=phase)
    if act == "silu":
        g = L.apply_linear(params["w_gate"], x, cfg=mpo, phase=phase)
        h = jax.nn.silu(g) * up
    elif act == "gelu":
        g = L.apply_linear(params["w_gate"], x, cfg=mpo, phase=phase)
        h = jax.nn.gelu(g) * up
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(up))
    elif act == "gelu_plain":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(act)
    return L.apply_linear(params["w_down"], h, cfg=mpo, phase=phase)


# --------------------------------------------------------------------------
# stacking for lax.scan
# --------------------------------------------------------------------------


def stack_layers(init_fn, key, n_layers: int):
    """vmap an ``init_fn(key) -> Annot tree`` into scan-stacked params."""
    keys = jax.random.split(key, n_layers)
    tree0 = init_fn(keys[0])
    _, axes = L.split_annotations(tree0)
    stacked = jax.vmap(lambda k: L.split_annotations(init_fn(k))[0])(keys)
    is_tup = lambda x: isinstance(x, tuple)
    axes = jax.tree.map(lambda a: ("layers",) + a, axes, is_leaf=is_tup)
    return jax.tree.map(lambda v, a: Annot(v, a), stacked, axes,
                        is_leaf=lambda x: hasattr(x, "shape"))
