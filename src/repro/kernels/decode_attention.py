"""Pallas flash decode-attention over a paged KV cache.

Decode is memory-bound: a dense ``(slots, max_len)`` KV cache makes every
slot pay ``max_len`` bandwidth per token even when its context is 10 tokens
long.  This module provides the serving-side fix:

* ``flash_decode_attention`` — one Pallas program per (slot, kv-head)
  streams that slot's KV *pages* through VMEM with an online softmax
  (running max / normalizer / accumulator in f32 scratch).  The KV
  ``BlockSpec`` index map resolves the slot's page table and CLAMPS the
  logical page index at the slot's last valid page: Mosaic skips the DMA
  when consecutive grid steps ask for the same block, so a slot's HBM
  traffic scales with its own length, not with ``max_len``.  Compute for
  out-of-length pages is predicated off with ``pl.when``.
* ``gather_pages`` — the XLA fallback's view: gathers a slot's pages back
  into a contiguous ``(B, kv_len, KV, Dh)`` tensor so the caller can run
  the same ``nn.attention_scores`` path the dense cache uses.
* ``decode_windows`` — how the XLA fallback bounds what it gathers: the
  slots sorted longest first, taken in groups of ``DECODE_GROUP``, and
  each group given the smallest window of ``window_pages`` that holds its
  longest context.  ``paged_cache.plan`` chooses them once a decode
  step, ``nn._windowed_decode`` gathers each group's window alone, and
  the pool's ``stats()["decode_kv_share"]`` counts the same windows on
  the host.  Every dropped key lies past its slot's length and
  is masked anyway, so the result is the same maths as a full-span gather,
  equal up to f32 reduction order.
* ``choose_impl`` — the dispatch decision, made at trace time from static
  shape/dtype info.  On measuring substrates it registers both
  implementations with the PR-3 autotuner (``kernels.autotune``) and races
  them per (head-config, context-bucket, dtype, backend); interpret-mode /
  CPU runs keep the XLA reference path unless ``REPRO_DECODE_ATTN=flash``
  forces the kernel (tests do).

The paged cache itself (page table, page allocation, the once-a-step
append plan) lives in ``models/paged_cache.py``; this module only
consumes its leaves.
"""

from __future__ import annotations

import functools
import math
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import interpret_mode
from repro.resilience import faults

ENV_IMPL = "REPRO_DECODE_ATTN"      # "flash" | "xla" force-override
MASK_VALUE = -2.3819763e38          # same fill nn.attention_scores uses
_TINY = 1e-30                       # zero-valid-keys guard (idle slots)

# times the flash kernel raised and the caller degraded to the XLA gather
# path this process (``note_fallback``); surfaced by ServePool.stats()
FALLBACKS = 0


def note_fallback(exc: BaseException) -> None:
    """Record (and warn about, once per process per message) a flash ->
    XLA degradation.  The gather path computes the same softmax (equal up
    to f32 reduction order), so serving continues instead of dying with
    the kernel."""
    global FALLBACKS
    FALLBACKS += 1
    warnings.warn(
        f"flash decode-attention failed ({type(exc).__name__}: {exc}); "
        "falling back to the XLA gather path",
        RuntimeWarning, stacklevel=3)


# --------------------------------------------------------------------------
# flash kernel
# --------------------------------------------------------------------------


def _flash_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size: int, scale: float,
                  softcap: float | None):
    """Grid (B, num_pages); page index innermost so the f32 scratch
    (acc / running max / normalizer, one row block per KV head) persists
    across a slot's pages.  Each program attends ALL KV heads of one page:
    the page block spans the full head dim, as Mosaic's block-shape rule
    requires of the second-to-last dim."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    npages = (lens_ref[b] + page_size - 1) // page_size

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(p < npages)
    def _step():
        q = q_ref[0].astype(jnp.float32)                       # (KV, G, Dh)
        k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)    # (KV, ps, Dh)
        v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
        s = jnp.einsum("hgd,hpd->hgp", q, k,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = s + bias_ref[0, 0][None]               # additive mask, (1, 1, ps)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        w = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(w, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hgp,hpd->hgd", w, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(p == jnp.maximum(npages, 1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], _TINY)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def block_shapes(batch: int, num_kv_heads: int, group: int, head_dim: int,
                 page_size: int, max_pages: int, pool_pages: int) -> list:
    """``(name, block, array)`` for every ``BlockSpec`` of
    ``flash_decode_attention`` — what ``repro.analysis.kernel_budget``
    checks against Mosaic's block-shape rule."""
    kv, g, dh, ps = num_kv_heads, group, head_dim, page_size
    page = ((1, ps, kv, dh), (pool_pages, ps, kv, dh))
    heads = ((1, kv, g, dh), (batch, kv, g, dh))
    return [("q", *heads), ("k_page", *page), ("v_page", *page),
            ("bias", (1, 1, 1, ps), (batch, max_pages, 1, ps)),
            ("out", *heads)]


def vmem_buffers(num_kv_heads: int, group: int, head_dim: int,
                 page_size: int, itemsize: int) -> list:
    """One program's VMEM-resident buffers: ``(name, shape, bytes_per_elem,
    pipelined)`` rows mirroring the ``BlockSpec``s + ``scratch_shapes`` of
    ``flash_decode_attention`` below — kept in this file so the residency
    model and the specs change together.  Consumed by
    ``repro.analysis.kernel_budget`` (pipelined rows cost 2x: Pallas
    double-buffers streamed blocks; scratch is resident once)."""
    kv, g, dh, ps = num_kv_heads, group, head_dim, page_size
    return [
        ("q", (1, kv, g, dh), itemsize, True),
        ("k_page", (1, ps, kv, dh), itemsize, True),
        ("v_page", (1, ps, kv, dh), itemsize, True),
        ("bias", (1, 1, 1, ps), 4, True),      # additive mask arrives f32
        ("out", (1, kv, g, dh), itemsize, True),
        ("acc_scratch", (kv, g, dh), 4, False),
        ("m_scratch", (kv, g, 1), 4, False),
        ("l_scratch", (kv, g, 1), 4, False),
    ]


def _kv_index_map(b, p, table, lens, *, page_size, max_pages):
    """Physical page for (slot b, logical page p), clamped to the slot's
    last valid page — consecutive identical block indices make Mosaic skip
    the re-fetch, which is what bounds a slot's bandwidth by its length."""
    npages = (lens[b] + page_size - 1) // page_size
    lp = jnp.minimum(p, jnp.maximum(npages - 1, 0))
    phys = jnp.maximum(table[b * max_pages + lp], 0)
    return phys, 0, 0, 0


def _bias_index_map(b, p, table, lens, *, page_size):
    npages = (lens[b] + page_size - 1) // page_size
    return b, jnp.minimum(p, jnp.maximum(npages - 1, 0)), 0, 0


def flash_decode_attention(q, k_pages, v_pages, page_table, lengths, bias,
                           *, softcap: float | None = None,
                           interpret: bool | None = None):
    """Single-token flash decoding over paged KV.

    q:          (B, KV, G, Dh)   — grouped query heads (H = KV * G)
    k_pages:    (P, ps, KV, Dh)  — physical page pool (v_pages alike)
    page_table: (B, MP) int32    — logical -> physical page, -1 = unmapped
    lengths:    (B,) int32       — valid keys per slot (<= MP * ps)
    bias:       (B, MP * ps) f32 — additive mask (0 keep / MASK_VALUE drop)

    Returns (B, KV, G, Dh) in q's dtype.  Softmax statistics are f32.
    ``interpret`` defaults to ``kernels.tpu.interpret_mode()``.
    """
    faults.check_flash()   # chaos: simulate a kernel failure at trace time
    if interpret is None:
        interpret = interpret_mode()
    return _flash_jit(q, k_pages, v_pages, page_table, lengths, bias,
                      softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def _flash_jit(q, k_pages, v_pages, page_table, lengths, bias,
               *, softcap: float | None = None, interpret: bool):
    b, kv, g, dh = q.shape
    _, page_size, _, _ = k_pages.shape
    max_pages = page_table.shape[1]
    grid = (b, max_pages)
    kv_map = functools.partial(_kv_index_map, page_size=page_size,
                               max_pages=max_pages)
    bias_map = functools.partial(_bias_index_map, page_size=page_size)
    kernel = functools.partial(_flash_kernel, page_size=page_size,
                               scale=1.0 / math.sqrt(dh), softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kv, g, dh), lambda b, p, t, L: (b, 0, 0, 0)),
                pl.BlockSpec((1, page_size, kv, dh), kv_map),
                pl.BlockSpec((1, page_size, kv, dh), kv_map),
                pl.BlockSpec((1, 1, 1, page_size), bias_map),
            ],
            out_specs=pl.BlockSpec((1, kv, g, dh),
                                   lambda b, p, t, L: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((kv, g, dh), jnp.float32),
                            pltpu.VMEM((kv, g, 1), jnp.float32),
                            pltpu.VMEM((kv, g, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        interpret=interpret,
    )(page_table.reshape(-1), lengths, q, k_pages, v_pages,
      bias.reshape(b, max_pages, 1, page_size))


# --------------------------------------------------------------------------
# XLA fallback view
# --------------------------------------------------------------------------


def gather_pages(pages, page_table, layer_idx=None):
    """(P, ps, KV, Dh) pages + (B, W) table -> contiguous (B, W*ps, KV, Dh).

    ``page_table`` is the whole table (``W = MP``, the chunk prefill and
    the full-span decode) or a group's first ``W`` logical pages (a
    ``decode_windows`` window).

    With ``layer_idx``, ``pages`` is the whole layer stack (L, P, ps, KV,
    Dh) and the layer index folds into the same gather: no slice of the
    layer's pool is materialized first.

    Unmapped (-1) entries are clamped to page 0 — their positions are past
    every slot's length, so the caller's mask zeroes their softmax weight
    exactly."""
    b, mp = page_table.shape
    ps, kv, dh = pages.shape[-3:]
    idx = jnp.maximum(page_table, 0)
    out = pages[idx] if layer_idx is None else pages[layer_idx, idx]
    return out.reshape(b, mp * ps, kv, dh)         # out: (B, W, ps, KV, Dh)


DECODE_GROUP = 8        # slots that share one decode window


def window_pages(max_pages: int) -> tuple[int, ...]:
    """The decode windows in pages, smallest first: ``max_pages`` over 8,
    4, 2 and 1, rounded up, so the largest is the whole span and none is
    under one page."""
    return tuple(sorted({-(-max_pages // d) for d in (8, 4, 2, 1)}))


def windowed(max_pages: int, model: int = 1) -> bool:
    """Whether decode gathers per-group windows: only with more than one
    window, and off a mesh that splits the model (there the full-span
    gather keeps the pool's pinned layout)."""
    return model == 1 and len(window_pages(max_pages)) > 1


def decode_windows(lengths, page_size: int, max_pages: int):
    """Sort slots longest first and give each group its window.

    ``lengths``: (B,) keys each slot attends (0 for a parked slot), a
    ``jnp`` or ``np`` array; the result is of the same kind.  Slots are
    taken in order in groups of ``DECODE_GROUP`` (or B); the last group
    holds the remainder.  Returns ``(order, branch, tokens)``:

    * ``order`` (B,): slot indices, longest first (ties keep slot order);
    * ``branch`` (groups,): 0 for a group with no live slot, else
      ``1 + i`` for ``window_pages(max_pages)[i]``, the smallest window
      that holds the group's longest context;
    * ``tokens`` (groups,): that window in keys, 0 for branch 0."""
    xp = np if isinstance(lengths, np.ndarray) else jnp
    group = min(DECODE_GROUP, lengths.shape[0])
    order = xp.argsort(-lengths, stable=True)
    longest = lengths[order][::group]               # each group's first
    spans = xp.asarray([0] + [w * page_size for w in window_pages(max_pages)])
    # 1 + the number of windows too short for the group's longest slot
    fits = (spans[1:][None, :] < longest[:, None]).sum(axis=-1) + 1
    branch = xp.where(longest > 0, fits, 0)
    return order, branch, spans[branch]


def decode_kv_share(lengths, page_size: int, max_pages: int,
                    model: int = 1) -> float:
    """Share of the ``(B, max_pages * page_size)`` key span that one decode
    step's XLA gather reads: each group's size times its window, over the
    whole span (1.0 where ``windowed`` is false).  ``lengths`` as for
    ``decode_windows``, on the host."""
    lengths = np.asarray(lengths)
    if not windowed(max_pages, model):
        return 1.0
    b = lengths.shape[0]
    group = min(DECODE_GROUP, b)
    _, _, tokens = decode_windows(lengths, page_size, max_pages)
    sizes = np.minimum(group, b - group * np.arange(tokens.shape[0]))
    return float(np.dot(sizes, tokens)) / (b * max_pages * page_size)


# --------------------------------------------------------------------------
# dispatch (autotuner-raced)
# --------------------------------------------------------------------------


def _context_bucket(kv_len: int) -> int:
    """Next power of two — one autotune verdict per context bucket, not per
    exact max_len."""
    return 1 << max(int(kv_len) - 1, 1).bit_length()


def _race_candidates(shapes, tokens, phase, dtype, interpret):
    """[(label, thunk)] for the autotuner: both implementations over
    synthetic operands at the real head-config/page geometry.  ``shapes``
    carries ((KV, G, Dh), (page_size, max_pages)); ``tokens`` the context
    bucket."""
    (kv, g, dh), (ps, mp) = shapes
    jdt = jnp.dtype(dtype)
    b = 4                                           # representative pool
    p = b * mp
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, kv, g, dh)).astype(jdt)
    kp = jax.random.normal(ks[1], (p, ps, kv, dh)).astype(jdt)
    vp = jax.random.normal(ks[2], (p, ps, kv, dh)).astype(jdt)
    lens = jnp.minimum(jax.random.randint(ks[3], (b,), 1, tokens + 1),
                       mp * ps).astype(jnp.int32)
    table = (jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp))
    bias = jnp.where(jnp.arange(mp * ps)[None, :] < lens[:, None],
                     0.0, MASK_VALUE).astype(jnp.float32)

    def xla_ref(q, kp, vp, table, lens, bias):
        k = gather_pages(kp, table)
        v = gather_pages(vp, table)
        s = jnp.einsum("bkgd,bskd->bkgs", q, k) / math.sqrt(dh)
        s = s + bias[:, None, None, :]
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.einsum("bkgs,bskd->bkgd", w.astype(v.dtype), v)

    flash = jax.jit(functools.partial(_flash_jit, interpret=interpret))
    xla = jax.jit(xla_ref)
    return [("flash", lambda: flash(q, kp, vp, table, lens, bias)),
            ("xla", lambda: xla(q, kp, vp, table, lens, bias))]


def choose_impl(num_kv_heads: int, group: int, head_dim: int,
                page_size: int, max_pages: int, dtype: str,
                interpret: bool | None = None) -> str:
    """"flash" or "xla", decided at trace time from static info only.

    Priority: ``REPRO_DECODE_ATTN`` env force > measured autotuner race
    (per head-config / context-bucket / dtype / backend, persisted next to
    the MPO-linear verdicts) > analytic default (XLA reference in interpret
    mode — the kernel interprets orders of magnitude slower than the
    fallback; flash when compiled on real hardware)."""
    forced = os.environ.get(ENV_IMPL)
    if forced in ("flash", "xla"):
        return forced
    if interpret is None:
        interpret = interpret_mode()
    from repro.kernels import autotune  # lazy: no import cycle at module load
    if autotune.should_measure(interpret):
        shapes = ((num_kv_heads, group, head_dim), (page_size, max_pages))
        bucket = _context_bucket(max_pages * page_size)
        return autotune.get_tuner().get(
            shapes, bucket, "decode_attn", dtype, interpret,
            candidates_fn=_race_candidates).mode
    return "xla" if interpret else "flash"
