"""The paged KV cache: its layout and every operation on its pages.

K/V live in a pool of fixed-size physical pages, one ``(L, P, ps, KV,
Dh)`` stack each for K and V.  Every layer allocates alike, so the
bookkeeping is held once for the whole stack:

* ``page_table`` (B, MP): each slot's logical page -> physical page,
  ``-1`` where unmapped;
* ``pos`` (B,): keys each slot holds (the capacity sentinel ``span`` or
  beyond for a parked slot, which neither writes nor allocates);
* ``free_list`` (P,) and ``free_count`` (): a stack of free physical
  pages, the top ``free_count`` entries live.

A serving program plans its append once (``plan``), before the layer
scan; each layer then writes its K/V at the planned indices (``write``)
and reads through the shared table.  ``ServePool`` moves tenants in and
out with ``adopt``, ``free`` and ``park``.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels import decode_attention as DA


def init(layers: int, batch: int, max_len: int, kv_heads: int,
         head_dim: int, dtype, *, page_size: int = 16,
         pool_pages: int | None = None) -> dict:
    """An empty paged cache for ``batch`` slots of ``max_len`` keys.

    By default the pool holds ``batch * max_len / page_size`` pages (every
    slot full), so allocation can never exhaust it; a smaller
    ``pool_pages`` oversubscribes it, and then ``ServePool``'s
    page-reservation admission keeps the free list from underflowing (a
    raw underflow would wrap the stack index negative and alias pages)."""
    if page_size <= 0:
        raise ValueError(f"page_size must be positive, got {page_size}")
    if max_len % page_size != 0:
        raise ValueError(
            f"page_size={page_size} does not divide max_len={max_len}: the "
            f"tail page would be only partially usable and the page-clamped "
            f"index maps assume full pages. Use a page_size that divides "
            f"max_len (e.g. {math.gcd(max_len, page_size)}) or round "
            f"max_len up to {page_size * (-(-max_len // page_size))}.")
    mp = max_len // page_size                     # logical pages per slot
    pool = batch * mp if pool_pages is None else int(pool_pages)
    if not 1 <= pool <= batch * mp:
        raise ValueError(
            f"pool_pages={pool_pages} out of range [1, {batch * mp}] "
            f"(batch={batch} slots x {mp} pages each); oversubscribe by "
            f"passing fewer pages than batch*max_pages, never more")
    pshape = (layers, pool, page_size, kv_heads, head_dim)
    return {
        "k_pages": jnp.zeros(pshape, dtype),
        "v_pages": jnp.zeros(pshape, dtype),
        "page_table": jnp.full((batch, mp), -1, jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
        "free_list": jnp.arange(pool, dtype=jnp.int32),
        "free_count": jnp.asarray(pool, jnp.int32),
    }


def span(cache) -> int:
    """Keys a slot can hold: ``MP * page_size`` (>= ``max_len``)."""
    return cache["page_table"].shape[1] * cache["k_pages"].shape[2]


def used_pages(cache):
    """Physical pages mapped to some slot (a device scalar)."""
    return cache["k_pages"].shape[1] - cache["free_count"]


def plan(cache, s: int):
    """Allocate for ``s`` new tokens per slot, once for the whole stack.

    Every page the tokens touch that has no physical page yet is popped
    off the free list, ranked row-major over (slot, page): a start-0
    prefill gives slot ``i`` page ``j`` the ``i * pages + j``-th page off
    the top, a decode step one page per slot crossing a page boundary, in
    slot order.  Tokens past capacity (a parked slot) allocate nothing and
    write out of bounds, which the scatter drops.

    Returns ``(cache, at)``: the cache with its table, free count and
    ``pos + s`` advanced (K/V untouched), and where each layer writes,
    ``at["phys"]``/``at["off"]`` (B, s).  For one token per slot ``at``
    also holds the step's decode windows, ``order`` and ``branch`` of
    ``decode_attention.decode_windows`` over each slot's length after the
    append (0 for a parked slot)."""
    table, fl, fc = cache["page_table"], cache["free_list"], cache["free_count"]
    pos = cache["pos"]
    p_total, ps = cache["k_pages"].shape[1], cache["k_pages"].shape[2]
    b, mp = table.shape
    cap = mp * ps
    pages = jnp.arange(mp)[None, :]
    lo = pos[:, None] // ps
    hi = jnp.minimum((pos[:, None] + s - 1) // ps, mp - 1)
    need = (pages >= lo) & (pages <= hi) & (table < 0)     # (B, MP)
    rank = jnp.cumsum(need.reshape(-1).astype(jnp.int32)) - 1
    table = jnp.where(need, fl[fc - 1 - rank].reshape(b, mp), table)
    g = pos[:, None] + jnp.arange(s)[None, :]      # (B, s) global positions
    oob = g >= cap
    phys = jnp.take_along_axis(table, jnp.minimum(g // ps, mp - 1), axis=1)
    at = {"phys": jnp.where(oob, p_total, phys),
          "off": jnp.where(oob, ps, g % ps)}
    pos = pos + s
    if s == 1:
        at["order"], at["branch"], _ = DA.decode_windows(
            jnp.where(pos > cap, 0, pos), ps, mp)
    return dict(cache, page_table=table, pos=pos,
                free_count=fc - jnp.sum(need.astype(jnp.int32))), at


def write(pages, at, rows, layer_idx=None):
    """Scatter ``rows`` (B, s, KV, Dh) at ``plan``'s indices: into layer
    ``layer_idx`` of a page stack (L, P, ps, KV, Dh), or into one layer's
    pool (P, ps, KV, Dh) without it."""
    lead = () if layer_idx is None else (layer_idx,)
    b, s = rows.shape[:2]
    return pages.at[(*lead, at["phys"].reshape(-1), at["off"].reshape(-1))
                    ].set(rows.reshape(b * s, *rows.shape[2:]
                                       ).astype(pages.dtype))


def park(cache):
    """Every slot idle: its position at the capacity sentinel."""
    return dict(cache, pos=jnp.full_like(cache["pos"], span(cache)))


def adopt(cache, one, slot):
    """Copy the batch-1 tenant cache ``one`` into slot ``slot``: pop one
    page per page the tenant uses, copy those pages of every layer, and
    point the slot's table row at them."""
    p_total, ps = cache["k_pages"].shape[1], cache["k_pages"].shape[2]
    fl, fc = cache["free_list"], cache["free_count"]
    n = one["pos"][0]                              # tenant context length
    used = jnp.arange(cache["page_table"].shape[1]) < (n + ps - 1) // ps
    rank = jnp.cumsum(used.astype(jnp.int32)) - 1
    pids = fl[fc - 1 - rank]                       # popped pool pages
    dst = jnp.where(used, pids, p_total)           # unused -> dropped
    src = jnp.maximum(one["page_table"][0], 0)     # tenant physical pages
    kp, vp = cache["k_pages"], cache["v_pages"]
    return dict(
        cache,
        k_pages=kp.at[:, dst].set(one["k_pages"][:, src].astype(kp.dtype)),
        v_pages=vp.at[:, dst].set(one["v_pages"][:, src].astype(vp.dtype)),
        page_table=cache["page_table"].at[slot].set(
            jnp.where(used, pids, -1)),
        pos=cache["pos"].at[slot].set(n),
        free_count=fc - jnp.sum(used.astype(jnp.int32)))


def free(cache, slot):
    """Recycle slot ``slot``: push its mapped pages back on the free list,
    clear its table row and park it."""
    p_total = cache["k_pages"].shape[1]
    fc, row = cache["free_count"], cache["page_table"][slot]
    valid = row >= 0
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    dest = jnp.where(valid, fc + rank, p_total)    # unmapped -> dropped
    return dict(cache,
                free_list=cache["free_list"].at[dest].set(row),
                page_table=cache["page_table"].at[slot].set(-1),
                pos=cache["pos"].at[slot].set(span(cache)),
                free_count=fc + jnp.sum(valid.astype(jnp.int32)))
