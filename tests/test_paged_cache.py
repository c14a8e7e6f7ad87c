"""The paged KV cache's bookkeeping (``models.paged_cache``).

One page table, free list and position vector serve every layer, and a
serving program plans its append once.  ``plan`` is held to hand-written
page ids: those the prefill, chunk and decode appends gave when each layer
allocated for itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import Session
from repro.models import paged_cache

PS, MP = 4, 4                   # 16 keys a slot
CAP = PS * MP


def _cache(table, pos, free_count, layers=1):
    """A cache of ``len(pos)`` slots over a pool of ``slots * MP`` pages
    whose free list is ``arange``: the top ``free_count`` entries are free."""
    b = len(pos)
    pages = jnp.zeros((layers, b * MP, PS, 1, 1), jnp.float32)
    return {"k_pages": pages, "v_pages": pages,
            "page_table": jnp.asarray(table, jnp.int32),
            "pos": jnp.asarray(pos, jnp.int32),
            "free_list": jnp.arange(b * MP, dtype=jnp.int32),
            "free_count": jnp.asarray(free_count, jnp.int32)}


P_ = 8                          # out-of-bounds page of a two-slot pool
CASES = {
    # a six-token prompt from position 0 in both slots: slot i, page j
    # takes the (2i + j)-th page off the top of the stack
    "prefill": dict(
        table=[[-1] * 4, [-1] * 4], pos=[0, 0], free=8, s=6,
        want_table=[[7, 6, -1, -1], [5, 4, -1, -1]], want_free=4,
        phys=[[7, 7, 7, 7, 6, 6], [5, 5, 5, 5, 4, 4]],
        off=[[0, 1, 2, 3, 0, 1], [0, 1, 2, 3, 0, 1]]),
    # a four-token chunk that starts inside a mapped page in each slot
    # and crosses into a new one; slot 0's new page is popped first
    "chunk_mid_page": dict(
        table=[[7, 6, -1, -1], [5, -1, -1, -1]], pos=[6, 2], free=5, s=4,
        want_table=[[7, 6, 4, -1], [5, 3, -1, -1]], want_free=3,
        phys=[[6, 6, 4, 4], [5, 5, 3, 3]],
        off=[[2, 3, 0, 1], [2, 3, 0, 1]]),
    # a decode step: slots 0 and 2 cross a page boundary and take a page
    # each, in slot order; slot 1 writes inside its page
    "decode_crossing": dict(
        table=[[11, 10, -1, -1], [9, 8, -1, -1], [7, -1, -1, -1]],
        pos=[8, 5, 4], free=7, s=1,
        want_table=[[11, 10, 6, -1], [9, 8, -1, -1], [7, 5, -1, -1]],
        want_free=5, phys=[[6], [8], [5]], off=[[0], [1], [0]],
        order=[0, 1, 2], branch=[3]),
    # a parked slot (at the capacity sentinel) allocates nothing and
    # writes out of bounds; the live slot writes inside its page
    "parked": dict(
        table=[[7, -1, -1, -1], [-1] * 4], pos=[3, CAP], free=7, s=1,
        want_table=[[7, -1, -1, -1], [-1] * 4], want_free=7,
        phys=[[7], [P_]], off=[[3], [PS]], order=[0, 1], branch=[1]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_plan_allocates_the_pages_the_per_layer_appends_did(case):
    c = CASES[case]
    cache = _cache(c["table"], c["pos"], c["free"])
    new, at = jax.jit(paged_cache.plan, static_argnums=1)(cache, c["s"])
    np.testing.assert_array_equal(new["page_table"], c["want_table"])
    assert int(new["free_count"]) == c["want_free"]
    np.testing.assert_array_equal(new["free_list"], cache["free_list"])
    np.testing.assert_array_equal(new["pos"], np.asarray(c["pos"]) + c["s"])
    np.testing.assert_array_equal(at["phys"], c["phys"])
    np.testing.assert_array_equal(at["off"], c["off"])
    if c["s"] == 1:
        np.testing.assert_array_equal(at["order"], c["order"])
        np.testing.assert_array_equal(at["branch"], c["branch"])
    else:
        assert "order" not in at


def test_adopt_then_free_moves_pages_of_every_layer():
    """Adoption pops one pool page per tenant page and copies that page
    of every layer; freeing pushes the slot's pages back in row order."""
    pool = paged_cache.park(_cache([[-1] * 4, [-1] * 4], [0, 0], 8,
                                   layers=2))
    one_pages = jnp.arange(2 * 4 * PS, dtype=jnp.float32).reshape(
        2, 4, PS, 1, 1)
    one = {"k_pages": one_pages, "v_pages": -one_pages,
           "page_table": jnp.asarray([[1, 0, -1, -1]], jnp.int32),
           "pos": jnp.asarray([6], jnp.int32),
           "free_list": jnp.arange(4, dtype=jnp.int32),
           "free_count": jnp.asarray(2, jnp.int32)}
    pool = jax.jit(paged_cache.adopt)(pool, one, jnp.int32(1))
    np.testing.assert_array_equal(pool["page_table"],
                                  [[-1] * 4, [7, 6, -1, -1]])
    np.testing.assert_array_equal(pool["pos"], [CAP, 6])
    assert int(pool["free_count"]) == 6
    np.testing.assert_array_equal(pool["k_pages"][:, 7], one_pages[:, 1])
    np.testing.assert_array_equal(pool["k_pages"][:, 6], one_pages[:, 0])
    np.testing.assert_array_equal(pool["v_pages"][:, 6], -one_pages[:, 0])
    assert int(paged_cache.used_pages(pool)) == 2
    pool = jax.jit(paged_cache.free)(pool, jnp.int32(1))
    np.testing.assert_array_equal(pool["page_table"], [[-1] * 4] * 2)
    np.testing.assert_array_equal(pool["pos"], [CAP, CAP])
    np.testing.assert_array_equal(pool["free_list"],
                                  [0, 1, 2, 3, 4, 5, 7, 6])
    assert int(pool["free_count"]) == 8


def test_pool_cache_bookkeeping_has_no_layer_dim():
    """The served pool holds one table, free list and position vector
    for the whole stack; only the K/V stacks carry the layers dim."""
    session = Session.init("qwen3-14b")
    pool = session.serve_pool(slots=3, max_len=32, paged=True, page_size=4)
    cache, layers = pool._cache, session.cfg.num_layers
    assert cache["page_table"].shape == (3, 8)
    assert cache["pos"].shape == (3,)
    assert cache["free_list"].shape == (24,)
    assert cache["free_count"].shape == ()
    assert cache["k_pages"].shape[:2] == cache["v_pages"].shape[:2] == (
        layers, 24)
    dense = session.serve_pool(slots=3, max_len=32)._cache
    assert dense["pos"].shape == (3,)
    assert dense["k"].shape[:2] == (layers, 3)
