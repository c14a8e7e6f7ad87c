"""The layer scan writes the KV cache in place.

``transformer._run_stack`` carries the K/V stacks through the scan and
each layer appends into its own slice; the pool donates its cache to every
program that rebinds it.  These tests hold the served maths to the
per-layer form (each layer's K/V sliced out of the stacks and stacked
back), and the compiled decode to having no copy of a page pool."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import Session
from repro.models import paged_cache, transformer
from repro.pipeline.scheduler import ServePool
from repro.train.steps import make_serve_steps

MAX_LEN, PAGE, SLOTS = 32, 4, 3


@pytest.fixture(scope="module")
def session():
    return Session.init("qwen3-14b")


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 500, n).astype(np.int32)


KV = ("k", "v", "k_pages", "v_pages")


def _per_layer_stack(cfg, params, x, *, positions, mask, mask_local,
                     caches=None, phase="train", chunk=False):
    """The layer stack as a loop: each layer's K/V are sliced out of the
    stacks, run through the per-layer attention, and stacked back; the
    positions, page table and write plan are shared by every layer."""
    outs = []
    for i in range(cfg.num_layers):
        layer = dict(jax.tree.map(lambda a: a[i], params["layers"]))
        cache = {n: a[i] if n in KV else a for n, a in caches.items()}
        x, new_cache, _ = transformer._layer_fwd(
            cfg, x, layer, positions=positions, mask=mask,
            mask_local=mask_local, cache=cache, phase=phase, chunk=chunk)
        outs.append(new_cache)
    stacks = {n: jnp.stack([o[n] for o in outs]) for n in KV if n in caches}
    return x, dict(outs[-1], **stacks), 0.0


def _drive(session, paged):
    """Admit two tenants (a two-chunk prefill into slot 0, a whole-prompt
    prefill into slot 2, slot 1 left idle), decode across page boundaries,
    free slot 0 and admit a third tenant there, decode on.  Returns every
    step's logits and tokens, and the final pool cache."""
    kw = {"paged": True, "page_size": PAGE} if paged else {}
    steps = make_serve_steps(session.model, **kw)
    sparams, cache = steps.init_serve(session.params, SLOTS, MAX_LEN)
    decode, prefill = jax.jit(steps.decode), jax.jit(steps.prefill)
    chunk = jax.jit(steps.prefill_chunk)
    adopt = jax.jit(paged_cache.adopt if paged else ServePool._adopt_fn)
    if paged:
        cache = jax.jit(paged_cache.park)(cache)
    template = session.model.init_cache(1, MAX_LEN, **kw)
    seen, last = [], np.zeros((SLOTS, 1), np.int32)

    def admit_chunked(cache, slot, prompt, size):
        one = template
        for i in range(0, prompt.size, size):
            logits, one = chunk(sparams, {"tokens": prompt[None, i:i + size]},
                                one)
        seen.append(logits[0, -1])
        last[slot, 0] = int(jnp.argmax(logits[0, -1]))
        return adopt(cache, one, jnp.int32(slot))

    def run(cache, n):
        for _ in range(n):
            tok, logits, cache = decode(sparams, jnp.asarray(last), cache)
            seen.append(logits)
            last[:] = np.asarray(tok)
        return cache

    cache = admit_chunked(cache, 0, _prompt(6, 1), 4)
    logits, one = prefill(sparams, {"tokens": _prompt(5, 2)[None]}, template)
    seen.append(logits)
    last[2, 0] = int(jnp.argmax(logits[0, -1]))
    cache = adopt(cache, one, jnp.int32(2))
    cache = run(cache, 5)
    if paged:
        cache = jax.jit(paged_cache.free)(cache, jnp.int32(0))
    cache = admit_chunked(cache, 0, _prompt(3, 3), 4)
    cache = run(cache, 6)
    return [np.asarray(x) for x in seen], jax.tree.map(np.asarray, cache)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_in_place_stack_matches_per_layer_stack(session, monkeypatch, paged):
    """Prefill chunks, a whole-prompt prefill, and decode steps that cross
    page boundaries, with an idle slot and a freed and re-admitted slot:
    the in-place scan gives the per-layer stack's tokens exactly and its
    logits and cache within float32 rounding."""
    got, got_cache = _drive(session, paged)
    monkeypatch.setattr(transformer, "_run_stack", _per_layer_stack)
    want, want_cache = _drive(session, paged)
    assert len(got) == len(want) == 14
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g.argmax(-1), w.argmax(-1),
                                      err_msg=f"step {i}")
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5,
                                   err_msg=f"step {i}")
    assert got_cache.keys() == want_cache.keys()
    for name in got_cache:
        if np.issubdtype(got_cache[name].dtype, np.integer):
            np.testing.assert_array_equal(got_cache[name], want_cache[name],
                                          err_msg=name)
        else:
            np.testing.assert_allclose(got_cache[name], want_cache[name],
                                       rtol=1e-5, atol=1e-5, err_msg=name)


_MOVES = re.compile(r"= \w+\[([0-9,]*)\]\S*\s+"
                    r"(dynamic-slice|dynamic-update-slice|copy)\(")


def test_pool_decode_has_no_pool_copy_and_keeps_its_buffer(session):
    """The compiled pool decode slices, writes back or copies no page pool
    (a layer's or the stack's); across admissions, decode steps and slot
    releases the K stack stays in the buffer it was allocated in, and the
    batch-1 template every admission starts from stays readable."""
    pool = session.serve_pool(slots=SLOTS, max_len=MAX_LEN, paged=True,
                              page_size=PAGE, prefill_chunk=4,
                              bucket_prompts=True)
    kp = pool._cache["k_pages"]
    stack = kp.shape
    pools = {stack, stack[1:], (1,) + stack[1:]}
    hlo = pool._decode.lower(pool._sparams, jnp.zeros((SLOTS, 1), jnp.int32),
                             pool._cache).compile().as_text()
    moves = [(op, dims) for dims, op in _MOVES.findall(hlo)
             if tuple(int(d) for d in dims.split(",") if d) in pools]
    assert not moves, moves
    start = kp.unsafe_buffer_pointer()
    template = pool._cache1_template
    pool.submit(_prompt(6, 4), max_new_tokens=5)
    pool.step()
    pool.submit(_prompt(9, 5), max_new_tokens=4)
    pool.step()
    assert pool._cache["k_pages"].unsafe_buffer_pointer() == start
    assert pool.stats()["kv_in_place"] is True
    pool.run()
    assert pool.stats()["completed"] == 2
    assert pool._cache["k_pages"].unsafe_buffer_pointer() == start
    assert pool.stats()["kv_in_place"] is True
    assert kp.is_deleted()           # donated, never read again
    assert not any(a.is_deleted() for a in jax.tree.leaves(template))
    np.testing.assert_array_equal(np.asarray(template["k_pages"]), 0)
