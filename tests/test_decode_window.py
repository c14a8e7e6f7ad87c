"""Length-bounded decode attention on the XLA path: slots sorted longest
first, each group of ``DECODE_GROUP`` attending over its own page window
(``kernels.decode_attention.decode_windows``, ``nn._windowed_decode``).

The windowed path must give what the full-span gather gives for every
live slot (the same softmax, equal up to f32 reduction order), and the
pool's ``stats()["decode_kv_share"]`` must count the windows the device
chose."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import Session
from repro.kernels import decode_attention as DA
from repro.models import nn, paged_cache
from repro.train.steps import make_serve_steps

PS, MP = 4, 16                  # windows of 2, 4, 8, 16 pages: 8..64 keys
CAP = PS * MP


# --------------------------------------------------------------------------
# the window choice
# --------------------------------------------------------------------------


@pytest.mark.parametrize("max_pages,want", [
    (256, (32, 64, 128, 256)), (16, (2, 4, 8, 16)), (6, (1, 2, 3, 6)),
    (2, (1, 2)), (1, (1,))])
def test_window_pages(max_pages, want):
    assert DA.window_pages(max_pages) == want
    assert DA.windowed(max_pages) is (len(want) > 1)


def test_decode_windows_sorts_groups_and_picks_windows():
    """Longest first (ties in slot order), groups of 8; each group takes
    the smallest window that holds its longest slot; a group of parked
    slots takes branch 0.  ``np`` and ``jnp`` inputs agree."""
    lengths = np.array([0, 8, 9, 64, 1, 0, 16, 17, 33, 0, 12, 5, 3, 0, 0, 0])
    order, branch, tokens = DA.decode_windows(lengths, PS, MP)
    assert isinstance(order, np.ndarray)
    np.testing.assert_array_equal(
        order, [3, 8, 7, 6, 10, 2, 1, 11, 12, 4, 0, 5, 9, 13, 14, 15])
    np.testing.assert_array_equal(branch, [4, 1])
    np.testing.assert_array_equal(tokens, [64, 8])
    j_order, j_branch, j_tokens = DA.decode_windows(jnp.asarray(lengths),
                                                    PS, MP)
    assert isinstance(j_order, jax.Array)
    np.testing.assert_array_equal(j_order, order)
    np.testing.assert_array_equal(j_branch, branch)
    np.testing.assert_array_equal(j_tokens, tokens)


@pytest.mark.parametrize("longest,branch,tokens", [
    (0, 0, 0), (1, 1, 8), (8, 1, 8), (9, 2, 16), (16, 2, 16), (17, 3, 32),
    (33, 4, 64), (64, 4, 64)])
def test_decode_windows_edges(longest, branch, tokens):
    """A window holds exactly its own length: 8 keys fit the 8-key
    window, 9 take the next."""
    lengths = np.zeros(8, np.int64)
    lengths[5] = longest
    _, b, t = DA.decode_windows(lengths, PS, MP)
    assert (int(b[0]), int(t[0])) == (branch, tokens)


def test_decode_kv_share():
    lengths = np.array([0, 8, 9, 64, 1, 0, 16, 17, 33, 0, 12, 5, 3, 0, 0, 0])
    assert DA.decode_kv_share(lengths, PS, MP) == (8 * 64 + 8 * 8) / (16 * 64)
    assert DA.decode_kv_share(np.zeros(16, np.int64), PS, MP) == 0.0
    # a remainder group counts its own size: 8 + 4 slots, 16-key windows
    assert DA.decode_kv_share(np.full(12, 10), PS, MP) == 12 * 16 / (12 * 64)
    # one window, or a mesh that splits the model: the whole span
    assert DA.decode_kv_share(lengths, 64, 1) == 1.0
    assert DA.decode_kv_share(lengths, PS, MP, model=2) == 1.0


# --------------------------------------------------------------------------
# the windowed path against the full-span gather
# --------------------------------------------------------------------------


def _paged_state(key, lengths, kv=2, g=3, dh=16, layers=2, window=None):
    """A random page stack and a page table mapping each live slot's
    ``ceil(length / PS)`` pages (shuffled); parked slots (length 0) map
    none and sit past capacity.  ``pos`` and the mask are as the decode
    step sees them after its append."""
    b = len(lengths)
    ks = jax.random.split(key, 4)
    pool = b * MP
    kp = jax.random.normal(ks[0], (layers, pool, PS, kv, dh), jnp.float32)
    vp = jax.random.normal(ks[1], (layers, pool, PS, kv, dh), jnp.float32)
    q = jax.random.normal(ks[2], (b, 1, kv * g, dh), jnp.float32)
    phys = np.asarray(jax.random.permutation(ks[3], pool)).reshape(b, MP)
    lengths = np.asarray(lengths)
    used = np.arange(MP)[None] < -(-lengths[:, None] // PS)
    table = jnp.asarray(np.where(used, phys, -1), jnp.int32)
    pos = np.where(lengths > 0, lengths, CAP + 1)
    kj = np.arange(CAP)[None]
    mask = kj < pos[:, None]
    if window is not None:
        mask &= kj >= pos[:, None] - window
    return (q, kp, vp, table, jnp.asarray(pos, jnp.int32),
            jnp.asarray(mask[:, None, None]))


def _full_span(q, kp, vp, table, cfg, mask, layer):
    kc = DA.gather_pages(kp, table, layer)
    vc = DA.gather_pages(vp, table, layer)
    w = nn.attention_scores(q, kc, cfg, mask)
    return jnp.einsum("bkgqs,bskd->bqkgd", w.astype(vc.dtype), vc)


CASES = {
    # 10 live slots: the 8 longest read 64 keys, the rest 8
    "parked": ([1, 8, 0, 9, 16, 0, 17, 33, 0, 64, 5, 3, 0, 0, 12, 0], {}),
    # the second group holds only parked slots: zeros, no gather
    "empty_group": ([0, 5, 0, 0, 20, 0, 0, 0, 0, 2, 0, 31, 0, 0, 0, 7], {}),
    # every group's longest slot lies exactly on a window edge
    "window_edges": ([8, 16, 8, 16, 8, 16, 8, 16, 2, 8, 1, 8, 3, 8, 4, 8],
                     {}),
    # a slot at max_len beside short ones
    "max_len": ([64, 1, 2, 3, 1, 2, 3, 1, 64, 0, 0, 0, 0, 0, 0, 0], {}),
    # fewer slots than a group: one group of 5
    "few_slots": ([2, 0, 30, 31, 7], {}),
    "softcap": ([1, 8, 0, 9, 16, 0, 17, 33, 0, 64, 5, 3, 0, 0, 12, 0],
                {"softcap": 5.0}),
    "local_window": ([1, 8, 0, 9, 16, 0, 17, 33, 0, 64, 5, 3, 0, 0, 12, 0],
                     {"window": 6}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_windowed_decode_matches_full_span(case):
    """Every live slot reads what the full-span gather gives it; a group
    with no live slot returns zeros."""
    lengths, kw = CASES[case]
    q, kp, vp, table, pos, mask = _paged_state(
        jax.random.PRNGKey(len(case)), lengths, window=kw.get("window"))
    cfg = nn.AttnCfg(d_model=48, num_heads=6, num_kv_heads=2, head_dim=16,
                     attn_softcap=kw.get("softcap"))
    layer = jnp.int32(1)
    order, branch, _ = DA.decode_windows(jnp.where(pos > CAP, 0, pos), PS, MP)
    got = jax.jit(nn._windowed_decode, static_argnums=6)(
        q, kp, vp, table, order, branch, cfg, mask, layer)
    want = _full_span(q, kp, vp, table, cfg, mask, layer)
    live = np.asarray(lengths) > 0
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-6)
    order, branch, _ = DA.decode_windows(np.asarray(lengths), PS, MP)
    group = min(DA.DECODE_GROUP, len(lengths))
    for i, b in enumerate(branch):
        if b == 0:
            empty = order[i * group:(i + 1) * group]
            np.testing.assert_array_equal(np.asarray(got)[empty], 0.0)


# --------------------------------------------------------------------------
# through the model: a 16-slot pool whose decode runs the windowed branch
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def session():
    return Session.init("qwen3-14b")


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 500, n).astype(np.int32)


def _decode_logits(session, prompts, steps):
    """Adopt one tenant per ``prompts`` entry (slot -> prompt length) into
    a parked 16-slot paged pool and run ``steps`` decode steps; returns
    each step's logits and the decode program's jaxpr."""
    serve = make_serve_steps(session.model, paged=True, page_size=PS)
    sparams, cache = serve.init_serve(session.params, 16, CAP)
    cache = jax.jit(paged_cache.park)(cache)
    template = session.model.init_cache(1, CAP, paged=True, page_size=PS)
    prefill, adopt = jax.jit(serve.prefill), jax.jit(paged_cache.adopt)
    last = np.zeros((16, 1), np.int32)
    for slot, n in prompts.items():
        logits, one = prefill(sparams, {"tokens": _prompt(n, slot)[None]},
                              template)
        last[slot, 0] = int(jnp.argmax(logits[0, -1]))
        cache = adopt(cache, one, jnp.int32(slot))
    jaxpr = str(jax.make_jaxpr(serve.decode)(sparams, jnp.asarray(last),
                                             cache))
    decode, seen = jax.jit(serve.decode), []
    for _ in range(steps):
        tok, logits, cache = decode(sparams, jnp.asarray(last), cache)
        seen.append(np.asarray(logits))
        last[:] = np.asarray(tok)
    return seen, jaxpr


POOLS = {
    # ten tenants: lengths reach 16 and 32 (window edges) and max_len
    "long": ({0: 62, 2: 15, 3: 7, 5: 31, 6: 3, 8: 20, 9: 9, 11: 40, 13: 1,
              15: 11}, 2),
    # four short tenants: the second group is empty; the first group's
    # window grows 16 -> 16 -> 32 as its longest slot crosses 16 keys
    "short": ({1: 3, 4: 5, 7: 14, 10: 2}, 3),
}


@pytest.mark.parametrize("pool", list(POOLS))
def test_pool_decode_windowed_matches_full_span(session, monkeypatch, pool):
    """The served decode over a pool with four windows gives every live
    slot the full-span gather's tokens, and logits within f32 rounding."""
    prompts, steps = POOLS[pool]
    got, jaxpr = _decode_logits(session, prompts, steps)
    assert "cond[" in jaxpr                  # the windowed branch ran
    monkeypatch.setattr(DA, "windowed", lambda *a: False)
    want, jaxpr = _decode_logits(session, prompts, steps)
    assert "cond[" not in jaxpr
    live = sorted(prompts)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g[live].argmax(-1), w[live].argmax(-1),
                                      err_msg=f"step {i}")
        np.testing.assert_allclose(g[live], w[live], rtol=1e-5, atol=1e-5,
                                   err_msg=f"step {i}")


def test_decode_kv_share_counts_the_device_windows(session):
    """Step by step, the pool's host count equals the windows the device
    chose from its own cache positions; the mean lands in ``stats()``."""
    pool = session.serve_pool(slots=16, max_len=CAP, paged=True,
                              page_size=PS, prefill_chunk=8,
                              bucket_prompts=True)
    decode, device = pool._decode, []
    choose = jax.jit(lambda pos: DA.decode_windows(
        jnp.where(pos + 1 > CAP, 0, pos + 1), PS, MP)[2])

    def spy(sparams, tok, cache):
        tokens = np.asarray(choose(cache["pos"]))
        device.append(float(8 * tokens.sum()) / (16 * CAP))
        return decode(sparams, tok, cache)

    pool._decode = spy
    for i, (n, new) in enumerate([(3, 30), (20, 12), (9, 40), (1, 5),
                                  (33, 20), (12, 9), (5, 25), (2, 3),
                                  (40, 20), (7, 6)]):
        pool.submit(_prompt(n, i), max_new_tokens=new)
    host = []
    while pool.pending or pool.live or pool.admitting:
        before = pool._kv_share_sum
        if pool.step():
            host.append(pool._kv_share_sum - before)
    assert pool.stats()["completed"] == 10
    np.testing.assert_allclose(host, device, rtol=0, atol=1e-12)
    assert len(set(device)) > 2 and max(device) < 1.0
    assert pool.stats()["decode_kv_share"] == pytest.approx(
        sum(device) / len(device), rel=1e-12)


def test_decode_kv_share_is_whole_span_with_one_window(session):
    """A pool of one page per slot has one window: its decode gathers the
    whole span and the counter reads 1.0.  A dense pool reads None."""
    pool = session.serve_pool(slots=3, max_len=8, paged=True, page_size=8)
    for i in range(3):
        pool.submit(_prompt(2 + i, i), max_new_tokens=4)
    pool.run()
    assert pool.stats()["decode_kv_share"] == 1.0
    dense = session.serve_pool(slots=2, max_len=8)
    dense.submit(_prompt(3, 0), max_new_tokens=3)
    dense.run()
    assert dense.stats()["decode_kv_share"] is None
