"""Plain reference of a dense decoder-only transformer with MPO-factorized
matrices (Qwen3 and Mistral-Nemo layer equations), in float32 at highest
matmul precision.  It reads only the configuration file and the weights the
benchmark made from the seed; it imports nothing of the system under test.

Weights, as a tree of arrays:

* a matrix ``W[I, J]`` (``x @ W``) is ``{"cores": {"c0", "c1", "central",
  "c3", "c4"}}``: core ``k`` is ``T_k[d_{k-1}, i_k, j_k, d_k]`` with
  ``d_0 = d_n = 1`` and row/column digits in row-major order, and the
  central core is ``k = n // 2``;
* ``layers`` stacks each layer's leaves along a leading axis;
* norms are ``{"scale": (dim,)}``.

Layer (pre-norm): ``x += Wo(attn(rope(qknorm(Wq h)), rope(qknorm(Wk h)),
Wv h))`` with ``h = rmsnorm(x)``; ``x += Wd(silu(Wg h) * Wu h)`` with
``h = rmsnorm(x)``.  Attention is causal grouped-query attention with
rotate-half RoPE (``theta ** (-2i / head_dim)``) and scale
``head_dim ** -0.5``; ``qk_norm`` applies an RMSNorm over each head's
``head_dim`` before RoPE.  The final RMSNorm feeds an untied head.

``quant`` (for the lower-precision control) computes in float8, with one
scale per tensor, as float8 training does: every projection takes e4m3
inputs, and its two backward matmuls take the incoming gradient rounded
to e5m2; K and V and the residual stream after each block are rounded to
e4m3 where the program keeps them in its compute type (straight-through:
the gradient passes them unrounded).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def core_list(node: dict) -> list:
    cores = node["cores"]
    n = len(cores)
    return [cores["central" if k == n // 2 else f"c{k}"] for k in range(n)]


def reconstruct(cores: list) -> jax.Array:
    """``W[I, J]`` from its cores, contracted from the last core to the
    first so that the growing trailing column block is the minor dim."""
    t = cores[-1][..., 0]                              # (d, i_n, j_n)
    for c in reversed(cores[:-1]):
        d, i, j, _ = c.shape
        _, ir, jr = t.shape
        t = jnp.einsum("dije,eIJ->diIjJ", c, t).reshape(d, i * ir, j * jr)
    return t[0]


def embed_rows(cores: list, ids: jax.Array) -> jax.Array:
    """Rows ``W[ids, :]`` without building ``W``: each id's row-major digits
    pick one ``i`` slice of every core."""
    ins = [c.shape[1] for c in cores]
    digits, rest = [], ids
    for f in reversed(ins):
        digits.append(rest % f)
        rest = rest // f
    digits = digits[::-1]
    t = cores[0][0][digits[0]]                          # (N, j_1, d_1)
    for c, dg in zip(cores[1:], digits[1:]):
        sl = jnp.moveaxis(c[:, dg], 1, 0)               # (N, d, j, e)
        n, a, _ = t.shape
        t = jnp.einsum("naf,nfje->naje", t, sl).reshape(n, -1, sl.shape[-1])
    return t[..., 0]


def fp8(x: jax.Array, dtype=jnp.float8_e4m3fn) -> jax.Array:
    """float8 rounding with one scale per tensor; straight-through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    q = (x / s).astype(dtype).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def fp8_matmul(x, w):
    """``x @ w`` (2-D) in float8: e4m3 inputs; backward, e5m2 gradient."""
    return fp8(x) @ fp8(w)


def _fp8_matmul_fwd(x, w):
    qx, qw = fp8(x), fp8(w)
    return qx @ qw, (qx, qw)


def _fp8_matmul_bwd(res, dy):
    qx, qw = res
    g = fp8(dy, jnp.float8_e5m2)
    return g @ qw.T, qx.T @ g


fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)

QUANT = {None: None, "fp8": fp8}


def _mm(x, w, quant):
    return x @ w if quant is None else fp8_matmul(x, w)


def _q(x, quant):
    return x if quant is None else quant(x)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, Dh), pos: (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, by_head: bool = False):
    """Causal grouped-query attention.  q: (S, H, Dh), k/v: (S, KV, Dh).
    ``by_head`` computes one KV head's group at a time, so a long
    sequence's scores never exist for all heads at once."""
    s, h, dh = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, h // kvh, dh).transpose(1, 2, 0, 3)  # KV,G,S,Dh
    kk, vv = k.transpose(1, 0, 2), v.transpose(1, 0, 2)          # KV,S,Dh
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one(qh, kh, vh):
        sc = jnp.einsum("...gqd,...sd->...gqs", qh, kh) * dh ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return jnp.einsum("...gqs,...sd->...gqd", w, vh)

    if by_head:
        o = jax.lax.map(lambda xs: one(*xs), (qg, kk, vv))
    else:
        o = one(qg, kk, vv)
    return o.transpose(2, 0, 1, 3).reshape(s, h * dh)


def layer(cfg: dict, quant, x, p, by_head=False):
    h_, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, s = cfg["rms_norm_eps"], x.shape[0]
    pos = jnp.arange(s)
    w = lambda node: reconstruct(core_list(node))
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = _mm(h, w(a["wq"]), quant).reshape(s, h_, dh)
    k = _mm(h, w(a["wk"]), quant).reshape(s, kv, dh)
    v = _mm(h, w(a["wv"]), quant).reshape(s, kv, dh)
    if cfg["qk_norm"]:
        q = rmsnorm(q, a["q_norm"]["scale"], eps)
        k = rmsnorm(k, a["k_norm"]["scale"], eps)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k, v = _q(k, quant), _q(v, quant)
    x = _q(x + _mm(attention(q, k, v, by_head), w(a["wo"]), quant), quant)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    f = jax.nn.silu(_mm(h, w(m["w_gate"]), quant)) * _mm(h, w(m["w_up"]),
                                                         quant)
    return _q(x + _mm(f, w(m["w_down"]), quant), quant)


def hidden(cfg: dict, params, tokens, quant=None, by_head=False):
    """Final-normed hidden states (S, D) of one sequence."""
    x = embed_rows(core_list(params["embed"]), tokens)
    body = jax.checkpoint(lambda x, p: (layer(cfg, quant, x, p, by_head),
                                        None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def head(params) -> jax.Array:
    return reconstruct(core_list(params["lm_head"]))


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(params, w_head, tokens, rows, cfg_items, quant):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        hs = hidden(cfg, params, tokens, QUANT[quant], by_head=True)[rows]
        return _mm(hs, w_head, QUANT[quant])


def logits(cfg: dict, params, w_head, tokens, rows, quant=None):
    """Reference logits (len(rows), V) of one sequence at positions ``rows``."""
    return _logits(params, w_head, tokens, rows, tuple(sorted(cfg.items())),
                   quant)


# --------------------------------------------------------------------------
# training: next-token cross entropy, masked AdamW
# --------------------------------------------------------------------------


def ce_sum(cfg: dict, params, tokens, quant=None):
    """Summed next-token cross entropy of one sequence (its last position
    predicts nothing) and the number of predicted tokens."""
    hs = hidden(cfg, params, tokens, quant)[:-1]
    lg = _mm(hs, reconstruct(core_list(params["lm_head"])), quant)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], -1)[:, 0]
    return jnp.sum(lse - gold), tokens.shape[0] - 1


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _seq_grad(params, tokens, cfg_items, quant):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: ce_sum(cfg, p, tokens, QUANT[quant])[0])(params)


def loss_and_grad(cfg: dict, params, batch, quant=None):
    """Mean next-token cross entropy over a batch (B, S) and its gradient,
    one sequence at a time."""
    items = tuple(sorted(cfg.items()))
    total, grads = 0.0, None
    for row in batch:
        v, g = _seq_grad(params, row, items, quant)
        total += v
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = batch.shape[0] * (batch.shape[1] - 1)
    return total / n, jax.tree.map(lambda g: g / n, grads)


def lfa_trainable(path) -> bool:
    """LFA trains every leaf but the central cores."""
    return not any(getattr(k, "key", None) == "central" for k in path)


def adamw_steps(opt: dict, params, grads_fn, steps: int):
    """Masked AdamW (decoupled decay, global-norm clipping over the trainable
    gradients) for ``steps`` steps; ``grads_fn(params, k)`` gives step
    ``k``'s (loss, grads).  Returns (losses, first clipped gradients,
    params after the last step)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["grad_clip"]
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    train = [lfa_trainable(path) for path, _ in flat]
    p = [leaf for _, leaf in flat]
    mu = [jnp.zeros_like(x) for x in p]
    nu = [jnp.zeros_like(x) for x in p]
    losses, first = [], None
    for t in range(1, steps + 1):
        loss, g = grads_fn(tdef.unflatten(p), t - 1)
        g = jax.tree.leaves(g)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x, tr in zip(g, train) if tr))
        scale = jnp.minimum(1.0, clip / (gn + 1e-9))
        g = [x * scale for x in g]
        if first is None:
            first = tdef.unflatten(g)
        for i, tr in enumerate(train):
            if not tr:
                continue
            mu[i] = b1 * mu[i] + (1 - b1) * g[i]
            nu[i] = b2 * nu[i] + (1 - b2) * g[i] * g[i]
            upd = (mu[i] / (1 - b1 ** t)) / (jnp.sqrt(nu[i] / (1 - b2 ** t))
                                            + eps) + wd * p[i]
            p[i] = p[i] - lr * upd
        losses.append(float(loss))
    return losses, first, tdef.unflatten(p)
