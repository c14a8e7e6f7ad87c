"""Train / serve step functions (jit-able, mesh-aware).

``make_train_step`` builds the canonical SPMD step: forward (remat-scanned),
CE loss (optionally sequence-chunked so per-chip logits stay at one chunk —
critical at 200k+ vocab), backward, (optional EF-compressed) optimizer update.

Every MPO matmul inside the step executes through the engine's
``train``-phase ``ExecutionPlan`` (the model threads ``phase="train"``).
Since the fused Pallas kernel carries a custom VJP, a train plan may now
resolve to ``kernel`` — fwd AND bwd fused, gradients accumulated in core
space — with the tile height measured by ``kernels.autotune``; the step
builders below need no changes to pick that up.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.optim.optimizers import Optimizer, OptState

IGNORE = -100


class TrainState(NamedTuple):
    params: Any
    opt_state: OptState


def cross_entropy(logits, labels):
    """Sum of CE over valid labels + valid count.  labels==IGNORE skipped."""
    valid = labels != IGNORE
    safe = jnp.where(valid, labels, 0)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = jnp.where(valid, lse - gold, 0.0)
    return jnp.sum(ce), jnp.sum(valid)


def lm_loss(model: Model, params, batch):
    """(mean CE, metrics).  Chunked over the sequence when cfg.loss_chunk>0."""
    chunk = model.cfg.loss_chunk
    hidden, aux = model.forward_hidden(params, batch, phase="train")
    labels = batch["labels"]
    s = hidden.shape[1]
    if labels.shape[1] != s:  # vlm: labels cover full (patch+text) length
        labels = labels[:, -s:]
    # global next-token shift (boundary-safe under chunking)
    shifted = jnp.concatenate(
        [labels[:, 1:], jnp.full((labels.shape[0], 1), IGNORE, labels.dtype)],
        axis=1)
    if chunk and s % chunk == 0 and s > chunk:
        nch = s // chunk
        h = hidden.reshape(hidden.shape[0], nch, chunk, -1).transpose(1, 0, 2, 3)
        l = shifted.reshape(shifted.shape[0], nch, chunk).transpose(1, 0, 2)

        def body(carry, xs):
            hc, lc = xs
            logits = model.logits_head(params, hc, phase="train")
            ce, n = cross_entropy(logits, lc)
            return (carry[0] + ce, carry[1] + n), None

        body = jax.checkpoint(body)
        (ce, n), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                  (h, l))
    else:
        logits = model.logits_head(params, hidden, phase="train")
        ce, n = cross_entropy(logits, shifted)
    loss = ce / jnp.maximum(n, 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux, "tokens": n}


def make_train_step(model: Model, optimizer: Optimizer,
                    loss_fn: Callable | None = None):
    loss_fn = loss_fn or (lambda p, b: lm_loss(model, p, b))

    def train_step(state: TrainState, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(state.params)
        new_params, new_opt = optimizer.update(grads, state.opt_state,
                                               state.params)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        metrics = dict(metrics, grad_norm=gnorm)
        return TrainState(new_params, new_opt), metrics

    return train_step


def make_eval_step(model: Model, loss_fn: Callable | None = None):
    loss_fn = loss_fn or (lambda p, b: lm_loss(model, p, b))

    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch)
        return metrics

    return eval_step


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


class ServeSteps(NamedTuple):
    """The jit-able serving step bundle ``make_serve_steps`` returns.

    Unpacks like the historical 3-tuple (``prefill, decode, init_serve, _ =
    make_serve_steps(...)`` — or index it); ``prefill_chunk`` is the
    incremental-prefill step behind chunked admission
    (``model.prefill_chunk``), ``None`` for families without one."""

    prefill: Any
    decode: Any
    init_serve: Any
    prefill_chunk: Any = None


def make_serve_steps(model: Model, *, weight_cache: bool = True,
                     mesh=None, rules: dict | None = None, axes=None,
                     paged: bool = False, page_size: int = 16,
                     pool_pages: int | None = None) -> "ServeSteps":
    """``ServeSteps(prefill, decode, init_serve, prefill_chunk)`` for
    batched serving.

    ``prefill_chunk(params, batch, cache)`` continues a prefill at the
    cache's current per-slot offsets and returns logits for EVERY chunk
    position (the caller slices the real last prompt token's row — under
    length-bucketed padding that is not the last row).  It is ``None`` for
    families without a KV-sequence cache (ssm/hybrid/encdec).

    ``paged=True`` allocates the PAGED KV cache
    (``transformer.init_cache(paged=True, page_size=...)``): decode
    attention then appends into fixed-size pages and routes through
    ``kernels.decode_attention`` (flash kernel vs XLA gather, raced by the
    measured autotuner) — see docs/serving.md "Decode attention & paged
    KV".  The step functions themselves are unchanged; the cache pytree
    carries the paging state.  ``pool_pages`` oversubscribes the physical
    page pool below the ``batch * max_pages`` worst case — only meaningful
    behind ``ServePool``'s page-reservation admission (docs/resilience.md),
    which queues requests instead of letting the free list underflow.

    ``init_serve(params, batch, max_len)`` runs ONCE per serving session: it
    allocates the KV cache (per-slot positions — see
    ``transformer.init_cache``) and — when ``weight_cache`` — contracts
    every factorized matrix whose decode plan is ``cached`` into its dense W
    (``MPOEngine.cache_weights``), returning ``(serve_params, cache)``.  The
    decode loop then performs zero per-step core contractions; pass the
    returned ``serve_params`` (not the raw training params) to the steps.

    The weight cache is a SNAPSHOT of the cores, not a view: any core
    mutation after it was taken (further training, ``tt_round``, dimension
    squeezing) silently invalidates it, so ``init_serve`` must be re-run
    from the mutated cores.  ``Session`` automates exactly this — it
    version-stamps the weights on every mutation and rebuilds the serving
    snapshot on the next ``serve()`` instead of reusing a stale one.

    Mesh-sharded serving (``mesh=``, optional ``rules=``, required
    ``axes=``): the serving state is PLACED on a ``jax.sharding.Mesh``
    instead of replicated per host —

    * the densified weight cache flows through
      ``cache_weights(axes=...)`` so each dense W inherits its cores' TP
      layout, then through ``parallel.sharding.tree_shardings`` into
      ``NamedSharding``-committed device arrays;
    * matrices that STAY factorized (heavily compressed embedding tables)
      get per-core specs — the compression win is never resurrected as a
      replicated dense table;
    * the returned prefill/decode steps are jitted with
      ``in_shardings``/``out_shardings``: params pinned to their layout,
      the KV cache to ``parallel.sharding.cache_sharding`` (batch over
      ``data``, cache seq dim over ``model`` — the flash-decoding layout),
      prompt/token inputs and logits replicated;
    * the mesh-jitted decode DONATES the cache it is given: the caller
      rebinds its cache to the returned one and never reads the old one.

    Example::

        mesh = make_host_mesh(model=4)            # 8 devices -> (2, 4)
        params, axes = model.init_params(key)
        prefill, decode, init_serve, _ = make_serve_steps(
            model, mesh=mesh, axes=axes)
        sparams, cache = init_serve(params, batch=8, max_len=128)
        logits, cache = prefill(sparams, batch_inputs, cache)
    """

    cache_kw = {"paged": True, "page_size": page_size} if paged else {}
    if paged and pool_pages is not None:
        cache_kw["pool_pages"] = pool_pages

    def init_serve(params, batch: int, max_len: int):
        cache = model.init_cache(batch, max_len, **cache_kw)
        serve_params = model.cache_weights(params) if weight_cache else params
        return serve_params, cache

    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache, phase="prefill")

    def decode_step(params, tokens, cache):
        logits, cache = model.decode_step(params, tokens, cache,
                                          phase="decode")
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        return next_tok, logits, cache

    prefill_chunk_step = None
    if model.prefill_chunk is not None:
        def prefill_chunk_step(params, batch, cache):
            return model.prefill_chunk(params, batch, cache, phase="prefill")

    if mesh is None:
        return ServeSteps(prefill_step, decode_step, init_serve,
                          prefill_chunk_step)

    from jax.sharding import NamedSharding, PartitionSpec
    from repro.parallel import sharding as S
    from repro.parallel.ctx import maybe_mesh

    if axes is None:
        raise ValueError(
            "make_serve_steps(mesh=...) needs axes= (the logical-axis tree "
            "from model.init_params / split_annotations) to place the "
            "serving params on the mesh")
    rules = S.make_rules(mesh) if rules is None else rules
    # never let a K/V projection shard split head_dim across devices
    # (numerically wrong under GSPMD — see head_safe_rules)
    rules = S.head_safe_rules(rules, model.cfg, mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    _specs = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    jitted: dict = {}

    def init_serve_mesh(params, batch: int, max_len: int):
        cache = model.init_cache(batch, max_len, **cache_kw)
        # contract the dense snapshot on the mesh's own devices, not on the
        # default device: a fleet replica's weights never pass through
        # another replica's chip
        params = jax.device_put(
            params, S.tree_shardings(axes, _specs(params), mesh, rules))
        if weight_cache:
            serve_params, serve_axes = model.cache_weights(params, axes=axes)
        else:
            serve_params, serve_axes = params, axes
        pshard = S.tree_shardings(serve_axes, _specs(serve_params), mesh,
                                  rules)
        cshard = S.cache_sharding(_specs(cache), mesh, rules)
        serve_params = jax.device_put(serve_params, pshard)
        cache = jax.device_put(cache, cshard)
        jitted["prefill"] = jax.jit(prefill_step,
                                    in_shardings=(pshard, repl, cshard),
                                    out_shardings=(repl, cshard))
        # the cache is donated: callers rebind it to the step's result
        jitted["decode"] = jax.jit(decode_step,
                                   in_shardings=(pshard, repl, cshard),
                                   out_shardings=(repl, repl, cshard),
                                   donate_argnums=2)
        return serve_params, cache

    def prefill_sharded(params, batch, cache):
        with maybe_mesh(mesh):  # activation constraints active at trace
            return jitted["prefill"](params, batch, cache)

    def decode_sharded(params, tokens, cache):
        with maybe_mesh(mesh):
            return jitted["decode"](params, tokens, cache)

    chunk_sharded = None
    if prefill_chunk_step is not None:
        # admission-side step: inputs arrive committed (the batch-1 cache
        # template is device_put by the caller), so no explicit shardings —
        # only the mesh context for activation constraints at trace
        jit_chunk = jax.jit(prefill_chunk_step)

        def chunk_sharded(params, batch, cache):
            with maybe_mesh(mesh):
                return jit_chunk(params, batch, cache)

        chunk_sharded.jitted = True

    # the returned steps are already jit-backed with explicit shardings:
    # callers (ServeHandle) must not wrap them in a second jax.jit
    prefill_sharded.jitted = decode_sharded.jitted = True
    return ServeSteps(prefill_sharded, decode_sharded, init_serve_mesh,
                      chunk_sharded)


# --------------------------------------------------------------------------
# classification (paper's GLUE-analog experiments)
# --------------------------------------------------------------------------


def make_cls_loss(cfg):
    from repro.models import transformer

    def loss_fn(params, batch):
        logits, aux = transformer.forward_cls(params, batch, cfg)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ce = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return ce + 0.01 * aux, {"loss": ce, "acc": acc, "aux": aux}

    return loss_fn
