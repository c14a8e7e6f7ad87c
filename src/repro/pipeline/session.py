"""``Session``: the stage-based lifecycle API for the paper's workflow.

The paper's contribution is a *pipeline* — decompose a pretrained model into
central + auxiliary tensors (Algorithm 1), fine-tune only the auxiliary
tensors (§4.1), dimension-squeeze the bonds (Algorithm 2), then serve the
compressed model.  Historically every example re-wired that pipeline by hand
(configs + ``model.build`` + ``trainable_mask`` + masked optimizer + jitted
steps + ``make_serve_steps``).  ``Session`` is the single object that owns
the moving parts and the invariants BETWEEN stages:

    Session.init(cfg) ── or ── Session.from_dense(dense_params, cfg)
        │                          (Alg. 1 conversion + error report)
        ▼
    .finetune(mode="lfa")      trainability mask + masked optimizer +
        │                      jitted train loop (aux tensors only)
        ▼
    .squeeze(delta=...)        Algorithm 2; every eval runs on a FRESHLY
        │                      densified weight snapshot, and any serving
        ▼                      snapshot taken earlier is invalidated
    .serve(batch, max_len)     one-time ``init_serve`` (KV cache + cached-W
        │                      contraction) -> prefill/decode handle
        ▼
    .report()                  compression ratio, trainable-param reduction,
                               conversion error, per-stage wall timings

The invariant the stages protect: a densified ``cache_weights`` tree is a
snapshot of the cores.  Every mutation (``finetune``, ``squeeze``) bumps the
session's weights version; ``serve`` compares versions and re-contracts
instead of reusing a stale W (the ROADMAP open item this module closes).
The layer-level functions (``repro.core.*``, ``repro.train.steps``) remain
the low-level escape hatch — ``Session`` only composes them.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import configs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import convert, lightweight, squeeze as squeeze_mod
from repro.core import layers as L
from repro.core.engine import engine_for
from repro.data.pipeline import SyntheticCLS, make_batch_fn
from repro.models import model as M
from repro.optim import optimizers, schedule
from repro.runtime import enable_compile_cache
from repro.train.loop import LoopConfig, run_training
from repro.train.steps import (TrainState, lm_loss, make_cls_loss,
                               make_serve_steps, make_train_step)

STAGES = ("init", "from_dense", "finetune", "squeeze", "serve")


@dataclasses.dataclass(frozen=True)
class StageRecord:
    """One completed stage transition, for ``Session.report()`` — e.g.
    ``StageRecord("finetune", 12.3, {"steps": 60, "trainable": 91321})``
    appears as ``report()["stages"][i]``."""
    stage: str
    seconds: float
    info: dict


def _to_device(batch: dict) -> dict:
    return {k: jnp.asarray(v) for k, v in batch.items()}


class ServeHandle:
    """A bound serving session: jitted prefill/decode steps over a weight
    snapshot taken ONCE at construction (``init_serve``: KV-cache allocation
    + ``MPOEngine.cache_weights`` densification).  Carries the weights
    version it was built from so ``Session.serve`` can detect staleness.

    With ``mesh=`` the snapshot is PLACED on a ``jax.sharding.Mesh``: dense
    cached Ws carry the ``NamedSharding`` their cores' TP layout implies,
    still-factorized tables keep per-core placements, and the prefill/decode
    steps run with explicit ``in_shardings``/``out_shardings`` (the KV cache
    pinned to its flash-decoding layout).  Example::

        handle = session.serve(batch_size=8, max_len=64)
        out = handle.generate({"tokens": prompts}, num_tokens=16)  # (8, 16)
    """

    def __init__(self, model, params, batch_size: int, max_len: int, *,
                 weight_cache: bool = True, version: int = 0,
                 mesh=None, rules=None, axes=None,
                 paged: bool = False, page_size: int = 16):
        self.batch_size, self.max_len = batch_size, max_len
        self.weight_cache = weight_cache
        self.version = version
        self.mesh = mesh
        self.paged = paged
        prefill_step, decode_step, init_serve, _ = make_serve_steps(
            model, weight_cache=weight_cache, mesh=mesh, rules=rules,
            axes=axes, paged=paged, page_size=page_size)
        t0 = time.perf_counter()
        self.params, self._cache0 = jax.block_until_ready(
            init_serve(params, batch_size, max_len))
        self.init_seconds = time.perf_counter() - t0
        # mesh-sharded steps come back already jitted (with explicit
        # shardings); wrapping them again would erase those
        jitted = getattr(prefill_step, "jitted", False)
        self._prefill = prefill_step if jitted else jax.jit(prefill_step)
        self._decode = decode_step if jitted else jax.jit(decode_step)
        self.cache = self._cache0

    def reset(self):
        """Rewind to the freshly-initialized (empty) KV cache."""
        self.cache = self._cache0
        return self

    def prefill(self, batch: dict) -> jax.Array:
        logits, self.cache = self._prefill(self.params, _to_device(batch),
                                           self.cache)
        return logits

    def decode(self, tokens: jax.Array):
        if self.cache is self._cache0:
            # a donating (mesh-jitted) decode would consume the pristine
            # cache ``reset()`` hands out again
            self.cache = jax.tree.map(jnp.copy, self._cache0)
        tok, logits, self.cache = self._decode(self.params, tokens, self.cache)
        return tok, logits

    def generate(self, batch: dict, num_tokens: int) -> jax.Array:
        """Greedy generation: prefill the prompt, decode ``num_tokens``.
        Returns (batch, num_tokens) token ids."""
        self.reset()
        logits = self.prefill(batch)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out = [tok]
        for _ in range(num_tokens - 1):
            tok, _ = self.decode(tok)
            out.append(tok)
        return jnp.concatenate(out, axis=1)


class Session:
    """Owns params, the ``MPOEngine``, the trainability mask, and weight-
    cache validity across the compress -> fine-tune -> squeeze -> serve
    lifecycle.  See the module docstring for the stage diagram.

    Example (the paper's full workflow at smoke scale)::

        from repro import Session
        s = Session.init("qwen3-14b")          # or .from_dense(ckpt, cfg)
        s.finetune(mode="lfa", steps=60)       # auxiliary tensors only
        s.squeeze(delta=0.05, max_iters=8)     # Algorithm 2
        out = s.serve(8, 64).generate(batch, num_tokens=16)
        pool = s.serve_pool(slots=4, max_len=64)   # multi-tenant decode
        print(s.report())                      # rho, reductions, pool stats
        s.save("runs/s1")                      # full-session persistence
        s2 = Session.restore("runs/s1")        # serves token-identically
    """

    def __init__(self, cfg: ModelConfig, params, axes=None):
        enable_compile_cache()
        self.cfg = cfg
        self.model = M.build(cfg)
        self.engine = engine_for(cfg.mpo)
        self.params = params
        self.axes = axes
        self.mask = None                  # last trainability mask
        self.conversion_report: dict = {}
        self.squeeze_history: list = []
        self.stage = "init"
        self._records: list[StageRecord] = []
        self._version = 0                 # bumped on every core mutation
        # (batch, max_len, weight_cache, mesh, rules) -> ServeHandle, all at
        # _version; cleared on every bump so a stale snapshot is never reused
        self._serve: dict[tuple, ServeHandle] = {}
        # ServePools are observed weakly: report() surfaces stats for pools
        # the caller still holds, without the session pinning every pool's
        # weight snapshot for its whole lifetime
        self._pools: list = []            # list[weakref.ref[ServePool]]
        self._loss_default: Callable | None = None
        # (mode, lr, wd, loss id, params treedef) -> (mask, optimizer, step):
        # reusing the same jitted step across finetune calls / squeeze
        # re-tunes avoids a re-trace per call (mask values depend only on
        # tree structure, which is part of the key)
        self._step_cache: dict = {}

    # ---- constructors ----

    @classmethod
    def init(cls, cfg: ModelConfig | str, *, seed: int = 0,
             smoke: bool = True, **overrides) -> "Session":
        """Fresh MPO-parameterized model.  ``cfg`` may be a ``ModelConfig``
        or an arch name (``"qwen3-14b"``; ``smoke=True`` scales it down to
        the CPU-sized config the examples/tests use).  ``overrides`` are
        config-field replacements and apply either way."""
        if isinstance(cfg, str):
            cfg = (configs.smoke_config(cfg, **overrides) if smoke
                   else configs.get_config(cfg, **overrides))
        elif overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        t0 = time.perf_counter()
        model = M.build(cfg)
        params, axes = model.init_params(jax.random.PRNGKey(seed))
        s = cls(cfg, params, axes)
        s._record("init", t0, {"params": lightweight.count_params(params)})
        return s

    @classmethod
    def from_dense(cls, dense_params, cfg: ModelConfig, *,
                   report: bool = True) -> "Session":
        """The paper's actual workflow: MPO-decompose a *pretrained* dense
        checkpoint (Algorithm 1) into this config's core layout (bond-
        truncated per the config), with a per-matrix reconstruction-error
        report (Eq. 4 drift)."""
        t0 = time.perf_counter()
        model = M.build(cfg)
        template, axes = L.split_annotations(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        params = convert.convert_dense_to_mpo(dense_params, template)
        s = cls(cfg, params, axes)
        s.stage = "from_dense"
        errs = {}
        if report:
            errs = convert.conversion_error(dense_params, params)
            s.conversion_report = errs
        s._record("from_dense", t0, {
            "matrices": len(errs),
            "max_rel_err": max(errs.values(), default=0.0),
        })
        return s

    # ---- stage bookkeeping ----

    def _record(self, stage: str, t0: float, info: dict):
        self.stage = stage
        self._records.append(
            StageRecord(stage, time.perf_counter() - t0, info))

    def _bump(self):
        """Core mutation: any weight-cache snapshot is now stale."""
        self._version += 1
        self._serve.clear()

    @property
    def weights_version(self) -> int:
        return self._version

    # ---- task defaults (cls vs lm) ----

    @property
    def task(self) -> str:
        return "cls" if self.cfg.num_classes else "lm"

    def _default_loss_fn(self) -> Callable:
        if self._loss_default is None:
            self._loss_default = (
                make_cls_loss(self.cfg) if self.task == "cls"
                else lambda p, b: lm_loss(self.model, p, b))
        return self._loss_default

    def _cached_train_step(self, mode: str, lr: float, weight_decay: float,
                           loss_fn: Callable, params=None):
        """(mask, optimizer, jitted step) memoized per configuration.  The
        mask depends only on the params TREE STRUCTURE (part of the key), so
        squeeze-truncated trees reuse the entry — jit re-traces on the new
        shapes by itself."""
        params = self.params if params is None else params
        key = (mode, float(lr), float(weight_decay), id(loss_fn),
               jax.tree.structure(params))
        hit = self._step_cache.get(key)
        if hit is None:
            mask = lightweight.trainable_mask(params, mode=mode)
            opt = optimizers.adamw(lr, weight_decay=weight_decay, mask=mask)
            step = jax.jit(make_train_step(self.model, opt, loss_fn=loss_fn))
            hit = self._step_cache[key] = (mask, opt, step)
        return hit

    def _default_batch_fn(self, seq_len: int, batch_size: int,
                          seed: int) -> Callable:
        if self.task == "cls":
            ds = SyntheticCLS(self.cfg.vocab_size, seq_len, batch_size,
                              num_classes=self.cfg.num_classes, seed=seed)
            return ds.batch
        shape = ShapeConfig("pipeline", "train", seq_len, batch_size)
        return make_batch_fn(self.cfg, shape, seed=seed)

    # ---- finetune ----

    def finetune(self, *, mode: str = "lfa", steps: int = 60,
                 lr: float | Callable = 2e-3, warmup: int = 0,
                 weight_decay: float = 0.0, seq_len: int = 32,
                 batch_size: int = 16, seed: int = 0, mask=None,
                 optimizer=None, loss_fn: Callable | None = None,
                 batch_fn: Callable | None = None, ckpt_dir: str | None = None,
                 ckpt_every: int = 100, log_every: int = 50,
                 donate: bool = False, verbose: bool = False) -> dict:
        """Lightweight fine-tuning (paper §4.1): build the trainability mask
        (``mode="lfa"`` freezes the central tensors), a masked optimizer
        (frozen leaves allocate no state and receive no updates), and run the
        jitted train loop.  Every MPO matmul inside the step routes through
        the engine's ``train``-phase plan — on real TPUs that can now be the
        fused differentiable kernel at a measured ``block_m``
        (``kernels.autotune``); no finetune API surface changes either way.
        ``ckpt_dir`` enables checkpoint/resume (written
        every ``ckpt_every`` steps).  ``donate=True`` donates the train-state
        buffers to each step (halves peak params+optimizer memory at scale;
        any pre-finetune reference to ``session.params`` becomes invalid).
        Returns a stage report; the session's params advance in place."""
        t0 = time.perf_counter()
        loss_fn = loss_fn or self._default_loss_fn()
        batch_fn = batch_fn or self._default_batch_fn(seq_len, batch_size,
                                                      seed)
        if mask is None and optimizer is None and not callable(lr) \
                and not warmup and not donate:
            mask, optimizer, step_fn = self._cached_train_step(
                mode, lr, weight_decay, loss_fn)
        else:
            if mask is None and optimizer is None:
                mask = lightweight.trainable_mask(self.params, mode=mode)
            # a caller-supplied optimizer owns its own masking — do NOT
            # fabricate a mode-derived mask for it, the trainable counts
            # below would claim freezes that never happened
            if optimizer is None:
                lr_fn = lr if (callable(lr) or not warmup) else \
                    schedule.cosine_warmup(lr, warmup=warmup, total=steps)
                optimizer = optimizers.adamw(lr_fn,
                                             weight_decay=weight_decay,
                                             mask=mask)
            step_fn = jax.jit(make_train_step(self.model, optimizer,
                                              loss_fn=loss_fn),
                              donate_argnums=(0,) if donate else ())
        state = TrainState(self.params, optimizer.init(self.params))
        loop = LoopConfig(steps=steps, ckpt_dir=ckpt_dir,
                          ckpt_every=ckpt_every,
                          log_every=max(1, min(log_every, steps)))
        log = print if verbose else (lambda *a, **k: None)
        try:
            state, history = run_training(step_fn, state, batch_fn, loop,
                                          to_device=_to_device, log_fn=log)
        except BaseException as e:
            if donate:
                # the first donated step already invalidated the buffers
                # self.params points at — fail the session loudly instead of
                # leaving it to die later with "Array has been deleted"
                self.params = None
                if hasattr(e, "add_note"):  # py3.11+
                    e.add_note(
                        "Session.finetune(donate=True) failed mid-run: the "
                        "session's params were donated and are gone; rebuild "
                        "the session (or resume from ckpt_dir)")
            raise
        self.params = state.params
        self.mask = mask
        self._bump()
        info = {"mode": mode, "steps": steps,
                "total": lightweight.count_params(self.params),
                "loss_first": history[0]["loss"] if history else None,
                "loss_final": history[-1]["loss"] if history else None}
        if mask is not None:
            tr, tot = lightweight.count_trainable(self.params, mask)
            info.update(trainable=tr,
                        reduction=1.0 - tr / max(tot, 1))
        self._record("finetune", t0, info)
        return dict(info, history=history)

    # ---- evaluation ----

    def evaluate(self, params=None, *, num_batches: int = 8,
                 seq_len: int = 32, batch_size: int = 16, seed: int = 0,
                 loss_fn: Callable | None = None,
                 batch_fn: Callable | None = None) -> float:
        """Held-out metric, higher = better: mean accuracy for
        classification configs, negative mean loss for LMs.  Evaluates the
        session params unless an explicit tree is passed (``squeeze`` passes
        freshly densified snapshots through here)."""
        params = self.params if params is None else params
        loss_fn = loss_fn or self._default_loss_fn()
        batch_fn = batch_fn or self._default_batch_fn(seq_len, batch_size,
                                                      seed)
        key = ("eval", id(loss_fn))
        eval_fn = self._step_cache.get(key)
        if eval_fn is None:
            eval_fn = self._step_cache[key] = jax.jit(
                lambda p, b: loss_fn(p, b)[1])
        vals = []
        for i in range(1000, 1000 + num_batches):
            m = eval_fn(params, _to_device(batch_fn(i)))
            vals.append(float(m["acc"]) if "acc" in m else -float(m["loss"]))
        return float(np.mean(vals))

    # ---- squeeze ----

    def squeeze(self, *, delta: float = 0.05, max_iters: int = 8,
                step: int = 1, min_bond: int = 1, finetune_steps: int = 12,
                lr: float = 1e-3, mode: str = "lfa", seq_len: int = 32,
                batch_size: int = 16, seed: int = 0,
                eval_fn: Callable | None = None,
                loss_fn: Callable | None = None,
                batch_fn: Callable | None = None, weight_cache: bool = True,
                ckpt_dir: str | None = None,
                verbose: bool = False) -> list:
        """Dimension squeezing (paper Algorithm 2): repeatedly truncate the
        least-error bond, re-tune the auxiliary tensors, stop when the metric
        gap exceeds ``delta``.  Every evaluation runs on a freshly contracted
        weight snapshot (``weight_cache=True``), and any serving snapshot
        taken before this call is invalidated — a post-squeeze ``serve``
        always re-densifies from the squeezed cores.

        ``ckpt_dir`` journals every ACCEPTED iteration (params + history +
        the stop rule's baseline metric) through
        ``resilience.SqueezeJournal``: a preempted run re-invoked with the
        same ``ckpt_dir`` resumes at the last completed iteration and
        reproduces the uninterrupted run's history and final params exactly
        (asserted in tests/test_resilience.py)."""
        t0 = time.perf_counter()
        loss_fn = loss_fn or self._default_loss_fn()
        batch_fn = batch_fn or self._default_batch_fn(seq_len, batch_size,
                                                      seed)
        if eval_fn is None:
            eval_fn = lambda p: self.evaluate(
                p, loss_fn=loss_fn, batch_fn=batch_fn)
        journal, start_iter, init_hist, baseline = None, 0, None, None
        if ckpt_dir:
            from repro.resilience.journal import SqueezeJournal  # lazy
            journal = SqueezeJournal(ckpt_dir)
            resumed = journal.load(self.params)
            if resumed is not None:
                self.params, start_iter, init_hist, baseline = resumed
        rho0 = squeeze_mod.model_compression_ratio(self.params)

        def finetune_fn(p):
            return self._tune_params(p, steps=finetune_steps, lr=lr,
                                     mode=mode, loss_fn=loss_fn,
                                     batch_fn=batch_fn)

        self.params, history = squeeze_mod.run_dimension_squeezing(
            self.params, finetune_fn, eval_fn, delta=delta,
            max_iters=max_iters, step=step, min_bond=min_bond,
            verbose=verbose,
            weight_cache=self.engine.cache_weights if weight_cache else None,
            start_iter=start_iter, initial_history=init_hist,
            baseline_metric=baseline,
            on_iteration=journal.record if journal else None)
        self._bump()
        self.squeeze_history.extend(history)
        self._record("squeeze", t0, {
            "events": len(history), "delta": delta,
            "rho_before": rho0,
            "rho_after": squeeze_mod.model_compression_ratio(self.params)})
        return history

    def _tune_params(self, params, *, steps: int, lr: float, mode: str,
                     loss_fn: Callable, batch_fn: Callable,
                     batch_offset: int = 2000):
        """Short LFA re-tune on an explicit tree (the inner loop of
        Algorithm 2) — no stage record, no version bump (the enclosing
        ``squeeze`` owns both).  The jitted step is shared across squeeze
        iterations (bond truncation changes shapes, which jit re-traces on
        its own; the Python-level trace machinery is built once)."""
        mask, opt, step_fn = self._cached_train_step(mode, lr, 0.0, loss_fn,
                                                     params=params)
        state = TrainState(params, opt.init(params))
        for i in range(steps):
            state, _ = step_fn(state, _to_device(batch_fn(batch_offset + i)))
        return state.params

    # ---- serve ----

    def serve(self, batch_size: int, max_len: int, *,
              weight_cache: bool = True, mesh=None,
              rules: dict | None = None, paged: bool = False,
              page_size: int = 16) -> ServeHandle:
        """Serving handle for the CURRENT weights.  The one-time
        ``init_serve`` (KV cache + cached-W contraction) runs only when no
        valid handle exists for this (batch, max_len, weight_cache, mesh)
        shape: handles built before any ``finetune``/``squeeze`` were
        dropped at the version bump and are rebuilt, never reused; handles
        for other shapes at the current version stay cached.

        ``mesh=`` places the serving state on a ``jax.sharding.Mesh``
        (``launch.mesh.make_host_mesh`` / ``make_production_mesh``): cached
        dense Ws inherit their cores' TP layout as ``NamedSharding``s,
        factorized tables stay factorized with per-core placements, and the
        prefill/decode steps carry explicit in/out shardings.  ``rules``
        overrides the default ``parallel.sharding.make_rules(mesh)`` logical
        axis -> mesh axis mapping.  Example::

            from repro.launch.mesh import make_host_mesh
            handle = session.serve(8, 64, mesh=make_host_mesh(model=4))
        """
        t0 = time.perf_counter()
        if mesh is not None and self.axes is None:
            raise ValueError(
                "Session.serve(mesh=...) needs the logical-axis tree; this "
                "session was constructed without one (Session(cfg, params)) "
                "— build it via Session.init/from_dense, or pass axes to "
                "the constructor")
        rules_key = None if rules is None else tuple(sorted(rules.items()))
        key = (batch_size, max_len, weight_cache, mesh, rules_key,
               paged, page_size)
        h = self._serve.get(key)
        if h is not None:
            return h.reset()
        handle = ServeHandle(self.model, self.params, batch_size, max_len,
                             weight_cache=weight_cache,
                             version=self._version, mesh=mesh, rules=rules,
                             axes=self.axes if mesh is not None else None,
                             paged=paged, page_size=page_size)
        self._serve[key] = handle
        self._record("serve", t0, {"batch": batch_size, "max_len": max_len,
                                   "weight_cache": weight_cache,
                                   "mesh": None if mesh is None else
                                   dict(zip(mesh.axis_names,
                                            mesh.devices.shape)),
                                   "init_seconds": handle.init_seconds})
        return handle

    def serve_pool(self, slots: int, max_len: int, *,
                   weight_cache: bool = True, mesh=None,
                   rules: dict | None = None, paged: bool = False,
                   page_size: int = 16, pool_pages: int | None = None,
                   admission_retry_limit: int = 1000,
                   guard_logits: bool = True,
                   prefill_chunk: int | None = None,
                   bucket_prompts: bool = False, bucket_min: int = 8,
                   clock=None):
        """Multi-tenant batched decode over the CURRENT weights: a
        ``pipeline.scheduler.ServePool`` with ``slots`` decode rows.
        Independent requests are admitted into free slots (batch-1 prefill
        scattered into the pool KV cache), decode advances ALL live tenants
        in one jitted step, and finished slots are recycled without
        re-prefilling anyone.  Pool stats surface in ``Session.report()``.

        Like ``serve()``, the pool snapshots the weights at construction
        (``mesh=`` places them on a device mesh); build a new pool after
        any ``finetune``/``squeeze``.

        Degradation knobs (docs/resilience.md): ``pool_pages``
        oversubscribes the paged KV pool (admission then backpressures on
        page reservations instead of crashing), ``guard_logits`` quarantines
        a slot whose logits go NaN/inf, ``admission_retry_limit`` bounds the
        backpressure retries before a request fails.

        Continuous-admission knobs (docs/serving.md "Continuous batching"):
        ``bucket_prompts=True`` pads prompts to power-of-two length buckets
        (bounds admission jit retraces at ~log2(max_len));
        ``prefill_chunk=N`` streams the admission prefill N tokens at a
        time, interleaved with decode, so a long prompt never stalls live
        tenants.  Both are token-identical to the default whole-prompt
        admission.  ``clock=`` injects the time source the pool's
        deadlines/budgets read (``pipeline.clock``; a shared
        ``VirtualClock`` makes expiry tests deterministic).  Example::

            pool = session.serve_pool(slots=4, max_len=64)
            rids = [pool.submit(p, max_new_tokens=16) for p in prompts]
            outputs = pool.run()            # {rid: token ids}
        """
        from repro.pipeline.scheduler import ServePool  # lazy: keep import cheap
        if mesh is not None and self.axes is None:
            raise ValueError(
                "Session.serve_pool(mesh=...) needs the logical-axis tree; "
                "build the session via Session.init/from_dense")
        t0 = time.perf_counter()
        import weakref
        pool = ServePool(self.model, self.params, slots, max_len,
                         weight_cache=weight_cache, mesh=mesh, rules=rules,
                         axes=self.axes if mesh is not None else None,
                         version=self._version, paged=paged,
                         page_size=page_size, pool_pages=pool_pages,
                         admission_retry_limit=admission_retry_limit,
                         guard_logits=guard_logits,
                         prefill_chunk=prefill_chunk,
                         bucket_prompts=bucket_prompts,
                         bucket_min=bucket_min, clock=clock)
        self._pools = [r for r in self._pools if r() is not None]
        self._pools.append(weakref.ref(pool))
        self._record("serve", t0, {"pool": True, "slots": slots,
                                   "max_len": max_len,
                                   "init_seconds": pool.init_seconds})
        return pool

    def serve_fleet(self, replicas: int, slots: int, max_len: int, *,
                    session_dir: str | None = None, clock=None,
                    router: dict | None = None, **pool_kw):
        """A replicated serving fleet behind one ``PoolRouter``
        (docs/resilience.md "Fleet degradation"): ``replicas`` pools over
        the CURRENT weights, least-loaded routing, retry-on-another-replica
        with capped backoff, per-replica circuit breaking, and queue-depth
        load shedding — behind the same ``submit/step/run/stats`` surface
        a single pool exposes (``traffic.replay`` drives it unchanged).

        ``session_dir`` is the crash-recovery substrate: the session is
        saved there ONCE, and a tripped/killed replica is rebuilt by
        ``Session.restore(session_dir).serve_pool(...)`` — the restored
        weights are token-identical, so a rebuilt replica rejoins the
        fleet serving exactly what the others serve.  Without it, rebuilds
        re-snapshot this live session's weights instead.

        With more than one device (and no ``mesh`` in ``pool_kw``), replica
        ``i`` lives on device ``i % device_count``: its weight snapshot and
        KV cache are placed through ``serve_pool(mesh=...)`` with a
        one-device mesh, and its rebuilds land on the same device.

        ``router`` kwargs pass through to ``PoolRouter`` (``retry_limit``,
        ``breaker_failures``, ``breaker_cooldown_s``, ``shed_queue_depth``,
        ...); ``pool_kw`` to every ``serve_pool`` replica.  All replicas,
        the router, and any replay loop share ONE ``clock``.  Example::

            router = session.serve_fleet(replicas=3, slots=4, max_len=64,
                                         paged=True, pool_pages=32,
                                         session_dir="runs/fleet")
            outputs = router.run()
        """
        from repro.pipeline.clock import WallClock  # lazy
        from repro.pipeline.router import PoolRouter  # lazy
        if replicas < 1:
            raise ValueError(f"replicas={replicas} must be >= 1")
        clock = WallClock() if clock is None else clock
        devices = jax.devices()

        def replica_kw(idx: int) -> dict:
            if "mesh" in pool_kw or len(devices) == 1:
                return dict(pool_kw, clock=clock)
            dev = devices[idx % len(devices)]
            mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"))
            return dict(pool_kw, clock=clock, mesh=mesh)

        pools = [self.serve_pool(slots, max_len, **replica_kw(i))
                 for i in range(replicas)]
        if session_dir is not None:
            self.save(session_dir)

            def rebuild(idx: int):
                restored = Session.restore(session_dir)
                return restored.serve_pool(slots, max_len, **replica_kw(idx))
        else:
            def rebuild(idx: int):
                return self.serve_pool(slots, max_len, **replica_kw(idx))
        return PoolRouter(pools, rebuild_fn=rebuild, clock=clock,
                          **(router or {}))

    # ---- persistence ----

    def save(self, directory: str) -> str:
        """Persist the FULL session under ``directory`` — weights (atomic
        ``CheckpointManager`` step dirs), stage records, squeeze history,
        trainability mask, conversion report, weights version, and the
        autotuner's verdicts — behind one atomically-written manifest
        (``resilience.state``): a crash at any point leaves the directory
        at either the previous complete session or the new one.  Returns
        the directory.  Example::

            session.save("runs/compressed")
            ...                              # preemption / new process
            s = Session.restore("runs/compressed")
            s.serve(8, 64)                   # token-identical serving
        """
        from repro.resilience import state as rstate  # lazy
        return rstate.save_session(self, directory)

    @classmethod
    def restore(cls, directory: str) -> "Session":
        """Rebuild a session from ``save(directory)``: the model/axes come
        from the serialized config, weights from the manifest's checkpoint
        step (through the ``latest``-symlink crash-consistency contract),
        and the lifecycle state (stage, records, squeeze history, mask,
        weights version) from the manifest — so the restored session
        reports and serves exactly like the one that was saved."""
        from repro.resilience import state as rstate  # lazy
        return rstate.restore_session(directory, cls=cls)

    # ---- report ----

    def report(self) -> dict:
        """Lifecycle summary: where the session is, what each stage cost,
        and the paper's headline numbers (compression ratio rho, trainable-
        parameter reduction, conversion error)."""
        out: dict[str, Any] = {
            "arch": self.cfg.name,
            "task": self.task,
            "stage": self.stage,
            "weights_version": self._version,
            "compression_ratio":
                squeeze_mod.model_compression_ratio(self.params),
            "params_total": lightweight.count_params(self.params),
            "stages": [{"stage": r.stage,
                        "seconds": round(r.seconds, 4), **r.info}
                       for r in self._records],
        }
        if self.mask is not None:
            tr, tot = lightweight.count_trainable(self.params, self.mask)
            out["trainable"] = tr
            out["trainable_reduction"] = 1.0 - tr / max(tot, 1)
        if self.conversion_report:
            errs = list(self.conversion_report.values())
            out["conversion_max_rel_err"] = max(errs)
            out["conversion_mean_rel_err"] = float(np.mean(errs))
        if self.squeeze_history:
            out["squeeze_events"] = len(self.squeeze_history)
        pools = [ref() for ref in self._pools]
        if any(p is not None for p in pools):
            # multi-tenant serving: slot occupancy + aggregate tok/s for
            # every still-alive ServePool this session created (weakly
            # held; stale-version pools included — their stats carry the
            # version they serve)
            out["serve_pools"] = [p.stats() for p in pools if p is not None]
        from repro.kernels import autotune  # lazy: report stays cheap
        tuner = autotune.get_tuner()
        if tuner.timing_runs or tuner.stats()["keys_resolved"]:
            # measured kernel autotuning was consulted this process (real
            # TPU or REPRO_AUTOTUNE_MEASURE=1): surface where the verdicts
            # live and whether this run paid any tuning cost
            out["autotune"] = tuner.stats()
        # static-analysis summary over the LIVE trees (sharding placement at
        # the abstract mesh sweep + kernel budgets at the current core
        # shapes — squeeze-truncated bonds are re-checked for free).  Never
        # allowed to break a report.
        from repro.analysis import session_summary  # lazy
        try:
            out["analysis"] = session_summary(self.cfg, self.params,
                                              self.axes)
        except Exception as e:  # pragma: no cover - defensive
            out["analysis"] = {"error": f"{type(e).__name__}: {e}"}
        return out
