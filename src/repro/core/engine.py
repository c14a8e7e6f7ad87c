"""Unified MPO execution engine: phase-aware planning + serving weight cache.

DESIGN
------
An MPO-factorized matrix can be *executed* several ways, and the right way
depends on where in the model lifecycle the matmul happens:

  mode          what runs                                  when it wins
  ------------  -----------------------------------------  ----------------------
  factorized    sequential chain contraction               memory-bound / heavily
                (``mpo.apply_mpo``, Table 2 O(n m d^3))    truncated bonds
  reconstruct   contract cores -> dense W, MXU matmul      compute-bound shapes,
                (``mpo.matmul_reconstruct``; custom VJP    training (factorized
                keeps the backward in core-space)          VJP shards badly)
  kernel        fused on-chip rebuild + matmul Pallas      dense-favored shapes on
                kernel — W never round-trips HBM, and      real TPUs, ALL phases
                the custom VJP accumulates gradients       (interpret mode is
                directly in core space                     never fast)
                (``kernels.ops.mpo_linear``)
  cached        dense W contracted ONCE at serving init    decode: the rebuild is
                and reused for every decode step           amortized to zero

Historically this choice was re-derived ad-hoc inside every ``apply_linear``
call: the kernel path was unreachable from ``mode="auto"``, and the decode
loop re-contracted every layer's cores into W on every generated token.  The
engine centralizes the decision:

* ``ExecutionPlan`` — one immutable plan per (core shapes, token count,
  phase, interpret, dtype).  Plans are memoized process-wide (``_plan``
  lru_cache): planning is pure Python on static shapes and happens once per
  distinct call signature, not per call.
* **Phases** — ``train`` (fwd+bwd: ``matmul_reconstruct``'s core-space
  backward vs the factorized chain vs — now that it carries a custom VJP —
  the fused kernel), ``prefill`` (forward-only, many tokens: same
  candidates), ``decode`` (forward-only, one token per step: ``cached`` vs
  ``factorized`` by per-token FLOPs — the one-time rebuild is amortized
  across the whole generation, so only the steady-state cost matters).
* **Measured autotuning** — when the kernel would run compiled on real
  hardware (or ``REPRO_AUTOTUNE_MEASURE=1``), the train/prefill decision and
  the kernel tile height ``block_m`` come from ``kernels.autotune``: a small
  candidate grid is TIMED once per (shapes, tokens, phase, dtype) key and
  the verdict persists to ``<checkout>/.cache/repro/autotune.json``
  (``REPRO_AUTOTUNE_CACHE``), so later processes plan with zero timing runs.
  Interpret mode keeps the analytic FLOPs heuristic.
* **Serving weight cache** — ``MPOEngine.cache_weights(params)`` walks a
  params tree once at serving init (alongside KV-cache allocation) and
  replaces every factorized matrix whose decode plan is ``cached`` with its
  contracted dense ``{"w": W}``.  Matrices whose factorized per-token cost
  beats the dense matmul (e.g. heavily compressed embedding tables, where
  densifying would also resurrect the full [vocab, d] memory footprint)
  stay factorized.  The decode loop then performs ZERO per-step core
  contractions: the dense path short-circuits before any planning.
* **Cache invalidation** — plans are keyed by core *shapes*, so
  ``tt_round`` / dimension squeezing (which shrink bonds) automatically get
  fresh plans.  A densified ``cache_weights`` tree, however, is a snapshot:
  any mutation of the underlying cores (squeeze, further fine-tuning)
  invalidates it and ``cache_weights`` must be re-run from the new cores.
* ``freeze_central_grads`` and master-weight -> activation-dtype casting are
  handled here, in exactly one place, for forward, transpose (tied logits)
  and embedding lookup alike.

Callers (``core.layers`` wrappers, models, serving steps, benchmarks) never
touch ``mpo.apply_mpo`` / ``mpo.matmul_reconstruct`` / ``kernels.ops``
directly — the engine is the single entry point for executing a factorized
matrix.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import mpo
from repro.kernels import autotune
# single source of truth for the kernel tile default + alignment/eligibility
# rules lives with the kernel itself (kernels.mpo_linear) — re-exported here
# because planning call sites historically import them from the engine
from repro.kernels.mpo_linear import DEFAULT_BLOCK_M, kernel_eligible
from repro.kernels.tpu import interpret_mode

PHASES = ("train", "prefill", "decode")
MODES = ("factorized", "reconstruct", "kernel", "cached")


# --------------------------------------------------------------------------
# cost model (moved here from core.layers — DESIGN §3.1 napkin math, now
# computed once per plan instead of per call)
# --------------------------------------------------------------------------


def flops_factorized_per_token(shapes: Sequence[tuple]) -> int:
    """FLOPs/token of the sequential contraction in ``mpo.apply_mpo``."""
    ins = [s[1] for s in shapes]
    total, rest = 0, math.prod(ins)
    out_done = 1
    for (d0, ik, jk, d1) in shapes:
        rest //= ik
        total += 2 * out_done * d0 * ik * rest * jk * d1
        out_done *= jk
    return total


def flops_reconstruct(shapes: Sequence[tuple]) -> int:
    """One-time FLOPs to contract the cores into W."""
    total = 0
    acc_rows = shapes[0][1] * shapes[0][2]
    for (d0, ik, jk, d1) in shapes[1:]:
        total += 2 * acc_rows * d0 * ik * jk * d1
        acc_rows *= ik * jk
    return total


def flops_dense_per_token(shapes: Sequence[tuple]) -> int:
    """FLOPs/token of the dense ``x @ W`` matmul once W exists."""
    ins = math.prod(s[1] for s in shapes)
    outs = math.prod(s[2] for s in shapes)
    return 2 * ins * outs


# --------------------------------------------------------------------------
# planning
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Immutable decision record for one (matrix, workload) pairing.

    Inspect a decision (memoized; planning never runs twice per key)::

        plan = engine_for(cfg.mpo).plan(shapes, tokens=1, phase="decode")
        plan.mode        # "cached" | "factorized" | ...
        plan.reason      # human-readable why, e.g. the FLOPs comparison
    """

    mode: str                      # factorized | reconstruct | kernel | cached
    phase: str                     # train | prefill | decode
    shapes: tuple                  # core shapes ((d0, i, j, d1), ...)
    tokens: int                    # tokens per call this plan was sized for
    flops_factorized: int          # per-token chain cost
    flops_dense: int               # per-token dense matmul cost
    flops_rebuild: int             # one-time cores -> W cost
    block_m: int = DEFAULT_BLOCK_M  # kernel tile height (measured when tuned)
    interpret: bool = True         # kernel interpreter flag (False on TPU)
    dtype: str = "float32"         # activation dtype the plan was sized for
    tuned: bool = False            # block_m/mode came from a measurement
    reason: str = ""               # human-readable why (for tests/debug)


def _decide(cfg, shapes: tuple, tokens: int, phase: str, interpret: bool,
            dtype: str) -> tuple[str, int, bool, str]:
    """(mode, block_m, tuned, reason) — the full planning decision.

    ``train`` and ``prefill`` first consult the measured autotuner
    (``kernels.autotune``) when measurement is meaningful (compiled kernels
    on real hardware, or forced via ``REPRO_AUTOTUNE_MEASURE=1``); interpret
    mode falls back to the analytic FLOPs heuristic.  ``decode``'s
    cached-vs-factorized choice stays analytic on purpose: it is a memory
    *policy* (never resurrect a heavily compressed table as dense HBM), not
    a latency race.
    """
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r} (expected one of {PHASES})")
    if cfg.mode != "auto":
        return cfg.mode, DEFAULT_BLOCK_M, False, \
            f"forced by cfg.mode={cfg.mode!r}"
    fact_tok = flops_factorized_per_token(shapes)
    dense_tok = flops_dense_per_token(shapes)
    rebuild = flops_reconstruct(shapes)
    if phase == "decode":
        # the one-time rebuild happens at serving init (cache_weights) and is
        # amortized over the whole generation -> steady-state FLOPs decide
        if dense_tok < fact_tok:
            return "cached", DEFAULT_BLOCK_M, False, (
                f"dense {dense_tok} < factorized {fact_tok} "
                "FLOPs/token; rebuild amortized at cache init")
        return "factorized", DEFAULT_BLOCK_M, False, (
            f"factorized {fact_tok} <= dense {dense_tok} "
            "FLOPs/token; caching W would also cost I*J HBM")
    if autotune.should_measure(interpret):
        # a candidate that fails to compile or run is a defect of the main
        # path: it fails planning instead of degrading to the heuristic
        res = autotune.get_tuner().get(shapes, tokens, phase, dtype,
                                       interpret)
        return res.mode, res.block_m, True, (
            f"autotuned ({res.source}): {res.mode}@{res.block_m} "
            f"fastest of {len(res.timings)} candidates")
    cost_fact = tokens * fact_tok
    cost_recon = rebuild + tokens * dense_tok
    if cost_fact < cost_recon:
        return "factorized", DEFAULT_BLOCK_M, False, (
            f"chain {cost_fact} < rebuild+dense "
            f"{cost_recon} FLOPs at {tokens} tokens")
    # differentiable kernel: a candidate for fwd+bwd (train) and forward-only
    # (prefill) alike — the backward accumulates core-space gradients
    # on-chip, so no dense dW traffic disqualifies it.  train's dL/dx pass
    # runs the kernel over i/j-SWAPPED cores, so both orientations must
    # compile and fit.  The tile is the largest candidate up to the default
    # that the eligibility gate admits.
    tiles = [bm for bm in sorted(autotune.CANDIDATE_BLOCK_MS, reverse=True)
             if bm <= DEFAULT_BLOCK_M
             and kernel_eligible(shapes, bm, train=phase == "train")]
    if not interpret and tiles:
        what = "fwd+bwd" if phase == "train" else "forward-only"
        return "kernel", tiles[0], False, (
            f"dense-favored {what} phase on TPU with a tile the compiler "
            "accepts: fuse rebuild on-chip (analytic gate; no measurement "
            "available)")
    return "reconstruct", DEFAULT_BLOCK_M, False, (
        f"rebuild+dense {cost_recon} <= chain {cost_fact} "
        f"FLOPs at {tokens} tokens")


def choose_mode(cfg, shapes: Sequence[tuple], tokens: int, phase: str,
                *, interpret: bool = True,
                dtype: str = "float32") -> tuple[str, str]:
    """(mode, reason) for one matrix execution.  ``cfg`` is an
    ``layers.MPOConfig``; a non-"auto" ``cfg.mode`` always wins.

    Example::

        mode, why = choose_mode(MPOConfig(), [c.shape for c in cores],
                                tokens=4096, phase="prefill")
        # -> ("reconstruct", "rebuild+dense ... <= chain ... FLOPs ...")
    """
    shapes = tuple(tuple(s) for s in shapes)
    mode, _, _, reason = _decide(cfg, shapes, tokens, phase, interpret,
                                 jnp.dtype(dtype).name)
    return mode, reason


@functools.lru_cache(maxsize=None)
def _plan(cfg, shapes: tuple, tokens: int, phase: str, interpret: bool,
          dtype: str) -> ExecutionPlan:
    mode, block_m, tuned, reason = _decide(cfg, shapes, tokens, phase,
                                           interpret, dtype)
    return ExecutionPlan(
        mode=mode, phase=phase, shapes=shapes, tokens=tokens,
        flops_factorized=flops_factorized_per_token(shapes),
        flops_dense=flops_dense_per_token(shapes),
        flops_rebuild=flops_reconstruct(shapes),
        block_m=block_m, interpret=interpret, dtype=dtype, tuned=tuned,
        reason=reason)


def clear_plan_cache() -> None:
    """Drop every memoized ``ExecutionPlan`` (tests; also needed after
    ``autotune.reset_tuner`` so new measurements are actually consulted)."""
    _plan.cache_clear()


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------


@jax.jit
def _reconstruct_stacked(cores: tuple) -> jax.Array:
    """``mpo.reconstruct`` mapped over any leading stacked dims (scanned
    layers, MoE experts) — cores are 4-D per matrix plus k batch dims.
    One compiled program per matrix, one stacked matrix at a time inside it
    (``lax.map``): run op by op, every chain intermediate (GBs for a
    vocabulary head) would stay resident until Python dropped it, and in
    one program for the whole snapshot the transients of several matrices
    would coexist."""
    fn = lambda cs: mpo.reconstruct(list(cs))
    for _ in range(cores[0].ndim - 4):
        fn = functools.partial(jax.lax.map, fn)
    return fn(cores)


class MPOEngine:
    """Execution engine for every MPO-factorized matrix under one
    ``MPOConfig``.  Owns plan lookup, mode dispatch, the serving-time weight
    cache, and the single authoritative implementation of
    ``freeze_central_grads`` + master-weight dtype casting.

    Stateless apart from the config: plans are memoized process-wide, so
    engines are cheap and ``engine_for(cfg)`` returns a shared instance.

    Example::

        eng = engine_for(cfg.mpo)
        y = eng.linear(params["w_up"], x, phase="train")   # planned matmul
        logits = eng.logits(params["embed"], h)            # tied head
        dense = eng.cache_weights(params)                  # decode snapshot
    """

    def __init__(self, cfg, *, interpret: bool | None = None):
        self.cfg = cfg
        # None -> follow the backend at call time (kernels.tpu.interpret_mode)
        self._interpret = interpret

    @property
    def interpret(self) -> bool:
        if self._interpret is not None:
            return self._interpret
        return interpret_mode()

    # ---- planning ----

    def plan(self, shapes: Sequence[tuple], tokens: int, phase: str,
             dtype="float32") -> ExecutionPlan:
        """The (memoized) plan for one matrix at one workload point."""
        return _plan(self.cfg, tuple(tuple(s) for s in shapes), int(tokens),
                     phase, self.interpret, jnp.dtype(dtype).name)

    # ---- core preparation: the ONE place freeze + casting happen ----

    def _prepare_cores(self, params: dict, dtype) -> list[jax.Array]:
        from repro.core import layers  # lazy: layers imports engine lazily too
        cores = layers.cores_to_list(params["cores"])
        if dtype is not None:
            cores = [c.astype(dtype) for c in cores]
        if self.cfg.freeze_central_grads:
            mid = len(cores) // 2
            cores[mid] = jax.lax.stop_gradient(cores[mid])
        return cores

    # ---- execution entry points ----

    def linear(self, params: dict, x: jax.Array, *, transpose: bool = False,
               phase: str = "train") -> jax.Array:
        """``y = x @ W`` (or ``x @ W^T``) through the planned mode.

        Master weights stay f32; compute is cast to the activation dtype
        (bf16 on the MXU) at the point of use.  A dense ``{"w": ...}`` entry
        — either a never-factorized matrix or a serving-time cached W —
        short-circuits before planning: zero per-step contractions.
        """
        if "w" in params:
            w = params["w"].astype(x.dtype)
            return x @ (w.T if transpose else w)
        cores = self._prepare_cores(params, x.dtype)
        if transpose:
            cores = mpo.transpose_cores(cores)
        tokens = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
        shapes = [c.shape for c in cores]
        plan = self.plan(shapes, tokens, phase, x.dtype)
        if plan.mode == "cached" and self.cfg.mode == "auto":
            # "cached" assumes the rebuild was amortized at cache init, but
            # the caller passed raw (un-densified) cores — the rebuild would
            # run on EVERY call.  Re-decide as a forward-only one-shot
            # execution (the prefill rule prices the per-call rebuild in).
            plan = self.plan(shapes, tokens, "prefill", x.dtype)
        if plan.mode == "kernel":
            from repro.kernels import ops  # lazy: avoid import cycle
            return ops.mpo_linear(cores, x, block_m=plan.block_m,
                                  interpret=plan.interpret)
        if plan.mode == "factorized":
            return mpo.apply_mpo(cores, x)
        # "reconstruct" (or a forced non-auto "cached" over raw cores:
        # contract now, same math)
        return mpo.matmul_reconstruct(x, tuple(cores))

    def logits(self, params: dict, h: jax.Array, *,
               phase: str = "train") -> jax.Array:
        """Tied-embedding output head: ``h @ E^T``."""
        return self.linear(params, h, transpose=True, phase=phase)

    def embedding(self, params: dict, ids: jax.Array, *, dtype=None,
                  phase: str = "train") -> jax.Array:
        """Row lookup ``W[ids, :]`` — dense take or factorized one-hot chain.

        ``phase`` is accepted for interface uniformity: the lookup itself has
        a single factorized realization (it is a gather, not a matmul), so no
        plan is consulted; a cached dense table short-circuits to ``take``.
        """
        if "w" in params:
            w = params["w"] if dtype is None else params["w"].astype(dtype)
            return jnp.take(w, ids, axis=0)
        cores = self._prepare_cores(params, dtype)
        return mpo.embed_lookup(cores, ids)

    # ---- serving-time weight cache ----

    def cache_weights(self, params, *, dtype=None, axes=None):
        """One-time densification at serving init (next to the KV cache).

        Returns a new params tree where every factorized matrix whose decode
        plan is ``cached`` is replaced by its contracted dense ``{"w": W}``;
        everything else (factorized-favored matrices, norms, biases, already-
        dense weights) passes through untouched.  Handles scan-stacked layer
        and MoE-expert leading dims.  The result is a SNAPSHOT: re-run after
        any core mutation (``tt_round``, dimension squeezing, training).

        When ``axes`` (the logical-axis tree from ``split_annotations``) is
        given, returns ``(params, axes)`` instead: the densified W inherits
        the cores' TP layout — its (in, out) dims carry whatever logical
        names annotated the cores' i/j legs, and stacked leading dims keep
        their axes — so ``parallel.sharding.tree_shardings`` places the
        cached dense W exactly where the cores' shards lived.
        """
        def visit(node, ax):
            if isinstance(node, dict):
                if "cores" in node:
                    from repro.core import layers  # lazy
                    cores = layers.cores_to_list(node["cores"])
                    shapes = tuple(c.shape[-4:] for c in cores)
                    plan = self.plan(shapes, 1, "decode")
                    if plan.mode != "cached":
                        return node, ax
                    w = _reconstruct_stacked(tuple(cores))
                    if dtype is not None:
                        # a program of its own: fused into the rebuild, the
                        # cast made XLA plan a v5e head rebuild at 10.5 GB
                        # of temporaries instead of 3.5 GB
                        w = w.astype(dtype)
                    new_ax = ax
                    if ax is not None:
                        new_ax = {"w": _dense_axes_from_cores(
                            [ax["cores"][n] for n in
                             layers.core_names(len(cores))])}
                    return {"w": w}, new_ax
                out, out_ax = {}, {}
                for k, v in node.items():
                    out[k], out_ax[k] = visit(v, None if ax is None
                                              else ax[k])
                return out, (None if ax is None else out_ax)
            return node, ax
        new_params, new_axes = visit(params, axes)
        return new_params if axes is None else (new_params, new_axes)


def _dense_axes_from_cores(core_axes: Sequence[tuple]) -> tuple:
    """Logical axes of the contracted dense W, inherited from its cores.

    Each core's trailing four legs are (bond, i, j, bond); W's in/out dims
    take the first non-``None`` name found on any core's i/j leg (at most one
    core carries the TP annotation — see ``layers._core_axes``).  Leading
    stacked dims (scan layers, MoE experts) keep their names.  Bond-leg
    names (the central core's FSDP ``"bond"``) do not survive densification:
    the bond dim is contracted away.
    """
    lead = tuple(core_axes[0][:-4])
    in_axis = next((a[-3] for a in core_axes if a[-3] is not None), None)
    out_axis = next((a[-2] for a in core_axes if a[-2] is not None), None)
    return lead + (in_axis, out_axis)


@functools.lru_cache(maxsize=None)
def engine_for(cfg) -> MPOEngine:
    """Shared engine instance per (hashable, frozen) ``MPOConfig``.

    The canonical way to execute a factorized matrix::

        eng = engine_for(model_cfg.mpo)
        y = eng.linear(params["wq"], x, phase="prefill")
        serve_tree = eng.cache_weights(params)     # serving-time snapshot
    """
    return MPOEngine(cfg)
