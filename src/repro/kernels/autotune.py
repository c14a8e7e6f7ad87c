"""Measured autotuning for MPO-linear execution.

The engine's historical ``kernel`` gate was *analytic*: a hardcoded
``block_m = 256`` plus an alignment rule, never validated against the
hardware (ROADMAP open item since PR 1).  This module replaces the guess
with a measurement: per ``(core shapes, token count, phase, dtype)`` key it
times a small candidate grid — the fused Pallas kernel at several tile
heights, ``matmul_reconstruct``, and the factorized chain — on synthetic
operands of the real shapes, and records which candidate (and which
``block_m``) actually wins.  ``train``-phase candidates are timed as
fwd+bwd (``jax.grad`` through each path — the kernel is differentiable as
of this PR), forward-only phases as plain forwards.

Results persist to an on-disk JSON cache so subsequent processes (CI, the
next serving session) pay ZERO tuning cost:

* location: ``<checkout>/.cache/repro/autotune.json`` (``repro.runtime``),
  overridable via the ``REPRO_AUTOTUNE_CACHE`` env var;
* corrupted / stale / wrong-version files are IGNORED (re-tuned and
  rewritten), never crashed on;
* delete the file (or point ``REPRO_AUTOTUNE_CACHE`` elsewhere) to force a
  re-tune.

Measurement is only meaningful on real hardware: by default it runs when
the kernel would run compiled (``interpret=False`` on a TPU backend) and
falls back to the analytic heuristic in interpret mode.  The
``REPRO_AUTOTUNE_MEASURE`` env var forces it on (``1``, used by tests and
CPU bring-up) or off (``0``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.mpo_linear import (BLOCK_M_ALIGN, DEFAULT_BLOCK_M,
                                      kernel_eligible, mpo_linear)

ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
ENV_MEASURE = "REPRO_AUTOTUNE_MEASURE"

# v2: keys gained a jax=<version> field — pre-upgrade verdicts are dropped
# wholesale instead of silently answering post-upgrade lookups.
CACHE_VERSION = 2
# the "small candidate grid" of tile heights; candidates collapse to one
# entry when the token count caps the effective tile anyway.  1024/2048
# exist for long-prefill shapes (4k+ token calls) where a 512 tile leaves
# the MXU underfed — they dedupe away at short token counts.
CANDIDATE_BLOCK_MS = (64, 128, 256, 512, 1024, 2048)
BENCH_WARMUP = 1   # compile + cache warm, excluded from timing
BENCH_REPS = 3     # best-of

# "flash"/"xla" are the decode-attention race (kernels.decode_attention);
# they share this cache and key scheme but bring their own candidates_fn
_TUNABLE_MODES = ("factorized", "reconstruct", "kernel", "flash", "xla")


def cache_path() -> str:
    env = os.environ.get(ENV_CACHE)
    if env:
        return env
    from repro.runtime import CACHE_ROOT  # lazy: kernels import standalone
    return str(CACHE_ROOT / "repro" / "autotune.json")


def should_measure(interpret: bool) -> bool:
    """Measure (vs analytic fallback)?  Default: compiled kernels on a real
    TPU only; ``REPRO_AUTOTUNE_MEASURE=1/0`` forces either way."""
    env = os.environ.get(ENV_MEASURE)
    if env == "0":
        return False
    if env == "1":
        return True
    return (not interpret) and jax.default_backend() == "tpu"


def make_key(shapes: Sequence[tuple], tokens: int, phase: str, dtype: str,
             interpret: bool = True) -> str:
    """Cache key.  Includes the measurement substrate (backend + interpret
    flag + JAX version): a CPU-interpret bring-up verdict must never be
    served to a real TPU session, and a verdict measured under an older JAX
    must never silently answer lookups after an upgrade — compiler changes
    reshuffle the rankings."""
    s = ";".join("x".join(str(d) for d in sh) for sh in shapes)
    return (f"backend={jax.default_backend()}|jax={jax.__version__}"
            f"|interpret={int(interpret)}"
            f"|shapes={s}|tokens={int(tokens)}|phase={phase}|dtype={dtype}")


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """One tuning verdict: the winning execution mode and kernel tile."""

    mode: str                 # factorized | reconstruct | kernel
    block_m: int              # measured tile height (kernel) or default
    source: str               # "measured" | "disk"
    timings: tuple = ()       # ((candidate label, seconds), ...) sorted


def _block_m_candidates(tokens: int) -> list[int]:
    """Tile heights worth timing: dedupe by *effective* tile (a 32-token
    call shrinks every candidate to 32 rows — time it once)."""
    cap = BLOCK_M_ALIGN * ((tokens + BLOCK_M_ALIGN - 1) // BLOCK_M_ALIGN)
    out, seen = [], set()
    for bm in CANDIDATE_BLOCK_MS:
        eff = min(bm, cap)
        if eff not in seen:
            seen.add(eff)
            out.append(bm)
    return out


def chain_in_race(shapes, tokens: int) -> bool:
    """Is the factorized chain a candidate?  Only where its FLOPs at this
    token count do not exceed rebuild + dense matmul.  Timed alone, XLA may
    reassociate the chain of small matmuls into a rebuild of W (a v5e race
    at qwen3-14b widths timed both paths equal with the chain at 3-60x the
    dense FLOPs), while inside a layer the chain runs as written and its
    intermediates, many times W's size, outgrow HBM."""
    from repro.core import engine  # lazy: engine imports this module
    return (tokens * engine.flops_factorized_per_token(shapes)
            <= engine.flops_reconstruct(shapes)
            + tokens * engine.flops_dense_per_token(shapes))


def _candidates(shapes, tokens, phase, dtype, interpret):
    """[(label, jitted zero-arg fn)] — real implementations over synthetic
    operands of the tuned shapes.  train times fwd+bwd, others fwd-only."""
    from repro.core import mpo  # lazy: keep kernels importable standalone

    jdt = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes) + 1)
    cores = tuple(jax.random.normal(k, s).astype(jdt)
                  for k, s in zip(keys, shapes))
    i_dim = math.prod(s[1] for s in shapes)
    x = jax.random.normal(keys[-1], (int(tokens), i_dim)).astype(jdt)

    fwd = {"reconstruct": lambda cs, xs: mpo.matmul_reconstruct(xs, cs)}
    if chain_in_race(shapes, tokens):
        fwd["factorized"] = lambda cs, xs: mpo.apply_mpo(list(cs), xs)
    for bm in _block_m_candidates(tokens):
        if kernel_eligible(shapes, bm, train=phase == "train"):
            fwd[f"kernel@{bm}"] = (
                lambda cs, xs, bm=bm: mpo_linear(cs, xs, block_m=bm,
                                                 interpret=interpret))
    out = []
    for label, fn in fwd.items():
        if phase == "train":
            step = jax.jit(jax.grad(
                lambda cs, xs, fn=fn: jnp.sum(jnp.abs(fn(cs, xs))),
                argnums=(0, 1)))
        else:
            step = jax.jit(fn)
        out.append((label, lambda step=step: step(cores, x)))
    return out


def _parse_label(label: str) -> tuple[str, int]:
    if label.startswith("kernel@"):
        return "kernel", int(label.split("@", 1)[1])
    return label, DEFAULT_BLOCK_M


def _read_cache(path: str) -> dict:
    """Entries from disk; anything unreadable/stale is silently dropped
    (the caller re-tunes and rewrites)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
        return {}
    entries = raw.get("entries")
    if not isinstance(entries, dict):
        return {}
    out = {}
    for key, ent in entries.items():
        if (isinstance(ent, dict)
                and ent.get("mode") in _TUNABLE_MODES
                and isinstance(ent.get("block_m"), int)
                and ent["block_m"] > 0
                and ent["block_m"] % BLOCK_M_ALIGN == 0):
            out[key] = ent
    return out


def _write_cache(path: str, entries: dict) -> None:
    """Atomic best-effort persist — an unwritable cache dir must never fail
    planning."""
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                      indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except OSError:
        pass


class Autotuner:
    """Memory -> disk -> measure lookup chain for tuning verdicts.

    ``timing_runs`` counts timed candidate executions — tests assert it
    stays 0 when a warm disk cache answers every lookup.
    """

    def __init__(self, path: str | None = None):
        self._path = path
        self._mem: dict[str, TuneResult] = {}
        self._disk: dict | None = None
        self.timing_runs = 0

    @property
    def path(self) -> str:
        return self._path or cache_path()

    def _entries(self) -> dict:
        if self._disk is None:
            self._disk = _read_cache(self.path)
        return self._disk

    def get(self, shapes: Sequence[tuple], tokens: int, phase: str,
            dtype: str, interpret: bool,
            candidates_fn=None) -> TuneResult:
        """``candidates_fn`` defaults to the MPO-linear grid; other kernels
        (decode attention) pass their own ``(shapes, tokens, phase, dtype,
        interpret) -> [(label, thunk)]`` builder and share the cache."""
        shapes = tuple(tuple(s) for s in shapes)
        key = make_key(shapes, tokens, phase, dtype, interpret)
        hit = self._mem.get(key)
        if hit is not None:
            return hit
        ent = self._entries().get(key)
        if ent is not None:
            result = TuneResult(mode=ent["mode"], block_m=ent["block_m"],
                                source="disk",
                                timings=tuple(sorted(
                                    (ent.get("timings") or {}).items(),
                                    key=lambda kv: kv[1])))
            self._mem[key] = result
            return result
        result = self.measure(shapes, tokens, phase, dtype, interpret,
                              candidates_fn)
        self._mem[key] = result
        # re-read before persisting: another process may have tuned other
        # keys since our first load — dumping the stale snapshot would
        # silently erase their verdicts (and re-impose their tuning cost)
        entries = _read_cache(self.path)
        entries[key] = {"mode": result.mode, "block_m": result.block_m,
                        "timings": dict(result.timings)}
        self._disk = entries
        _write_cache(self.path, entries)
        return result

    def measure(self, shapes, tokens, phase, dtype, interpret,
                candidates_fn=None) -> TuneResult:
        """Time every candidate; a candidate that fails is a defect of the
        main path and fails the race (no partial verdict)."""
        candidates_fn = candidates_fn or _candidates
        timings = [(label, self._time(fn)) for label, fn in
                   candidates_fn(shapes, tokens, phase, dtype, interpret)]
        timings.sort(key=lambda kv: kv[1])
        mode, block_m = _parse_label(timings[0][0])
        return TuneResult(mode=mode, block_m=block_m, source="measured",
                          timings=tuple(timings))

    def stats(self) -> dict:
        """Small observability surface (``Session.report`` embeds this):
        where the cache lives, how many keys this process resolved, and how
        many timed candidate runs it paid for (0 == fully warm)."""
        return {"path": self.path, "keys_resolved": len(self._mem),
                "timing_runs": self.timing_runs}

    def _time(self, fn) -> float:
        self.timing_runs += 1
        for _ in range(BENCH_WARMUP):
            jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(BENCH_REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best


# ---- fleet warm-start: shippable verdict artifacts ----


def export_cache(dest: str) -> dict:
    """Pack the on-disk verdict cache into a shippable artifact at ``dest``
    (same schema as the cache file, so the artifact is itself a valid
    cache).  A fleet of serving processes imports it once and never
    cold-tunes.  Returns ``{"exported": n, "path": dest}``."""
    entries = _read_cache(cache_path())
    if _tuner is not None:
        # verdicts measured by THIS process are already persisted by
        # get(), but a tuner pointed at a custom path may hold more
        entries.update(_read_cache(_tuner.path))
    os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
    tmp = f"{dest}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                  indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, dest)
    return {"exported": len(entries), "path": dest}


def import_cache(src: str, *, overwrite: bool = False) -> dict:
    """Merge an exported artifact into the local verdict cache.  Local
    verdicts win on conflict unless ``overwrite=True`` (a locally-measured
    verdict is at least as fresh as a shipped one).  Invalid/stale
    artifacts import zero entries instead of failing — warm-start is an
    optimization, never a crash.  Returns merge counts."""
    incoming = _read_cache(src)
    path = _tuner.path if _tuner is not None else cache_path()
    local = _read_cache(path)
    added = 0
    for key, ent in incoming.items():
        if overwrite or key not in local:
            local[key] = ent
            added += 1
    _write_cache(path, local)
    if _tuner is not None:
        _tuner._disk = None  # next lookup re-reads the merged cache
    return {"imported": added, "skipped": len(incoming) - added,
            "total": len(local), "path": path}


_tuner: Autotuner | None = None


def get_tuner() -> Autotuner:
    """The process-wide tuner.  Mostly consulted indirectly (the engine's
    ``_decide``), directly useful for observability::

        from repro import autotune
        autotune.get_tuner().stats()   # {"path": ..., "timing_runs": 0, ...}
    """
    global _tuner
    if _tuner is None:
        _tuner = Autotuner()
    return _tuner


def reset_tuner(path: str | None = None) -> Autotuner:
    """Fresh tuner (tests; also drops the in-memory layer so the disk cache
    is consulted again).  The engine's plan memo caches *planning* results
    on top of this — clear it too (``core.engine.clear_plan_cache``)."""
    global _tuner
    _tuner = Autotuner(path)
    return _tuner
