"""Fwd+bwd step time of the MPO-linear execution paths.

One train-shaped step (``jax.grad`` of a scalar loss w.r.t. cores AND
activations) per candidate:

  * ``kernel``      — fused Pallas kernel + its custom VJP (core-space
                      gradient accumulation, no dense dW);
  * ``reconstruct`` — ``mpo.matmul_reconstruct`` (dense fwd, core-space
                      projected bwd — the previous train fast path);
  * ``factorized``  — the paper-faithful sequential chain, VJP'd by JAX.

Three config sizes: the bert_base / qwen3_14b smoke FFN shapes the tests
train at, plus the full-scale bert-base FFN (768 x 3072).  Off the TPU
the kernel runs in interpret mode (``kernels.tpu.interpret_mode``) — its
absolute numbers are then correctness-path timings, not TPU performance,
and the reconstruct/factorized columns are XLA-CPU timings.  Results land
in ``BENCH_kernel.json`` next to ``BENCH_engine.json``; run on a TPU to
refresh with compiled-kernel numbers.

Run:  PYTHONPATH=src python -m benchmarks.kernel_vjp
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

TOKENS = 128
REPS = 3

_JSON_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_kernel.json")


def _configs():
    from repro import configs
    from repro.core import layers as L

    out = []
    for label, cfg in (("bert_base_smoke", configs.smoke_config("bert-base")),
                       ("qwen3_14b_smoke", configs.smoke_config("qwen3-14b")),
                       ("bert_base_full", configs.get_config("bert-base"))):
        spec = L.make_spec(cfg.mpo, cfg.d_model, cfg.d_ff, "ffn",
                           False, False)
        out.append((label, tuple(spec.core_shapes())))
    return out


def _bench(fn, *args) -> float:
    jax.block_until_ready(fn(*args))  # compile outside the timed region
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run() -> list[str]:
    from repro.core import mpo
    from repro.kernels.mpo_linear import (DEFAULT_BLOCK_M, kernel_eligible,
                                          mpo_linear)
    from repro.kernels.tpu import interpret_mode
    interpret = interpret_mode()

    rows, results = [], []
    for label, shapes in _configs():
        keys = jax.random.split(jax.random.PRNGKey(0), len(shapes) + 1)
        cores = tuple(jax.random.normal(k, s)
                      for k, s in zip(keys, shapes))
        i_dim = 1
        for s in shapes:
            i_dim *= s[1]
        x = jax.random.normal(keys[-1], (TOKENS, i_dim))

        # interpreted, the kernel is timed even on gate-failing tiles (the
        # row documents what the gate saves the planner from); compiled, a
        # tile the gate refuses does not lower at all
        eligible = kernel_eligible(shapes, DEFAULT_BLOCK_M)
        paths = {
            "factorized": lambda cs, xs: mpo.apply_mpo(list(cs), xs),
            "reconstruct": lambda cs, xs: mpo.matmul_reconstruct(xs, cs),
        }
        if interpret or eligible:
            paths["kernel"] = lambda cs, xs: mpo_linear(
                cs, xs, block_m=DEFAULT_BLOCK_M, interpret=interpret)

        entry = {"config": label, "shapes": [list(s) for s in shapes],
                 "tokens": TOKENS, "interpret": interpret,
                 "kernel_eligible": eligible, "fwd_bwd_s": {}}
        for name, fn in paths.items():
            step = jax.jit(jax.grad(
                lambda cs, xs, fn=fn: jnp.sum(jnp.abs(fn(cs, xs))),
                argnums=(0, 1)))
            t = _bench(step, cores, x)
            entry["fwd_bwd_s"][name] = round(t, 6)
            rows.append(f"kernel_vjp,{label},{name},fwd_bwd_s={t:.6f}")
        results.append(entry)

    payload = {"tokens": TOKENS, "reps": REPS, "interpret": interpret,
               "device": jax.devices()[0].device_kind,
               "note": ("fwd+bwd step time" + (
                   "; kernel interpreted off the TPU — correctness path, "
                   "not TPU perf" if interpret else "")),
               "results": results}
    with open(_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
