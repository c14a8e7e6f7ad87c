"""Drive the main path once on a TPU at qwen3-14b's published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip paths only

One chip, one process: the LFA fine-tune (``Session.finetune``), the paged
``ServePool`` serving mixed-length requests, and prefill + decode through
the KV cache against the model's own float32 forward.  With ``--chips 4``,
in float32 at two layers: the mesh-sharded pool against the same requests
on one chip, and a four-replica ``serve_fleet`` with each replica on its
own chip.

Every width is qwen3-14b's as published (``configs/qwen3_14b.py``); only the
depth is cut, and the cut is printed as ``reduced``.  Weights are random,
made from ``--seed``.  Any failed check exits non-zero before the last
line, which is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits non-zero at once and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ARCH = "qwen3-14b"
PUBLISHED_LAYERS = 40
LAYERS = 4                   # depth cut: every width stays as published
MAX_LEN = 256
SLOTS = 8
NEW_TOKENS = 16
# mixed prompt lengths over four power-of-two admission buckets
PROMPT_LENS = (12, 16, 30, 31, 60, 64, 100, 120)
REF_PROMPT_LEN = 24
REF_DECODE_STEPS = 4
# Served logits vs the float32 reference, relative L2 error per position.
# The served path computes in bfloat16, the configured dtype: activations,
# weights and the KV cache each round to 8 mantissa bits (unit roundoff
# 2^-9 ~ 2e-3) at every one of the few dozen roundings a position passes
# through.  Two bfloat16 layers at smoke widths on a CPU land at 1.3e-2
# to 2.5e-2; the limit leaves about 2x room over that.  An 8-bit float
# (3 mantissa bits, roundoff 6e-2) would miss it many times over.
REF_REL_L2 = 5e-2
# The four-chip checks compare the sharded pool with one chip, both in
# float32 at highest matmul precision, so that the two runs differ only in
# the order of partial sums: unit roundoff 6e-8 over reductions of at most
# 2e4 terms bounds that at about 1e-3 relative in the worst case, and far
# less in the mean.  In bfloat16 the same reordering moves logits by 1e-2,
# enough to flip greedy picks between near-tied random-weight logits.
MESH_REL_L2 = 1e-3
# float32 doubles the dense snapshot (1.1 GB a layer plus 6.2 GB of
# embedding and head): the one-chip side of that comparison holds it and
# its rebuild at two layers, not at four
MESH_LAYERS = 2


class Failure(Exception):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


class CompileMeter:
    """Seconds JAX spent compiling (or fetching from the persistent cache)
    and persistent-cache hits, read from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.hits = 0.0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.seconds, self.hits

    def since(self, mark: tuple) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 3),
                "cache_hits": self.hits - mark[1]}


def prompts(vocab: int, seed: int) -> list:
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def release() -> None:
    """Free what a phase built: its arrays, and the compiled programs JAX
    keeps loaded in device memory.  Those programs sit between the freed
    buffers: after one float32 pool (a 9 GB snapshot on a 16.9 GB v5e) was
    dropped, 0.1 GB was in use but the largest free block was 8.9 GB, and
    the next snapshot failed to place a 2.9 GB matrix.  Dropping the
    programs (they are recompiled, or read back from the persistent cache,
    when next needed) gives each phase the whole chip in one piece."""
    import jax
    gc.collect()
    jax.clear_caches()


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def phase_finetune(session, meter) -> None:
    """Three LFA steps; finite losses; each distinct MPO matrix's plan."""
    import numpy as np
    from repro.core import layers as L
    from repro.kernels.tpu import interpret_mode
    batch, seq = 16, 32
    m0, t0 = meter.mark(), time.perf_counter()
    out = session.finetune(mode="lfa", steps=3, seq_len=seq,
                           batch_size=batch, log_every=1, verbose=True)
    losses = [h["loss"] for h in out["history"]]
    log("finetune", steps=len(losses), losses=losses,
        trainable=out["trainable"], total=out["total"],
        trainable_share=out["trainable"] / out["total"],
        seconds=round(time.perf_counter() - t0, 3), **meter.since(m0))
    check(len(losses) == 3 and all(np.isfinite(losses)),
          f"LFA losses not 3 finite values: {losses}")

    seen = {}

    def visit(node, path):
        if isinstance(node, dict):
            if "cores" in node:
                cores = L.cores_to_list(node["cores"])
                shapes = tuple(tuple(c.shape[-4:]) for c in cores)
                seen.setdefault(shapes, []).append(path)
                return
            for k, v in node.items():
                visit(v, f"{path}/{k}")

    visit(session.params, "")
    for shapes, paths in seen.items():
        if paths == ["/embed"]:
            continue                     # a row gather: no matmul to plan
        plan = session.engine.plan(shapes, batch * seq, "train",
                                   session.cfg.jnp_dtype)
        log("train-plan", matrices=paths, mode=plan.mode,
            block_m=plan.block_m, tuned=plan.tuned,
            interpret=plan.interpret)
        check(plan.tuned, f"train plan for {paths} was not measured")
        check(plan.interpret == interpret_mode(),
              f"train plan for {paths} has interpret={plan.interpret}")


def decode_attn_race(cfg, dtype: str):
    """The decode-attention race verdict the serving path already made."""
    import jax.numpy as jnp
    from repro.kernels import autotune
    from repro.kernels import decode_attention as DA
    from repro.kernels.tpu import interpret_mode
    page_size = 16
    mp = MAX_LEN // page_size
    tuner = autotune.get_tuner()
    runs = tuner.timing_runs
    res = tuner.get(((cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads,
                      cfg.head_dim), (page_size, mp)),
                    DA._context_bucket(mp * page_size), "decode_attn",
                    str(jnp.dtype(dtype)), interpret_mode(),
                    candidates_fn=DA._race_candidates)
    check(tuner.timing_runs == runs,
          "the decode-attention race had not run during serving")
    return res


def phase_serve(session, meter, seed: int) -> dict:
    """Eight mixed-length requests through the paged pool, to completion."""
    m0, t0 = meter.mark(), time.perf_counter()
    pool = session.serve_pool(slots=SLOTS, max_len=MAX_LEN, paged=True,
                              bucket_prompts=True)
    build = time.perf_counter() - t0
    rids = [pool.submit(p, max_new_tokens=NEW_TOKENS)
            for p in prompts(session.cfg.vocab_size, seed)]
    outputs = pool.run()
    stats = pool.stats()
    reqs = [pool.request(r) for r in rids]
    log("serve", requests=len(reqs),
        done=sum(r.status == "done" for r in reqs),
        flash_fallbacks=stats["flash_fallbacks"],
        failures=stats["failures"], build_s=round(build, 3),
        seconds=round(time.perf_counter() - t0, 3),
        decode_steps=stats["decode_steps"],
        phases=stats["phases"],
        prefill_traces=stats.get("prefill_traces"), **meter.since(m0))
    check(all(r.status == "done" and len(r.tokens) == NEW_TOKENS
              for r in reqs), f"not every request done: "
          f"{[(r.rid, r.status, r.error) for r in reqs]}")
    check(stats["flash_fallbacks"] == 0,
          f"{stats['flash_fallbacks']} flash -> XLA fallbacks")
    race = decode_attn_race(session.cfg, session.cfg.dtype)
    labels = {label for label, _ in race.timings}
    log("decode-attn-race", mode=race.mode, source=race.source,
        timings=dict(race.timings))
    check({"flash", "xla"} <= labels,
          f"decode-attention race timed only {sorted(labels)}")
    out = {rid: outputs[rid] for rid in rids}
    del pool
    release()
    return out


def served_logits(session, prompt, *, mesh=None, feed=None):
    """Prefill + decode through the paged KV cache: the logits of the last
    prompt position and of each decode step, and the tokens fed — greedy,
    or ``feed`` when given (teacher forcing, for comparing two runs on the
    same inputs)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.pipeline.session import ServeHandle
    # a handle of its own (not Session.serve's cached one), so that its
    # weight snapshot is freed when this returns
    handle = ServeHandle(session.model, session.params, 1, MAX_LEN,
                         paged=True, mesh=mesh,
                         axes=None if mesh is None else session.axes)
    logits = [handle.prefill({"tokens": prompt[None]})[0, -1]]
    fed = []
    for k in range(REF_DECODE_STEPS):
        fed.append(int(jnp.argmax(logits[-1])) if feed is None else feed[k])
        _, step_logits = handle.decode(jnp.full((1, 1), fed[-1], jnp.int32))
        logits.append(step_logits[0, -1])
    out = np.stack([np.asarray(l, np.float32) for l in logits]), fed
    del handle
    release()
    return out


def reference_logits(session, tokens):
    """The model's own uncached forward in float32 at highest matmul
    precision, every matrix rebuilt from its cores inside the forward
    (one layer's W at a time, so the reference fits beside nothing)."""
    import dataclasses

    import jax
    import numpy as np
    from repro.models import model as M
    cfg = session.cfg
    ref_cfg = dataclasses.replace(
        cfg, dtype="float32", remat=False,
        mpo=dataclasses.replace(cfg.mpo, mode="reconstruct"))
    ref = M.build(ref_cfg)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(ref.forward)(session.params,
                                         {"tokens": tokens[None]})
    return np.asarray(logits[0], np.float32)


def phase_reference(session, meter, seed: int) -> None:
    import numpy as np
    m0, t0 = meter.mark(), time.perf_counter()
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, session.cfg.vocab_size,
                          REF_PROMPT_LEN).astype(np.int32)
    served, fed = served_logits(session, prompt)
    full = np.concatenate([prompt, np.asarray(fed, np.int32)])
    ref = reference_logits(session, full)[REF_PROMPT_LEN - 1:]
    errs = [rel_l2(s, r) for s, r in zip(served, ref)]
    top1 = [int(np.argmax(s) == np.argmax(r)) for s, r in zip(served, ref)]
    log("reference", positions=len(errs), rel_l2=errs, limit=REF_REL_L2,
        top1_agree=top1, seconds=round(time.perf_counter() - t0, 3),
        **meter.since(m0))
    check(len(errs) == REF_DECODE_STEPS + 1 and max(errs) <= REF_REL_L2,
          f"served logits off the f32 reference: {errs}")


def one_chip(session, meter, seed: int) -> None:
    phase_finetune(session, meter)
    phase_serve(session, meter, seed)
    phase_reference(session, meter, seed)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def pool_outputs(session, seed: int, **kw) -> dict:
    pool = session.serve_pool(slots=SLOTS, max_len=MAX_LEN, paged=True,
                              bucket_prompts=True, **kw)
    rids = [pool.submit(p, max_new_tokens=NEW_TOKENS)
            for p in prompts(session.cfg.vocab_size, seed)]
    outputs = pool.run()
    check(all(pool.request(r).status == "done" for r in rids),
          "not every pool request done")
    out = [outputs[r].tolist() for r in rids]
    del pool
    release()
    return out


def phase_mesh(session, meter, seed: int) -> None:
    """The model-sharded pool on four chips vs the same requests on one:
    identical tokens, and teacher-forced logits within MESH_REL_L2."""
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    m0, t0 = meter.mark(), time.perf_counter()
    mesh = make_host_mesh(model=4)
    one = pool_outputs(session, seed)
    four = pool_outputs(session, seed, mesh=mesh)
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, session.cfg.vocab_size,
                          REF_PROMPT_LEN).astype(np.int32)
    l1, fed = served_logits(session, prompt)
    l4, _ = served_logits(session, prompt, mesh=mesh, feed=fed)
    errs = [rel_l2(a, b) for a, b in zip(l4, l1)]
    same = sum(a == b for a, b in zip(one, four))
    log("mesh", mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
        requests_token_identical=same, requests=len(one),
        rel_l2=errs, limit=MESH_REL_L2,
        seconds=round(time.perf_counter() - t0, 3), **meter.since(m0))
    check(max(errs) <= MESH_REL_L2, f"sharded logits off one chip: {errs}")
    check(same == len(one), f"only {same}/{len(one)} requests "
          "token-identical between the sharded pool and one chip")


def phase_fleet(session, meter, seed: int) -> None:
    """``serve_fleet(replicas=4)``: one replica per chip, all served."""
    import jax
    m0, t0 = meter.mark(), time.perf_counter()
    router = session.serve_fleet(4, SLOTS, MAX_LEN, paged=True,
                                 bucket_prompts=True)
    placed = []
    for rep in router._replicas:            # placement is the check here
        leaves = jax.tree.leaves(rep.pool._sparams)
        leaves += jax.tree.leaves(rep.pool._cache)
        placed.append(sorted({d.id for a in leaves for d in a.devices()}))
    rids = [router.submit(p, max_new_tokens=NEW_TOKENS)
            for p in prompts(session.cfg.vocab_size, seed)]
    router.run()
    reqs = [router.request(r) for r in rids]
    served = [r["pool"]["completed"] for r in router.stats()["replicas"]]
    log("fleet", devices_per_replica=placed,
        done=sum(r.done for r in reqs), requests=len(reqs),
        completed_per_replica=served,
        seconds=round(time.perf_counter() - t0, 3), **meter.since(m0))
    check(placed == [[d.id] for d in jax.devices()[:4]],
          f"replicas not one per device: {placed}")
    check(all(r.done for r in reqs), "not every fleet request done")


def four_chips(session, meter, seed: int) -> None:
    import jax
    with jax.default_matmul_precision("highest"):
        phase_mesh(session, meter, seed)
        phase_fleet(session, meter, seed)


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run on another backend", file=sys.stderr)
        return 2
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices))
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.pipeline.session import Session
    from repro.runtime import enable_compile_cache

    meter = CompileMeter()
    log("compile-cache", dir=enable_compile_cache(),
        from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)
    m0, t0 = meter.mark(), time.perf_counter()
    model_kw = (dict(num_layers=MESH_LAYERS, dtype="float32")
                if args.chips == 4 else dict(num_layers=LAYERS))
    session = Session.init(ARCH, smoke=False, seed=args.seed, **model_kw)
    cfg = session.cfg
    log("model", arch=ARCH, d_model=cfg.d_model, heads=cfg.num_heads,
        kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        vocab=cfg.vocab_size, dtype=cfg.dtype,
        reduced={"num_layers": {"published": PUBLISHED_LAYERS,
                                "run": cfg.num_layers}},
        seconds=round(time.perf_counter() - t0, 3), **meter.since(m0))
    try:
        if args.chips == 4:
            four_chips(session, meter, args.seed)
        else:
            one_chip(session, meter, args.seed)
    except Failure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log("total", **meter.since((0.0, 0)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
