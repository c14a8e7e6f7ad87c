"""``repro-pipeline``: the full paper workflow from the command line.

Runs the ``Session`` lifecycle on a smoke-scale architecture: init (or the
ALBERT classification subject), lightweight fine-tune, optional dimension
squeezing, a short greedy generation through the serving path, and the final
stage report as JSON.

Run:  repro-pipeline --arch qwen3-14b --steps 40 --tokens 8
      repro-pipeline --arch albert-base --cls --squeeze
      (or: python -m repro.pipeline.cli ...)

Resilience (docs/resilience.md):

* ``--session-dir DIR`` — restore the session from DIR when a manifest
  exists there (skipping straight to serving + report), else run the
  lifecycle and ``Session.save`` it to DIR at the end.
* ``--ckpt-dir DIR`` — fine-tune checkpoints in DIR, squeeze journal in
  DIR/squeeze; a preempted run re-invoked with the same flags resumes.
* ``--chaos SPEC`` (repeatable) — activate a deterministic ``FaultPlan``
  (grammar in ``resilience.faults.FaultPlan.parse``), e.g.
  ``--chaos preempt-squeeze:2``.  An injected preemption exits 3, an
  injected checkpoint crash exits 4 — rerun to resume.

Fleet warm-start subcommands (autotune verdicts as a shippable artifact):

    repro-pipeline tune-export PATH      pack this host's autotune cache
    repro-pipeline tune-import PATH      merge an artifact into the cache

Serving-frontend subcommand (docs/serving.md "Continuous batching"):

    repro-pipeline serve-replay --requests 100 --rate 20 --chunk 8 --bucket

replays a seeded open-loop Poisson trace against a ``ServePool`` and
prints the latency/throughput summary as JSON.  ``--replicas N`` serves
the trace through an N-replica ``PoolRouter`` fleet instead
(docs/resilience.md "Fleet degradation"); combine with ``--chaos
kill-pool:1:40`` to watch a mid-replay crash fail over, rebuild and
rejoin.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys


def _tune_main(argv) -> int:
    """tune-export / tune-import: pack or merge the autotune disk cache."""
    cmd = argv[0]
    ap = argparse.ArgumentParser(
        prog=f"repro-pipeline {cmd}",
        description="Export this host's kernel-autotune verdicts as a "
                    "fleet-shippable artifact, or merge such an artifact "
                    "into the local cache (local verdicts win unless "
                    "--overwrite).")
    ap.add_argument("path", help="artifact path (a JSON verdict pack)")
    if cmd == "tune-import":
        ap.add_argument("--overwrite", action="store_true",
                        help="imported verdicts replace local ones on "
                             "key collisions")
    args = ap.parse_args(argv[1:])
    from repro.kernels import autotune
    if cmd == "tune-export":
        res = autotune.export_cache(args.path)
        print(f"[tune-export] {res['exported']} verdicts -> {res['path']}")
    else:
        res = autotune.import_cache(args.path, overwrite=args.overwrite)
        print(f"[tune-import] {res['imported']} imported, "
              f"{res['skipped']} skipped (local wins) -> {res['path']} "
              f"({res['total']} total)")
    return 0


def _replay_main(argv) -> int:
    """serve-replay: open-loop Poisson traffic against a ServePool."""
    ap = argparse.ArgumentParser(
        prog="repro-pipeline serve-replay",
        description="Replay a seeded open-loop (Poisson-arrival) request "
                    "trace against a multi-tenant ServePool and print the "
                    "latency/throughput summary as JSON.  The trace is "
                    "deterministic in --seed; --virtual-clock makes the "
                    "whole replay deterministic (tests/CI).")
    from repro import configs
    ap.add_argument("--arch", default="qwen3-14b",
                    choices=list(configs.ARCHS))
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="offered load, requests/second (Poisson)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 24),
                    metavar=("LO", "HI"))
    ap.add_argument("--max-new", type=int, nargs=2, default=(1, 16),
                    metavar=("LO", "HI"))
    ap.add_argument("--chunk", type=int, default=None,
                    help="chunked admission prefill size (tokens); omit "
                         "for whole-prompt admission")
    ap.add_argument("--bucket", action="store_true",
                    help="pad prompts to power-of-two length buckets "
                         "(bounds admission jit retraces)")
    ap.add_argument("--paged", action="store_true",
                    help="paged pool KV cache")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--virtual-clock", action="store_true",
                    help="deterministic virtual time (fixed cost per pool "
                         "step) instead of wall clock")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a PoolRouter fleet of N replica "
                         "pools (health-checked routing, retries, circuit "
                         "breaking; docs/resilience.md)")
    ap.add_argument("--shed-depth", type=int, default=None,
                    help="fleet load-shedding: fail fast (status 'shed') "
                         "past this many outstanding requests")
    ap.add_argument("--session-dir", default=None,
                    help="save the session here and rebuild tripped "
                         "replicas from the checkpoint (default: rebuild "
                         "from the live session)")
    ap.add_argument("--chaos", action="append", default=[], metavar="SPEC",
                    help="deterministic fault injection (repeatable), e.g. "
                         "kill-pool:IDX:STEP, trip-pool:IDX, shed-storm:K, "
                         "nan-decode:STEP[:SLOT]; grammar in "
                         "resilience.faults.FaultPlan.parse")
    args = ap.parse_args(argv[1:])

    from repro.pipeline import traffic
    from repro.pipeline.clock import VirtualClock, WallClock
    from repro.pipeline.session import Session
    from repro.resilience import faults
    session = Session.init(args.arch)
    clock = VirtualClock() if args.virtual_clock else WallClock()
    pool_kw = dict(paged=args.paged, page_size=args.page_size,
                   prefill_chunk=args.chunk, bucket_prompts=args.bucket)
    if args.replicas > 1:
        pool = session.serve_fleet(
            args.replicas, args.slots, args.max_len, clock=clock,
            session_dir=args.session_dir,
            router={"shed_queue_depth": args.shed_depth}, **pool_kw)
    else:
        pool = session.serve_pool(args.slots, args.max_len, clock=clock,
                                  **pool_kw)
    trace = traffic.make_trace(
        args.requests, args.rate, seed=args.seed,
        prompt_len=tuple(args.prompt_len), max_new=tuple(args.max_new),
        vocab_size=min(session.cfg.vocab_size, 1000))
    scope = (faults.fault_scope(faults.FaultPlan.parse(args.chaos))
             if args.chaos else contextlib.nullcontext())
    with scope:
        report = traffic.replay(pool, trace, clock=clock)
    stats = pool.stats()
    out = {"summary": report.summary}
    if args.replicas > 1:
        out["router"] = {
            "replicas": [{"idx": r["idx"], "state": r["state"],
                          "trips": r["trips"], "rebuilds": r["rebuilds"]}
                         for r in stats["replicas"]],
            "retries": stats["retries"], "shed": stats["shed"],
            "trips": stats["trips"], "rebuilds": stats["rebuilds"],
            "fail_reasons": stats["fail_reasons"],
        }
    else:
        out.update(prefill_traces=stats["prefill_traces"],
                   phases=stats["phases"],
                   occupancy=round(stats["occupancy"], 4))
    print(json.dumps(out, indent=2))
    return 0


def _run(args) -> int:
    from repro.pipeline import Session

    session = None
    if args.session_dir and os.path.exists(
            os.path.join(args.session_dir, "session.json")):
        session = Session.restore(args.session_dir)
        print(f"[repro-pipeline] restored session from {args.session_dir} "
              f"(stage={session.stage}, "
              f"weights_version={session.weights_version})")
    if session is None:
        overrides = {"num_classes": 2} if args.cls else {}
        session = Session.init(args.arch, **overrides)
        session.finetune(mode=args.mode, steps=args.steps, lr=args.lr,
                         ckpt_dir=args.ckpt_dir, verbose=args.verbose)
        if args.squeeze:
            jdir = (os.path.join(args.ckpt_dir, "squeeze")
                    if args.ckpt_dir else None)
            session.squeeze(delta=args.delta, max_iters=args.max_iters,
                            ckpt_dir=jdir, verbose=args.verbose)
        if args.session_dir:
            session.save(args.session_dir)
            print(f"[repro-pipeline] session saved to {args.session_dir}")
    if args.tokens and session.task == "lm":
        from repro.configs.base import ShapeConfig
        from repro.models import model as M
        handle = session.serve(args.batch,
                               args.prompt_len + args.tokens + 1)
        batch = M.make_batch(session.cfg, ShapeConfig(
            "cli", "prefill", args.prompt_len, args.batch))
        ids = handle.generate(batch, args.tokens)
        print(f"[repro-pipeline] sample ids: {ids[0].tolist()}")
    report = session.report()
    print(json.dumps(report, indent=2))
    if args.strict_analysis and report.get("analysis", {}).get("errors"):
        return 1
    return 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    if argv and argv[0] in ("tune-export", "tune-import"):
        return _tune_main(argv)
    if argv and argv[0] == "serve-replay":
        return _replay_main(argv)

    from repro import configs

    ap = argparse.ArgumentParser(prog="repro-pipeline", description=__doc__)
    ap.add_argument("--arch", default="qwen3-14b", choices=list(configs.ARCHS))
    ap.add_argument("--cls", action="store_true",
                    help="classification task (adds a 2-class head; the "
                         "paper's GLUE-analog setting)")
    ap.add_argument("--mode", default="lfa",
                    choices=["lfa", "full", "central_only"])
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--squeeze", action="store_true",
                    help="run dimension squeezing (Algorithm 2) after the "
                         "fine-tune")
    ap.add_argument("--delta", type=float, default=0.08)
    ap.add_argument("--max-iters", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=8,
                    help="tokens to decode through the serving path "
                         "(LM tasks only; 0 disables)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="fine-tune checkpoints here; the squeeze journal "
                         "goes in <dir>/squeeze — rerun with the same "
                         "flags to resume a preempted run")
    ap.add_argument("--session-dir", default=None,
                    help="restore the session from here if a manifest "
                         "exists, else save the finished session here")
    ap.add_argument("--chaos", action="append", default=[], metavar="SPEC",
                    help="inject a deterministic fault (repeatable); "
                         "grammar: preempt-finetune:K, preempt-squeeze:K, "
                         "crash-ckpt:mid_write[:STEP], "
                         "crash-ckpt:pre_latest[:STEP], io:SITE:N, "
                         "nan-decode:STEP[:SLOT], deny-pages:N, "
                         "flash-raise, expire-admit:K, kill-pool:IDX:STEP, "
                         "trip-pool:IDX, shed-storm:K")
    ap.add_argument("--strict-analysis", action="store_true",
                    help="exit nonzero if the report's static-analysis "
                         "summary contains errors (repro-lint runs the full "
                         "sweep; this gates just this session's trees)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    from repro.resilience import faults
    scope = (faults.fault_scope(faults.FaultPlan.parse(args.chaos))
             if args.chaos else contextlib.nullcontext())
    try:
        with scope:
            return _run(args)
    except faults.Preemption as e:
        print(f"[repro-pipeline] preempted: {e} — rerun with the same "
              "--ckpt-dir/--session-dir to resume", file=sys.stderr)
        return 3
    except faults.CrashPoint as e:
        print(f"[repro-pipeline] crashed: {e} — the previous checkpoint "
              "is intact; rerun to resume", file=sys.stderr)
        return 4


if __name__ == "__main__":
    sys.exit(main())
