"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run makes the weights on the device from ``--seed``, builds the system
under test (``Session`` -> ``ServePool`` or the LFA train step) from the
cell's configuration file, warms up the cell's own shapes, measures for
``--seconds``, then checks what the window produced against the plain
reference.  Earlier lines of standard output report plans, tuner verdicts,
generator lateness, compiles inside the window, peak HBM and the cuts from
the published configuration.  The last line is one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "plans": {...}, "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a few
seconds inside the window.  ``plans`` holds the engine's plan for each
distinct matrix shape (a tuner verdict that flips between checkouts shows
there; a verdict that a mix pins through ``"program_env"`` is in the
first line).  ``checks`` holds each number compared beside
its limit; the same lines end standard error.

Without a TPU, or with fewer chips than the cell needs, the run exits
non-zero and prints no result.  ``--control 1`` (never used by the
benchmark's own runs) also reads the lower-precision control and the
planted faults on the same sample, for setting limits.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402
from bench.harness import log, now  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402


class Tracer:
    """Profiles a sub-window of the measured window: it starts at
    ``start_frac`` of the window and lasts ``seconds``."""

    def __init__(self, directory: str, window_s: float, mix: dict):
        self.dir = directory
        tr = mix.get("trace", {})
        self.start = window_s * tr.get("start_frac", 0.4)
        self.length = min(tr.get("seconds", 4.0), window_s * 0.5)
        self.t0 = self.span = None
        self.bounds = None

    def __call__(self, phase: str, t: float) -> None:
        import jax
        if phase == "open":
            self.t0 = t
        elif phase == "tick" and self.span is None and self.bounds is None \
                and t - self.t0 >= self.start:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.bounds = [now(), None]
        elif self.span is not None and (phase == "close" or (
                phase == "tick" and t - self.bounds[0] >= self.length)):
            self.span.__exit__(None, None, None)
            self.bounds[1] = now()
            self.span = None
            jax.profiler.stop_trace()


def _annotate(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def log_plans(session, mix: dict) -> dict:
    """Each distinct MPO matrix's engine plan at the window's token counts,
    and the tuner verdicts on disk; returns ``{"<matrices> <phase>":
    plan}`` for the result line, where a plan that flips shows."""
    from repro.core import layers as L
    from repro.kernels import autotune
    seen: dict = {}

    def visit(node, path):
        if isinstance(node, dict) and "cores" in node:
            cores = L.cores_to_list(node["cores"])
            seen.setdefault(tuple(tuple(c.shape[-4:]) for c in cores),
                            []).append(path)
        elif isinstance(node, dict):
            for k, v in node.items():
                visit(v, f"{path}/{k}")

    visit(session.params, "")
    if mix["loop"] == "train":
        points = [("train", mix["batch"] * mix["seq_len"])]
    else:
        pool = mix["pool"]
        points = [("decode", pool["slots"]),
                  ("prefill", pool.get("prefill_chunk") or 1)]
    dtype = session.cfg.jnp_dtype
    plans = {}
    for shapes, paths in seen.items():
        if paths == ["/embed"]:
            continue
        for phase, tokens in points:
            p = session.engine.plan(shapes, tokens, phase, dtype)
            log("plan", matrices=paths, phase=phase, tokens=tokens,
                mode=p.mode, block_m=p.block_m, tuned=p.tuned)
            names = ",".join(sorted({q.rsplit("/", 1)[-1] for q in paths}))
            plans[f"{names} {phase}"] = (f"kernel@{p.block_m}"
                                         if p.mode == "kernel" else p.mode)
    tuner = autotune.get_tuner()
    log("autotune", path=tuner.path, stats=tuner.stats(),
        verdicts={k: v for k, v in tuner._entries().items()})
    return plans


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             control: bool = False, check_chip: bool = True,
             conf_override: dict | None = None,
             mix_override: dict | None = None,
             readings_out: dict | None = None) -> dict:
    """One run of cell ``name``; returns the result object (the last line).
    Tests pass ``check_chip=False`` and small overrides, and may collect
    every reading (control and faults included) in ``readings_out``."""
    info = harness.cell(name)
    conf = dict(info["config"], **(conf_override or {}))
    mix = dict(info["mix"], **(mix_override or {}))
    with harness.program_env(mix) as pins:
        return _run(info, conf, mix, pins, seed, seconds, trace, control,
                    check_chip, readings_out)


def _run(info, conf, mix, pins, seed, seconds, trace, control, check_chip,
         readings_out) -> dict:
    name = info["workload"]["name"]
    chips = info["workload"]["chips"]
    import jax
    devices = harness.check_devices(chips) if check_chip else \
        jax.devices()[:chips]
    dev = devices[0]
    harness.use_checkout_program()
    from repro.models import model as M
    from repro.pipeline.session import Session
    from repro.runtime import enable_compile_cache
    log("cell", workload=name, seed=seed, seconds=seconds, trace=trace,
        config=info["workload"]["config"], traffic=info["workload"]["traffic"],
        reduced=info["config_entry"]["reduced"],
        published=conf.get("published"), program_env=pins,
        compile_cache=enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = harness.CompileMeter()
    cfg = harness.program_config(conf)
    model = M.build(cfg)
    t = now()
    params = jax.block_until_ready(harness.make_weights(model, seed))
    log("weights", seconds=now() - t)
    session = Session(cfg, params)
    vocab = harness.id_vocab(conf)
    trace_dir = str(harness.ROOT / ".cache" / "bench" / "trace")
    tracer = Tracer(trace_dir, seconds, mix) if trace else \
        (lambda phase, t: None)
    annotate = _annotate(trace)
    peaks = peaks_for(dev.device_kind) if check_chip else \
        {"bf16_flops": 1.0, "hbm_bytes_s": 1.0}
    counters: dict = {}
    if mix["loop"] == "train":
        from bench.loops import train as D
        t = now()
        step, state, _ = D.build(model, params, mix)
        feed = D.make_feed(mix, seed, vocab)
        state, prog = D.first_steps(step, state, feed, mix)
        log("first-steps", seconds=now() - t, loss=prog["loss"])
        plans = log_plans(session, mix)
        setup = now() - T_START
        m_open = meter.snapshot()
        state, res = D.window(step, state, feed, mix, seconds, annotate,
                              tracer)
        m_close = meter.snapshot()
        e2e = {"train_tok_s": res["train_tok_s"]}
        attempted, failed = res["steps"], 0
        log("window", steps=res["steps"], seconds=res["t_close"] - res["t0"])
    else:
        from bench.loops import serve as D
        t = now()
        pool = session.serve_pool(**mix["pool"])
        log("pool", seconds=now() - t, init_seconds=pool.init_seconds)
        t = now()
        D.warm(pool, mix, vocab)
        log("warm-up", seconds=now() - t)
        plans = log_plans(session, mix)
        box = {}
        collector = harness.GcMeter()

        def on_window(phase, t):
            if phase == "open":
                box["setup"] = t - T_START
                box["m_open"] = meter.snapshot()
                counters["stats_open"] = pool.stats()
                collector.start()
            if phase == "close":
                box["gc"] = collector.stop()
                box["m_close"] = meter.snapshot()
                counters["stats_close"] = pool.stats()
            tracer(phase, t)

        res = D.LOOPS[mix["loop"]](pool, mix, seed, seconds, vocab,
                                       annotate, on_window)
        setup, m_open, m_close = box["setup"], box["m_open"], box["m_close"]
        e = D.end_to_end(res)
        e2e = {k: e[k] for k in ("ttft_p95_ms", "itl_p95_ms",
                                 "decode_tok_s")}
        attempted, failed = e["attempted"], e["failed"]
        counters.update(D.window_counters(res))
        late = sorted(res["late"])
        log("window", requests=attempted, failed=failed,
            steps=len(counters["steps"]),
            generator_late_p50_ms=1e3 * late[len(late) // 2] if late else 0,
            generator_late_max_ms=1e3 * late[-1] if late else 0,
            step_gap_max_ms=1e3 * max(
                (b[0] - a[0] for a, b in zip(counters["steps"],
                                             counters["steps"][1:])
                 if b[0] <= res["t_close"]), default=0),
            gc=box["gc"],
            pool=counters["stats_close"])
        picked = D.sample(res, seed, mix["check"]["requests"])
    log("compiles-in-window", **{k: m_close[k] - m_open[k] for k in m_open})
    mem = harness.memory_peak(devices) if check_chip else 0
    log("memory", peak_bytes=mem, stats=devices[0].memory_stats())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    metrics, breakdown = {}, None
    if trace:
        from bench import trace as T
        tr = T.load(trace_dir)
        if mix["loop"] != "train":
            lo, hi = tracer.bounds
            counters["traced_steps"] = [s for s in counters["steps"]
                                        if lo <= s[0] <= hi]
        core_params = sum(int(math.prod(x.shape))
                          for x in jax.tree.leaves(session.params))
        obs = {"trace": tr, "counters": counters, "conf": conf, "mix": mix,
               "peaks": peaks, "core_params": core_params}
        for m in info["per_layer"]:
            v = harness.metric_reader(m["name"])(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=T.busy_s(tr), window_s=tr.window_s)
        breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in info["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup, "unit": "s"}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # ---- correctness: the program's state is freed first ----
    t = now()
    ref = harness.reference(conf["reference"])
    rcfg = harness.reference_config(conf)
    if mix["loop"] == "train":
        del state, step, session, params
        harness.free_program()
        rparams = harness.make_weights(model, seed)
        if not control:
            readings = D.compare(prog, D.reference_readings(
                ref, rcfg, rparams, feed, mix))
        if control:
            full = D.reference_readings(ref, rcfg, rparams, feed, mix)
            readings = D.compare(prog, full)
            for k, kw in (("control_fp8", {"quant": "fp8"}),
                          ("fault_half_batch", {"rows": mix["batch"] // 2})):
                readings[k] = D.compare(D.reference_readings(
                    ref, rcfg, rparams, feed, mix, **kw), full)
    else:
        del pool, session, params, res
        harness.free_program()
        rparams = harness.make_weights(model, seed)
        readings = D.check(ref, rcfg, rparams, picked, seed, control)
    log("reference", seconds=now() - t)
    limits = info["limits"]
    checks = {k: {"value": readings.get(k, float("nan")), "limit": lim}
              for k, lim in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    if mix["loop"] != "train" and not readings.get("tokens"):
        correct = False
    log("readings", **readings)
    if readings_out is not None:
        readings_out.update(readings)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["plans"] = plans
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
