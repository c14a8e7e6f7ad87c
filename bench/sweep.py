"""Find the highest rate an open-loop cell sustains, once, on the chip.

    python3 bench/sweep.py --workload qwen3-14b.chat-open --seed 5 \
        --seconds 20 --rates 4 6 8 10 12

One process builds the cell's pool once and runs one open-loop window per
rate, each followed by its drain.  Each line reports the window's tails,
the requests still queued at its close and how long the drain took: a rate
is sustained while the queue at the close stays near empty and the drain
stays short.  The benchmark's cells then offer a fixed rate below it
(``rate_rps`` in the traffic file); no run searches for one.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402
from bench.harness import log, now  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    info = harness.cell(args.workload)
    conf, mix = info["config"], info["mix"]
    if mix["loop"] != "open_loop":
        raise SystemExit("a sweep needs an open-loop cell")
    harness.check_devices(info["workload"]["chips"])
    harness.use_checkout_program()
    import jax
    from repro.models import model as M
    from repro.pipeline.session import Session
    from repro.runtime import enable_compile_cache
    from bench.loops import serve as D
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg = harness.program_config(conf)
    model = M.build(cfg)
    session = Session(cfg, harness.make_weights(model, args.seed))
    vocab = conf.get("published", {}).get("vocab_size", conf["vocab_size"])
    pool = session.serve_pool(**mix["pool"])
    D.warm(pool, mix, vocab)
    quiet = lambda name: contextlib.nullcontext()  # noqa: E731
    for rate in args.rates:
        m = dict(mix, rate_rps=rate)
        res = D.open_loop(pool, m, args.seed, args.seconds, vocab, quiet,
                          lambda phase, t: None)
        e = D.end_to_end(res)
        queued = sum(ob.left_queue is None or ob.left_queue > res["t_close"]
                     for ob in res["obs"])
        log("sweep", rate_rps=rate, requests=e["attempted"],
            failed=e["failed"], ttft_p95_ms=e["ttft_p95_ms"],
            itl_p95_ms=e["itl_p95_ms"], queued_at_close=queued,
            drain_s=res["t_end"] - res["t_close"],
            out_tok_s=sum(ob.count for ob in res["obs"])
            / (res["t_end"] - res["t0"]), at=now())
    return 0


if __name__ == "__main__":
    sys.exit(main())
