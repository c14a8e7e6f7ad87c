"""Open-loop traffic replay benchmark: tail latency vs offered load.

For each offered load (Poisson arrivals at ``LOADS`` requests/s, identical
seeded trace per load level) the same trace is replayed wall-clock against
two admission frontends on a fresh ``ServePool``:

  * ``legacy``     — whole-prompt admission (the pre-frontend behavior):
                     every distinct prompt length jit-retraces the batch-1
                     prefill, and a long prompt stalls all live tenants for
                     its full prefill;
  * ``continuous`` — ``prefill_chunk=8, bucket_prompts=True``: prompts pad
                     to power-of-two buckets (distinct prefill traces
                     collapse to ~log2(max_len)) and stream one chunk per
                     step, interleaved with decode.

Both replays are OPEN-LOOP (arrivals never wait for completions), so
admission stalls pile up as queueing delay and surface in p99 sojourn
latency — the headline is ``p99_win`` (legacy p99 / continuous p99) at the
highest load.  Sustained tok/s and p50/p99 TTFT ride along.  Results merge
into ``BENCH_serve.json`` (section ``traffic_replay``).

Run:  PYTHONPATH=src python -m benchmarks.traffic_replay
      PYTHONPATH=src python -m benchmarks.traffic_replay --loads 5 20
"""

from __future__ import annotations

import argparse
import json
import os

ARCH = "qwen3-14b"
LOADS = (4.0, 12.0, 30.0)      # offered requests/second
N_REQ = 60
SLOTS = 4
MAX_LEN = 64
PROMPT_LEN = (4, 24)
MAX_NEW = (1, 16)
SEED = 42

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON_PATH = os.path.join(_ROOT, "BENCH_serve.json")


def _measure(session, trace, **pool_kw) -> dict:
    from repro.pipeline import traffic
    pool = session.serve_pool(slots=SLOTS, max_len=MAX_LEN, **pool_kw)
    report = traffic.replay(pool, trace)
    st = pool.stats()
    out = dict(report.summary)
    out.update({
        "prefill_traces": st["prefill_traces"],
        "occupancy": round(st["occupancy"], 4),
    })
    return out


def run(loads=LOADS) -> list[str]:
    from repro.pipeline import traffic
    from repro.pipeline.session import Session

    session = Session.init(ARCH)
    by_load: dict[str, dict] = {}
    rows: list[str] = []
    for rps in loads:
        # draw prompt ids from the MODEL's vocab: out-of-range ids produce
        # non-finite logits, and the pool quarantines every request
        trace = traffic.make_trace(N_REQ, rps, seed=SEED,
                                   prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                                   vocab_size=session.cfg.vocab_size)
        legacy = _measure(session, trace)
        cont = _measure(session, trace, prefill_chunk=8, bucket_prompts=True)
        win = (round(legacy["p99_latency_s"] / cont["p99_latency_s"], 2)
               if cont["p99_latency_s"] > 0 else 0.0)
        by_load[str(rps)] = {"legacy": legacy, "continuous": cont,
                             "p99_win": win}
        for label, res in (("legacy", legacy), ("continuous", cont)):
            rows.append(
                f"traffic_replay,rps={rps},mode={label},"
                f"p50_latency_s={res['p50_latency_s']},"
                f"p99_latency_s={res['p99_latency_s']},"
                f"p99_ttft_s={res['p99_ttft_s']},tok_s={res['tok_s']},"
                f"prefill_traces={res['prefill_traces']}")
        rows.append(f"traffic_replay,rps={rps},p99_win={win}x")

    section = {"arch": ARCH, "requests": N_REQ, "slots": SLOTS,
               "max_len": MAX_LEN, "prompt_len": list(PROMPT_LEN),
               "max_new": list(MAX_NEW), "seed": SEED,
               "continuous_kw": {"prefill_chunk": 8, "bucket_prompts": True},
               "by_load": by_load}
    try:
        with open(_JSON_PATH) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        existing = {}
    existing["traffic_replay"] = section
    with open(_JSON_PATH, "w") as f:
        json.dump(existing, f, indent=2)
        f.write("\n")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--loads", type=float, nargs="+", default=list(LOADS))
    args = ap.parse_args()
    print("\n".join(run(tuple(args.loads))))


if __name__ == "__main__":
    main()
