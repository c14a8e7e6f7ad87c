"""Whole decode step: its least time on the chip over its measured device
time (``jit_decode_step`` in the trace), in percent.

The least time is the larger of FLOPs over peak FLOP/s and bytes over peak
HBM bandwidth, for the step's required work:

* FLOPs: ``2`` per dense-equivalent weight per live row, plus attention
  ``4 * H * Dh`` per (query, cached key) pair, over all layers;
* bytes: the stored MPO cores in the configuration's dtype, plus K and V
  of every live row's context.

The count is the same whatever plan implements the step: a plan that
reads a dense snapshot instead of the cores is charged only the cores.
"""

from bench import counts
from bench.trace import program_calls


def work(conf: dict, live_rows: float, context: float, core_params: int):
    """(FLOPs, bytes) of one decode step with ``live_rows`` live rows whose
    contexts sum to ``context`` positions."""
    flops = (2 * counts.dense_weights(conf) * live_rows
             + counts.attention_flops(conf, context))
    nbytes = (core_params * counts.itemsize(conf)
              + counts.kv_bytes(conf, context, counts.itemsize(conf)))
    return flops, nbytes


def read(obs):
    tr = obs.get("trace")
    steps = obs["counters"].get("traced_steps")
    calls = program_calls(tr, "decode_step") if tr else []
    if not calls or not steps:
        return None
    rows = sum(s[1] for s in steps) / len(steps)
    ctx = sum(s[2] for s in steps) / len(steps)
    flops, nbytes = work(obs["conf"], rows, ctx, obs["core_params"])
    pk = obs["peaks"]
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"])
    return 100.0 * least / (sum(calls) / len(calls))
