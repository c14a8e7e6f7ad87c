"""Whole LFA train step (``jit_train_step``): model FLOPs of forward and
backward over its measured device time and the chip's peak, in percent.

Model FLOPs per step: ``6`` per dense-equivalent weight per token, plus
attention (causal: ``S * (S + 1) / 2`` query-key pairs per sequence) at
three times its forward FLOPs.  Recomputation (remat) and rebuilding W from
cores do not count.
"""

from bench import counts
from bench.trace import program_calls


def work(conf: dict, batch: int, seq: int) -> float:
    pairs = batch * seq * (seq + 1) // 2
    return (6 * counts.dense_weights(conf) * batch * seq
            + 3 * counts.attention_flops(conf, pairs))


def read(obs):
    tr = obs.get("trace")
    calls = program_calls(tr, "train_step") if tr else []
    if not calls:
        return None
    mix = obs["mix"]
    flops = work(obs["conf"], mix["batch"], mix["seq_len"])
    return 100.0 * flops / (sum(calls) / len(calls)) / obs["peaks"][
        "bf16_flops"]
