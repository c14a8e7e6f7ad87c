"""Whole LFA train step (``jit_train_step``): the FLOPs that any LFA step
must do, over its measured device time and the chip's peak, in percent.

FLOPs per step: ``4`` per dense-equivalent weight per token (the forward
matmul and the input gradient ``dy @ W^T``), plus attention (causal:
``S * (S + 1) / 2`` query-key pairs per sequence) at three times its
forward FLOPs.  Left out:

* the weight gradient ``x^T dy`` (another ``2`` per weight per token).  LFA
  freezes the central tensors and trains only the auxiliary cores, whose
  gradients a plan may get without ever forming ``dW`` (the fused kernel
  pulls ``x^T dy`` back into core space tile by tile); their least cost
  depends on the plan, so counting a dense ``dW`` could let a sound step
  read over 100%;
* recomputation (remat) and rebuilding W from its cores.

The count reads the configuration file only, never the plan.
"""

from bench import counts
from bench.trace import program_calls


def work(conf: dict, batch: int, seq: int) -> float:
    pairs = batch * seq * (seq + 1) // 2
    return (4 * counts.dense_weights(conf) * batch * seq
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
