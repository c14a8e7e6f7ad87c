"""Serving steps: mean device time per call of the batched decode program
(``jit_decode_step``), from the profiler trace."""

from bench.trace import program_calls


def read(obs):
    tr = obs.get("trace")
    calls = program_calls(tr, "decode_step") if tr else []
    return 1e3 * sum(calls) / len(calls) if calls else None
