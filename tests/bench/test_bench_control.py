"""The lower-precision control, at a tiny size on the CPU: the plain
reference computed with float8 (e4m3, one scale per tensor) matmul inputs,
put in the program's place, fails each cell's comparison."""

import pytest
from bench_tiny import run


@pytest.mark.parametrize("cell,number,control", [
    ("qwen3-14b.chat-open", "max_logit_gap", "control_fp8_max_logit_gap"),
    ("mistral-nemo-12b.lfa-finetune", "grad_rel_err", "control_fp8"),
])
def test_float8_control_is_not_correct(cell, number, control):
    readings = {}
    out = run(cell, control=True, readings_out=readings)
    limit = out["checks"][number]["limit"]
    got = readings[control]
    got = got[number] if isinstance(got, dict) else got
    assert got > limit, (got, limit)
    assert out["checks"][number]["value"] <= limit
