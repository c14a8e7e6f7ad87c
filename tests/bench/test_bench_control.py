"""The lower-precision control, at a tiny size on the CPU: the plain
reference computed in float8 (e4m3 matmul inputs, an e5m2 gradient into
the backward matmuls, one scale per tensor), put in the program's place,
fails each cell's comparison."""

import pytest
from bench_tiny import run


@pytest.mark.parametrize("cell,number,control", [
    ("qwen3-14b.chat-open", "max_logit_gap", "control_fp8_max_logit_gap"),
    ("mistral-nemo-12b.lfa-finetune", "grad_rel_err", "control_fp8"),
    ("mistral-nemo-12b.lfa-finetune", "grad_rel_err_med", "control_fp8"),
])
def test_float8_control_is_not_correct(cell, number, control):
    readings = {}
    out = run(cell, control=True, readings_out=readings)
    limit = out["checks"][number]["limit"]
    got = readings[control]
    got = got[number] if isinstance(got, dict) else got
    assert got > limit, (got, limit)
    assert out["checks"][number]["value"] <= limit


def test_float8_matmul_rounds_inputs_and_the_incoming_gradient():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.references import dense
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (8, 16))
    w = jax.random.normal(kw, (16, 4))
    dy = jax.random.normal(kg, (8, 4))
    y, vjp = jax.vjp(dense.fp8_matmul, x, w)
    qx, qw = dense.fp8(x), dense.fp8(w)
    g = dense.fp8(dy, jnp.float8_e5m2)
    dx, dw = vjp(dy)
    np.testing.assert_allclose(y, qx @ qw, rtol=1e-6)
    np.testing.assert_allclose(dx, g @ qw.T, rtol=1e-6)
    np.testing.assert_allclose(dw, qx.T @ g, rtol=1e-6)
    assert not np.allclose(g, dy, rtol=1e-3)
    assert not np.allclose(qx, x, rtol=1e-3)
