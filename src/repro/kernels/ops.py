"""jit'd public wrappers for the Pallas kernels."""

from __future__ import annotations

from typing import Sequence

import jax

from repro.kernels.mpo_linear import DEFAULT_BLOCK_M
from repro.kernels.mpo_linear import mpo_linear as _mpo_linear
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
from repro.kernels.tpu import interpret_mode


def mpo_linear(cores: Sequence[jax.Array], x: jax.Array,
               block_m: int = DEFAULT_BLOCK_M,
               interpret: bool | None = None) -> jax.Array:
    """Differentiable fused MPO-linear (see ``kernels.mpo_linear``); the
    engine passes the plan's (possibly autotuned) ``block_m``.
    ``interpret`` defaults to ``kernels.tpu.interpret_mode()``."""
    if interpret is None:
        interpret = interpret_mode()
    return _mpo_linear(tuple(cores), x, block_m=block_m, interpret=interpret)


def ssd_scan(x, dt, a_log, b, c, d_skip, chunk: int = 64):
    return _ssd_scan(x, dt, a_log, b, c, d_skip, chunk=chunk,
                     interpret=interpret_mode())
