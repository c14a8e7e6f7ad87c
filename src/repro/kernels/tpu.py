"""What the Pallas kernels need to know about the device they compile for.

* ``interpret_mode()`` — the ONE place the interpret flag is derived: kernel
  bodies run compiled on a TPU backend and interpreted everywhere else (the
  CPU test suite gets interpret mode with no flag to set).
* ``block_shape_ok`` — Mosaic's block-shape rule: the last two dims of every
  ``BlockSpec`` block must be divisible by (8, 128) or equal the array's own
  dims.  Interpret mode never checks it, so the kernels' eligibility gates
  and ``repro.analysis.kernel_budget`` apply it instead.
"""

from __future__ import annotations

from typing import Sequence

import jax

SUBLANES = 8
LANES = 128


def interpret_mode() -> bool:
    """Run Pallas kernel bodies in the interpreter (any non-TPU backend)?"""
    return jax.default_backend() != "tpu"


def block_shape_ok(block: Sequence[int], array: Sequence[int]) -> bool:
    """Does a ``BlockSpec`` block over ``array`` satisfy Mosaic's tiling
    rule (last dim % 128, second-to-last % 8, each unless equal to the
    array's dim)?"""
    block, array = tuple(block), tuple(array)
    if len(block) != len(array):
        return False
    for k, tile in ((1, LANES), (2, SUBLANES)):
        if len(block) >= k and block[-k] % tile and block[-k] != array[-k]:
            return False
    return True
