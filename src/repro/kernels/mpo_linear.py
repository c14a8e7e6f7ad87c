"""Fused MPO-reconstruct + matmul Pallas TPU kernel — differentiable.

``reconstruct`` mode round-trips the dense W through HBM (and, sharded, an
all-gather) every step.  This kernel tiles the grid over the *leading MPO
factors* (i1, j1): each program rebuilds one ``(I/i1, J/j1)`` tile of W from
the (tiny, VMEM-resident) cores via on-chip chain dots and immediately
consumes it in the x-tile matmul, accumulating over the i1 reduction axis.
W never exists in HBM — per-step HBM traffic is activations + *compressed*
cores only, which is the TPU-native realization of the paper's compression
claim (DESIGN §3.2).

Tile layout.  Mosaic lowers only reshapes that keep the lane (last) dim a
multiple of 128, so the W tile is rebuilt in two parts:

* the LEFT part ``L[(a_l), (b_l, e)]`` — core 0's (i1, j1) bond fiber
  chained through cores ``1 .. k-1`` in-kernel (their bonds are the wide,
  lane-aligned ones);
* the RIGHT part ``R[e, a_r, b_r]`` — the trailing cores ``k ..`` (small
  legs, narrow bonds) contracted once per call in XLA; it is tiny (for
  qwen3-14b ``wq``: 128 x 16 x 16).

For each trailing output index ``s`` the program forms the column block
``W_s[(a_l, a_r), b_l] = sum_e L[a_l, b_l, e] R[e, a_r, s]`` and
accumulates ``x_tile @ W_s`` into the output block ``(j1, b_r, M, b_l)``,
which the wrapper transposes back to ``(M, J)``.

Forward grid: ``(M/bm, j1, i1)`` — i1 innermost = sequential reduction over
the output tile (standard Pallas accumulation pattern).

Backward (``jax.custom_vjp``) stays fused and core-space:

* ``dL/dx = dy @ W^T`` runs the SAME forward kernel over the transposed
  cores (swap every core's i/j legs): the cotangent is contracted against
  tile-reconstructed W^T tiles, never a dense W^T.
* ``dL/dcores`` runs ``_bwd_cores_kernel`` on grid ``(i1, j1, M/bm)``: each
  program forms ``dW_s = x^T dy_s`` column blocks in VMEM and immediately
  pulls them back into ``dR`` and through the left chain (``jax.vjp`` of
  ``_build_left``), so the gradient is *accumulated directly in core
  space*; ``dR`` goes back to the trailing cores in XLA.  The dense dW —
  whose per-layer all-reduce is exactly what lightweight fine-tuning exists
  to avoid — never materializes.

This is what makes ``kernel`` a legal ``train``-phase mode: the engine's
planner (``core.engine`` + ``kernels.autotune``) may pick it for fwd+bwd
workloads, not just forward-only prefill.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import LANES, SUBLANES, block_shape_ok

# Single source of truth for the kernel tile height (imported by
# ``core.engine`` and ``kernels.autotune`` — do not re-declare):
# BLOCK_M_ALIGN is the f32 sublane count; unaligned tile heights make
# Mosaic pad every x/out tile.  DEFAULT_BLOCK_M is the analytic fallback
# used when no measured autotune result exists for a shape.
BLOCK_M_ALIGN = 8
DEFAULT_BLOCK_M = 256
# per-core VMEM (pallas_guide: ~16 MiB per TensorCore).  The feasibility
# gate below keeps every program's worst-case residency inside it; the
# static analyzer (repro.analysis.kernel_budget) re-checks the same model.
VMEM_BUDGET = 16 * 1024 * 1024


def validate_block_m(block_m: int) -> None:
    """The one place the ``block_m % 8`` alignment rule is written."""
    if block_m <= 0 or block_m % BLOCK_M_ALIGN:
        raise ValueError(f"block_m must be a positive multiple of "
                         f"{BLOCK_M_ALIGN}, got {block_m}")


def _split(n: int) -> int:
    """Cores ``[1, k)`` are chained in-kernel, ``[k, n)`` form R."""
    return max(1, n - 2)


def _geometry(shapes: Sequence[tuple]) -> tuple:
    """``(k, a_l, b_l, a_r, b_r, e)`` of the two-part tile rebuild."""
    k = _split(len(shapes))
    a_l = math.prod(s[1] for s in shapes[1:k])
    b_l = math.prod(s[2] for s in shapes[1:k])
    a_r = math.prod(s[1] for s in shapes[k:])
    b_r = math.prod(s[2] for s in shapes[k:])
    return k, a_l, b_l, a_r, b_r, shapes[k][0]


def _lane_split_ok(minor: int, outer: int) -> bool:
    """Splitting or merging a lane dim into ``(outer, minor)``."""
    return outer == 1 or minor % LANES == 0


def layout_ok(shapes: Sequence[tuple]) -> bool:
    """Can Mosaic lower the in-kernel tile rebuild for these cores?

    Mirrors the reshapes of ``_build_left`` / ``_column_block`` (each must
    keep the lane dim a multiple of 128 unless it is not split at all) and
    the x block's ``(bm, I/i1)`` shape (``block_shape_ok``).  Interpret
    mode accepts any shape; this is what the compiler adds."""
    shapes = [tuple(s) for s in shapes]
    if len(shapes) < 2:
        return False
    k, a_l, b_l, a_r, b_r, e = _geometry(shapes)
    i1 = shapes[0][1]
    i_dim = i1 * a_l * a_r
    if not block_shape_ok((SUBLANES, a_l * a_r), (2 * SUBLANES, i_dim)):
        return False
    cols = 1
    for (d, a, b, e_k) in shapes[1:k]:
        if not _lane_split_ok(d, cols) or not _lane_split_ok(b * e_k, a):
            return False
        cols *= b
    return _lane_split_ok(e, b_l) and (a_r % SUBLANES == 0 or a_l == 1)


def kernel_eligible(shapes: Sequence[tuple], block_m: int, *,
                    train: bool = False) -> bool:
    """Can the fused Pallas kernel run these core shapes on the chip?

    Two gates, both enforced statically by ``repro.analysis.kernel_budget``:

    * **layout** — ``layout_ok``: the compiler accepts the blocks and the
      in-kernel reshapes of the tile rebuild;
    * **VMEM feasibility** — the program's worst-case residency
      (``kernel_fits``) must clear the per-core budget; some factorizations
      produce W-tiles that alone exceed VMEM (a 13824x1024 f32 tile is 54
      MiB), and compiling those would abort on hardware.

    ``train=True`` additionally requires the backward passes to pass: dL/dx
    runs this same kernel over i/j-SWAPPED cores (both orientations must
    pass) and dL/dcores runs ``_bwd_cores_kernel``.

    Used as the *candidate filter* by the autotuner and as the analytic
    gate when no measurement is available.
    """
    shapes = [tuple(s) for s in shapes]
    ok = (block_m % BLOCK_M_ALIGN == 0 and layout_ok(shapes)
          and kernel_fits(shapes, block_m))
    if ok and train:
        transposed = [(d0, j, i, d1) for (d0, i, j, d1) in shapes]
        ok = (layout_ok(transposed) and kernel_fits(transposed, block_m)
              and kernel_fits(shapes, block_m, backward=True))
    return ok


def _effective_block_m(block_m: int, m: int) -> int:
    """Tile height actually used: aligned, never exceeding ``block_m`` or
    (the 8-aligned ceiling of) the token count."""
    return min(block_m, BLOCK_M_ALIGN * ((m + BLOCK_M_ALIGN - 1)
                                         // BLOCK_M_ALIGN))


def _tiled(shape: tuple) -> tuple:
    """A VMEM value's shape padded to Mosaic's (8, 128) tiling."""
    shape = tuple(shape)
    if not shape:
        return shape
    lanes = -(-shape[-1] // LANES) * LANES
    if len(shape) == 1:
        return (lanes,)
    subl = -(-shape[-2] // SUBLANES) * SUBLANES
    return shape[:-2] + (subl, lanes)


def block_shapes(shapes: Sequence[tuple], block_m: int, m: int, *,
                 backward: bool = False) -> list:
    """``(name, block, array)`` for every ``BlockSpec`` of ``_fwd_call`` /
    ``_bwd_cores_call`` — what ``repro.analysis.kernel_budget`` checks
    against Mosaic's block-shape rule."""
    shapes = [tuple(s) for s in shapes]
    k, a_l, b_l, a_r, b_r, e = _geometry(shapes)
    _, i1, j1, d1 = shapes[0]
    bm = _effective_block_m(block_m, m)
    mp = -(-m // bm) * bm
    core0 = ((1, 1, 1, d1), (i1, j1, 1, d1))
    rows = [("core0_fiber", *core0)]
    rows += [(f"core{t}", shapes[t], shapes[t]) for t in range(1, k)]
    rows.append(("right", (b_r, e, a_r), (b_r, e, a_r)))
    rows.append(("x", (bm, a_l * a_r), (mp, i1 * a_l * a_r)))
    out = ((1, b_r, bm, b_l), (j1, b_r, mp, b_l))
    if backward:
        rows.append(("dy", *out))
        rows.append(("dcore0_fiber", *core0))
        rows += [(f"dcore{t}", shapes[t], shapes[t]) for t in range(1, k)]
        rows.append(("dright", (b_r, e, a_r), (b_r, e, a_r)))
    else:
        rows.append(("out", *out))
    return rows


def vmem_buffers(shapes: Sequence[tuple], block_m: int, m: int,
                 itemsize: int, *, backward: bool = False) -> list:
    """One program's VMEM-resident buffers: ``(name, shape, bytes_per_elem,
    pipelined)`` rows, shapes padded to the (8, 128) VMEM tiling.

    MUST mirror the ``BlockSpec``s of ``_fwd_call`` / ``_bwd_cores_call``
    (``block_shapes``) and the f32 intermediates of the kernel bodies — it
    lives in this file so the model and the specs change together.
    ``repro.analysis.kernel_budget`` sums the rows against the per-core
    VMEM budget, making a tile that cannot fit a lint error before Mosaic
    ever sees it.  Pipelined rows (blocks whose index map CHANGES across
    the grid, so the Pallas pipeline double-buffers the HBM<->VMEM stream)
    cost 2x in residency; whole-array blocks (constant index maps) and
    kernel-body intermediates are resident once."""
    shapes = [tuple(s) for s in shapes]
    k, a_l, b_l, a_r, b_r, e = _geometry(shapes)
    bm = _effective_block_m(block_m, m)
    bufs = []
    for name, block, array in block_shapes(shapes, block_m, m,
                                           backward=backward):
        isz = 4 if name in ("right", "dright") else itemsize
        bufs.append((name, _tiled(block), isz, block != array))
    # f32 values of the kernel body: the left part (and, backward, its
    # cotangent carry), one column block before and after its relayout,
    # the upcast x block and the per-column partial product / dW block
    bufs.append(("left_f32", _tiled((a_l * b_l, e)), 4, False))
    bufs.append(("col_f32", _tiled((a_l * b_l, a_r)), 4, False))
    bufs.append(("w_col_f32", _tiled((a_l * a_r, b_l)), 4, False))
    bufs.append(("x_f32", _tiled((bm, a_l * a_r)), 4, False))
    bufs.append(("part_f32", _tiled((bm, b_l)), 4, False))
    if backward:
        bufs.append(("dleft_f32", _tiled((a_l * b_l, e)), 4, False))
        bufs.append(("dw_col_f32", _tiled((a_l * a_r, b_l)), 4, False))
    return bufs


def kernel_fits(shapes: Sequence[tuple], block_m: int, *,
                itemsize: int = 4, backward: bool = False,
                budget: int = VMEM_BUDGET) -> bool:
    """Worst-case VMEM feasibility of one program at this tile height
    (f32 operands assumed — the conservative case)."""
    used = 0
    for _, shape, isz, pipelined in vmem_buffers(shapes, block_m, block_m,
                                                 itemsize,
                                                 backward=backward):
        used += math.prod(shape) * isz * (2 if pipelined else 1)
    return used <= budget


# --------------------------------------------------------------------------
# the two-part tile rebuild (pure functions of VALUES, not refs: the
# kernels call them on loaded blocks, and the cores-backward pulls
# cotangents back through them with ``jax.vjp``)
# --------------------------------------------------------------------------


def _build_left(fiber: jax.Array, lcores: list) -> jax.Array:
    """``L[(a_l), (b_l, e)]``: core 0's bond fiber chained through the
    left cores, rows = their i legs, cols = their j legs then the bond."""
    v = fiber[None, :]                                     # (1, d1)
    rows = cols = 1
    for c in lcores:
        d, a, b, e = c.shape
        m = v.reshape(rows * cols, d) @ c.reshape(d, a * b * e)
        if cols == 1:
            v = m.reshape(rows * a, b * e)
        else:
            v = (m.reshape(rows, cols, a, b * e).transpose(0, 2, 1, 3)
                 .reshape(rows * a, cols * b * e))
        rows, cols = rows * a, cols * b
    return v


def _column_block(left2: jax.Array, r_s: jax.Array, a_l: int,
                  b_l: int) -> jax.Array:
    """``W_s[(a_l, a_r), b_l]`` for one trailing output index ``s``."""
    a_r = r_s.shape[1]
    t = left2 @ r_s                                        # (a_l*b_l, a_r)
    return t.reshape(a_l, b_l, a_r).transpose(0, 2, 1).reshape(a_l * a_r,
                                                               b_l)


def _column_block_t(dw: jax.Array, a_l: int, a_r: int, b_l: int):
    """Transpose of ``_column_block``'s relayout: ``dW_s -> dT_s``."""
    return dw.reshape(a_l, a_r, b_l).transpose(0, 2, 1).reshape(a_l * b_l,
                                                                a_r)


def _right_block(rcores: list) -> jax.Array:
    """``R`` arranged ``(b_r, e, a_r)`` in f32: the trailing cores
    contracted (XLA, once per call — a few KiB)."""
    r = rcores[0].astype(jnp.float32)                      # (e, a, b, d)
    for c in rcores[1:]:
        e0, a0, b0, _ = r.shape
        _, a, b, d = c.shape
        r = jnp.einsum("xABd,dabe->xAaBbe", r, c.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST
                       ).reshape(e0, a0 * a, b0 * b, d)
    return r[..., 0].transpose(2, 0, 1)


def _load_left(core0_ref, lrefs):
    fiber = core0_ref[0, 0, 0, :].astype(jnp.float32)
    return fiber, [r[...].astype(jnp.float32) for r in lrefs]


def _fwd_kernel(*refs, n_left: int, a_l: int, b_l: int):
    core0_ref, lrefs = refs[0], refs[1:1 + n_left]
    r_ref, x_ref, o_ref = refs[1 + n_left:]
    b_r, e, _ = r_ref.shape
    left2 = _build_left(*_load_left(core0_ref, lrefs)).reshape(a_l * b_l, e)
    x_tile = x_ref[...].astype(jnp.float32)                # (bm, I/i1)
    first = pl.program_id(2) == 0

    def column(s, carry):
        w_s = _column_block(left2, r_ref[s], a_l, b_l)
        part = x_tile @ w_s                                # (bm, b_l)

        @pl.when(first)
        def _init():
            o_ref[0, s] = part.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(first))
        def _acc():
            o_ref[0, s] = (o_ref[0, s].astype(jnp.float32)
                           + part).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, b_r, column, 0)


def _prep(cores: Sequence[jax.Array], m: int, block_m: int):
    shapes = [tuple(c.shape) for c in cores]
    k, a_l, b_l, a_r, b_r, e = _geometry(shapes)
    _, i1, j1, d1 = shapes[0]
    bm = _effective_block_m(block_m, m)
    mp = -(-m // bm) * bm
    return k, a_l, b_l, a_r, b_r, e, i1, j1, d1, bm, mp


def _fwd_call(cores: Sequence[jax.Array], x: jax.Array,
              block_m: int, interpret: bool) -> jax.Array:
    """Raw fused forward: ``y[..., J] = x[..., I] @ W(cores)``, W in VMEM
    tiles only."""
    cores = list(cores)
    i_dim = math.prod(c.shape[1] for c in cores)
    j_dim = math.prod(c.shape[2] for c in cores)
    lead = x.shape[:-1]
    m = math.prod(lead) if lead else 1
    k, a_l, b_l, a_r, b_r, e, i1, j1, d1, bm, mp = _prep(cores, m, block_m)
    xm = x.reshape(m, i_dim)
    if mp != m:
        xm = jnp.pad(xm, ((0, mp - m), (0, 0)))
    right = _right_block(cores[k:])

    in_specs = [pl.BlockSpec((1, 1, 1, d1),
                             lambda mi, jj, ii: (ii, jj, 0, 0))]
    for c in cores[1:k]:
        in_specs.append(pl.BlockSpec(c.shape, lambda mi, jj, ii: (0,) * 4))
    in_specs.append(pl.BlockSpec(right.shape, lambda mi, jj, ii: (0,) * 3))
    in_specs.append(pl.BlockSpec((bm, a_l * a_r),
                                 lambda mi, jj, ii: (mi, ii)))
    out_spec = pl.BlockSpec((1, b_r, bm, b_l),
                            lambda mi, jj, ii: (jj, 0, mi, 0))

    kernel = functools.partial(_fwd_kernel, n_left=k - 1, a_l=a_l, b_l=b_l)
    y = pl.pallas_call(
        kernel,
        grid=(mp // bm, j1, i1),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((j1, b_r, mp, b_l), x.dtype),
        interpret=interpret,
    )(cores[0].reshape(i1, j1, 1, d1), *cores[1:k], right, xm)
    y = y.transpose(2, 0, 3, 1).reshape(mp, j_dim)
    return y[:m].reshape(*lead, j_dim)


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------


def _bwd_cores_kernel(*refs, n_left: int, a_l: int, b_l: int):
    """One (i1, j1, token-block) program of ``dW = x^T dy``, pulled back
    into core space column block by column block.

    No dW column block outlives its loop iteration: each is pulled back
    into ``dR`` (accumulated in the output) and into the left part's
    cotangent, which ``jax.vjp`` of ``_build_left`` turns into per-core
    gradient contributions.  Grid is ``(i1, j1, M/bm)`` with the token axis
    innermost: core 0's (i1, j1) gradient block is revisited consecutively
    over token blocks, and the whole-array outputs (left cores, ``dR``)
    are revisited by every program.
    """
    core0_ref, lrefs = refs[0], refs[1:1 + n_left]
    r_ref, x_ref, dy_ref = refs[1 + n_left:4 + n_left]
    dcore0_ref = refs[4 + n_left]
    dl_refs = refs[5 + n_left:5 + 2 * n_left]
    dr_ref = refs[5 + 2 * n_left]
    b_r, e, a_r = r_ref.shape
    left, pullback = jax.vjp(_build_left, *_load_left(core0_ref, lrefs))
    left2 = left.reshape(a_l * b_l, e)
    x_tile = x_ref[...].astype(jnp.float32)                # (bm, I/i1)
    mi = pl.program_id(2)
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (mi == 0))

    def accum(ref, idx, val, init):
        @pl.when(init)
        def _init():
            ref[idx] = val.astype(ref.dtype)

        @pl.when(jnp.logical_not(init))
        def _acc():
            ref[idx] = (ref[idx].astype(jnp.float32) + val).astype(ref.dtype)

    def column(s, dleft2):
        r_s = r_ref[s]                                     # (e, a_r)
        dy_s = dy_ref[0, s].astype(jnp.float32)            # (bm, b_l)
        dw = x_tile.T @ dy_s                               # (I/i1, b_l)
        dt = _column_block_t(dw, a_l, a_r, b_l)            # (a_l*b_l, a_r)
        accum(dr_ref, s, left2.T @ dt, first)
        return dleft2 + dt @ r_s.T

    dleft2 = jax.lax.fori_loop(0, b_r, column,
                               jnp.zeros((a_l * b_l, e), jnp.float32))
    dfiber, dlcores = pullback(dleft2.reshape(a_l, b_l * e))
    accum(dcore0_ref, (0, 0, 0, slice(None)), dfiber, mi == 0)
    for ref, val in zip(dl_refs, dlcores):
        accum(ref, ..., val, first)


def _bwd_cores_call(cores: list, x: jax.Array, dy: jax.Array,
                    block_m: int, interpret: bool) -> tuple:
    """Per-core gradients of ``sum(dy * (x @ W(cores)))`` — dense dW is
    never materialized (one VMEM column block at a time)."""
    cores = list(cores)
    i_dim = math.prod(c.shape[1] for c in cores)
    j_dim = math.prod(c.shape[2] for c in cores)
    m = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    k, a_l, b_l, a_r, b_r, e, i1, j1, d1, bm, mp = _prep(cores, m, block_m)
    xm = x.reshape(m, i_dim)
    dym = dy.reshape(m, j_dim)
    if mp != m:
        # zero rows contribute nothing to x^T dy
        xm = jnp.pad(xm, ((0, mp - m), (0, 0)))
        dym = jnp.pad(dym, ((0, mp - m), (0, 0)))
    dym = dym.reshape(mp, j1, b_l, b_r).transpose(1, 3, 0, 2)
    right, right_vjp = jax.vjp(_right_block, cores[k:])

    fiber_spec = pl.BlockSpec((1, 1, 1, d1),
                              lambda ii, jj, mi: (ii, jj, 0, 0))
    whole = [pl.BlockSpec(c.shape, lambda ii, jj, mi: (0,) * 4)
             for c in cores[1:k]]
    right_spec = pl.BlockSpec(right.shape, lambda ii, jj, mi: (0,) * 3)
    in_specs = [fiber_spec, *whole, right_spec,
                pl.BlockSpec((bm, a_l * a_r), lambda ii, jj, mi: (mi, ii)),
                pl.BlockSpec((1, b_r, bm, b_l),
                             lambda ii, jj, mi: (jj, 0, mi, 0))]
    out_specs = [fiber_spec, *whole, right_spec]
    out_shape = ([jax.ShapeDtypeStruct((i1, j1, 1, d1), cores[0].dtype)]
                 + [jax.ShapeDtypeStruct(c.shape, c.dtype)
                    for c in cores[1:k]]
                 + [jax.ShapeDtypeStruct(right.shape, jnp.float32)])

    kernel = functools.partial(_bwd_cores_kernel, n_left=k - 1, a_l=a_l,
                               b_l=b_l)
    outs = pl.pallas_call(
        kernel,
        grid=(i1, j1, mp // bm),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(cores[0].reshape(i1, j1, 1, d1), *cores[1:k], right, xm, dym)
    dcore0, dleft, dright = outs[0], outs[1:k], outs[k]
    (drcores,) = right_vjp(dright)
    return (dcore0.reshape(cores[0].shape), *dleft,
            *(d.astype(c.dtype) for d, c in zip(drcores, cores[k:])))


# --------------------------------------------------------------------------
# custom VJP assembly
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mpo_linear(cores: tuple, x: jax.Array, block_m: int,
                interpret: bool) -> jax.Array:
    return _fwd_call(cores, x, block_m, interpret)


def _mpo_linear_fwd(cores, x, block_m, interpret):
    return _fwd_call(cores, x, block_m, interpret), (cores, x)


def _mpo_linear_bwd(block_m, interpret, res, dy):
    cores, x = res
    # dx = dy @ W^T: the forward kernel over i/j-swapped cores — the
    # cotangent is contracted against tile-reconstructed W^T, tile by tile.
    cores_t = tuple(c.transpose(0, 2, 1, 3) for c in cores)
    dx = _fwd_call(cores_t, dy, block_m, interpret).astype(x.dtype)
    lead = x.shape[:-1]
    m = math.prod(lead) if lead else 1
    dcores = _bwd_cores_call(list(cores), x.reshape(m, -1),
                             dy.reshape(m, -1), block_m, interpret)
    return dcores, dx


_mpo_linear.defvjp(_mpo_linear_fwd, _mpo_linear_bwd)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def mpo_linear(cores: Sequence[jax.Array], x: jax.Array, *,
               block_m: int = DEFAULT_BLOCK_M, interpret: bool) -> jax.Array:
    """``y[..., J] = x[..., I] @ W(cores)`` without materializing W in HBM.

    Differentiable: gradients flow to ``cores`` (accumulated in core space
    by ``_bwd_cores_kernel`` — no dense dW) and to ``x`` (forward kernel on
    transposed cores).  ``interpret`` is REQUIRED: the caller (normally the
    execution engine via ``kernels.ops``) decides whether the kernel bodies
    run compiled on TPU (``False``) or interpreted in Python on CPU
    (``True``, correctness-only) — ``kernels.tpu.interpret_mode()``.

    ``block_m`` must be a positive multiple of ``BLOCK_M_ALIGN`` (the f32
    sublane count — unaligned tile heights make Mosaic pad every x/out
    tile).  Token counts smaller than ``block_m`` shrink the tile to the
    next multiple of 8 instead of silently adopting an unaligned size.
    The fastest value is shape-dependent — ``kernels.autotune`` measures it.
    """
    validate_block_m(block_m)
    return _mpo_linear(tuple(cores), x, block_m, interpret)
