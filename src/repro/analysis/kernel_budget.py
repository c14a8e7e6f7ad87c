"""Pallas kernel budget checker — VMEM residency, alignment, index bounds.

The residency models (``vmem_buffers``) live NEXT TO the kernels whose
``BlockSpec``s they mirror (``kernels/mpo_linear.py``,
``kernels/decode_attention.py``); this module walks a config's MPO core
shapes and serving attention geometry, sums worst-case per-program VMEM
bytes against a per-core budget, and enforces the centralized tile rules:

``kernel/vmem-budget``      worst-case residency of one program exceeds
                            the per-core VMEM budget (error at the
                            analytic default tile, warning for larger
                            autotuner candidates — those lose the race by
                            construction but show the headroom).
``kernel/tile-alignment``   the centralized ``block_m``/candidate-grid
                            alignment rules (``BLOCK_M_ALIGN``, lane=128)
                            — a tripwire against editing one constant
                            without the other.
``kernel/block-shape``      Mosaic's block-shape rule: the last two dims of
                            every ``BlockSpec`` block divisible by (8, 128)
                            or equal to the array's — interpret mode never
                            checks it, the TPU compiler refuses the kernel.
``kernel/page-bounds``      ``decode_attention``'s page-table index maps,
                            evaluated at the corner cases (empty slot,
                            full slot, unmapped ``-1`` pages, last logical
                            page), must stay inside the physical pool.
"""

from __future__ import annotations

import math

import numpy as np

from repro.analysis.findings import Finding
from repro.kernels import autotune
from repro.kernels import decode_attention as DA
from repro.kernels import mpo_linear as MK
from repro.kernels.tpu import block_shape_ok

MPO_FILE = "src/repro/kernels/mpo_linear.py"
DA_FILE = "src/repro/kernels/decode_attention.py"

# pallas_guide: ~16 MiB of VMEM per TensorCore; the budget is deliberately
# the full size — the checker models *worst-case* residency (everything
# double-buffered), so a pass here means the tile genuinely fits.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


def residency_bytes(buffers) -> int:
    """Sum ``(name, shape, bytes_per_elem, pipelined)`` rows; pipelined
    blocks are double-buffered by the Pallas pipeline (2x)."""
    total = 0
    for _, shape, itemsize, pipelined in buffers:
        total += math.prod(shape) * itemsize * (2 if pipelined else 1)
    return int(total)


def _fmt_mib(b: int) -> str:
    return f"{b / (1024 * 1024):.2f} MiB"


def lint_block_shapes(rows, *, file: str, location: str,
                      config: str = "") -> list:
    """``kernel/block-shape`` errors for ``(name, block, array)`` rows that
    break Mosaic's (8, 128)-or-full rule."""
    return [Finding(
        check="kernel/block-shape", severity="error", file=file,
        location=f"{location}:{name}",
        message=f"block {tuple(block)} over array {tuple(array)}: the last "
                f"two block dims must be divisible by (8, 128) or equal the "
                f"array's — the TPU compiler refuses this kernel",
        config=config)
        for name, block, array in rows if not block_shape_ok(block, array)]


def lint_mpo_call(shapes, *, config: str = "", location: str = "",
                  itemsize: int = 4,
                  budget: int = DEFAULT_VMEM_BUDGET,
                  eligible_fn=None, blocks_fn=None) -> list:
    """Budget findings for one fused-MPO-linear call site (one core shape
    set), in all three program variants the custom_vjp can run: forward,
    dx (forward kernel over i/j-swapped cores), and the cores-backward.

    The invariant: any (shapes, block_m) the eligibility gate admits must
    fit the per-core VMEM budget at worst-case residency — the gate
    (``kernel_eligible``) embeds ``kernel_fits``, so a finding here means
    the gate and the residency model have diverged (someone relaxed one
    without the other).  ``eligible_fn`` is injectable so the regression
    test can seed the pre-fix gate (alignment only) and watch the
    over-budget tile get reported; ``blocks_fn`` (``MK.block_shapes``'s
    signature) likewise seeds a block geometry the compiler refuses."""
    eligible_fn = eligible_fn or MK.kernel_eligible
    blocks_fn = blocks_fn or MK.block_shapes
    shapes = tuple(tuple(s) for s in shapes)
    loc = location or "x".join(str(d) for s in shapes for d in s)
    findings = []
    shapes_t = tuple((s[0], s[2], s[1], s[3]) for s in shapes)
    candidates = sorted(set(autotune.CANDIDATE_BLOCK_MS)
                        | {MK.DEFAULT_BLOCK_M})
    any_admitted = False
    for bm in candidates:
        for label, shp, backward, train in (
                ("fwd", shapes, False, False),
                ("dx", shapes_t, False, True),
                ("dcores", shapes, True, True)):
            if not eligible_fn(shapes, bm, train=train):
                continue
            any_admitted = True
            findings += lint_block_shapes(
                blocks_fn(shp, bm, bm, backward=backward), file=MPO_FILE,
                location=f"{loc}:{label}@block_m={bm}", config=config)
            used = residency_bytes(MK.vmem_buffers(
                shp, bm, bm, itemsize, backward=backward))
            if used > budget:
                findings.append(Finding(
                    check="kernel/vmem-budget", severity="error",
                    file=MPO_FILE,
                    location=f"{loc}:{label}@block_m={bm}",
                    message=f"eligibility gate admits this tile but its "
                            f"worst-case VMEM residency {_fmt_mib(used)} "
                            f"exceeds the {_fmt_mib(budget)} per-core "
                            f"budget — compiling it would abort on "
                            f"hardware", config=config))
    if MK.layout_ok(shapes) and not any_admitted:
        findings.append(Finding(
            check="kernel/vmem-budget", severity="info", file=MPO_FILE,
            location=loc,
            message="the compiler accepts this layout, but no candidate "
                    "tile fits the VMEM budget — the fused kernel is "
                    "disabled for this matrix (planner falls back to "
                    "factorized/reconstruct)",
            config=config))
    return findings


def lint_decode_attention_call(num_kv_heads: int, group: int, head_dim: int,
                               page_size: int, max_pages: int, *,
                               config: str = "", itemsize: int = 2,
                               budget: int = DEFAULT_VMEM_BUDGET,
                               blocks_fn=None) -> list:
    """Budget + block-shape + alignment + index-map-bounds findings for one
    flash decode-attention geometry (``blocks_fn`` seeds a block geometry,
    as in ``lint_mpo_call``)."""
    loc = (f"kv={num_kv_heads},g={group},dh={head_dim},"
           f"ps={page_size},mp={max_pages}")
    blocks_fn = blocks_fn or DA.block_shapes
    # two slots sharing the pool: a block must fit any slot/page count
    findings = lint_block_shapes(
        blocks_fn(2, num_kv_heads, group, head_dim, page_size, max_pages,
                  2 * max_pages), file=DA_FILE, location=loc, config=config)

    used = residency_bytes(DA.vmem_buffers(num_kv_heads, group, head_dim,
                                           page_size, itemsize))
    if used > budget:
        findings.append(Finding(
            check="kernel/vmem-budget", severity="error", file=DA_FILE,
            location=loc,
            message=f"worst-case VMEM residency {_fmt_mib(used)} exceeds "
                    f"the {_fmt_mib(budget)} per-core budget",
            config=config))

    if head_dim % 128 != 0:
        findings.append(Finding(
            check="kernel/tile-alignment", severity="info", file=DA_FILE,
            location=loc,
            message=f"head_dim={head_dim} is not lane-aligned (128): Mosaic "
                    f"pads every q/k/v block — correct but bandwidth-wasteful",
            config=config))
    if page_size % 8 != 0:
        findings.append(Finding(
            check="kernel/tile-alignment", severity="warning", file=DA_FILE,
            location=loc,
            message=f"page_size={page_size} is not sublane-aligned (8): "
                    f"every streamed KV page block gets padded",
            config=config))

    # ---- page-table index-map bounds at the corner cases ----
    pool = max(max_pages, 1)  # worst case: one slot owns every page
    table_cases = {
        "unmapped": np.full((max_pages,), -1, np.int32),
        "identity": np.arange(max_pages, dtype=np.int32),
        "last-page": np.full((max_pages,), pool - 1, np.int32),
    }
    len_cases = (0, 1, page_size, page_size * max_pages)
    for tname, table in table_cases.items():
        for ln in len_cases:
            lens = np.array([ln], np.int32)
            for p in (0, max(max_pages - 1, 0)):
                idx = DA._kv_index_map(0, p, table, lens,
                                       page_size=page_size,
                                       max_pages=max_pages)
                phys = int(idx[0])
                if not 0 <= phys < pool:
                    findings.append(Finding(
                        check="kernel/page-bounds", severity="error",
                        file=DA_FILE,
                        location=f"{loc}:_kv_index_map(p={p},len={ln},"
                                 f"table={tname})",
                        message=f"physical page index {phys} is outside the "
                                f"pool [0, {pool}) — out-of-bounds DMA",
                        config=config))
                b_idx = DA._bias_index_map(0, p, table, lens,
                                           page_size=page_size)
                lp = int(b_idx[1])
                if not 0 <= lp < max_pages:
                    findings.append(Finding(
                        check="kernel/page-bounds", severity="error",
                        file=DA_FILE,
                        location=f"{loc}:_bias_index_map(p={p},len={ln})",
                        message=f"logical page index {lp} is outside "
                                f"[0, {max_pages})",
                        config=config))
    return findings


def lint_constants() -> list:
    """Config-independent tripwires on the centralized tile constants."""
    findings = []
    for bm in autotune.CANDIDATE_BLOCK_MS:
        try:
            MK.validate_block_m(bm)
        except ValueError as e:
            findings.append(Finding(
                check="kernel/tile-alignment", severity="error",
                file=MPO_FILE, location=f"CANDIDATE_BLOCK_MS[{bm}]",
                message=str(e)))
    try:
        MK.validate_block_m(MK.DEFAULT_BLOCK_M)
    except ValueError as e:
        findings.append(Finding(
            check="kernel/tile-alignment", severity="error", file=MPO_FILE,
            location="DEFAULT_BLOCK_M", message=str(e)))
    return findings


def _core_shape_sets(shapes_tree) -> set:
    """Distinct MPO core shape tuples in a params-shape tree (trailing 4
    legs — leading stacked dims are per-matrix batching, not tile shape)."""
    from repro.core import layers
    out = set()

    def visit(node):
        if isinstance(node, dict):
            if "cores" in node:
                cores = layers.cores_to_list(node["cores"])
                out.add(tuple(tuple(c.shape[-4:]) for c in cores))
                return
            for v in node.values():
                visit(v)

    visit(shapes_tree)
    return out


def lint_kernels(cfg, *, shapes_tree=None, page_size: int = 16,
                 max_pages: int = 16,
                 budget: int = DEFAULT_VMEM_BUDGET) -> list:
    """All kernel-budget findings for one config."""
    from repro.analysis.sharding_lint import abstract_params
    if shapes_tree is None:
        shapes_tree, _ = abstract_params(cfg)
    itemsize = np.dtype(cfg.jnp_dtype).itemsize
    findings = list(lint_constants())
    for shapes in sorted(_core_shape_sets(shapes_tree)):
        findings += lint_mpo_call(shapes, config=cfg.name,
                                  itemsize=itemsize, budget=budget)
    # paged serving (and therefore the flash decode kernel) is rejected for
    # families whose caches aren't per-slot token KV — don't lint a kernel
    # that can never run there
    if cfg.num_heads and cfg.num_kv_heads \
            and cfg.family not in ("ssm", "hybrid", "encdec"):
        group = max(cfg.num_heads // max(cfg.num_kv_heads, 1), 1)
        head_dim = cfg.head_dim or cfg.d_model // cfg.num_heads
        findings += lint_decode_attention_call(
            cfg.num_kv_heads, group, head_dim, page_size, max_pages,
            config=cfg.name, itemsize=itemsize, budget=budget)
    return findings
