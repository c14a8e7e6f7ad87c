"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Every parameter carries a tuple of logical axis names (from its ``Annot``).
``make_rules(mesh)`` maps logical names -> mesh axes; ``tree_shardings``
resolves a whole axes-tree into ``NamedSharding``s, silently falling back to
replication for any dim whose size doesn't divide the mesh-axis product
(e.g. qwen3's 40 heads over model=16 — see DESIGN §4).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_rules(mesh: Mesh, *, fsdp: bool = True, sp: bool = False) -> dict:
    """logical axis name -> tuple of mesh axis names.

    ``sp=True`` switches to the sequence-parallel layout: weights are
    REPLICATED over `model` (MPO compression makes them small enough) and
    the `model` axis shards the activations' sequence dim instead — chosen
    for archs whose head counts don't divide the mesh (DESIGN §4).
    """
    multi_pod = "pod" in mesh.axis_names
    batch = ("pod", "data") if multi_pod else ("data",)
    tp = None if sp else ("model",)
    rules = {
        # ---- parameters ----
        "vocab": tp,
        "qkv": tp,               # flattened H*Dh projection dim
        "kv_qkv": tp,            # flattened KV*Dh projection dim
        "ffn": tp,
        "expert": ("model",),    # expert-parallel MoE (kept even under SP)
        "embed": ("data",) if fsdp else None,   # ZeRO-style param shard
        "bond": ("data",) if fsdp else None,    # central-core bond (FSDP)
        "layers": None,          # scan axis
        # ---- activations ----
        "batch": batch,
        "heads": tp,
        "act_seq": ("model",) if sp else None,
        "act_embed": None,
    }
    return rules


def head_safe_rules(rules: dict, cfg, mesh: Mesh) -> dict:
    """Drop TP rules for flattened attention projections whose HEAD count
    doesn't divide the model-axis product.

    ``spec_for``'s divisibility fallback only sees dim sizes: a flattened
    (H*Dh) projection dim usually IS divisible by the mesh axis even when
    the head count is not — the shards then split ``head_dim`` across
    devices after the (B, S, H, Dh) reshape, the exact layout
    ``nn.init_attention`` refuses to annotate at init time (its
    ``q_ok``/``kv_ok`` gate).  Smoke-scale configs disable that gate
    (``shard_multiple=1``), so serving-time placement must re-check against
    the ACTUAL mesh: a head-splitting K/V sharding is not just slow, it has
    produced numerically wrong prefill output under GSPMD partitioning
    (observed on the 8-device forced-CPU mesh: 2 KV heads over model=4).
    Replicating those two projections costs little — MPO compression keeps
    them small, the DESIGN §4 argument."""
    sizes = mesh_axis_sizes(mesh)

    def axis_prod(name):
        ax = rules.get(name)
        if ax is None:
            return 1
        ax = (ax,) if isinstance(ax, str) else ax
        return math.prod(sizes[a] for a in ax)

    out = dict(rules)
    if cfg.num_heads % max(axis_prod("qkv"), 1) != 0:
        out["qkv"] = None
    if cfg.num_kv_heads % max(axis_prod("kv_qkv"), 1) != 0:
        out["kv_qkv"] = None
    return out


def mesh_axis_sizes(mesh: Mesh) -> dict:
    """mesh axis name -> size.  Reads only ``axis_names``/``devices.shape``,
    so any duck-typed stand-in (e.g. ``analysis.sharding_lint.MeshSpec``)
    works — the rule/spec machinery never touches actual devices."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_dims(axes: tuple, shape: tuple, rules: dict, sizes: dict) -> list:
    """Per-dim resolution with provenance: ``(mesh_axes | None, reason)``.

    ``reason`` is one of ``"sharded"`` (rule applied), ``"replicated"`` (no
    rule / explicit None), ``"indivisible"`` (rule present but the dim size
    doesn't divide the mesh-axis product — the silent fallback), or
    ``"axis_reused"`` (mesh axis already consumed by an earlier dim).
    ``spec_for`` keeps only the first element; the static linter
    (``repro.analysis``) reads the reasons to make the fallbacks loud."""
    used = set()
    out = []
    for dim, name in zip(shape, axes):
        mesh_axes = rules.get(name) if name is not None else None
        if mesh_axes is None:
            out.append((None, "replicated"))
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        prod = math.prod(sizes[a] for a in mesh_axes)
        if dim % prod != 0:
            out.append((None, "indivisible"))
            continue
        if any(a in used for a in mesh_axes):
            out.append((None, "axis_reused"))
            continue
        used.update(mesh_axes)
        out.append((mesh_axes, "sharded"))
    return out


def spec_for(axes: tuple, shape: tuple, rules: dict, mesh: Mesh) -> P:
    """PartitionSpec with per-dim divisibility fallback."""
    sizes = mesh_axis_sizes(mesh)
    parts = []
    for mesh_axes, _ in resolve_dims(axes, shape, rules, sizes):
        if mesh_axes is None:
            parts.append(None)
        else:
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(axes_tree, shape_tree, mesh: Mesh, rules: dict):
    """NamedSharding tree from (axes tuples, ShapeDtypeStructs)."""
    is_tup = lambda x: isinstance(x, tuple) or x is None

    def one(axes, sd):
        if axes is None:
            axes = (None,) * len(sd.shape)
        return NamedSharding(mesh, spec_for(axes, sd.shape, rules, mesh))

    return jax.tree.map(one, axes_tree, shape_tree, is_leaf=is_tup)


def batch_sharding(batch_specs, mesh: Mesh, rules: dict):
    """Inputs: shard dim 0 (global batch) over the batch mesh axes, with the
    same divisibility fallback as params (batch=1 decode -> replicate)."""
    b = rules["batch"]
    b = (b,) if isinstance(b, str) else b
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    prod = math.prod(sizes[a] for a in b)

    def one(sd):
        if not sd.shape or sd.shape[0] % prod != 0:
            return NamedSharding(mesh, P())
        first = b if len(b) > 1 else b[0]
        return NamedSharding(mesh, P(first, *([None] * (len(sd.shape) - 1))))

    return jax.tree.map(one, batch_specs)


def cache_sharding(cache_specs, mesh: Mesh, rules: dict):
    """Decode caches: batch dim is dim 1 (dim 0 = layers) for the K/V
    stacks and SSM states, heads/kv dims sharded over model when
    divisible.  Integer leaves (the per-slot ``pos`` counters and the
    paged cache's bookkeeping, held once for all layers) are tiny and stay
    replicated — every device needs every slot's position for masking and
    every page mapping for the gather.

    Paged KV leaves (``k_pages``/``v_pages``: (L, pages, page_size, KV,
    Dh)) get the paged flash layout: the KV-head dim over ``model`` where
    the axis divides it (each device's flash kernel attends its own heads;
    ``nn._paged_attention``), else the in-page sequence dim; the physical
    page dim UNsharded — pages are slot-agnostic, so splitting the pool
    over data devices would turn every table-indexed gather into
    cross-device traffic."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b = rules["batch"]
    b = (b,) if isinstance(b, str) else b
    bprod = math.prod(sizes[a] for a in b)
    mprod = sizes.get("model", 1)

    def paged_leaf(sd):
        parts = [None] * len(sd.shape)
        if sd.shape[3] % mprod == 0:
            parts[3] = "model"
        elif sd.shape[2] % mprod == 0:
            parts[2] = "model"
        return NamedSharding(mesh, P(*parts))

    def one_with_path(path, sd):
        name = str(getattr(path[-1], "key", "")) if path else ""
        if name in ("k_pages", "v_pages"):
            return paged_leaf(sd)
        return one(sd)

    def one(sd):
        shape = sd.shape
        parts = [None] * len(shape)
        if np.issubdtype(np.dtype(sd.dtype), np.integer):
            return NamedSharding(mesh, P())
        if len(shape) >= 5:
            # (L, B, S, KV, Dh) kv-cache or (L, B, H, N, P) ssm state:
            # batch on the data axes; model axis on the LARGEST divisible
            # inner dim — for KV caches that is the sequence dim
            # (flash-decoding layout: attention reduces over the sharded
            # seq with small partial-softmax collectives instead of
            # gathering the cache; §Perf it.10), for SSM states the heads.
            if shape[1] % bprod == 0:
                parts[1] = b if len(b) > 1 else b[0]
            inner = [(shape[i], i) for i in range(2, len(shape) - 1)
                     if shape[i] % mprod == 0]
            if inner:
                parts[max(inner)[1]] = "model"
        elif len(shape) >= 2:
            if shape[0] % bprod == 0:
                parts[0] = b if len(b) > 1 else b[0]
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(one_with_path, cache_specs)


def constrain(x, mesh: Mesh, rules: dict, names: tuple):
    """with_sharding_constraint by logical activation names."""
    spec = spec_for(names, x.shape, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
