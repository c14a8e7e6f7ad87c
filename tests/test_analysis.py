"""repro.analysis: seeded-violation regression tests per detector family,
baseline workflow, clean-run sweeps, and the CLI gate.

Each detector family gets a test that re-introduces the bug class it was
built to catch (the PR 4 head-dim-splitting rule table, an over-admitting
kernel eligibility gate, a dtype-drifting decode cache) and asserts the
finding comes back with the right check name, severity, and file
provenance."""

import dataclasses
import json

import jax
import pytest

from repro import configs
from repro.analysis import (DEFAULT_MESHES, MeshSpec, lint_sharding,
                            lint_traces, load_baseline, new_findings,
                            save_baseline, summarize)
from repro.analysis import kernel_budget as KB
from repro.analysis import trace_lint as TL
from repro.analysis.findings import Finding
from repro.analysis.sharding_lint import SHARDING_FILE, abstract_params
from repro.parallel import sharding as S

QWEN = configs.get_config("qwen3-14b")


# ------------------------------------------------------- sharding linter


def test_seeded_head_safety_violation_raw_rules():
    """The PR 4 bug class, re-introduced: the RAW make_rules table (no
    head_safe_rules) on a mesh whose model product doesn't divide the head
    count must produce a sharding/head-safety error with provenance."""
    mesh = MeshSpec({"data": 1, "model": 16})
    raw = S.make_rules(mesh)
    assert QWEN.num_heads % 16 != 0  # the seed premise
    found = lint_sharding(QWEN, mesh, rules=raw)
    errs = [f for f in found if f.check == "sharding/head-safety"]
    assert errs, "seeded head-splitting rule table produced no finding"
    assert all(f.severity == "error" for f in errs)
    assert all(f.file == SHARDING_FILE for f in errs)
    assert all(f.config == "qwen3-14b" for f in errs)
    # the production (head-safe) table is clean on the same mesh
    clean = lint_sharding(QWEN, mesh)
    assert not [f for f in clean if f.check == "sharding/head-safety"]


def test_seeded_small_leaf_and_coverage():
    """A data-sharded norm vector (the qk-norm-scale bug) and an
    uncovered logical axis name are both errors."""
    mesh = MeshSpec({"data": 2, "model": 4})
    shapes = {"norm": jax.ShapeDtypeStruct((8,), "float32"),
              "w": jax.ShapeDtypeStruct((16, 16), "float32")}
    axes = {"norm": ("embed",), "w": ("mystery_axis", "ffn")}
    rules = {"embed": ("data",), "ffn": ("model",)}
    found = lint_sharding(QWEN, mesh, rules=rules, shapes=shapes, axes=axes)
    by_check = {f.check for f in found}
    assert "sharding/small-leaf" in by_check
    assert "sharding/coverage" in by_check
    small = next(f for f in found if f.check == "sharding/small-leaf")
    assert small.severity == "error" and small.location == "norm"


def test_divisibility_fallback_is_a_warning():
    mesh = MeshSpec({"data": 1, "model": 4})
    shapes = {"w": jax.ShapeDtypeStruct((10, 16), "float32")}
    axes = {"w": ("ffn", None)}
    found = lint_sharding(QWEN, mesh, rules={"ffn": ("model",)},
                          shapes=shapes, axes=axes)
    div = [f for f in found if f.check == "sharding/divisibility"]
    assert len(div) == 1 and div[0].severity == "warning"
    assert "10" in div[0].message and "[dim 0]" in div[0].location


def test_resolve_dims_reasons():
    sizes = {"data": 2, "model": 4}
    rules = {"ffn": ("model",), "embed": ("data",)}
    res = S.resolve_dims(("ffn", "embed", "ffn", None), (16, 5, 8, 3),
                         rules, sizes)
    assert res[0] == (("model",), "sharded")
    assert res[1] == (None, "indivisible")
    assert res[2] == (None, "axis_reused")
    assert res[3] == (None, "replicated")


# -------------------------------------------------- kernel budget checker


def test_seeded_overbudget_tile_reported():
    """Pre-fix eligibility gate (alignment only, no VMEM feasibility):
    the checker must flag tiles the gate admits but VMEM can't hold."""
    shapes_tree, _ = abstract_params(QWEN)
    big = max(KB._core_shape_sets(shapes_tree),
              key=lambda s: sum(a * b * c * d for a, b, c, d in s))
    alignment_only = lambda shapes, bm, train=False: True
    found = KB.lint_mpo_call(big, config="qwen3-14b",
                             eligible_fn=alignment_only)
    errs = [f for f in found if f.check == "kernel/vmem-budget"
            and f.severity == "error"]
    assert errs, "over-admitting gate produced no vmem-budget error"
    assert all(f.file == KB.MPO_FILE for f in errs)
    assert any("block_m=" in f.location for f in errs)
    # the REAL gate embeds kernel_fits: same shapes, no errors
    real = KB.lint_mpo_call(big, config="qwen3-14b")
    assert not [f for f in real
                if f.check == "kernel/vmem-budget" and f.severity == "error"]


def test_decode_attention_geometry_checks():
    clean = KB.lint_decode_attention_call(8, 4, 128, 16, 16, config="x")
    assert not [f for f in clean if f.severity == "error"]
    # unaligned head_dim/page_size are informational, not gating
    padded = KB.lint_decode_attention_call(8, 4, 64, 12, 16, config="x")
    checks = {(f.check, f.severity) for f in padded}
    assert ("kernel/tile-alignment", "info") in checks
    assert ("kernel/tile-alignment", "warning") in checks
    # an absurd VMEM budget turns residency into an error
    tight = KB.lint_decode_attention_call(8, 4, 128, 16, 16, config="x",
                                          budget=1024)
    assert [f for f in tight if f.check == "kernel/vmem-budget"
            and f.severity == "error"]


def test_seeded_block_shape_violations_reported():
    """The geometries the TPU compiler refused before the kernels were
    repaired — core 0's (1, 1, 1, d1) fiber block over (1, i1, j1, d1), and
    a one-KV-head page block (1, ps, 1, Dh) — are block-shape errors; the
    kernels' real geometries at qwen3-14b widths are clean."""
    wq = ((1, 5, 5, 25), (25, 8, 8, 128), (128, 8, 8, 128), (128, 4, 4, 16),
          (16, 4, 4, 1))

    def fiber_blocks(shapes, bm, m, backward=False):
        _, i1, j1, d1 = shapes[0]
        return [("core0_fiber", (1, 1, 1, d1), (1, i1, j1, d1))]

    found = KB.lint_mpo_call(wq, config="qwen3-14b", blocks_fn=fiber_blocks)
    errs = [f for f in found if f.check == "kernel/block-shape"]
    assert errs and all(f.severity == "error" for f in errs)
    assert all(f.location.endswith(":core0_fiber") for f in errs)
    real = KB.lint_mpo_call(wq, config="qwen3-14b")
    assert not [f for f in real if f.check == "kernel/block-shape"]

    def one_head_pages(b, kv, g, dh, ps, mp, pool):
        return [("k_page", (1, ps, 1, dh), (pool, ps, kv, dh))]

    found = KB.lint_decode_attention_call(8, 5, 128, 16, 16, config="x",
                                          blocks_fn=one_head_pages)
    assert [f.location for f in found if f.check == "kernel/block-shape"] \
        == ["kv=8,g=5,dh=128,ps=16,mp=16:k_page"]
    real = KB.lint_decode_attention_call(8, 5, 128, 16, 16, config="x")
    assert not [f for f in real if f.check == "kernel/block-shape"]


def test_kernel_constants_tripwire():
    assert KB.lint_constants() == []


# ------------------------------------------------------ trace-hazard lint


def test_seeded_cache_dtype_drift():
    """A decode step whose output cache leaf drifts to another dtype is the
    donation-breaking bug; the check must name the leaf."""
    cache_in = {"k": jax.ShapeDtypeStruct((2, 8), "bfloat16"),
                "pos": jax.ShapeDtypeStruct((2,), "int32")}
    cache_out = {"k": jax.ShapeDtypeStruct((2, 8), "float32"),
                 "pos": jax.ShapeDtypeStruct((2,), "int32")}
    found = TL.cache_drift_findings(cache_in, cache_out, config="seeded")
    assert len(found) == 1
    f = found[0]
    assert f.check == "trace/cache-drift" and f.severity == "error"
    assert "cache/k" in f.location and f.file == TL.MODEL_FILE
    # structural drift (a leaf present on only one side) is also an error
    found = TL.cache_drift_findings(cache_in, {"pos": cache_out["pos"]},
                                    config="seeded")
    assert [f for f in found if "cache/k" in f.location]


def test_trace_lint_clean_on_dense_config():
    found = lint_traces(configs.get_config("bert-base"))
    assert not [f for f in found if f.severity == "error"], \
        summarize(found)


def test_trace_shapes_cover_vlm_frontend():
    cfg = configs.get_config("llava-next-34b")
    shapes = TL.trace_shapes(cfg)
    assert shapes["prefill"].seq_len > cfg.frontend_len
    assert shapes["train"].seq_len > cfg.frontend_len


# ------------------------------------------------------ baseline workflow


def _mk(loc, sev="error"):
    return Finding(check="c", severity=sev, file="f.py", location=loc,
                   message="m", config="cfg")


def test_baseline_roundtrip_suppresses_known_findings(tmp_path):
    path = str(tmp_path / "base.json")
    known = [_mk("a"), _mk("b")]
    save_baseline(path, known)
    fps = load_baseline(path)
    assert new_findings(known, fps) == []
    novel = _mk("c")
    assert new_findings(known + [novel], fps) == [novel]
    # fingerprints ignore the message: re-worded finding stays suppressed
    reworded = dataclasses.replace(known[0], message="different words")
    assert new_findings([reworded], fps) == []


def test_malformed_baseline_suppresses_nothing(tmp_path):
    path = str(tmp_path / "bad.json")
    path2 = str(tmp_path / "worse.json")
    with open(path, "w") as f:
        f.write("not json {")
    with open(path2, "w") as f:
        json.dump({"version": 99, "fingerprints": {"x": "y"}}, f)
    assert load_baseline(path) == set()
    assert load_baseline(path2) == set()


# --------------------------------------------------- clean sweep + report


@pytest.mark.parametrize("arch", ["qwen3-14b", "whisper-tiny",
                                  "mamba2-130m", "phi3.5-moe-42b-a6.6b"])
def test_sharding_and_kernels_clean_across_default_meshes(arch):
    """The acceptance bar: production rule tables and kernel budgets are
    error-free for in-tree configs at 1/4/8-device meshes (warnings — the
    designed divisibility fallbacks — are allowed)."""
    cfg = configs.get_config(arch)
    found = []
    for mesh in DEFAULT_MESHES:
        found += lint_sharding(cfg, mesh)
    found += KB.lint_kernels(cfg)
    assert not [f for f in found if f.severity == "error"], summarize(found)


def test_session_report_surfaces_analysis():
    from repro.pipeline import Session
    s = Session.init("albert-base", num_classes=2)
    rep = s.report()
    ana = rep["analysis"]
    assert ana["errors"] == 0, ana
    assert ana["meshes"] and "by_check" in ana


# --------------------------------------------------------------- the CLI


def test_cli_gate_and_baseline(tmp_path, capsys):
    from repro.analysis.cli import main
    base = str(tmp_path / "baseline.json")
    args = ["--configs", "albert-base", "--families", "sharding", "-q"]
    # albert's bond-3 cores produce divisibility warnings at model=4:
    # default gate (error) passes, warning gate fails...
    assert main(args) == 0
    assert main(args + ["--fail-on", "warning"]) == 1
    # ...until the findings are recorded as the baseline
    assert main(args + ["--write-baseline", base]) == 0
    assert main(args + ["--fail-on", "warning", "--baseline", base]) == 0
    out = capsys.readouterr().out
    assert "baseline-suppressed" in out


def test_cli_json_output(capsys):
    from repro.analysis.cli import main
    rc = main(["--configs", "bert-base", "--families", "sharding",
               "--meshes", "1x1", "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert "summary" in payload and "findings" in payload
    for f in payload["findings"]:
        assert "fingerprint" in f and "new" in f
