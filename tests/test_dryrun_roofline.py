"""Dry-run integration (subprocess with placeholder devices) + HLO-analysis
calibration tests."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=ROOT, timeout=timeout, env=env)


def test_hlo_analysis_calibration():
    """Trip-count-corrected per-device dot flops match hand computation."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        mesh = jax.make_mesh((4, 4), ("data", "model"),
                             (jax.sharding.AxisType.Auto,) * 2)
        B, K, L = 64, 256, 8
        def g(x, ws):
            def body(h, w):
                return jnp.tanh(h @ w), None
            return jax.lax.scan(body, x, ws)[0]
        x = jax.ShapeDtypeStruct((B, K), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, K, K), jnp.float32)
        with mesh:
            c = jax.jit(g, in_shardings=(NamedSharding(mesh, P("data", None)),
                                         None)).lower(x, ws).compile()
        res = analyze(c.as_text())
        expected = 2 * (B // 4) * K * K * L   # per-device, x trip count
        assert res["hlo_dot_flops_per_device"] == expected, res
        print("CALIBRATION_OK")
    """)
    r = _run(code)
    assert "CALIBRATION_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


@pytest.mark.slow
def test_dryrun_one_cell_both_meshes():
    """whisper-tiny decode_32k must lower+compile on 16x16 and 2x16x16."""
    r = _run(textwrap.dedent("""
        import sys; sys.path.insert(0, "src")
        from repro.launch import dryrun  # sets XLA_FLAGS before jax init
        for mp in (False, True):
            rec = dryrun.run_cell("whisper-tiny", "decode_32k", multi_pod=mp,
                                  verbose=False)
            assert rec["devices"] == (512 if mp else 256)
            assert rec["flops_per_device"] > 0
            assert "memory_analysis" in rec
        print("DRYRUN_OK")
    """))
    assert "DRYRUN_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


def test_roofline_terms_math():
    from repro.launch.roofline import roofline
    rec = {"devices": 256,
           "flops_per_device": 197e12,        # exactly 1s of compute
           "bytes_per_device": 819e9,         # exactly 1s of HBM
           "collective_bytes": {"all-gather": 25e9, "all-reduce": 25e9},
           "model_flops": 197e12 * 128,       # half the fleet's peak-second
           "model_flops_dense": 197e12 * 256}
    out = roofline(rec)
    assert out["compute_s"] == pytest.approx(1.0)
    assert out["memory_s"] == pytest.approx(1.0)
    assert out["collective_s"] == pytest.approx(1.0)
    assert out["roofline_fraction"] == pytest.approx(0.5)
    assert out["roofline_fraction_dense_equiv"] == pytest.approx(1.0)
    assert out["dominant"] in ("compute_s", "memory_s", "collective_s")


def test_sweep_results_exist_and_clean():
    """If the sweep artifact is present, every cell must be error-free and
    cover both meshes for all non-skipped cells."""
    path = os.path.join(ROOT, "results_dryrun.jsonl")
    if not os.path.exists(path):
        pytest.skip("sweep not run in this checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs
    recs = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            recs[(r["arch"], r["shape"], r["mesh"])] = r
    missing, errors = [], []
    for arch, shape, skip in configs.cells():
        for mesh in ("16x16", "2x16x16"):
            r = recs.get((arch, shape, mesh))
            if r is None:
                missing.append((arch, shape, mesh))
            elif "error" in r:
                errors.append((arch, shape, mesh, r["error"][:80]))
    assert not errors, errors
    assert not missing, missing
