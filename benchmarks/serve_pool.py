"""Multi-tenant serving benchmark: aggregate decode tok/s vs tenant count,
at 1 / 4 / 8 (simulated, forced-host) CPU devices.

For each device count a fresh subprocess (device count is fixed at jax
startup) measures:

  * ``serial``  — one batch-1 ``ServeHandle``, requests generated one after
                  another (the pre-scheduler behavior);
  * ``pool``    — a ``ServePool`` with ``slots == tenants``: all tenants
                  admitted into one batched decode, finished slots recycled.

The headline number is the aggregate-throughput multiple at 4 tenants
(``speedup_at_4``): one batched decode step costs roughly one single-tenant
step, so serving k tenants concurrently approaches k-fold aggregate tok/s
until the step goes compute-bound.  Results merge into
``BENCH_serve.json`` (section ``serve_pool``) next to the repo root.

Run:  PYTHONPATH=src python -m benchmarks.serve_pool
      PYTHONPATH=src python -m benchmarks.serve_pool --devices 1 4
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ARCH = "qwen3-14b"
PROMPT_LEN = 8
BUDGET = 16
TENANTS = (1, 2, 4, 8)
MAX_LEN = PROMPT_LEN + BUDGET + 1

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON_PATH = os.path.join(_ROOT, "BENCH_serve.json")


def _worker(devices: int) -> dict:
    """Measure serial vs pool tok/s in THIS process (device count already
    forced via XLA_FLAGS by the driver)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro import Session
    from repro.launch.mesh import make_host_mesh

    assert jax.device_count() == devices, (jax.device_count(), devices)
    mesh = make_host_mesh(model=2) if devices > 1 else None
    session = Session.init(ARCH)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, size=PROMPT_LEN).astype(np.int32)
               for _ in range(max(TENANTS))]

    # ---- serial baseline: batch-1 handle, one request after another ----
    h1 = session.serve(1, MAX_LEN, mesh=mesh)
    warm = {"tokens": jnp.asarray(prompts[0])[None, :]}
    jax.block_until_ready(h1.generate(warm, 2))          # compile outside
    n_serial = 4
    t0 = time.perf_counter()
    for p in prompts[:n_serial]:
        jax.block_until_ready(
            h1.generate({"tokens": jnp.asarray(p)[None, :]}, BUDGET))
    serial_s = time.perf_counter() - t0
    serial_tok_s = n_serial * BUDGET / serial_s

    # ---- pool: slots == tenants, all admitted concurrently ----
    pool_tok_s = {}
    for tenants in TENANTS:
        pool = session.serve_pool(slots=tenants, max_len=MAX_LEN, mesh=mesh)
        pool.submit(prompts[0], max_new_tokens=2)        # warm prefill+decode
        pool.run()
        t0 = time.perf_counter()
        for p in prompts[:tenants]:
            pool.submit(p, max_new_tokens=BUDGET)
        pool.run()
        pool_tok_s[tenants] = tenants * BUDGET / (time.perf_counter() - t0)

    return {
        "devices": devices,
        "mesh": None if mesh is None else
        dict(zip(mesh.axis_names, mesh.devices.shape)),
        "serial_tok_s": round(serial_tok_s, 1),
        "pool_tok_s": {str(t): round(v, 1) for t, v in pool_tok_s.items()},
        "speedup_at_4": round(pool_tok_s[4] / serial_tok_s, 2),
    }


def run(device_counts=(1, 4, 8)) -> list[str]:
    results = {}
    for n in device_counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        # children are CPU-only by design: forced host devices stand in for
        # a mesh, and a chip belongs to the one process that opened it
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.join(_ROOT, "src")
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.serve_pool", "--worker",
             "--devices", str(n)],
            capture_output=True, text=True, cwd=_ROOT, env=env, timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(f"worker devices={n} failed:\n{r.stderr[-2000:]}")
        results[str(n)] = json.loads(r.stdout.strip().splitlines()[-1])

    rows = []
    for n, res in results.items():
        for t, v in res["pool_tok_s"].items():
            rows.append(f"serve_pool,devices={n},tenants={t},"
                        f"pool_tok_s={v},serial_tok_s={res['serial_tok_s']}")
        rows.append(f"serve_pool,devices={n},speedup_at_4="
                    f"{res['speedup_at_4']}x")

    section = {"arch": ARCH, "prompt_len": PROMPT_LEN, "budget": BUDGET,
               "by_devices": results}
    try:
        with open(_JSON_PATH) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        existing = {}
    existing["serve_pool"] = section
    with open(_JSON_PATH, "w") as f:
        json.dump(existing, f, indent=2)
        f.write("\n")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 4, 8])
    args = ap.parse_args()
    if args.worker:
        print(json.dumps(_worker(args.devices[0])))
    else:
        print("\n".join(run(tuple(args.devices))))


if __name__ == "__main__":
    main()
