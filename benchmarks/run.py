"""Benchmark harness — one entry per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [table1 table2 ...]``
Prints ``name,metric,...`` CSV rows per the assignment contract; exits
non-zero when any requested suite raised.
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> int:
    from benchmarks import (decode_attention, engine_modes, fig2_lowrank,
                            kernel_vjp, roofline, router_fleet, serve_pool,
                            table1_variation, table2_complexity,
                            table3_glue_analog, table4_variants,
                            table5_last_layers, traffic_replay)
    suites = {
        "table1": table1_variation.run,
        "table2": table2_complexity.run,
        "table3": table3_glue_analog.run,
        "table4": table4_variants.run,
        "table5": table5_last_layers.run,
        "fig2": fig2_lowrank.run,
        "roofline": roofline.run,
        "engine": engine_modes.run,
        "kernel": kernel_vjp.run,
        "serve_pool": serve_pool.run,
        "decode_attn": decode_attention.run,
        "traffic": traffic_replay.run,
        "router": router_fleet.run,
    }
    want = sys.argv[1:] or list(suites)
    failed = []
    for name in want:
        t0 = time.time()
        try:
            rows = suites[name]()
        except Exception as e:  # pragma: no cover
            # keep running the other suites, but the run as a whole fails
            traceback.print_exc()
            failed.append(name)
            rows = [f"{name},ERROR,{type(e).__name__}: {e}"]
        for r in rows:
            print(r)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
