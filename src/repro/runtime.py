"""Where the program keeps what it builds at run time.

Everything lives under one git-ignored directory of the checkout,
``<checkout>/.cache``:

* ``.cache/jax`` — JAX's persistent compilation cache, unless
  ``JAX_COMPILATION_CACHE_DIR`` names another directory (JAX reads that
  variable itself; the program then sets no directory of its own).  The
  path is fixed: JAX keys cache entries by path, so a directory built
  from a temporary name, a PID or the time would never be hit again.
* ``.cache/repro/autotune.json`` — measured kernel verdicts
  (``kernels.autotune``; ``REPRO_AUTOTUNE_CACHE`` overrides).
"""

from __future__ import annotations

from pathlib import Path

import jax

CACHE_ROOT = Path(__file__).resolve().parents[2] / ".cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Called at program start-up (``Session``, the CLIs).  Safe to call more
    than once; a directory already configured (``JAX_COMPILATION_CACHE_DIR``
    or an earlier ``jax.config`` update) is kept."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = str(CACHE_ROOT / "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
