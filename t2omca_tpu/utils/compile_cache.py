"""Where JAX's persistent compilation cache lives.

The cache key includes the directory path, so a directory that moves
(a temp name, a pid, a timestamp, a per-artifact folder) never hits.
One rule, applied once per process by every entry point before its
first compile (``python -m t2omca_tpu``, ``python -m t2omca_tpu.serve``,
``bench.py``, ``chip_smoke.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set — the directory is placed from
  outside. JAX reads the variable itself; nothing here touches
  ``jax_compilation_cache_dir``.
* unset — ``<checkout>/.jax_cache`` (git-ignored), a fixed path beside
  the code that compiled into it.

JAX's own thresholds stay as they are: a program that compiles in under
a second is not worth a cache entry.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — this file is <checkout>/t2omca_tpu/utils/…
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the rule above; → the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
