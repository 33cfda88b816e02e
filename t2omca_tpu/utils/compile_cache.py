"""Where JAX's persistent compilation cache lives.

The cache key includes the directory path, so a directory that moves
(a temp name, a pid, a timestamp, a per-artifact folder) never hits.
One rule, applied once per process by every entry point before its
first compile (``python -m t2omca_tpu``, ``python -m t2omca_tpu.serve``,
``chip_smoke.py``, ``benchmark/run.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set — the directory is placed from
  outside. JAX reads the variable itself; nothing here touches
  ``jax_compilation_cache_dir``.
* unset — ``<checkout>/.jax_cache`` (git-ignored), a fixed path beside
  the code that compiled into it.

JAX's own thresholds stay as they are: a program that compiles in under
a second is not worth a cache entry.

The key includes the programs' metadata
(``jax_compilation_cache_include_metadata_in_key``). JAX's default key
strips it, so two programs that differ only in their name stacks share
one entry — and the named scopes (``obs/spans.KNOWN_SCOPES``) live
nowhere else: they reach a profiler's trace through the *executable's*
operation metadata. With the default key a cache warmed by a tree
without scopes (or with other scopes) hands this tree an executable
whose trace names nothing (measured on the chip, PERF.md §6 PR 25). The
cost: the metadata holds source paths and lines, so a checkout at
another path, or an edit that moves a traced line, compiles once more.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — this file is <checkout>/t2omca_tpu/utils/…
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the rule above; → the directory in use."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
