"""The benchmark's own tests run on the CPU backend (``JAX_PLATFORMS=cpu``
is set in the sandbox; pinned here for any other caller), float32 matmuls
at full precision so that the reference and the program agree to rounding."""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
