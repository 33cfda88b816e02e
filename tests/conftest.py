"""Test configuration: the JAX CPU backend with 8 virtual devices, and
the Pallas kernels in interpreter mode.

SURVEY.md §4: multi-chip paths are tested without a cluster, on a faked
8-device CPU mesh. ``import pytest`` itself imports jax (plugin entry
points), so env-var mutation here would be too late; the jax config API
works post-import because backends initialize lazily. Interpreter mode
is set here, in the harness, because the program has no switch for it:
``kernels.attention: pallas`` compiles for the chip unless a caller
flips ``kernels.attention.INTERPRET``
(tests/test_mosaic_compile.py covers the compiled lowering).
"""

import jax

import t2omca_tpu.kernels.attention as _attention

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_default_matmul_precision", "highest")
_attention.INTERPRET = True
