"""Rollout hot-path kernel layer (docs/PERF.md).

Hand-written accelerator kernels for the programs the rollout scan
spends its time in, with the XLA lowering as the fallback and CPU-gate
parity tests pinning equivalence (``tests/test_kernels.py``): the flash
kernel of ``MultiHeadAttention`` behind ``kernels.attention``
(``attention.py``), and acting's entity-table attention block
(``entity_attention.py``), which engages from shapes and platform
alone. graftlint treats this package as hot-path (GL105: no host
syncs), and graftprog fingerprints/ratchets both kernel modes of every
program registered here (``analysis/registry.py``).
"""

from .attention import flash_attention

__all__ = ["flash_attention"]
