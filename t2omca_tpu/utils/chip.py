"""The device check of every path that measures or proves something on
the chip (``chip_smoke.py``): a TPU, or a non-zero exit.
No CPU continuation, no interpret fallback. In-process — the caller is
the one process that will hold the chip, so a child probe would save
nothing and a parent that has touched JAX could not start one anyway."""

from __future__ import annotations

import sys


def require_tpu(n_chips: int = 1):
    """→ ``jax.devices()`` when they are at least ``n_chips`` TPU
    chips; otherwise exit with a message on stderr and status 1."""
    import jax
    devices = jax.devices()
    who = sys.argv[0] or "t2omca_tpu"
    if devices[0].platform != "tpu":
        sys.exit(f"{who}: no TPU — JAX found {devices[0].platform!r} "
                 f"({devices[0].device_kind}); this path measures the "
                 f"chip and does not fall back")
    if len(devices) < n_chips:
        sys.exit(f"{who}: needs {n_chips} chips, JAX found {len(devices)}")
    return devices
