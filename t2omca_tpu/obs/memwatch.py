"""HBM memwatch: per-device memory snapshots with phase attribution.

The repo's memory story so far is all *predictive*: the compiler's
``memory_analysis()`` of the programs at full size
(``benchmark/tests/test_compile_v5e.py``), and graftprog's GP303
ratchet of the compiled programs' peak (temp + output-alias) at the
frozen audit config. What
dies on a chip is the *live* number — and when it does, nothing says
what held HBM at the time. This module closes that gap:

* :class:`MemWatch` — reads ``device.memory_stats()`` (the PJRT
  allocator counters: ``bytes_in_use``, ``peak_bytes_in_use``, ...)
  at PHASE BOUNDARIES the driver already owns (startup, log cadence,
  checkpoint save), tracking per-device high water **attributed to the
  phase that first reached it** — so an OOM or wedge post-mortem says
  "the high water was N GiB, first seen at the ``checkpoint.save``
  boundary at t_env=M", not just a number.
* the report rides the existing artifacts: the driver merges
  ``report()`` into ``flight_recorder.json`` and
  ``stall_diagnosis.json`` (``spans.SpanRecorder.persist(extra=)`` /
  ``watchdog.write_diagnosis(extra=)``). During a stall only the
  CACHED high water is reported — a snapshot would touch the wedged
  backend from the diagnostic path.
* :func:`audit_peak_budgets` — the graftprog GP303 peaks
  (``analysis/programs.json``, jax-free read) ride along in the report
  as ``budgets_audit_peak_bytes`` so the post-mortem can line the live
  number up against what the *compiled programs* claim to need.
  Honesty: the budgets are measured at the frozen tiny audit config —
  they anchor "which program is the HBM hog", not an absolute bound at
  run scale.

Allocator support varies: TPU/GPU PJRT clients report real counters,
the CPU client usually returns ``None`` — every read degrades to
"unsupported" (``supported: false`` in the report), never a crash.
jax is imported lazily inside ``snapshot`` so importing this module
stays free for the jax-free CLIs.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

from .spans import NULL_RECORDER

#: memory_stats keys copied into each snapshot when present (allocator
#: dialects differ; absent keys are simply omitted)
_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
              "largest_free_block_bytes", "num_allocs")


def audit_peak_budgets(programs_json: Optional[str] = None
                       ) -> Dict[str, float]:
    """→ ``{program: peak_bytes}`` for every compiled-level entry in
    graftprog's baseline (jax-free; empty on any read problem — the
    budgets decorate the report, they are not load-bearing)."""
    try:
        from ..analysis.baseline import DEFAULT_PROGRAMS, load_programs
        base = load_programs(programs_json or DEFAULT_PROGRAMS)
        return {name: float(entry["peak_bytes"])
                for name, entry in base.get("programs", {}).items()
                if isinstance(entry, dict) and "peak_bytes" in entry}
    except Exception:  # noqa: BLE001 — decoration only
        return {}


class MemWatch:
    """Phase-boundary HBM snapshots + high-water attribution. Thread-
    safe: the driver snapshots from the main thread while the stall
    path reads ``report()`` from the watchdog thread."""

    enabled = True

    def __init__(self, rec=NULL_RECORDER,
                 budgets: Optional[Dict[str, float]] = None,
                 _devices: Optional[Callable[[], list]] = None) -> None:
        self._rec = rec
        self._budgets = dict(budgets or {})
        self._devices_fn = _devices          # test hook (fake devices)
        self._lock = threading.Lock()
        # device id -> {"bytes_in_use", ..., "high_water_bytes",
        #               "high_water_phase", "high_water_t_env"}
        self._dev: Dict[str, Dict[str, Any]] = {}
        self.snapshots = 0
        #: None until the first snapshot; False when no device reports
        #: allocator stats (CPU client) — the report states it instead
        #: of showing an empty table with no explanation
        self.supported: Optional[bool] = None

    def _devices(self) -> list:
        if self._devices_fn is not None:
            return self._devices_fn()
        import jax
        return jax.local_devices()

    def snapshot(self, phase: str, t_env: int = 0
                 ) -> Optional[Dict[str, Dict[str, int]]]:
        """One per-device read at a phase boundary. Returns the raw
        per-device stats (None when unsupported) and folds the high
        water — attributed to ``phase``/``t_env`` when this read is the
        new maximum. Spanned (``memwatch.snapshot``) so its cost shows
        up in the phase table like any other boundary."""
        with self._rec.span("memwatch.snapshot", t_env=t_env, at=phase):
            try:
                devices = self._devices()
            except Exception:  # noqa: BLE001 — telemetry only
                with self._lock:
                    # a transient device-list failure (backend teardown
                    # racing the final snapshot) must not erase the
                    # verdict earlier successful reads earned — the
                    # report would say "unsupported" over populated rows
                    if not self._dev:
                        self.supported = False
                return None
            out: Dict[str, Dict[str, int]] = {}
            for i, d in enumerate(devices):
                try:
                    ms = d.memory_stats()
                except Exception:  # noqa: BLE001 — per-device degrade
                    ms = None
                if not ms:
                    continue
                did = str(getattr(d, "id", i))
                snap = {k: int(ms[k]) for k in _STAT_KEYS if k in ms}
                out[did] = snap
            with self._lock:
                self.snapshots += 1
                self.supported = bool(out) or bool(self._dev)
                for did, snap in out.items():
                    rec = self._dev.setdefault(did, {
                        "high_water_bytes": -1,
                        "high_water_phase": None,
                        "high_water_t_env": 0})
                    rec.update(snap)
                    # prefer the allocator's own peak counter (it sees
                    # between-boundary spikes); fall back to in-use
                    hw = snap.get("peak_bytes_in_use",
                                  snap.get("bytes_in_use", 0))
                    if hw > rec["high_water_bytes"]:
                        rec["high_water_bytes"] = hw
                        rec["high_water_phase"] = phase
                        rec["high_water_t_env"] = int(t_env)
            return out or None

    def report(self) -> Dict[str, Any]:
        """The post-mortem block merged into flight/stall artifacts.
        Pure cached state — safe to call from the stall path over a
        wedged backend (no device reads)."""
        with self._lock:
            devices = {did: dict(rec) for did, rec in self._dev.items()}
            return {"supported": self.supported,
                    "snapshots": self.snapshots,
                    "devices": devices,
                    # graftprog GP303 peaks at the frozen AUDIT config —
                    # a which-program anchor, not a run-scale bound
                    "budgets_audit_peak_bytes": dict(self._budgets)}


class NullMemWatch:
    """The disabled memwatch: every operation a no-op, so call sites
    stay unconditional (the NullRecorder pattern)."""

    enabled = False
    supported = None
    snapshots = 0

    def snapshot(self, phase: str, t_env: int = 0):
        return None

    def report(self) -> Dict[str, Any]:
        return {}


NULL_MEMWATCH = NullMemWatch()


def make_memwatch(obs_cfg, rec=NULL_RECORDER):
    """:data:`NULL_MEMWATCH` unless ``obs.enabled`` AND
    ``obs.memwatch`` (sanity_check enforces the pairing)."""
    if obs_cfg is None or not getattr(obs_cfg, "enabled", False) \
            or not getattr(obs_cfg, "memwatch", False):
        return NULL_MEMWATCH
    return MemWatch(rec=rec, budgets=audit_peak_budgets())
