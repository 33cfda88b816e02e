"""graftserve — AOT-compiled policy serving (ROADMAP open item 5).

The first user-facing subsystem: a frozen-params greedy
``select_actions`` step exported ahead of traffic and fed by a
host-side batcher.

* ``serve/program.py`` — the ONE serving program definition (greedy
  step + request-surface avals + the graftprog registry hook).
* ``serve/export.py`` — ``python -m t2omca_tpu.serve export``: turn a
  training checkpoint into a self-contained artifact (stripped +
  pre-folded params in f32/bf16, per-bucket ``jax.export`` programs, a
  warm persistent compile cache, provenance meta).
* ``serve/frontend.py`` — the batched front-end: ragged request
  batches pad/bucket into the compiled shapes, with per-request hidden
  carry and full span telemetry.
* ``serve/fleet.py`` — graftfleet: N share-nothing frontends behind a
  bounded admission queue with per-engine supervision (watchdog +
  quarantine + backoff restart), hedged retries, explicit load
  shedding, a pressure-degradation ladder and rolling hot param
  refresh with fingerprint gate and auto-rollback (ROADMAP item 4).

Gated by the same static machinery as training: the serve step is
ratcheted in ``analysis/programs.json`` (FLOPs/bytes/fingerprint) and
the span phases are pinned by GL110. Serving latency has not been
measured on the chip: no cell of ``BENCHMARK.json`` serves yet.
docs/SERVING.md is the contract.
"""

from .export import (ARTIFACT_FORMAT, DEFAULT_BUCKETS, export_artifact,
                     load_acting_params)
from .fleet import (FleetConfig, FleetResult, RefreshRefused, ServeFleet,
                    check_refresh)
from .frontend import ServeFrontend, SessionStore, pad_request, pick_bucket
from .program import build_serve_step, serve_avals

__all__ = [
    "ARTIFACT_FORMAT", "DEFAULT_BUCKETS", "FleetConfig", "FleetResult",
    "RefreshRefused", "ServeFleet", "ServeFrontend", "SessionStore",
    "build_serve_step", "check_refresh", "export_artifact",
    "load_acting_params", "pad_request", "pick_bucket", "serve_avals",
]
