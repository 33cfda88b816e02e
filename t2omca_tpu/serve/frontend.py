"""Batched inference front-end: ragged requests → fixed compiled buckets.

The serving step is AOT-compiled at fixed batch shapes (the export's
power-of-2 buckets); traffic arrives as variable-size request batches.
This module is the host-side seam between the two — the EnvPool /
TF-Agents host-side batching pattern (PAPERS.md, arXiv 2206.10558):

* **bucketing** — a request batch of ``n`` rows pads up to the smallest
  bucket ≥ ``n`` (``pick_bucket``); batches larger than the biggest
  bucket split into max-bucket chunks plus a bucketed remainder, so any
  request size is served by at most ``len(buckets)`` compiled programs.
* **mask-correct padding** — pad rows get an avail mask with ONLY
  action 0 legal (never all-zero: the masked argmax stays well-defined
  with no ±inf edge cases), zero obs and zero hidden; their outputs are
  sliced away in unpad, so padding can never leak into real rows.
* **per-request hidden carry** — ``select`` threads the recurrent
  hidden state explicitly (None = fresh zeros); :class:`SessionStore`
  keys it by caller session ids for multi-turn traffic.
* **telemetry** — every boundary is spanned (``serve.pad`` /
  ``serve.dispatch`` / ``serve.unpad``; GL110 pins the names against
  ``obs/spans.KNOWN_PHASES``), so ``python -m t2omca_tpu.obs report``
  reads a serving run exactly like a training run.

The dispatched program is the export's own ``jax.export`` blob
(deserialized StableHLO — no Python re-trace), falling back to
rebuilding ``build_serve_step`` from the artifact's train config when a
blob is absent. The persistent compile cache is the process's
(``utils/compile_cache.py``), never the artifact's: a first dispatch is
a cache hit where the export ran against the same cache directory.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..obs.spans import NULL_RECORDER
from .export import ARTIFACT_FORMAT
from .program import build_serve_step

logger = logging.getLogger(__name__)


def _watched(phase, rec, **meta):
    """One spanned serving boundary. Module-level and named like the
    driver's wrapper so graftlint GL110 checks every literal phase here
    against ``obs/spans.KNOWN_PHASES`` — a new serving boundary cannot
    appear without flight/report coverage."""
    return rec.span(phase, **meta)


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket ≥ ``n`` (``buckets`` sorted ascending). ``n``
    above the largest bucket is the caller's chunking job — asking for
    a bucket for it is a bug, not a clamp."""
    if n < 1:
        raise ValueError(f"request batch must be >= 1 row, got {n}")
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(
        f"request batch {n} exceeds the largest bucket {buckets[-1]} — "
        f"chunk it first (ServeFrontend.select does)")


def pad_request(obs: np.ndarray, avail: np.ndarray, hidden: np.ndarray,
                bucket: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad ``(n, ...)`` request arrays up to ``bucket`` rows. Pad rows:
    zero obs/hidden and an avail mask legalizing ONLY action 0 — real
    rows' masks pass through untouched (cast to bool, the compiled
    aval), so padding is mask-correct by construction."""
    n = obs.shape[0]
    avail = avail.astype(np.bool_, copy=False)
    if n == bucket:
        return obs, avail, hidden
    pad = bucket - n
    pad_avail = np.zeros((pad,) + avail.shape[1:], np.bool_)
    pad_avail[..., 0] = True
    return (np.concatenate([obs, np.zeros((pad,) + obs.shape[1:],
                                          obs.dtype)]),
            np.concatenate([avail, pad_avail]),
            np.concatenate([hidden, np.zeros((pad,) + hidden.shape[1:],
                                             hidden.dtype)]))


class ServeFrontend:
    """Loaded serving artifact + batched dispatch. Build with
    :meth:`load`; thread-compatible with one dispatcher thread (the
    program cache is not locked — shard frontends per thread)."""

    def __init__(self, artifact_dir: str, meta: dict, mac, params,
                 dtype: str, use_exported: bool, rec,
                 hub=None) -> None:
        self.artifact_dir = artifact_dir
        self.meta = meta
        self.dtype = dtype
        self.buckets: List[int] = sorted(int(b) for b in meta["buckets"])
        self.n_agents = int(meta["n_agents"])
        self.obs_dim = int(meta["obs_dim"])
        self.n_actions = int(meta["n_actions"])
        self.emb = int(meta["emb"])
        self._mac = mac
        self._params = params
        self._rec = rec
        # graftpulse MetricsHub (obs/pulse.py, docs/OBSERVABILITY.md
        # §pulse): None (default) = zero extra work per request; set =
        # the scrapeable per-engine surface the fleet-scale story
        # (ROADMAP item 4, EnvPool share-nothing engines) load-balances
        # on — sliding-window select p50/p99, per-bucket request/row
        # counters (padding occupancy), session-LRU fill
        self._hub = hub
        self._use_exported = use_exported
        self._steps: Dict[int, object] = {}
        self._fallback = None

    # ------------------------------------------------------------- load

    @classmethod
    def load(cls, artifact_dir: str, dtype: str = "float32",
             use_exported: bool = True,
             rec=NULL_RECORDER, hub=None) -> "ServeFrontend":
        """Load an exported artifact (``serve/export.py`` layout).
        ``dtype`` picks the param variant."""
        import jax
        from flax import serialization

        with _watched("serve.load", rec, dtype=dtype):
            with open(os.path.join(artifact_dir, "meta.json")) as f:
                meta = json.load(f)
            fmt = meta.get("format", 0)
            if fmt > ARTIFACT_FORMAT:
                raise ValueError(
                    f"serve artifact {artifact_dir} has format v{fmt}, "
                    f"newer than this build's v{ARTIFACT_FORMAT} — "
                    f"upgrade the framework to load it")
            entry = meta.get("params", {}).get(dtype)
            if entry is None:
                raise ValueError(
                    f"artifact {artifact_dir} ships no {dtype!r} param "
                    f"variant (has: {sorted(meta.get('params', {}))})")
            with open(os.path.join(artifact_dir, entry["file"]), "rb") as f:
                blob = f.read()
            import hashlib
            digest = hashlib.sha256(blob).hexdigest()
            if entry.get("sha256") and digest != entry["sha256"]:
                raise ValueError(
                    f"param blob {entry['file']} fails its integrity "
                    f"check ({digest[:12]}… != recorded "
                    f"{entry['sha256'][:12]}…) — re-export the artifact")
            params = jax.device_put(serialization.msgpack_restore(blob))
            del blob

            # rebuild the exact MAC the trainer used — the fallback
            # (and validation) path; the exported blobs carry the
            # program itself
            from ..config import from_dict
            from ..controllers.basic_mac import MAC_REGISTRY
            from ..envs.registry import make_env
            cfg = from_dict(meta["train_config"])
            env_info = make_env(cfg.env_args).get_env_info()
            mac = MAC_REGISTRY[cfg.mac].build(cfg, env_info)
            if (mac.n_agents != meta["n_agents"]
                    or env_info["obs_shape"] != meta["obs_dim"]
                    or env_info["n_actions"] != meta["n_actions"]):
                raise ValueError(
                    f"artifact {artifact_dir} meta disagrees with its "
                    f"own train_config rebuild (agents/obs/actions "
                    f"{meta['n_agents']}/{meta['obs_dim']}/"
                    f"{meta['n_actions']} vs {mac.n_agents}/"
                    f"{env_info['obs_shape']}/{env_info['n_actions']}) "
                    f"— corrupt meta.json?")
        return cls(artifact_dir, meta, mac, params, dtype, use_exported,
                   rec, hub=hub)

    # --------------------------------------------------------- programs

    def _program(self, bucket: int):
        """The compiled step for one bucket: the deserialized
        ``jax.export`` blob when the artifact ships it, else the
        config-rebuilt ``build_serve_step`` (one jitted fn, retraced
        per bucket shape)."""
        fn = self._steps.get(bucket)
        if fn is not None:
            return fn
        import jax
        entry = (self.meta.get("programs", {}).get(self.dtype, {})
                 .get(str(bucket), {}))
        path = entry.get("file")
        if self._use_exported and path:
            from jax import export as jax_export
            with open(os.path.join(self.artifact_dir, path), "rb") as f:
                exported = jax_export.deserialize(f.read())
            fn = jax.jit(exported.call)
        else:
            if self._use_exported and not path:
                logger.warning(
                    "bucket %d has no exported program blob — rebuilding "
                    "the step from the artifact's train config", bucket)
            if self._fallback is None:
                self._fallback = build_serve_step(self._mac)
            fn = self._fallback
        self._steps[bucket] = fn
        return fn

    # ----------------------------------------------------------- serve

    def _validate(self, obs, avail, hidden) -> None:
        a, d, na = self.n_agents, self.obs_dim, self.n_actions
        if obs.ndim != 3 or obs.shape[1:] != (a, d):
            raise ValueError(f"obs must be (n, {a}, {d}), got {obs.shape}")
        if avail.shape != (obs.shape[0], a, na):
            raise ValueError(f"avail must be ({obs.shape[0]}, {a}, {na}), "
                             f"got {avail.shape}")
        if hidden.shape != (obs.shape[0], a, self.emb):
            raise ValueError(f"hidden must be ({obs.shape[0]}, {a}, "
                             f"{self.emb}), got {hidden.shape}")

    def select(self, obs, avail, hidden=None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Greedy actions for a ragged request batch: ``obs (n, A,
        obs_dim)``, ``avail (n, A, n_actions)``, optional carried
        ``hidden (n, A, emb)`` (None = episode start) → ``(actions
        (n, A) int32, hidden' (n, A, emb) f32)``. Blocks until the
        actions are on host — serving is a latency surface, not a
        pipeline."""
        obs = np.asarray(obs, np.float32)
        avail = np.asarray(avail)
        if obs.ndim != 3:
            raise ValueError(f"obs must be (n, {self.n_agents}, "
                             f"{self.obs_dim}), got shape {obs.shape}")
        n = obs.shape[0]
        if hidden is None:
            hidden = np.zeros((n, self.n_agents, self.emb), np.float32)
        else:
            hidden = np.asarray(hidden, np.float32)
        self._validate(obs, avail, hidden)

        bmax = self.buckets[-1]
        actions_out = np.empty((n, self.n_agents), np.int32)
        hidden_out = np.empty((n, self.n_agents, self.emb), np.float32)
        t_req0 = time.perf_counter() if self._hub is not None else 0.0
        for lo in range(0, n, bmax):
            hi = min(lo + bmax, n)
            cn = hi - lo
            bucket = pick_bucket(cn, self.buckets)
            with _watched("serve.pad", self._rec, bucket=bucket, n=cn):
                po, pa, ph = pad_request(obs[lo:hi], avail[lo:hi],
                                         hidden[lo:hi], bucket)
            with _watched("serve.dispatch", self._rec, bucket=bucket):
                a_dev, h_dev = self._program(bucket)(self._params, po,
                                                     pa, ph)
                a_host = np.asarray(a_dev)       # the blocking fetch
                h_host = np.asarray(h_dev, dtype=np.float32)
            with _watched("serve.unpad", self._rec, bucket=bucket):
                actions_out[lo:hi] = a_host[:cn]
                hidden_out[lo:hi] = h_host[:cn]
            if self._hub is not None:
                # per-bucket occupancy counters: rows/ (dispatches ×
                # bucket) is the padding-waste read the bucket tuning
                # needs — one inc pair per compiled dispatch
                self._hub.inc("serve_dispatches_total", bucket=bucket)
                self._hub.inc("serve_rows_total", cn, bucket=bucket)
        if self._hub is not None:
            # whole-request latency into the sliding window: /metrics
            # renders serve_select_ms_p50/_p99 at scrape time
            self._hub.observe(
                "serve_select_ms",
                (time.perf_counter() - t_req0) * 1000.0)
            self._hub.inc("serve_requests_total")
        return actions_out, hidden_out

    def warmup(self) -> None:
        """Dispatch one padded batch per bucket so every compiled
        program exists before traffic (persistent-cache hits when the
        process's compile cache saw the export)."""
        for b in self.buckets:
            obs = np.zeros((b, self.n_agents, self.obs_dim), np.float32)
            avail = np.ones((b, self.n_agents, self.n_actions), np.bool_)
            self.select(obs, avail)


class SessionStore:
    """Per-session hidden-state carry over a :class:`ServeFrontend`:
    multi-turn traffic names each request row with a session id; the
    store gathers each row's carried hidden (zeros for new sessions),
    serves the batch, and scatters the new hiddens back. Call
    :meth:`end` when a session's episode finishes (or rely on
    ``max_sessions`` LRU eviction — an evicted session restarts from
    zeros, degraded but well-defined, and NOT silent: each eviction
    increments the ``serve_session_evicted`` stat, and :meth:`select`
    returns a per-row ``fresh`` sentinel so a caller who believes a
    session is live can detect the mid-conversation reset)."""

    def __init__(self, frontend: ServeFrontend,
                 max_sessions: int = 100_000) -> None:
        self._fe = frontend
        self._max = int(max_sessions)
        self._h: Dict[object, np.ndarray] = {}
        self.evicted = 0                # cumulative LRU evictions

    def __len__(self) -> int:
        return len(self._h)

    def select(self, session_ids: Sequence, obs, avail
               ) -> Tuple[np.ndarray, np.ndarray]:
        """→ ``(actions (n, A) int32, fresh (n,) bool)``. ``fresh[i]``
        is True when row i's session had NO carried hidden — a brand-new
        session, or a live one whose carry was LRU-evicted (the caller
        knows which ids it just created, so fresh on a supposedly-live
        id IS the eviction sentinel)."""
        if len(session_ids) != np.asarray(obs).shape[0]:
            raise ValueError(
                f"{len(session_ids)} session ids for "
                f"{np.asarray(obs).shape[0]} request rows")
        fe = self._fe
        zeros = np.zeros((fe.n_agents, fe.emb), np.float32)
        fresh = np.array([s not in self._h for s in session_ids], np.bool_)
        hidden = np.stack([self._h.get(s, zeros) for s in session_ids])
        actions, hidden2 = fe.select(obs, avail, hidden)
        for i, s in enumerate(session_ids):
            # move-to-end LRU semantics: re-insert on every touch
            self._h.pop(s, None)
            self._h[s] = hidden2[i]
        hub = getattr(fe, "_hub", None)     # duck-typed frontends (tests)
        while len(self._h) > self._max:
            self._h.pop(next(iter(self._h)))
            # an eviction drops a LIVE conversation's carry (the victim
            # was touched more recently than never) — count it where
            # the operator can see it instead of silently degrading
            self.evicted += 1
            if hub is not None:
                hub.inc("serve_session_evicted")
        if hub is not None:
            # LRU fill fraction: 1.0 means evictions are live and
            # long-lived sessions silently restart from zero hiddens —
            # the signal to widen max_sessions before quality decays
            hub.set("serve_sessions", len(self._h))
            hub.set("serve_session_lru_fill",
                    len(self._h) / self._max if self._max else 1.0)
        return actions, fresh

    def end(self, session_id) -> None:
        self._h.pop(session_id, None)
