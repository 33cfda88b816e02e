"""Export a verified checkpoint as a serving artifact.

``python -m t2omca_tpu.serve export <ckpt_dir>`` turns a training
checkpoint into the self-contained directory the inference front-end
(``serve/frontend.py``) loads:

* ``params_float32.msgpack`` / ``params_bfloat16.msgpack`` — the agent
  parameters ONLY (optimizer, target net, mixer and replay state are
  stripped: acting needs none of them), with
  ``BasicMAC.prepare_acting_params`` applied — the qslice projection
  pre-fold, done once at export instead of once per dispatch. The bf16
  variant halves the artifact and the per-load host→device bytes; f32
  is the bit-parity variant (tests/test_serve.py).
* ``programs/serve_step_<dtype>_b<bucket>.jaxexport`` — the greedy
  ``serve_step`` AOT-lowered per batch bucket and serialized with
  ``jax.export`` (StableHLO): a portable, version-checked program the
  front-end deserializes instead of re-tracing Python. Each bucket is
  also compiled at export time — a validation pass, and (where the
  process has a persistent compile cache, ``utils/compile_cache.py``)
  the write a later serving process on the same checkout hits instead
  of paying cold XLA compiles in front of traffic.
* ``meta.json`` — format version, bucket list, param digests, the full
  train config (the front-end rebuilds the exact MAC from it), and
  provenance: source checkpoint + its state SHA-256, git commit, jax
  version, and the per-bucket stable-HLO fingerprints/costs in the
  graftprog style (``analysis/graftprog.fingerprint_text``).

The checkpoint is read through ``utils.checkpoint.restore_host_state``
— the same host-side leaf loader the DP sharded resume uses — so the
export never allocates the replay ring on a device; it does pay one
host-RAM decode of the checkpoint blob (documented in
docs/SERVING.md).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from ..analysis.graftprog import fingerprint_text
from ..config import TrainConfig, sanity_check
from ..controllers.basic_mac import MAC_REGISTRY
from ..envs.registry import make_env
from ..obs.spans import NULL_RECORDER
from ..utils.checkpoint import find_checkpoint, restore_host_state
from ..utils.ioutil import write_bytes_atomic, write_json_atomic
from .program import build_serve_step, serve_avals

logger = logging.getLogger(__name__)

#: bump when the artifact layout changes incompatibly
ARTIFACT_FORMAT = 1

#: power-of-2 batch buckets (docs/SERVING.md bucket policy): every
#: request batch pads up to the smallest bucket ≥ its size, so at most
#: len(buckets) compiled programs serve any traffic mix and padding
#: waste is < 2x worst-case
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)

#: the serialized-param variants an artifact ships
PARAM_DTYPES: Tuple[str, ...] = ("float32", "bfloat16")


def _git_commit() -> Optional[str]:
    import subprocess
    try:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=root,
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None


def _sha256_bytes(blob: bytes) -> str:
    import hashlib
    return hashlib.sha256(blob).hexdigest()


def load_acting_params(cfg: TrainConfig, ckpt_dir: str, load_step: int = 0):
    """→ ``(acting_params, mac, env_info, ckpt_info)``: the checkpoint's
    agent parameters restored host-side (``restore_host_state`` — no
    device-resident replay ring), shape-validated against the config's
    own init, and pre-folded for acting."""
    if cfg.model.trunk is not None:
        # the artifact's program (serve/program.py) is the T2OMCA sliced
        # or dense forward; a catalog trunk's share is trained, not served
        raise ValueError(
            "serve/export does not export a model.trunk agent "
            "(models/trunk.py): the serving program has no forward for a "
            "catalog trunk's share of a layer yet — ROADMAP B7")
    found = find_checkpoint(ckpt_dir, load_step)
    if found is None:
        raise FileNotFoundError(
            f"no valid checkpoint under {ckpt_dir!r} (export needs a "
            f"published training checkpoint; run with save_model=true)")
    dirname, step = found
    env = make_env(cfg.env_args)
    env_info = env.get_env_info()
    mac = MAC_REGISTRY[cfg.mac].build(cfg, env_info)
    ckpt_meta, raw = restore_host_state(dirname, verify=False)
    try:
        agent_raw = raw["learner"]["params"]["agent"]
    except (KeyError, TypeError) as e:
        raise ValueError(
            f"checkpoint {dirname} has no learner/params/agent subtree "
            f"({e!r}) — not a t2omca_tpu training checkpoint?") from e
    del raw                         # drop the ring/optimizer host copy now
    template = mac.init_params(jax.random.PRNGKey(0),
                               env_info["obs_shape"])
    params = serialization.from_state_dict(template, agent_raw)
    t_leaves = jax.tree_util.tree_leaves_with_path(template)
    r_leaves = jax.tree_util.tree_leaves_with_path(params)
    bad = [jax.tree_util.keystr(kp)
           for (kp, lt), (_, lr) in zip(t_leaves, r_leaves)
           if getattr(lt, "shape", None) != getattr(lr, "shape", None)]
    if bad:
        raise ValueError(
            f"checkpoint {dirname} holds a different MODEL than the "
            f"export config: {len(bad)} agent leaves mismatch (first: "
            f"{bad[0]}) — pass the training run's config")
    # fold at the TRAIN dtype explicitly: model.act_dtype is a
    # training-run rollout knob, and letting it leak into the fold would
    # ship bf16 leaves inside the artifact's canonical "float32" variant
    # (voiding the f32 bit-parity serving contract above)
    acting = mac.prepare_acting_params(params, dtype=mac.agent.dtype)
    ckpt_info = {"dir": dirname, "t_env": int(step),
                 "state_sha256": (ckpt_meta or {}).get("sha256")}
    return acting, mac, env_info, ckpt_info


def _cast_variant(tree, dtype_name: str):
    """Param variant: floating leaves cast to the variant dtype
    (``float32`` keeps the canonical leaves untouched — including the
    pre-fold products, whose dtype is the model's compute dtype)."""
    if dtype_name == "float32":
        return tree
    dt = jnp.dtype(dtype_name)

    def cast(x):
        a = np.asarray(x) if not hasattr(x, "dtype") else x
        if jnp.issubdtype(getattr(a, "dtype", np.int32), jnp.floating):
            return jnp.asarray(a, dt)
        return x
    return jax.tree.map(cast, tree)


def export_artifact(cfg: TrainConfig, ckpt_dir: str, out_dir: str,
                    buckets: Sequence[int] = DEFAULT_BUCKETS,
                    dtypes: Sequence[str] = PARAM_DTYPES,
                    load_step: int = 0, export_blobs: bool = True,
                    rec=NULL_RECORDER) -> dict:
    """Write the serving artifact for ``cfg``'s newest (or
    ``load_step``-nearest) checkpoint under ``ckpt_dir`` into
    ``out_dir``; → the ``meta.json`` dict. See the module docstring for
    the layout."""
    cfg = sanity_check(cfg)
    buckets = sorted({int(b) for b in buckets})
    if not buckets or buckets[0] < 1:
        raise ValueError(f"buckets must be positive ints, got {buckets}")
    for d in dtypes:
        jnp.dtype(d)                 # fail fast on a typo'd dtype
    # resolve + restore the checkpoint BEFORE any filesystem side
    # effect: a missing/mismatched checkpoint must be a clean error,
    # not a half-written artifact
    with rec.span("serve.export", phase_detail="load"):
        acting, mac, env_info, ckpt_info = load_acting_params(
            cfg, ckpt_dir, load_step)
    os.makedirs(out_dir, exist_ok=True)
    step = build_serve_step(mac)
    obs_dim, n_actions = env_info["obs_shape"], env_info["n_actions"]

    params_meta: Dict[str, dict] = {}
    programs_meta: Dict[str, dict] = {}
    prog_dir = os.path.join(out_dir, "programs")
    if export_blobs:
        os.makedirs(prog_dir, exist_ok=True)
    for dtype_name in dtypes:
        variant = jax.device_put(_cast_variant(acting, dtype_name))
        blob = serialization.msgpack_serialize(
            jax.tree.map(lambda x: np.asarray(jax.device_get(x)), variant))
        fname = f"params_{dtype_name}.msgpack"
        # atomic (tmp+fsync+rename, like meta.json): a crash mid-export
        # must never publish a truncated blob at the final path — the
        # front-end's sha256 check would reject it, but only AFTER a
        # serving process trusted the artifact enough to load it
        write_bytes_atomic(os.path.join(out_dir, fname), blob)
        params_meta[dtype_name] = {"file": fname,
                                   "sha256": _sha256_bytes(blob),
                                   "bytes": len(blob)}
        del blob

        per_bucket: Dict[str, dict] = {}
        for b in buckets:
            obs, avail, hidden = serve_avals(mac, obs_dim, n_actions, b)
            with rec.span("serve.export", phase_detail="lower",
                          dtype=dtype_name, bucket=b):
                lowered = step.trace(variant, obs, avail, hidden).lower()
                fp = fingerprint_text(lowered.as_text())
                # None on TPU: no cost model before compilation there
                cost = lowered.cost_analysis() or {}
            entry = {"fingerprint": fp,
                     "flops": cost.get("flops"),
                     "bytes_accessed": cost.get("bytes accessed")}
            if export_blobs:
                from jax import export as jax_export
                with rec.span("serve.export", phase_detail="export",
                              dtype=dtype_name, bucket=b):
                    exported = jax_export.export(step)(variant, obs,
                                                       avail, hidden)
                    eblob = exported.serialize()
                    bname = f"serve_step_{dtype_name}_b{b}.jaxexport"
                    write_bytes_atomic(os.path.join(prog_dir, bname),
                                       eblob)
                    # validate + warm-start with the program the
                    # FRONT-END actually dispatches — jit over the
                    # deserialized call has its own cache key, so
                    # compiling the raw step here would warm nothing
                    # the serving process looks up
                    jax.jit(jax_export.deserialize(eblob).call).lower(
                        variant, obs, avail, hidden).compile()
                entry["file"] = f"programs/{bname}"
            else:
                # no blobs: the front-end falls back to rebuilding the
                # raw step, whose HLO (hence cache key) this compile
                # warms — and it validates the program end-to-end
                lowered.compile()
            per_bucket[str(b)] = entry
        programs_meta[dtype_name] = per_bucket
        logger.info("exported %s variant: %d buckets %s",
                    dtype_name, len(buckets), buckets)

    meta = {
        "format": ARTIFACT_FORMAT,
        "created": time.time(),
        "checkpoint": ckpt_info,
        "provenance": {"git_commit": _git_commit(),
                       "jax": jax.__version__,
                       "backend": jax.default_backend()},
        "train_config": dataclasses.asdict(cfg),
        "env_info": {k: int(v) for k, v in env_info.items()
                     if isinstance(v, (int, np.integer))},
        "n_agents": int(mac.n_agents),
        "obs_dim": int(obs_dim),
        "n_actions": int(n_actions),
        "emb": int(mac.emb),
        "folded": bool(mac.use_qslice),
        "buckets": buckets,
        "params": params_meta,
        "programs": programs_meta,
    }
    write_json_atomic(os.path.join(out_dir, "meta.json"), meta)
    logger.info("serve artifact written to %s (checkpoint t_env=%d)",
                out_dir, ckpt_info["t_env"])
    return meta
