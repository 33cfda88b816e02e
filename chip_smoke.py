"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on ONE TPU chip, at
the full width of ``configs/config3_tpu_northstar.yaml`` (64 AGVs x 8
MEC x 8 channels, 1024 envs, 150-slot episodes, emb 256 x depth 2,
bf16), through the entry points a user calls:

* **train** — ``load_config`` + ``run.run``: rollout -> ring insert ->
  PER sample -> learner unroll -> optimizer, checkpoints written and
  found again, every program compiled once however often dispatched;
* **kernels** — ``kernels.attention=pallas`` forward and gradient at
  the shapes that configuration traces, compiled by Mosaic, against the
  einsum reference at the tolerances ``tests/test_kernels.py`` pins;
* **serve** — ``serve.export`` of the checkpoint the train phase wrote,
  loaded through ``serve/frontend.py``, answering requests across two
  buckets, against the training side's greedy actions.

``--chips 4`` runs the data-parallel path (``train.dp_devices=4``) on
four chips and its one-chip comparison, and nothing else.

One process holds the chip for its whole life. Each phase prints one
JSON line and is fatal: an exception ends the run with a non-zero exit
and no result line. There is no rehearsal switch and no size option —
without a TPU the script stops at the device check. The CPU rehearsal
lives in ``tests/test_chip_smoke.py``, which imports the phase
functions and hands them a tiny configuration.

Last line of stdout, and nothing else on it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

from t2omca_tpu.utils.chip import require_tpu

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG3 = os.path.join(REPO, "configs", "config3_tpu_northstar.yaml")

#: (rtol, atol) of pallas vs the f32 einsum reference, by input dtype —
#: tests/test_kernels.py's pins (forward; gradient, whose atol scales
#: with the gradient's magnitude at bf16)
FWD_TOL = {"float32": (1e-6, 2e-6), "bfloat16": (0.05, 0.02)}
GRAD_TOL = {"float32": (1e-4, 1e-5), "bfloat16": (0.05, 0.02)}
#: tests/test_parallel.py's sharded-vs-unsharded loss tolerance
DP_LOSS_RTOL = 2e-4


@contextlib.contextmanager
def phase(name: str):
    """One timed phase: the body fills the yielded dict, which is
    printed as the phase's JSON line. No ``finally``: a raising phase
    prints nothing and ends the process."""
    facts: dict = {}
    t0 = time.perf_counter()
    yield facts
    print(json.dumps({"phase": name,
                      "seconds": round(time.perf_counter() - t0, 2),
                      **facts}), flush=True)


# ---------------------------------------------------------------- train

def phase_train(cfg, workdir: str, ledger, save: bool = True):
    """(``ledger``: an installed ``obs.compiles.CompileListener``.)
    The normal entry point, ``run.run``, for as many driver
    dispatches as make three train iterations (two at least) ->
    (facts, final TrainState, checkpoint dir). Every cadence (test,
    log, save) fires at every dispatch, so each program the driver owns
    is dispatched at least twice."""
    import jax

    from t2omca_tpu import run as run_mod
    from t2omca_tpu.utils.checkpoint import find_checkpoint
    from t2omca_tpu.utils.logging import Logger

    k = cfg.superstep if run_mod.superstep_eligible(cfg) else 1
    dispatches = max(2, -(-3 // k))
    per_dispatch = k * cfg.batch_size_run * cfg.env_args.episode_limit
    cfg = cfg.replace(
        t_max=(dispatches - 1) * per_dispatch,      # loop runs while <=
        local_results_path=workdir,
        test_interval=1, log_interval=1, runner_log_interval=1,
        save_model=save, save_model_interval=1,
        # block inside each stage so time_*_ms is device time, not the
        # enqueue (the loop's own option; same programs)
        profile_stages=True)
    logger = Logger()
    ts = run_mod.run(cfg, logger)
    logger.close()

    t_env = int(jax.device_get(ts.runner.t_env))
    assert t_env == dispatches * per_dispatch, (t_env, per_dispatch)
    # the driver's own gate: an iteration trains once the ring holds a
    # batch (and accumulated_episodes have been collected)
    lanes = cfg.batch_size_run
    trained = sum(
        min(i * lanes, cfg.replay.buffer_size) >= cfg.batch_size
        and i * lanes >= cfg.accumulated_episodes
        for i in range(1, dispatches * k + 1))
    assert trained >= 3, f"{trained} train iterations < 3"
    losses = [v for _, v in logger.stats["loss"]]
    assert losses and all(math.isfinite(v) for v in losses), losses
    assert "nonfinite_steps" not in logger.stats, "a train step was skipped"

    # a second dispatch of each program did not recompile
    driver = ("_superstep",) if k > 1 else ("_insert", "_train_iter")
    compiles = ledger.of("_rollout", *driver)
    assert set(driver) <= set(compiles), sorted(ledger.seconds)
    # K > 1 compiles _rollout for the test cadence only; the classic
    # loop also for training (test_mode is a static argument)
    allowed = dict.fromkeys(driver, 1) | {"_rollout": 1 if k > 1 else 2}
    for name, secs in compiles.items():
        assert len(secs) <= allowed[name], (name, secs)

    model_dir = None
    if save:
        # the checkpoint, found and hash-verified the way a resume does
        models = os.path.join(workdir, "models")
        (token,) = os.listdir(models)
        model_dir = os.path.join(models, token)
        found = find_checkpoint(model_dir)
        assert found is not None and found[1] == t_env, found

    stage = "time_superstep_ms" if k > 1 else "time_train_ms"
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "t_env": t_env, "train_iterations": trained,
        "dispatches": dispatches, "superstep": k,
        "loss": losses,
        "compile_seconds": compiles,
        "cache_hits": ledger.cache_hits,
        # the last log window holds warm dispatches only
        "warm_ms": {s: round(logger.stats[s][-1][1], 1)
                    for s in (stage, "time_rollout_ms", "time_test_ms")
                    if s in logger.stats},
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "checkpoint": found[0] if save else None,
    }, ts, model_dir


# -------------------------------------------------------------- kernels

def attention_cases(cfg, env_info):
    """{name: (B, H, Tq, Tk, D)} of every attention ``cfg``'s learner
    runs through the flash kernel under ``kernels.attention=pallas``:
    the query-slice unrolls (sliced rows x heads as one head-free call,
    ``ops/query_slice.transformer_rows``) and the dense unrolls
    (``use_qslice=false``). tests/test_chip_smoke.py holds these
    against the shapes config 3 traces."""
    m = cfg.model
    a = env_info["n_agents"]
    tok_agent = env_info["obs_shape"] // env_info["obs_entity_feats"] + 1
    tok_mixer = (env_info["state_shape"] // env_info["state_entity_feats"]
                 + a + 3)
    s = cfg.batch_size * a

    def head_dim(emb, heads):
        return emb // heads if m.standard_heads else emb
    return {
        "agent-qslice": (s, 1, m.heads, tok_agent, m.emb),
        "mixer-qslice": (cfg.batch_size, 1, (a + 3) * m.mixer_heads,
                         tok_mixer, m.mixer_emb),
        "agent-dense": (s, m.heads, tok_agent, tok_agent,
                        head_dim(m.emb, m.heads)),
        "mixer-dense": (cfg.batch_size, m.mixer_heads, tok_mixer,
                        tok_mixer, head_dim(m.mixer_emb, m.mixer_heads)),
    }


def phase_kernels(cfg, seed: int = 0):
    """Pallas forward and gradient at ``attention_cases(cfg)``, in the
    config's compute dtype, against the f32 einsum reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from t2omca_tpu.envs.registry import make_env
    from t2omca_tpu.kernels import attention

    def fwd(q, k, v):
        return attention.flash_attention(q, k, v)

    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            return attention._reference_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), None, False)

    def grad(f):
        return jax.grad(lambda *x: (f(*x).astype(jnp.float32) ** 2).sum(),
                        argnums=(0, 1, 2))

    dtype = cfg.model.dtype
    env_info = make_env(cfg.env_args).get_env_info()
    facts = {}
    for name, (b, h, t_q, t_k, d) in attention_cases(cfg, env_info).items():
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.float32)
                   .astype(dtype)
                   for kk, t in zip(keys, (t_q, t_k, t_k)))
        outs = {}
        for what, fn in (("fwd", fwd), ("grad", grad(fwd))):
            compiled = jax.jit(fn).lower(q, k, v).compile()
            if not attention.INTERPRET:
                assert "tpu_custom_call" in compiled.as_text(), \
                    f"{name} {what}: not a Mosaic kernel"
            outs[what] = compiled(q, k, v)
        want = {"fwd": jax.jit(ref)(q, k, v),
                "grad": jax.jit(grad(ref))(q, k, v)}

        err = {}
        rtol, atol = FWD_TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(outs["fwd"], np.float32), np.asarray(want["fwd"]),
            rtol=rtol, atol=atol, err_msg=f"{name} forward")
        err["fwd"] = float(jnp.abs(outs["fwd"].astype(jnp.float32)
                                   - want["fwd"]).max())
        rtol, atol = GRAD_TOL[dtype]
        for arg, got, exp in zip("qkv", outs["grad"], want["grad"]):
            scale = max(float(jnp.abs(exp).max()), 1.0)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(exp), rtol=rtol,
                atol=atol * (scale if dtype == "bfloat16" else 1.0),
                err_msg=f"{name} d{arg}")
            err[f"d{arg}_rel"] = float(
                jnp.abs(got.astype(jnp.float32) - exp).max()) / scale
        facts[name] = {"shape": [b, h, t_q, t_k, d], "dtype": dtype,
                       "max_err": {k_: float(f"{v_:.3g}")
                                   for k_, v_ in err.items()}}
    return {"mosaic": not attention.INTERPRET, "cases": facts}


# ---------------------------------------------------------------- serve

def phase_serve(cfg, ts, model_dir: str, workdir: str,
                buckets=(4, 16), seed: int = 0):
    """Export the train phase's checkpoint, load it through the
    front-end, and answer ragged requests across both buckets (and one
    past the largest) with the hidden carried — against the training
    side's ``select_actions(test_mode=True)`` on the live parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from t2omca_tpu.controllers.basic_mac import MAC_REGISTRY
    from t2omca_tpu.envs.registry import make_env
    from t2omca_tpu.serve.export import export_artifact
    from t2omca_tpu.serve.frontend import ServeFrontend

    art = os.path.join(workdir, "artifact")
    meta = export_artifact(cfg, model_dir, art, buckets=buckets,
                           dtypes=("float32",))
    fe = ServeFrontend.load(art, dtype="float32")

    env_info = make_env(cfg.env_args).get_env_info()
    mac = MAC_REGISTRY[cfg.mac].build(cfg, env_info)
    params = mac.prepare_acting_params(ts.learner.params["agent"],
                                       dtype=mac.agent.dtype)
    select = jax.jit(lambda p, o, av, h: mac.select_actions(
        p, o, av, h, jax.random.PRNGKey(0), jnp.asarray(0),
        test_mode=True))
    a, d, na = fe.n_agents, fe.obs_dim, fe.n_actions
    rng = np.random.default_rng(seed)
    decisions = agree = 0
    hidden_err = 0.0
    sizes = (1, buckets[0], buckets[0] + 1, buckets[1], buckets[1] + 3)
    for n in sizes:
        h_ref, h_fe = np.zeros((n, a, mac.emb), np.float32), None
        for _ in range(2):              # second request carries the hidden
            obs = rng.standard_normal((n, a, d)).astype(np.float32)
            avail = rng.random((n, a, na)) < 0.7
            avail[..., 0] = True
            a_ref, h_ref, _ = select(params, obs, avail.astype(np.int32),
                                     h_ref)
            a_fe, h_fe = fe.select(obs, avail, h_fe)
            assert a_fe.shape == (n, a) and a_fe.dtype == np.int32
            assert np.take_along_axis(avail, a_fe[..., None], -1).all(), \
                "served an unavailable action"
            h_ref = np.asarray(h_ref, np.float32)
            decisions += a_fe.size
            agree += int((np.asarray(a_ref) == a_fe).sum())
            hidden_err = max(hidden_err, float(np.abs(h_ref - h_fe).max()))
    # two programs of the same math: f32 parity is bit-exact
    # (tests/test_serve.py); bf16 compute may round a near-tie the
    # other way, so the pin is the representation plus near-total
    # agreement (the serve tests' bf16 tolerance)
    exact = cfg.model.dtype == "float32"
    assert hidden_err <= (0.0 if exact else 0.15), hidden_err
    assert agree >= (decisions if exact else 0.99 * decisions), \
        (agree, decisions)
    return {"checkpoint_t_env": meta["checkpoint"]["t_env"],
            "buckets": list(buckets), "request_sizes": list(sizes),
            "decisions": decisions, "agree": agree,
            "hidden_max_err": float(f"{hidden_err:.3g}")}


# -------------------------------------------------------- four chips

def dp_config(cfg, n: int):
    """``cfg`` data-parallel over ``n`` chips with env lanes and ring
    sized so each chip holds what one chip holds under ``cfg``; the
    train batch stays the global batch. Through the three-program loop:
    the sharded insert all-gathers the GLOBAL rollout batch on every
    chip, and at config 3 x 4 the compiler sizes the fused K=4 program
    at 20.64 GB a chip (insert alone: 12.31 GB, which fits)."""
    return cfg.replace(
        dp_devices=n, superstep=1, batch_size_run=cfg.batch_size_run * n,
        replay=dataclasses.replace(
            cfg.replay, buffer_size=cfg.replay.buffer_size * n))


def phase_dp(cfg, workdir: str, ledger, n: int = 4):
    """``run.run`` of ``dp_config(cfg, n)`` (no checkpoints: the
    gathered state is n rings), then on its final state:
    (a) every chip holds a shard of the ring and of the env lanes,
    (b) parameters are replicated and identical, (c) the loss of one
    global batch sampled from the ring equals the same batch and
    learner on ONE chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from t2omca_tpu.parallel import make_mesh
    from t2omca_tpu.run import Experiment

    cfg = dp_config(cfg, n)
    facts, ts, _ = phase_train(cfg, workdir, ledger, save=False)
    mesh = make_mesh(n)
    chips = set(mesh.devices.flat)

    def shards(leaf):
        assert leaf.shape[0] % n == 0, leaf.shape
        held = {s.device: s.data.shape[0] for s in leaf.addressable_shards}
        assert set(held) == chips, f"held by {sorted(map(str, held))}"
        assert set(held.values()) == {leaf.shape[0] // n}, held
        return leaf.shape[0] // n
    ring = {shards(x) for x in jax.tree.leaves(ts.buffer.storage)}
    lanes = {shards(x) for x in jax.tree.leaves(ts.runner.env_states)}
    assert ring == {cfg.replay.buffer_size // n}, ring
    assert lanes == {cfg.batch_size_run // n}, lanes

    for leaf in jax.tree.leaves(ts.learner.params):
        assert leaf.sharding.is_fully_replicated
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == n
        for c in copies[1:]:
            np.testing.assert_array_equal(copies[0], c)

    exp = Experiment.build(cfg)
    t_env = jnp.asarray(facts["t_env"])
    batch, _, weights = jax.jit(
        lambda buf, key: exp.buffer.sample(buf, key, cfg.batch_size, t_env)
    )(ts.buffer, jax.random.PRNGKey(cfg.seed))
    train = jax.jit(exp.learner.train)

    episode = jnp.asarray(int(jax.device_get(ts.episode)))

    def put(tree, at):
        return jax.device_put(jax.device_get(tree), at)

    def loss_on(learner_at, batch_at):
        """The bare learner update on the same values, placed: learner
        state at ``learner_at``, episode axis at ``batch_at``."""
        _, info = train(put(ts.learner, learner_at), put(batch, batch_at),
                        put(weights, batch_at), t_env, episode)
        return float(info["loss"])
    # DataParallel's placement (parallel/mesh.py): learner replicated,
    # every batch leaf sharded on its leading episode axis
    loss_dp = loss_on(NamedSharding(mesh, P()), NamedSharding(mesh, P("data")))
    loss_one = loss_on(jax.devices()[0], jax.devices()[0])
    assert math.isfinite(loss_dp) and math.isclose(
        loss_dp, loss_one, rel_tol=DP_LOSS_RTOL), (loss_dp, loss_one)
    return {**facts, "dp_devices": n,
            "ring_episodes_per_chip": ring.pop(),
            "env_lanes_per_chip": lanes.pop(),
            "params_replicated_identical": True,
            "loss_dp": loss_dp, "loss_one_chip": loss_one,
            "loss_rel_diff": float(
                f"{abs(loss_dp - loss_one) / abs(loss_one):.3g}")}


# ----------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the data-parallel phase and its one-chip "
                         "comparison only (the driver never passes it)")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    from t2omca_tpu.config import load_config
    from t2omca_tpu.obs.compiles import CompileListener
    from t2omca_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # compile seconds by program and the cache's hits, process-wide (the
    # package's listener without a recorder; run.run installs its own,
    # bound to its spans, where a configuration has telemetry on)
    ledger = CompileListener().install()
    cfg = load_config(CONFIG3)
    # one endpoint per process is the config's; a smoke run needs none
    cfg = cfg.replace(obs=dataclasses.replace(cfg.obs, pulse_port=0))
    print(json.dumps({"phase": "device", "devices": len(devices),
                      "kind": devices[0].device_kind,
                      "compile_cache": cache_dir}), flush=True)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 4:
            with phase("dp4") as out:
                out.update(phase_dp(cfg, workdir, ledger))
        else:
            with phase("train") as out:
                facts, ts, model_dir = phase_train(cfg, workdir, ledger)
                out.update(facts)
            with phase("kernels") as out:
                out.update(phase_kernels(cfg))
            with phase("serve") as out:
                out.update(phase_serve(cfg, ts, model_dir, workdir))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
