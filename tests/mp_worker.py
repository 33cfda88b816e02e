"""Worker for the multi-process (multi-host leg) test — NOT a test module.

Launched twice by ``test_multihost.py`` with the standard JAX topology
env vars (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/
``JAX_PROCESS_ID``) set, exactly the scheduler contract
``parallel.distributed.maybe_initialize_distributed`` consumes in
production (wired at ``t2omca_tpu/__main__.py``). Each process owns 4
virtual CPU devices; the global mesh spans both processes, so the data
axis crosses the process boundary and every collective in the train step
takes the DCN leg (gloo on CPU; ICI/DCN on a real pod).

With ``MP_CKPT_DIR`` set, the worker additionally saves a full-state
checkpoint from the 2-process mesh (the gather-to-process-0 path in
``utils.checkpoint.save_checkpoint``) and prints a deterministic greedy
evaluation fingerprint of the trained model; the parent then restores
the checkpoint model-only in a plain single-process build and asserts
the identical fingerprint (SURVEY.md §5(4) + A8).

With ``MP_CHAOS=1`` additionally set, process 1 SIGKILLs itself after
the collective save and process 0 runs the graftmorph coordinated-
preemption exit path against the dead peer: announce, bounded barrier
(must fail, not hang), degraded per-host shard save, and the
all-shards-or-skip fallback to the newest COMPLETE save
(docs/RESILIENCE.md §6) — then exits 0.

The jax config setup lives under ``__main__`` so the parent test process
can import :func:`worker_config` / :func:`eval_fingerprint` without
mutating its own already-initialized backend.
"""

import os
import sys


def worker_config():
    """The shared tiny config — the parent's single-process restore must
    build the identical model."""
    from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                                   TrainConfig, sanity_check)
    return sanity_check(TrainConfig(
        batch_size_run=8, batch_size=8,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=4),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=16),
    ))


def eval_fingerprint(exp, agent_params) -> float:
    """Deterministic greedy-eval metric: mean episode return of one
    test-mode rollout from a FIXED runner seed, on the default local
    device (host-local numpy params in, so no mesh/topology leaks into
    the program — both mp_worker processes and the parent's restored
    single-process build must produce the identical float on CPU)."""
    import jax
    import numpy as np

    params = jax.device_get(agent_params)     # host-local, uncommitted
    rs = exp.runner.init_state(jax.random.PRNGKey(7))
    run = jax.jit(exp.runner.run, static_argnames="test_mode")
    _, _, stats = run(params, rs, test_mode=True)
    return float(np.mean(np.asarray(
        jax.device_get(stats.episode_return))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from t2omca_tpu.parallel import (DataParallel, make_mesh,
                                     maybe_initialize_distributed)
    from t2omca_tpu.run import Experiment

    assert maybe_initialize_distributed(), "topology env vars must be set"
    assert jax.device_count() == 8, jax.device_count()
    assert len(jax.local_devices()) == 4

    cfg = worker_config()
    exp = Experiment.build(cfg)
    mesh = make_mesh(8)
    dp = DataParallel(exp, mesh)
    # every process computes the identical initial state (same seed), so
    # each can build its LOCAL shards of the global arrays directly
    # (make_array_from_callback) — zero cross-process traffic. The
    # obvious dp.shard()/device_put route funnels its per-device
    # transfers through the gloo tcp pair concurrently, which races on
    # an oversubscribed CPU box (pre-existing jaxlib flake: gloo
    # EnforceNotMet preamble-size mismatch — observed even for a single
    # scalar leaf). On a real TPU pod dp.shard is ICI/DCN traffic and
    # stays the production path.
    import numpy as np

    def _place(x, s):
        arr = np.asarray(jax.device_get(x))
        return jax.make_array_from_callback(arr.shape, s,
                                            lambda idx: arr[idx])

    init = exp.init_train_state(0)
    ts = jax.tree.map(_place, init, dp.state_shardings(init))
    rollout, insert, train_iter = dp.jitted_programs()

    # block after every program: the driver's async dispatch is the point
    # in production, but on the gloo CPU transport two overlapping
    # executables whose collectives interleave on one tcp pair race the
    # transport (observed flake: gloo EnforceNotMet preamble-size
    # mismatch, a pre-existing jaxlib/gloo issue on oversubscribed CPU) —
    # the worker is a correctness fixture, so serialize for determinism
    rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                           test_mode=False)
    jax.block_until_ready((rs, batch))
    obs_leaf = jax.tree.leaves(batch.obs)[0]
    assert len(obs_leaf.sharding.device_set) == 8, "episode axis not global"
    ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                    episode=ts.episode + cfg.batch_size_run)
    jax.block_until_ready(ts.buffer)
    ts, info = train_iter(ts, jax.random.PRNGKey(1), jnp.asarray(32))
    jax.block_until_ready(ts)
    loss = float(jax.device_get(info["loss"]))
    assert jnp.isfinite(loss)
    leaf = jax.tree.leaves(ts.learner.params)[0]
    assert leaf.sharding.is_fully_replicated, "params must stay replicated"
    # the parent compares this line across both processes: identical loss
    # proves the gradient psum crossed the process boundary coherently
    print(f"LOSS {loss:.10f}", flush=True)

    ckpt_dir = os.environ.get("MP_CKPT_DIR")
    if ckpt_dir:
        from t2omca_tpu.utils.checkpoint import save_checkpoint
        # collective: both processes must call; process 0 writes
        save_checkpoint(ckpt_dir, 32, ts)
        # %.17g round-trips the float64 exactly — the parent asserts
        # bit-equality against its own single-process restore
        print(f"EVAL {eval_fingerprint(exp, ts.learner.params['agent']):.17g}",
              flush=True)

    if ckpt_dir and os.environ.get("MP_CHAOS") == "1":
        # graftmorph chaos acceptance (docs/RESILIENCE.md §6): SIGKILL
        # one of the two gloo hosts, then drive the SURVIVOR through the
        # driver's coordinated-preemption exit path against the corpse.
        import signal
        import time

        from t2omca_tpu.parallel import distributed as dist
        from t2omca_tpu.utils.checkpoint import (find_checkpoint,
                                                 save_checkpoint_shards,
                                                 verify_checkpoint)
        if jax.process_index() == 1:
            # the victim: die the hard way — no atexit, no handler, no
            # goodbye to the coordinator; exactly what a spot-VM reclaim
            # looks like to the surviving host. The parent must NOT
            # assert this process's returncode (-SIGKILL by design).
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(1.0)                 # let the SIGKILL actually land
        t_cut = 48
        dist.announce_shutdown(t_cut)
        # the bounded barrier against a dead peer: must fail INSIDE the
        # timeout instead of hanging (a collective save here would block
        # forever on the gloo transport — that is the whole point of the
        # degrade-to-shards protocol)
        target, ok = dist.negotiate_stop_step(t_cut, timeout_s=3.0)
        assert not ok, "barrier must degrade against a dead peer"
        assert target == t_cut
        # degraded exit: zero collectives — this host's shard only
        save_checkpoint_shards(ckpt_dir, t_cut, ts)
        # all-shards-or-skip gate: shard 0-of-2 alone is NOT valid; the
        # newest RESUMABLE save is the complete collective one at 32
        assert not verify_checkpoint(os.path.join(ckpt_dir, str(t_cut)))
        found = find_checkpoint(ckpt_dir)
        assert found is not None, "completeness gate skipped everything"
        print(f"CKPT {found[1]}", flush=True)
        # skip atexit: jax.distributed.shutdown would wait on the dead
        # peer's never-arriving disconnect. The exit STATUS is the
        # survivor's contract, not its teardown.
        sys.stdout.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    import jax

    # CPU-only by construction: these children never need a chip, so a
    # parent that holds one can start them
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    # CPU cross-process collectives backend (jaxlib ships gloo); a TPU pod
    # uses the ICI/DCN fabric instead, so this stays test-side
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    sys.exit(main())
