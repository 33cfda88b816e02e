"""Benchmark: fused rollout throughput at the north-star config.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Measures env-steps/sec of the jitted rollout program (vmapped env + MAC
action selection + episode-batch emission fused into one XLA program) at the
BASELINE.json north-star scale point: 64 AGVs × 8 MECs × 1024 parallel envs,
d_model 256 agent network. ``vs_baseline`` is the ratio to the 50,000
env-steps/s/chip target (the reference publishes no numbers of its own).

Every measuring mode needs a TPU and exits non-zero without one: a CPU
timing is not a speed, and no record is written under a device metric's
name from one (``utils/chip.require_tpu``). ``--smoke`` (tiny config,
CPU pin, Pallas interpret) is the control-flow rehearsal of this
harness, ``--hbm`` is shape arithmetic; only those two run chip-less.
One process holds the chip for the whole run — no child of this
program ever needs it.

Flags:
  --smoke       tiny CPU config (CI validation of the bench harness itself)
  --envs N      override the env-batch size
  --steps N     override episode_limit for the timed program
  --iters N     timed repetitions (median reported)
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time

import numpy as np

from t2omca_tpu.obs.spans import SpanRecorder

#: graftscope span recorder for the bench phases (stdlib-only import —
#: must not trigger jax before the smoke path pins JAX_PLATFORMS). The
#: emitted record embeds ``_REC.summary()`` so every record carries the
#: per-phase breakdown (build / compile / warm / measure), and on
#: failure ``main_flight`` emits a partial record with the open phase +
#: flight tail, so a failed bench says WHERE it died.
_REC = SpanRecorder(ring_size=128)

#: keys merged into every emitted success record (``_finalize``): the
#: live platform the run committed to (``main``)
_RECORD_EXTRA: dict = {}

#: BENCH record schema version — every record carries uniform
#: ``schema``/``platform``/``host`` meta
BENCH_SCHEMA = 1
_HOST = socket.gethostname()


def _finalize(rec: dict) -> dict:
    """Attach the per-phase span summary and the uniform
    ``schema``/``platform``/``host`` meta to a bench record before
    emission."""
    rec.setdefault("spans", _REC.summary())
    rec.update(_RECORD_EXTRA)
    rec.setdefault("schema", BENCH_SCHEMA)
    rec.setdefault("host", _HOST)
    # platform: the live backend when main() recorded one
    # (_RECORD_EXTRA), else the env pin (a record from before the
    # backend was committed — usage/config failures)
    rec.setdefault("platform", os.environ.get("JAX_PLATFORMS") or None)
    return rec


def _sync(x):
    """Device→host fetch of one scalar: the timed region's barrier."""
    return float(np.asarray(x))


def _chain_seconds(step, carry, k):
    """Seconds per iteration of k async-chained dispatches with ONE
    terminal sync. Each dispatch consumes the previous carry, so the
    device serializes them, but the host enqueues ahead — per-call
    dispatch latency overlaps device compute.
    This is the steady-state rate the production driver loop sees (it
    never blocks on a host fetch per episode); a blocking median is the
    per-dispatch latency."""
    # one warm chained step first: the chained carry can have a different
    # layout/sharding than the caller's warm-path input (GSPMD output
    # placement), and that one-time recompile must not be timed
    carry, out = step(carry)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(k):
        carry, out = step(carry)
    _sync(out)
    return (time.perf_counter() - t0) / k


def breakdown(cfg, exp, ts, _time, args) -> int:
    """Attribute the rollout slot time (stderr table + one JSON line)."""
    import dataclasses
    import jax
    import jax.numpy as jnp

    env, mac = exp.env, exp.mac
    b, t_len = cfg.batch_size_run, cfg.env_args.episode_limit
    params = ts.learner.params["agent"]
    rs = ts.runner
    rows = {}

    def env_only(env_obj):
        def run(rs_states, key):
            def step_fn(carry, key_t):
                states, t = carry
                actions = jax.random.randint(
                    key_t, (b, env_obj.n_agents), 0, env_obj.n_actions)
                # empty-buffer lanes must take action 0 (legal projection)
                actions = actions * states.job_valid[:, :, 0]
                states, reward, *_ = jax.vmap(env_obj.step)(
                    states, actions, jax.random.split(key_t, b))
                return (states, t + 1), reward
            (states, _), rewards = jax.lax.scan(
                step_fn, (rs_states, 0), jax.random.split(key, t_len))
            return rewards.sum()
        return jax.jit(run)

    for label, fn in (("env_seq", False), ("env_fast", True)):
        e = dataclasses.replace(
            env, cfg=dataclasses.replace(env.cfg, fast_norm=fn))
        prog = env_only(e)
        rows[label] = _time(lambda p=prog: p(rs.env_states,
                                             jax.random.PRNGKey(0)))

    # acting-only: T sequential MAC forwards on a fixed obs batch
    obs = jnp.zeros((b, env.n_agents, env.obs_dim),
                    jnp.dtype(cfg.model.dtype))
    avail = jnp.ones((b, env.n_agents, env.n_actions), jnp.int32)

    def acting(params):
        # fold qslice weights outside the scan, as runner.run does
        params = mac.prepare_acting_params(params)

        def step_fn(carry, key_t):
            hidden, t_env = carry
            # entity-table acting recomputes the factored obs per step in
            # the real rollout scan — pay it here too for honest
            # attribution (XLA may still hoist this loop-invariant copy;
            # the 'full' row is the ground truth either way)
            compact = (jax.vmap(env.compact_obs)(rs.env_states)
                       if mac.use_entity_tables else None)
            actions, hidden, _ = mac.select_actions(
                params, obs, avail, hidden, key_t, t_env, test_mode=False,
                compact=compact)
            return (hidden, t_env + b), actions.sum()
        (_, _), outs = jax.lax.scan(
            step_fn, (mac.init_hidden(b), jnp.zeros((), jnp.int32)),
            jax.random.split(jax.random.PRNGKey(1), t_len))
        return outs.sum()

    rows["acting"] = _time(lambda: jax.jit(acting)(params))

    # one AOT compile serves both the timed calls and the cost model (a
    # second jit-cache compile of the full program would double bench
    # wall-clock at scale)
    rollout_c = (jax.jit(exp.runner.run, static_argnames="test_mode")
                 .lower(params, rs, test_mode=False).compile())
    def full():
        _, batch, _ = rollout_c(params, rs)
        return batch.reward[0, 0]
    rows["full"] = _time(full)

    # static XLA cost model of the full rollout program: attributes the
    # compute/bandwidth budget even when a profiler trace isn't available
    try:
        cost = rollout_c.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if cost:
            fl = cost.get("flops", 0.0)
            by = cost.get("bytes accessed", 0.0)
            print(f"# XLA cost model (full rollout): "
                  f"{fl / 1e12:.2f} TFLOP, {by / 1e9:.2f} GB accessed -> "
                  f"{fl / max(by, 1):.1f} FLOP/byte arithmetic intensity",
                  file=sys.stderr)
    except Exception as e:           # pragma: no cover - backend-dependent
        print(f"# cost_analysis unavailable: {e!r}", file=sys.stderr)

    env_steps = b * t_len
    acting_mode = ("entity" if mac.use_entity_tables
                   else "qslice" if mac.use_qslice else "dense")
    print(f"# breakdown at {b} envs x {t_len} slots "
          f"({cfg.env_args.agv_num} AGVs, d{cfg.model.emb}, "
          f"acting={acting_mode})", file=sys.stderr)
    for k, v in rows.items():
        print(f"#   {k:10s} {v * 1e3:8.1f} ms "
              f"({env_steps / v:,.0f} env-steps/s)", file=sys.stderr)
    print(json.dumps({k: round(env_steps / v, 1) for k, v in rows.items()}))
    return 0


def _train_numbers(cfg, _time, train_bs: int | None = None,
                   pipeline_k: int = 0) -> dict:
    """Learner-side throughput — the second half of the north-star metric
    (BASELINE.json: "env-steps/sec/chip + mixer train-steps/sec").

    Measures (a) ``train_iter``: PER sample → QMIX double-Q train step over
    the full episode scan → priority feedback, as one jitted program
    (reference hot loop /root/reference/per_run.py:224-238), and (b) one
    interleaved driver iteration (rollout + insert + train), reported as
    env-steps/s inclusive of training (config 4: PER + target-net sync)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from t2omca_tpu.run import Experiment

    bs = train_bs or 32
    cfg = cfg.replace(
        batch_size=bs,
        replay=dataclasses.replace(cfg.replay, prioritized=True,
                                   buffer_size=2 * cfg.batch_size_run))
    with _REC.span("bench.build", leg="train"):
        exp = Experiment.build(cfg)
        ts = exp.init_train_state(0)
    rollout, insert, train_iter = exp.jitted_programs()
    b, t_len = cfg.batch_size_run, cfg.env_args.episode_limit

    # fill the buffer with one rollout so PER has real priorities
    with _REC.span("bench.compile", leg="train"):
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=jnp.asarray(b, jnp.int32))
    key = jax.random.PRNGKey(7)

    def train_step(ts_):
        ts2, info = train_iter(ts_, key, jnp.asarray(1000))
        return ts2, info["loss"]

    def interleaved_step(ts_):
        rs2, batch2, _ = rollout(ts_.learner.params["agent"], ts_.runner,
                                 test_mode=False)
        ts2 = ts_.replace(runner=rs2, buffer=insert(ts_.buffer, batch2))
        return train_step(ts2)

    with _REC.span("bench.measure", leg="train"):
        dt_train = _time(lambda: train_step(ts)[1])
        dt_full = _time(lambda: interleaved_step(ts)[1])

    env_steps = b * t_len
    print(f"# train_iter ({bs} episodes x {t_len + 1} slots, PER on): "
          f"{dt_train * 1e3:.1f} ms -> {1.0 / dt_train:.2f} train-steps/s",
          file=sys.stderr)
    print(f"# interleaved rollout+insert+train: {dt_full * 1e3:.1f} ms -> "
          f"{env_steps / dt_full:,.0f} env-steps/s incl. training",
          file=sys.stderr)
    out = {
        "train_steps_per_sec": round(1.0 / dt_train, 2),
        "interleaved_env_steps_per_sec": round(env_steps / dt_full, 1),
        "train_batch_episodes": bs,
    }

    if pipeline_k:
        out["pipelined_train_steps_per_sec"] = round(
            1.0 / _chain_seconds(train_step, ts, pipeline_k), 2)
        out["pipelined_interleaved_env_steps_per_sec"] = round(
            env_steps / _chain_seconds(interleaved_step, ts, pipeline_k), 1)
    return out


def bench_dp(cfg, _time, args) -> int:
    """Config-5 measurement: the DP=8 training loop over a real device mesh
    (BASELINE.json configs[4]). Env lanes and replay episodes shard over the
    ``data`` axis; params replicate; GSPMD keeps the episode axis
    distributed and psums the grads. Measures BOTH metric halves: the
    rollout (env-steps/s) and the train iteration (PER sample → QMIX train
    over the episode scan → priority feedback; reference hot loop
    /root/reference/per_run.py:224-238). ``--train`` makes the train half
    the headline record. On a machine without 8 devices use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (CPU
    validation) — per-chip numbers only mean something on a real slice."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from t2omca_tpu.parallel import DataParallel, make_mesh
    from t2omca_tpu.run import Experiment

    n_dev = 8
    # every episode axis must divide by the mesh: round env lanes down
    # (with a note) and the replay ring up. The ring holds one train
    # batch's worth of episodes (2×batch_size): train cost scales with the
    # sampled batch, not ring capacity (PER sampling is O(capacity)
    # vectorized — negligible), so the bench doesn't pay config-5's
    # production-sized ring HBM just to time the iteration.
    envs = (cfg.batch_size_run // n_dev) * n_dev
    if envs != cfg.batch_size_run:
        print(f"# rounding --envs {cfg.batch_size_run} down to {envs} "
              f"(multiple of DP={n_dev})", file=sys.stderr)
    if envs == 0:
        raise SystemExit(f"--envs must be >= {n_dev} for --config 5")
    bs = min(32, envs)
    ring = -(-max(cfg.replay.buffer_size, 2 * bs) // n_dev) * n_dev
    cfg = cfg.replace(
        batch_size_run=envs, batch_size=bs,
        replay=dataclasses.replace(cfg.replay, buffer_size=ring,
                                   prioritized=True))
    with _REC.span("bench.build", leg="dp"):
        exp = Experiment.build(cfg)
        mesh = make_mesh(n_dev)
        dp = DataParallel(exp, mesh)
        ts = dp.shard(exp.init_train_state(0))
    rollout, insert, train_iter = dp.jitted_programs()
    params = ts.learner.params["agent"]

    with _REC.span("bench.compile", leg="dp"):
        rs, batch, _ = rollout(params, ts.runner, test_mode=False)
    obs_leaf = jax.tree.leaves(batch.obs)[0]
    assert len(obs_leaf.sharding.device_set) == n_dev

    def one():
        _, b, _ = rollout(params, ts.runner, test_mode=False)
        return b.reward[0, 0]

    with _REC.span("bench.measure", leg="dp"):
        dt = _time(one)
    env_steps = cfg.batch_size_run * cfg.env_args.episode_limit
    rate = env_steps / dt
    print(f"# DP={n_dev} rollout: {dt * 1e3:.1f} ms for {env_steps} "
          f"env-steps ({cfg.batch_size_run} envs sharded over "
          f"{n_dev} devices)", file=sys.stderr)

    rate_pipe = None
    if args.pipeline:
        def roll_step(rs_):
            rs2, b, _ = rollout(params, rs_, test_mode=False)
            return rs2, b.reward[0, 0]
        rate_pipe = round(
            env_steps / _chain_seconds(roll_step, ts.runner, args.pipeline),
            1)

    # ---- train half: fill the ring with a slice of real episodes (the
    # rollout batch can exceed ring capacity at config-5 scale), keeping
    # the episode axis sharded, then time the full DP train iteration
    fill = jax.tree.map(lambda x: x[:ring], batch)
    fill = jax.device_put(fill, NamedSharding(mesh, P("data")))
    ts = ts.replace(runner=rs, buffer=insert(ts.buffer, fill),
                    # mesh-replicated, matching dp.shard — a single-device
                    # scalar here would give the chained train_iter a
                    # different input aval and force a second compile
                    episode=jax.device_put(jnp.asarray(ring, jnp.int32),
                                           NamedSharding(mesh, P())))
    key = jax.random.PRNGKey(7)

    def one_train():
        _, info = train_iter(ts, key, jnp.asarray(1000))
        return info["loss"]

    dt_train = _time(one_train)
    train_pipe = None
    if args.pipeline:
        def train_step(ts_):
            ts2_, info = train_iter(ts_, key, jnp.asarray(1000))
            return ts2_, info["loss"]
        train_pipe = round(
            1.0 / _chain_seconds(train_step, ts, args.pipeline), 2)
    ts2, _ = train_iter(ts, key, jnp.asarray(1000))
    leaf = jax.tree.leaves(ts2.learner.params)[0]
    assert leaf.sharding.is_fully_replicated, \
        "params must stay replicated through the DP train step"
    t_len = cfg.env_args.episode_limit
    print(f"# DP={n_dev} train_iter ({bs} episodes x {t_len + 1} slots, "
          f"PER on): {dt_train * 1e3:.1f} ms -> "
          f"{1.0 / dt_train:.2f} train-steps/s", file=sys.stderr)

    cfg_id = None if args.envs or args.steps else 5
    rollout_rec = {
        "metric": "env_steps_per_sec",
        "value": round(rate, 1),
        "unit": f"env-steps/s/{n_dev}-device-mesh",
        # vs_baseline keeps the per-chip semantics of every other record
        "vs_baseline": round(rate / n_dev / 50_000.0, 3),
        # only claim the BASELINE scale point when unmodified
        "config": cfg_id,
        "n_envs": cfg.batch_size_run, "dp": n_dev,
        "per_chip": round(rate / n_dev, 1),
        "train_steps_per_sec": round(1.0 / dt_train, 2),
        "train_batch_episodes": bs,
    }
    pipe_keys = {k: v for k, v in (
        ("pipelined_env_steps_per_sec", rate_pipe),
        ("pipelined_train_steps_per_sec", train_pipe)) if v is not None}
    if args.train:
        rec = {
            "metric": "train_steps_per_sec",
            "value": round(1.0 / dt_train, 2),
            "unit": f"train-steps/s/{n_dev}-device-mesh",
            "vs_baseline": None,
            "config": cfg_id,
            "dp": n_dev,
            "train_batch_episodes": bs,
            "env_steps_per_sec": round(rate, 1),
        }
    else:
        rec = rollout_rec
    rec.update(pipe_keys)
    print(json.dumps(_finalize(rec)))
    return 0


def bench_kernels(make_cfg_kernels, _time, args) -> int:
    """``--kernels``: the attention-kernel A/B leg. One rollout
    measurement per requested kernel mode (xla = einsum path, pallas =
    fused flash kernel; ``ab`` = both, xla first), each as its own JSON
    record with the mode in the record, so a kernel win is attributable
    in ``obs report``'s roofline table instead of a bare before/after
    number. Like ``--all``, each record embeds the CUMULATIVE span
    summary (a failure in leg 2 still leaves leg 1's phase timings on
    record); the per-mode split lives in the span STREAM via the
    ``leg=kernels-<mode>`` meta on every span.

    The leg forces the DENSE acting path: MultiHeadAttention — the
    program the kernel switch selects — is what the dense rollout scan
    dispatches; the qslice/entity fast paths bypass it by construction,
    so an A/B over them would measure nothing.

    Each mode ALSO measures a TRAIN-STEP leg (PR 13): the jitted
    ``train_iter`` (sample → learner update → priority feedback) over a
    ring pre-filled from the rollout, one ``train_iters_per_sec`` record
    per mode — under ``pallas`` the learner's backward lowers through
    the flash backward kernels, which is the half of the A/B the
    rollout number can't see."""
    import jax
    import jax.numpy as jnp

    from t2omca_tpu.run import Experiment

    modes = ("xla", "pallas") if args.kernels == "ab" else (args.kernels,)
    rc = 0
    for mode in modes:
        cfg = make_cfg_kernels(mode)
        label = f"kernels-{mode}"
        with _REC.span("bench.build", leg=label):
            exp = Experiment.build(cfg)
            ts = exp.init_train_state(0)
        rollout = jax.jit(exp.runner.run, static_argnames="test_mode")
        params = ts.learner.params["agent"]
        with _REC.span("bench.compile", leg=label):
            rs, batch, _ = rollout(params, ts.runner, test_mode=False)
            _sync(batch.reward[0, 0])

        def one(rollout=rollout, params=params, rs=rs):
            _, b, _ = rollout(params, rs, test_mode=False)
            return b.reward[0, 0]

        with _REC.span("bench.measure", leg=label):
            dt = _time(one)
        env_steps = cfg.batch_size_run * cfg.env_args.episode_limit
        rate = env_steps / dt
        print(f"# kernels={mode}: {dt * 1e3:.1f} ms for {env_steps} "
              f"env-steps (dense acting, "
              f"{cfg.env_args.agv_num} AGVs, d{cfg.model.emb})",
              file=sys.stderr)
        print(json.dumps(_finalize({
            "metric": "env_steps_per_sec",
            "value": round(rate, 1),
            "unit": "env-steps/s/chip",
            "vs_baseline": round(rate / 50_000.0, 3),
            "kernels": mode,
            "acting": "dense",
            "config": (None if args.smoke or args.envs or args.steps
                       else args.config),
            "n_envs": cfg.batch_size_run,
            "episode_steps": cfg.env_args.episode_limit,
        })), flush=True)

        # ---- train-step leg: fill the ring from the measured rollout,
        # then time the UNdonated train_iter on a fixed state (donation
        # would delete the inputs the next repetition re-times)
        tlabel = f"{label}-train"
        _, insert, train_iter = exp.jitted_programs()
        with _REC.span("bench.compile", leg=tlabel):
            buf_state = ts.buffer
            fills = -(-cfg.batch_size // cfg.batch_size_run)
            for _ in range(max(fills, 1)):
                buf_state = insert(buf_state, batch)
            ts_fill = ts.replace(buffer=buf_state)
            key = jax.random.PRNGKey(0)
            t_env = jnp.asarray(env_steps)
            _, info = train_iter(ts_fill, key, t_env)
            _sync(info["loss"])

        def one_train(train_iter=train_iter, ts_fill=ts_fill, key=key,
                      t_env=t_env):
            _, info = train_iter(ts_fill, key, t_env)
            return info["loss"]

        with _REC.span("bench.measure", leg=tlabel):
            dt_train = _time(one_train)
        print(f"# kernels={mode}: train_iter {dt_train * 1e3:.1f} ms "
              f"(batch {cfg.batch_size} episodes, dense learner unroll)",
              file=sys.stderr)
        print(json.dumps(_finalize({
            "metric": "train_iters_per_sec",
            "value": round(1.0 / dt_train, 2),
            "unit": "train-iters/s/chip",
            "vs_baseline": None,
            "kernels": mode,
            "leg": tlabel,
            "train_batch_episodes": cfg.batch_size,
            "config": (None if args.smoke or args.envs or args.steps
                       else args.config),
        })), flush=True)
    return rc


def bench_sebulba(cfg, _time, args) -> int:
    """``--sebulba``: the decoupled actor/learner A/B (ROADMAP item 2).

    Measures the same chained rollout→insert→train workload three ways
    and reports all of them in ONE record:

    * **classic** (context) — the classic three-program loop on a
      single device, async-chained with one terminal sync: today's
      default driver shape;
    * **serialized** — the SPLIT pipeline (1 actor + 1 learner device,
      ``parallel/sebulba.py``) run strictly phase-by-phase: each stage
      (rollout, queue hop, train, params publish) blocks to completion
      before the next starts. This is the serialized regime the
      decoupled architecture exists to remove — identical per-iteration
      work to the overlapped leg, so the A/B isolates exactly what
      overlap buys;
    * **overlapped** — the same split driven the way
      ``run.run_sebulba`` drives it: an actor thread rollouts and feeds
      the device-resident trajectory queue while the main thread
      consumes, trains and publishes params back, no per-stage syncs.
      Wall-clock covers the same k batches produced AND consumed.

    Headline = overlapped env-steps/s (training included);
    ``overlap_speedup`` = overlapped/serialized. On a real 2-chip split
    the two phases also overlap in COMPUTE; on a CPU smoke host the
    devices share cores, so the speedup there measures the removed
    serialization points only (stated by the record's backend field).
    Needs ≥ 2 devices (``--smoke`` forces 2 CPU host devices)."""
    import dataclasses
    import threading

    import jax
    import jax.numpy as jnp

    from t2omca_tpu.config import SebulbaConfig
    from t2omca_tpu.parallel.sebulba import make_sebulba
    from t2omca_tpu.run import Experiment

    k = max(2 * args.iters, 6)
    bs = 4 if args.smoke else 32
    b, t_len = cfg.batch_size_run, cfg.env_args.episode_limit
    env_steps = k * b * t_len
    cfg = cfg.replace(
        batch_size=bs,
        replay=dataclasses.replace(
            cfg.replay, prioritized=True,
            buffer_size=max(cfg.replay.buffer_size, 2 * b, bs)))

    # ---- classic context leg: one device, async-chained loop ----------
    with _REC.span("bench.build", leg="sebulba-classic"):
        exp = Experiment.build(cfg)
        ts = exp.init_train_state(0)
    rollout, insert, train_iter = exp.jitted_programs()
    key = jax.random.PRNGKey(7)

    def classic_iter(ts, i):
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=ts.episode + b)
        ts, info = train_iter(ts, jax.random.fold_in(key, i),
                              jnp.asarray(1000 + i))
        return ts, info

    with _REC.span("bench.compile", leg="sebulba-classic"):
        ts, info = classic_iter(ts, 0)      # compile + ring fill
        _sync(info["loss"])
    with _REC.span("bench.measure", leg="sebulba-classic"):
        t0 = time.perf_counter()
        for i in range(k):
            ts, info = classic_iter(ts, 1 + i)
        _sync(info["loss"])
        dt_classic = time.perf_counter() - t0
    rate_classic = env_steps / dt_classic
    print(f"# sebulba A/B classic (1 device, async chain): "
          f"{dt_classic * 1e3:.1f} ms for {env_steps} env-steps + {k} "
          f"train iters -> {rate_classic:,.0f} env-steps/s",
          file=sys.stderr)
    del ts, rollout, insert, train_iter, exp

    # ---- overlapped: 1 actor + 1 learner device ------------------------
    seb_cfg = cfg.replace(sebulba=SebulbaConfig(
        actor_devices=1, learner_devices=1, queue_slots=2, staleness=1))
    with _REC.span("bench.build", leg="sebulba-overlap"):
        exp2 = Experiment.build(seb_cfg)
        seb = make_sebulba(exp2)
        rs, ls = seb.init_states(0)
        q = seb.init_queue()
    actor_step, queue_put, queue_get, learner_step = seb.programs()
    sb = seb_cfg.sebulba

    with _REC.span("bench.compile", leg="sebulba-overlap"):
        # warm every program once (compiles + ring fill so the timed
        # iterations all take the train branch)
        params = seb.publish_params(ls.learner.params["agent"])
        rs, tm, _ = actor_step(params, rs, test_mode=False)
        q = queue_put(q, jnp.asarray(0, jnp.int32), seb.to_learner(tm))
        ls, q = queue_get(ls, q, jnp.asarray(0, jnp.int32))
        ls, info = learner_step(ls, jax.random.fold_in(key, 999),
                                jnp.asarray(1000))
        _sync(info["loss"])

    # ---- serialized split: IDENTICAL per-iteration work, every stage
    # blocked to completion before the next starts — the serialized
    # regime the decoupled loop removes
    with _REC.span("bench.measure", leg="sebulba-serial"):
        t0 = time.perf_counter()
        params = seb.publish_params(ls.learner.params["agent"])
        jax.block_until_ready(params)
        for i in range(k):
            rs, tm, stats = actor_step(params, rs, test_mode=False)
            jax.block_until_ready(stats.epsilon)
            tm_l = seb.to_learner(tm)
            jax.block_until_ready(tm_l.reward)
            q = queue_put(q, jnp.asarray(0, jnp.int32), tm_l)
            ls, q = queue_get(ls, q, jnp.asarray(0, jnp.int32))
            ls, info = learner_step(ls, jax.random.fold_in(key, 3000 + i),
                                    jnp.asarray(3000 + i))
            _sync(info["loss"])
            params = seb.publish_params(ls.learner.params["agent"])
            jax.block_until_ready(params)
        dt_serial = time.perf_counter() - t0
    rate_serial = env_steps / dt_serial
    print(f"# sebulba A/B serialized split (1+1 devices, stage-"
          f"synchronized): {dt_serial * 1e3:.1f} ms -> "
          f"{rate_serial:,.0f} env-steps/s", file=sys.stderr)

    cond = threading.Condition()
    shared = {"q": q, "params": seb.publish_params(
        ls.learner.params["agent"]), "put": 0, "consumed": 0,
        "error": None}

    def actor(rs=rs):
        try:
            for i in range(k):
                with cond:
                    while (i - shared["consumed"] > sb.staleness
                           or shared["put"] - shared["consumed"]
                           >= sb.queue_slots):
                        cond.wait()
                    params = shared["params"]
                rs, tm, stats = actor_step(params, rs, test_mode=False)
                jax.block_until_ready(stats.epsilon)
                tm_l = seb.to_learner(tm)
                with cond:
                    shared["q"] = queue_put(
                        shared["q"],
                        jnp.asarray(shared["put"] % sb.queue_slots,
                                    jnp.int32), tm_l)
                    shared["put"] += 1
                    cond.notify_all()
        except Exception as e:  # noqa: BLE001 — surfaced by the main leg
            with cond:
                shared["error"] = e
                cond.notify_all()

    with _REC.span("bench.measure", leg="sebulba-overlap"):
        t0 = time.perf_counter()
        th = threading.Thread(target=actor, daemon=True)
        th.start()
        for i in range(k):
            with cond:
                while shared["put"] <= i and shared["error"] is None:
                    cond.wait()
                if shared["error"] is not None:
                    raise shared["error"]
                ls, shared["q"] = queue_get(
                    ls, shared["q"],
                    jnp.asarray(i % sb.queue_slots, jnp.int32))
            ls, info = learner_step(ls, jax.random.fold_in(key, i),
                                    jnp.asarray(2000 + i))
            with cond:
                shared["params"] = seb.publish_params(
                    ls.learner.params["agent"])
                shared["consumed"] = i + 1
                cond.notify_all()
        _sync(info["loss"])
        dt_overlap = time.perf_counter() - t0
        th.join(timeout=30)
    rate_overlap = env_steps / dt_overlap
    speedup = rate_overlap / rate_serial
    print(f"# sebulba A/B overlapped (1+1 devices, queue_slots="
          f"{sb.queue_slots}, staleness={sb.staleness}): "
          f"{dt_overlap * 1e3:.1f} ms -> {rate_overlap:,.0f} env-steps/s "
          f"({speedup:.2f}x serialized)", file=sys.stderr)
    print(json.dumps(_finalize({
        "metric": "env_steps_per_sec",
        "value": round(rate_overlap, 1),
        "unit": "env-steps/s/2-device-split",
        # per-chip semantics like the DP record: the split uses 2 chips
        "vs_baseline": round(rate_overlap / 2 / 50_000.0, 3),
        "sebulba": {"actor_devices": 1, "learner_devices": 1,
                    "queue_slots": sb.queue_slots,
                    "staleness": sb.staleness},
        # A/B pair: same split, same per-iteration work — serialized
        # blocks every stage, overlapped is the production coordination
        "serialized_env_steps_per_sec": round(rate_serial, 1),
        "overlap_speedup": round(speedup, 3),
        # context: the classic single-device async-chained loop (on a
        # shared-core CPU host this can exceed both split legs — the
        # split pays queue/copy overhead for compute overlap that only
        # disjoint real chips can deliver)
        "classic_env_steps_per_sec": round(rate_classic, 1),
        "config": (None if args.smoke or args.envs or args.steps
                   else args.config),
        "n_envs": b,
        "episode_steps": t_len,
        "train_batch_episodes": bs,
        "chained_iters": k,
        "backend": jax.default_backend(),
    })))
    return 0


def bench_superstep(cfg, _time, args) -> int:
    """``--superstep K``: the dispatch-amortized training rate. ONE fused
    XLA program scans K rollout → in-place ring insert → (gated)
    sample+train iterations per dispatch
    (``run.Experiment.superstep_program``) — the rate the production
    driver sees at ``superstep=K``, where the per-dispatch overhead is
    paid once per K full train iterations instead of 3× per iteration
    (how much that is on a local chip is not measured). Headline:
    env-steps/s over the whole dispatch INCLUDING training."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from t2omca_tpu.run import Experiment

    k = args.superstep
    bs = 4 if args.smoke else 32
    b = cfg.batch_size_run
    cfg = cfg.replace(
        batch_size=bs,
        replay=dataclasses.replace(
            cfg.replay, prioritized=True,
            buffer_size=max(cfg.replay.buffer_size, 2 * b, bs)))
    with _REC.span("bench.build"):
        exp = Experiment.build(cfg)
        ts = exp.init_train_state(0)
        # un-donated: the timed dispatches re-run on the same warmed state
        superstep = exp.superstep_program(k)
    keys = jax.random.split(jax.random.PRNGKey(7), k)
    t_len = cfg.env_args.episode_limit
    # warm dispatch (compile + ring fill: k·b episodes) so the timed
    # dispatches exercise the train branch of the gate
    with _REC.span("bench.compile", k=k):
        ts, _, _ = superstep(ts, keys, jnp.zeros((), jnp.int32))
        gate_open = int(jax.device_get(ts.buffer.episodes_in_buffer)) >= bs

    with _REC.span("bench.measure", k=k):
        dt = _time(lambda: superstep(
            ts, keys, jnp.asarray(1000, jnp.int32))[1].epsilon[-1])
    env_steps = k * b * t_len
    rate = env_steps / dt
    print(f"# superstep K={k}: {dt * 1e3:.1f} ms/dispatch for {env_steps} "
          f"env-steps + {k if gate_open else 0} train iters "
          f"({b} envs x {t_len} slots, train batch {bs})", file=sys.stderr)
    print(json.dumps(_finalize({
        "metric": "env_steps_per_sec",
        "value": round(rate, 1),
        "unit": "env-steps/s/chip",
        "vs_baseline": round(rate / 50_000.0, 3),
        "superstep": k,
        "config": (None if args.smoke or args.envs or args.steps
                   else args.config),
        "n_envs": b,
        "episode_steps": t_len,
        "train_batch_episodes": bs,
        "train_gate_open": gate_open,
        "dispatch_s": round(dt, 4),
    })))
    return 0


def bench_population(cfg, _time, args, dp=None) -> int:
    """``--population P``: the graftpop experiment-throughput leg
    (docs/POPULATION.md). ONE vmapped population superstep advances P
    seed variants per dispatch (``run.Experiment.
    population_superstep_program``); the A/B baseline is the SAME P
    experiments run SERIALIZED — P sequential solo superstep dispatches
    — which is exactly how the 16-AGV campaigns in git history burned
    wall-clock. Headline: ``experiments_per_sec`` = experiment·train-
    iters/s (P × per-dispatch iters / dispatch seconds); the record
    carries both rates and ``population_speedup``.

    Graftlattice compositions (docs/PERF.md §lattice):

    * ``--kernels pallas|xla`` selects the attention-kernel mode for
      BOTH sides of the A/B (vmap-over-pallas: the member axis vmaps
      over the fused flash kernels; dense acting forced like the
      ``--kernels`` leg);
    * ``dp=N`` (the ``--lattice`` matrix's population-over-dp sub-leg)
      shards the LEADING member axis over an N-device mesh
      (``parallel.population_shardings``) while the serialized baseline
      stays single-device."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from t2omca_tpu import population as graftpop
    from t2omca_tpu.config import PopulationConfig
    from t2omca_tpu.run import Experiment

    p = args.population
    mode = getattr(args, "kernels", None)
    if mode is not None:
        from t2omca_tpu.config import KernelsConfig
        # dense acting: the kernel switch selects the program the dense
        # rollout/learner unroll dispatches (bench_kernels docstring);
        # the population axis vmaps OVER the flash kernels
        cfg = cfg.replace(
            model=dataclasses.replace(cfg.model, use_qslice=False),
            kernels=KernelsConfig(attention=mode))
    leg = ("population" if mode is None and not dp
           else f"population-{mode or f'dp{dp}'}")
    k = 1                      # iters per dispatch: the speedup under
    # measurement is the population axis, not the superstep scan
    bs = 4 if args.smoke else 32
    if args.smoke and not args.envs and not args.steps:
        # the population smoke point: a deliberately dispatch-overhead-
        # dominated workload (2 lanes × 2 slots) — the one regime where
        # the member-axis amortization is measurable on a CPU host at
        # all (at CPU compute-bound scales the 2-core box
        # caps the win near 1.5x; pass --envs/--steps to measure those)
        cfg = cfg.replace(
            batch_size_run=2,
            env_args=dataclasses.replace(cfg.env_args, episode_limit=2))
    b = cfg.batch_size_run
    base = cfg.replace(
        batch_size=bs,
        replay=dataclasses.replace(
            cfg.replay, prioritized=True,
            buffer_size=max(cfg.replay.buffer_size, 2 * b, bs)))
    pop_cfg = base.replace(population=PopulationConfig(size=p))

    with _REC.span("bench.build", leg=leg):
        exp = Experiment.build(pop_cfg)
        ts, spec = graftpop.init_population(exp, pop_cfg)
        # un-donated: the timed dispatches re-run on the same warm state
        prog = exp.population_superstep_program(k)
        solo_exp = Experiment.build(base)
        solo_ts = solo_exp.init_train_state(0)
        solo_prog = solo_exp.superstep_program(k)
    keys = jnp.stack([jax.random.split(jax.random.PRNGKey(7 + m), k)
                      for m in range(p)])
    t_env = jnp.zeros((), jnp.int32)
    if dp:
        # population-over-dp: the mesh shards the LEADING member axis —
        # whole members per device, no cross-member collectives; the
        # key stack shards with the state so the dispatched program
        # sees the same input shardings as the ratcheted
        # pop_dp_superstep audit twin
        from t2omca_tpu.parallel import make_mesh, population_shardings
        mesh = make_mesh(dp)
        ts = jax.device_put(ts, population_shardings(mesh, ts))
        spec = jax.device_put(spec, population_shardings(mesh, spec))
        keys = jax.device_put(keys, population_shardings(mesh, keys))
    # enough warm dispatches to FILL the ring past the train batch (each
    # inserts k·b episodes), so the timed dispatches exercise the train
    # branch of the gate in both modes — a fixed warm count would leave
    # the gate closed at small --envs and time two different workloads
    # (the vmapped select still executes-and-discards the train branch;
    # the solo scalar cond genuinely skips it)
    warm = max(2, -(-bs // (k * b)) + 1)
    with _REC.span("bench.compile", leg=leg, p=p, warm=warm):
        for _ in range(warm):
            ts, _, _ = prog(ts, keys, t_env, spec)
            solo_ts, _, _ = solo_prog(solo_ts, keys[0], t_env)
        gate_open = bool(jax.device_get(
            exp.buffer.can_sample(
                jax.tree.map(lambda x: x[0], ts.buffer), bs)))
    if not gate_open:
        print("# population: train gate CLOSED after warm-up — record "
              "measures rollout+insert only", file=sys.stderr)

    t1k = jnp.asarray(1000, jnp.int32)
    with _REC.span("bench.measure", leg=leg, mode="vmapped"):
        dt_pop = _time(
            lambda: prog(ts, keys, t1k, spec)[1].epsilon[-1, -1])

    def _serial():
        # the serialized A/B: the SAME P experiments as P SEPARATE
        # sequential campaigns — which is what "running seeds serially"
        # means: each run's driver loop syncs at its own cadences and
        # two different processes' dispatches never overlap, so each
        # solo dispatch is fetched before the next begins (state reuse
        # is fine — this times dispatches, not learning)
        out = None
        for m in range(p):
            out = solo_prog(solo_ts, keys[m], t1k)[1].epsilon[-1]
            _sync(out)
        return out
    with _REC.span("bench.measure", leg=leg, mode="serialized"):
        dt_serial = _time(_serial)

    pop_rate = p * k / dt_pop
    serial_rate = p * k / dt_serial
    speedup = dt_serial / dt_pop
    combo = ("" if mode is None and not dp else
             f" × {f'kernels={mode}' if mode else f'dp={dp}'}")
    print(f"# population P={p}{combo}: {dt_pop * 1e3:.1f} ms/dispatch "
          f"vmapped vs {dt_serial * 1e3:.1f} ms for {p} serialized solo "
          f"dispatches ({speedup:.2f}x; {b} envs, train batch {bs}, "
          f"gate {'open' if gate_open else 'CLOSED'})", file=sys.stderr)
    rec = {
        "metric": "experiments_per_sec",
        "value": round(pop_rate, 2),
        "unit": (f"experiment-train-iters/s/{dp}-device-mesh" if dp
                 else "experiment-train-iters/s/chip"),
        "vs_baseline": None,
        "population": p,
        "serialized_experiments_per_sec": round(serial_rate, 2),
        "population_speedup": round(speedup, 3),
        "config": (None if args.smoke or args.envs or args.steps
                   else args.config),
        "n_envs": b,
        "episode_steps": cfg.env_args.episode_limit,
        "train_batch_episodes": bs,
        "train_gate_open": gate_open,
        "dispatch_s": round(dt_pop, 4),
        "serialized_dispatch_s": round(dt_serial, 4),
    }
    # graftlattice composition identity (absent on the plain leg so its
    # record shape is unchanged)
    if mode is not None:
        rec["kernels"] = mode
    if dp:
        rec["dp"] = dp
    print(json.dumps(_finalize(rec)), flush=True)
    return 0


def bench_population_sebulba(cfg, _time, args) -> int:
    """``--population P --sebulba``: graftlattice's population × Sebulba
    lockstep leg (docs/POPULATION.md §composition). The vmapped
    population learner runs BEHIND the device-resident trajectory queue
    on a 1+1 device split in lockstep (``queue_slots=1, staleness=0`` —
    the only legal pop × sebulba regime, config.sanity_check), measured
    four ways in ONE record:

    * **population-classic** (context) — the fused vmapped population
      superstep on a single device, async-chained with one terminal
      sync: the shape ``--population`` alone measures. The fused
      program strictly serializes rollout → train inside each dispatch,
      so ``lockstep_vs_classic`` >= 1 exactly when the split's compute
      overlap beats its queue/copy/publish cost — which requires >= 2
      host cores (two CPU devices on a 1-core host time-slice one
      core; the record's ``host_cores`` field says which regime was
      measured);
    * **serial-solo** (context) — the same P experiments as P separate
      classic solo campaigns run serially, each dispatch fetched before
      the next: the pre-graftlattice baseline the compounded
      population x overlap win divides against
      (``lockstep_vs_serial_solo``);
    * **serialized** — the split pipeline run strictly phase-by-phase,
      every stage blocked: the A/B floor that isolates what overlap
      buys (``overlap_speedup``);
    * **lockstep** (headline) — the production coordination
      (``run.run_sebulba``): the actor thread's rollout ``i+1``
      dispatches as soon as train ``i`` is ENQUEUED, so rollout
      executes on the actor device while train executes on the learner
      device — lockstep ordering (bit-parity with classic) with the
      two stages' COMPUTE overlapped across the split.

    env-steps are counted identically for all four legs (``k·B·T·P``).
    Needs ≥ 2 devices (``--smoke`` forces 2 CPU host devices via the
    ``--sebulba`` pre-import path)."""
    import dataclasses
    import threading

    import jax
    import jax.numpy as jnp

    from t2omca_tpu import population as graftpop
    from t2omca_tpu.config import PopulationConfig, SebulbaConfig
    from t2omca_tpu.run import Experiment

    p = args.population
    k = max(2 * args.iters, 6)
    bs = 4 if args.smoke else 32
    b, t_len = cfg.batch_size_run, cfg.env_args.episode_limit
    env_steps = k * b * t_len * p
    base = cfg.replace(
        batch_size=bs,
        population=PopulationConfig(size=p),
        replay=dataclasses.replace(
            cfg.replay, prioritized=True,
            buffer_size=max(cfg.replay.buffer_size, 2 * b, bs)))

    def _keys(i):
        # per-member (P, 2) key column — the stacked shape the
        # population learner step takes
        return jnp.stack([jax.random.fold_in(jax.random.PRNGKey(7 + m), i)
                          for m in range(p)])

    # ---- population-classic context: one device, fused vmapped pop
    # superstep, async-chained ----------------------------------------
    with _REC.span("bench.build", leg="pop-sebulba-classic"):
        exp = Experiment.build(base)
        ts, spec = graftpop.init_population(exp, base)
        # un-donated: rebinding keeps the warm state reusable
        prog = exp.population_superstep_program(1)
    # fill the ring past the train batch so every timed iteration takes
    # the train branch in ALL legs (same warm discipline as
    # bench_population)
    warm = max(2, -(-bs // b) + 1)
    with _REC.span("bench.compile", leg="pop-sebulba-classic", warm=warm):
        for i in range(warm):
            ts, stats, _ = prog(ts, _keys(900 + i)[:, None, :],
                                jnp.asarray(0, jnp.int32), spec)
        _sync(stats.epsilon[-1, -1])
    ckeys = [_keys(1000 + i)[:, None, :] for i in range(k)]
    t1k = jnp.asarray(1000, jnp.int32)
    with _REC.span("bench.measure", leg="pop-sebulba-classic"):
        t0 = time.perf_counter()
        for i in range(k):
            ts, stats, _ = prog(ts, ckeys[i], t1k, spec)
        _sync(stats.epsilon[-1, -1])
        dt_classic = time.perf_counter() - t0
    rate_classic = env_steps / dt_classic
    print(f"# pop x sebulba classic (1 device, fused vmapped superstep, "
          f"P={p}): {dt_classic * 1e3:.1f} ms for {env_steps} env-steps "
          f"+ {k} train iters/member -> {rate_classic:,.0f} env-steps/s",
          file=sys.stderr)
    del ts, spec, prog, exp

    # ---- serial-solo context: the pre-graftlattice campaign reality —
    # the SAME P experiments as P separate classic solo runs, one after
    # the other (bench_population's serialized A/B; the denominator the
    # ISSUE's compounded-smoke story multiplies against)
    solo_cfg = cfg.replace(
        batch_size=bs,
        replay=dataclasses.replace(
            cfg.replay, prioritized=True,
            buffer_size=max(cfg.replay.buffer_size, 2 * b, bs)))
    with _REC.span("bench.build", leg="pop-sebulba-solo"):
        solo_exp = Experiment.build(solo_cfg)
        solo_ts = solo_exp.init_train_state(0)
        solo_prog = solo_exp.superstep_program(1)
    with _REC.span("bench.compile", leg="pop-sebulba-solo", warm=warm):
        for i in range(warm):
            solo_ts, sstats, _ = solo_prog(
                solo_ts, jax.random.split(jax.random.PRNGKey(900 + i), 1),
                jnp.asarray(0, jnp.int32))
        _sync(sstats.epsilon[-1])
    solo_keys = [jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(7 + m), 4000 + i), 1)
        for i in range(k) for m in range(p)]
    with _REC.span("bench.measure", leg="pop-sebulba-solo"):
        t0 = time.perf_counter()
        for sk in solo_keys:
            # separate campaigns never overlap: each solo dispatch is
            # fetched before the next begins (state reuse is fine —
            # this times dispatches, not learning)
            _sync(solo_prog(solo_ts, sk, t1k)[1].epsilon[-1])
        dt_solo = time.perf_counter() - t0
    rate_solo = env_steps / dt_solo
    print(f"# pop x sebulba serial-solo context ({p} separate classic "
          f"campaigns, 1 device): {dt_solo * 1e3:.1f} ms -> "
          f"{rate_solo:,.0f} env-steps/s", file=sys.stderr)
    del solo_ts, solo_prog, solo_exp

    # ---- the lockstep split: 1 actor + 1 learner device ---------------
    from t2omca_tpu.parallel.sebulba import make_sebulba
    seb_cfg = base.replace(sebulba=SebulbaConfig(
        actor_devices=1, learner_devices=1, queue_slots=1, staleness=0))
    with _REC.span("bench.build", leg="pop-sebulba-split"):
        exp2 = Experiment.build(seb_cfg)
        seb = make_sebulba(exp2)
        rs, ls = seb.init_states(0)
        q = seb.init_queue()
    actor_step, queue_put, queue_get, learner_step = seb.programs()
    sb = seb_cfg.sebulba
    slot0 = jnp.asarray(0, jnp.int32)

    with _REC.span("bench.compile", leg="pop-sebulba-split", warm=warm):
        # warm every program once AND fill the ring (put/get round-trips
        # insert k·B episodes per member each)
        params = seb.publish_params(ls.learner.params["agent"])
        for i in range(warm):
            rs, tm, _ = actor_step(params, rs, test_mode=False)
            q = queue_put(q, slot0, seb.to_learner(tm))
            ls, q = queue_get(ls, q, slot0)
        ls, info = learner_step(ls, _keys(999), jnp.asarray(1000))
        _sync(info["loss"][-1])

    skeys = [_keys(3000 + i) for i in range(k)]
    with _REC.span("bench.measure", leg="pop-sebulba-serial"):
        t0 = time.perf_counter()
        params = seb.publish_params(ls.learner.params["agent"])
        jax.block_until_ready(params)
        for i in range(k):
            rs, tm, stats = actor_step(params, rs, test_mode=False)
            jax.block_until_ready(stats.epsilon)
            tm_l = seb.to_learner(tm)
            jax.block_until_ready(tm_l.reward)
            q = queue_put(q, slot0, tm_l)
            ls, q = queue_get(ls, q, slot0)
            ls, info = learner_step(ls, skeys[i], jnp.asarray(3000 + i))
            _sync(info["loss"][-1])
            params = seb.publish_params(ls.learner.params["agent"])
            jax.block_until_ready(params)
        dt_serial = time.perf_counter() - t0
    rate_serial = env_steps / dt_serial
    print(f"# pop x sebulba serialized split (1+1 devices, stage-"
          f"synchronized): {dt_serial * 1e3:.1f} ms -> "
          f"{rate_serial:,.0f} env-steps/s", file=sys.stderr)

    okeys = [_keys(2000 + i) for i in range(k)]
    cond = threading.Condition()
    shared = {"q": q, "params": seb.publish_params(
        ls.learner.params["agent"]), "put": 0, "consumed": 0,
        "error": None}

    def actor(rs=rs):
        try:
            for i in range(k):
                with cond:
                    # lockstep: rollout i+1 may dispatch as soon as
                    # train i is ENQUEUED (consumed advanced) — its
                    # device execution overlaps train i's
                    while (i - shared["consumed"] > sb.staleness
                           or shared["put"] - shared["consumed"]
                           >= sb.queue_slots):
                        cond.wait()
                    params = shared["params"]
                rs, tm, stats = actor_step(params, rs, test_mode=False)
                jax.block_until_ready(stats.epsilon)
                tm_l = seb.to_learner(tm)
                with cond:
                    shared["q"] = queue_put(
                        shared["q"],
                        jnp.asarray(shared["put"] % sb.queue_slots,
                                    jnp.int32), tm_l)
                    shared["put"] += 1
                    cond.notify_all()
        except Exception as e:  # noqa: BLE001 — surfaced by the main leg
            with cond:
                shared["error"] = e
                cond.notify_all()

    with _REC.span("bench.measure", leg="pop-sebulba-lockstep"):
        t0 = time.perf_counter()
        th = threading.Thread(target=actor, daemon=True)
        th.start()
        for i in range(k):
            with cond:
                while shared["put"] <= i and shared["error"] is None:
                    cond.wait()
                if shared["error"] is not None:
                    raise shared["error"]
                ls, shared["q"] = queue_get(
                    ls, shared["q"],
                    jnp.asarray(i % sb.queue_slots, jnp.int32))
            ls, info = learner_step(ls, okeys[i], jnp.asarray(2000 + i))
            with cond:
                shared["params"] = seb.publish_params(
                    ls.learner.params["agent"])
                shared["consumed"] = i + 1
                cond.notify_all()
        _sync(info["loss"][-1])
        dt_lock = time.perf_counter() - t0
        th.join(timeout=30)
    rate_lock = env_steps / dt_lock
    overlap_speedup = rate_lock / rate_serial
    vs_classic = rate_lock / rate_classic
    vs_solo = rate_lock / rate_solo
    print(f"# pop x sebulba lockstep (1+1 devices, queue_slots=1, "
          f"staleness=0, P={p}): {dt_lock * 1e3:.1f} ms -> "
          f"{rate_lock:,.0f} env-steps/s ({overlap_speedup:.2f}x "
          f"serialized, {vs_classic:.2f}x fused population-classic, "
          f"{vs_solo:.2f}x serial solo campaigns)", file=sys.stderr)
    print(json.dumps(_finalize({
        "metric": "env_steps_per_sec",
        "value": round(rate_lock, 1),
        "unit": "env-steps/s/2-device-split",
        # per-chip semantics like the sebulba record: 2 chips in play
        "vs_baseline": round(rate_lock / 2 / 50_000.0, 3),
        "population": p,
        "sebulba": {"actor_devices": 1, "learner_devices": 1,
                    "queue_slots": sb.queue_slots,
                    "staleness": sb.staleness},
        "serialized_env_steps_per_sec": round(rate_serial, 1),
        "overlap_speedup": round(overlap_speedup, 3),
        # two classic contexts. `lockstep_vs_classic` divides by the
        # fused single-device vmapped population superstep — the shape
        # `--population` alone drives; >= 1 needs the rollout/train
        # compute overlap to beat the split's queue+publish cost, which
        # takes >= 2 host cores (on a 1-core host the two device
        # streams time-slice one core and the copies are pure loss —
        # host_cores says which regime this record measured).
        # `lockstep_vs_serial_solo` divides by the pre-lattice
        # baseline: the same P experiments as P separate classic solo
        # campaigns run serially — the compounded population x overlap
        # win the lattice exists to deliver.
        "population_classic_env_steps_per_sec": round(rate_classic, 1),
        "lockstep_vs_classic": round(vs_classic, 3),
        "serial_solo_env_steps_per_sec": round(rate_solo, 1),
        "lockstep_vs_serial_solo": round(vs_solo, 3),
        "host_cores": os.cpu_count(),
        "config": (None if args.smoke or args.envs or args.steps
                   else args.config),
        "n_envs": b,
        "episode_steps": t_len,
        "train_batch_episodes": bs,
        "chained_iters": k,
        "backend": jax.default_backend(),
    })), flush=True)
    return 0


def bench_lattice(cfg, _time, args) -> int:
    """``--lattice``: the graftlattice composition matrix
    (docs/POPULATION.md §composition) — one schema-1 record per
    newly-legal combo of the population axis with the other graft axes,
    all in one process:

    * population × pallas — the member axis vmapped over the fused
      flash-attention kernels (vmapped vs serialized A/B);
    * population × dp — whole members sharded over a 2-device mesh
      (``parallel.population_shardings``);
    * population × sebulba — the vmapped learner in lockstep behind the
      device-resident queue, vs the fused classic pop superstep.

    ``--population P`` selects the member count (default 4; must be
    even for the 2-device dp sub-leg). Needs ≥ 2 devices (``--smoke``
    forces 2 CPU host devices pre-import)."""
    import argparse as _ap

    def sub(**over):
        ns = _ap.Namespace(**vars(args))
        for key, val in over.items():
            setattr(ns, key, val)
        return ns

    rc = bench_population(cfg, _time, sub(kernels="pallas"))
    rc |= bench_population(cfg, _time, sub(kernels=None), dp=2)
    rc |= bench_population_sebulba(cfg, _time, sub(kernels=None))
    return rc


def bench_train(cfg, _time, args) -> int:
    """``--train``: the learner measurement alone, as the headline line."""
    nums = _train_numbers(cfg, _time, train_bs=4 if args.smoke else 32,
                          pipeline_k=args.pipeline)
    rec = {
        "metric": "train_steps_per_sec",
        "value": nums.pop("train_steps_per_sec"),
        "unit": "train-steps/s/chip",
        "vs_baseline": None,
    }
    rec.update(nums)
    print(json.dumps(_finalize(rec)))
    return 0


def _episode_bytes_analytic(cfg, info, batch: int) -> int:
    """Bytes of ``batch`` stored episodes under the config's storage mode —
    the analytic model behind ``--hbm``, cross-checked against real
    allocated leaf bytes by ``--prod-hbm``."""
    from t2omca_tpu.ops.query_slice import entity_store_eligible

    a = info["n_agents"]
    obs_dim, state_dim = info["obs_shape"], info["state_shape"]
    n_act = info["n_actions"]
    t = cfg.env_args.episode_limit
    f = info["obs_entity_feats"]
    sd = 2 if cfg.replay.store_dtype == "bfloat16" else 4
    if entity_store_eligible(cfg):
        obs = batch * (t + 1) * a * ((f - 1) * 4 + 1 + 2 * f * 4)
    else:
        obs = batch * (t + 1) * a * obs_dim * sd
    state = batch * (t + 1) * state_dim * sd
    avail = batch * (t + 1) * a * n_act
    small = batch * t * (a * 4 + 4 + 1 + 1)
    return obs + state + avail + small


def bench_hbm(cfg, args) -> int:
    """``--hbm CONFIG.yaml``: analytic device-memory budget of a
    configuration file as committed — sizes the residents the classic
    three-program loop keeps (replay ring, in-flight episode batches,
    learner scan residuals) from shapes alone. A floor, not the
    compiler's answer: XLA adds workspace and fragmentation, and the
    fused superstep's temporaries (K > 1) are not modelled at all — at
    config 3 the compiler sizes that program at 14.25 GB where this
    arithmetic says under 7. ``tests/test_mosaic_compile.py`` (slow
    test) compiles the real programs for a described chip."""
    from t2omca_tpu.envs.registry import make_env
    from t2omca_tpu.ops.query_slice import entity_store_eligible

    env = make_env(cfg.env_args)
    info = env.get_env_info()
    a = info["n_agents"]
    t = cfg.env_args.episode_limit
    cd = 2 if cfg.model.dtype == "bfloat16" else 4
    compact = entity_store_eligible(cfg)

    def episode_bytes(batch):
        return _episode_bytes_analytic(cfg, info, batch)

    ring = episode_bytes(cfg.replay.buffer_size)
    rollout_batch = episode_bytes(cfg.batch_size_run)
    train_batch = episode_bytes(cfg.batch_size)

    # learner backward residuals: per timestep each unrolled forward keeps
    # O(tokens · emb) activations per block for the VJP unless remat is on
    emb = cfg.model.emb
    tokens_agent = 2 if compact else (a + 1)   # entity tables: folded rows
    act_per_step = (cfg.batch_size * a * tokens_agent * emb * cd
                    * cfg.model.depth * (2 + cfg.model.ff_hidden_mult))
    mixer_tokens = a + 3 + info["n_entities"]
    mix_per_step = (cfg.batch_size * mixer_tokens * cfg.model.mixer_emb * cd
                    * cfg.model.mixer_depth * (2 + cfg.model.ff_hidden_mult))
    residuals = (t + 1) * (act_per_step + mix_per_step)
    if cfg.model.remat:
        residuals = act_per_step + mix_per_step   # one step live at a time

    rows = {
        "replay_ring": ring,
        # ×3: the async driver loop bounds dispatch run-ahead at 2, so up
        # to 3 episode batches can be live at once (run.run_sequential) —
        # an upper bound; cadence barriers (B·T ≥ log intervals at the
        # large configs) usually keep fewer in flight
        "rollout_episode_batch": 3 * rollout_batch,
        "train_episode_batch": train_batch,
        "learner_scan_residuals": residuals,
    }
    total = sum(rows.values())
    gib = 1024 ** 3
    for k, v in rows.items():
        print(f"# {k:24s} {v / gib:8.3f} GiB", file=sys.stderr)
    print(f"# {'total (est.)':24s} {total / gib:8.3f} GiB "
          f"(storage={'compact' if compact else 'dense'}, "
          f"remat={'on' if cfg.model.remat else 'off'}; excludes XLA "
          f"workspace/fragmentation)", file=sys.stderr)
    print(json.dumps(_finalize({
        "metric": "hbm_estimate_gib",
        "value": round(total / gib, 3),
        "unit": "GiB",
        "vs_baseline": None,
        "config": os.path.basename(args.hbm),
        "superstep_temporaries_modelled": False,
        "breakdown_gib": {k: round(v / gib, 3) for k, v in rows.items()},
    })))
    return 0


def bench_prod_hbm(cfg) -> int:
    """``--prod-hbm``: config-5 at PRODUCTION storage scale, actually
    allocated. Unlike ``--config 5`` (which shrinks
    the ring to ~2x batch for timing) this builds the
    ``configs/config5_dp8.yaml`` replay ring — 16384 episodes x T=150,
    bf16 compact storage — as real arrays sharded over the DP=8 mesh,
    inserts a rollout's episodes, and runs one full-horizon train
    iteration (PER sample -> T=150 learner scan -> priorities) with the
    ring co-resident, under the production donation contract (in-place
    ring/state, no 2x transient). Reports the MEASURED resident bytes of
    the ring next to the ``--hbm`` analytic for the same shapes — the
    cross-check that keeps the analytic honest.

    Two honest reductions on a non-chip host (both recorded in the
    emitted JSON): the fill rollout runs ``--envs`` lanes (default 64,
    not 8192 — the in-flight 8192-lane batch stays analytic), and the
    learner compute dtype is f32 (CPU bf16 is emulated and ~50x slower;
    f32 residuals UPPER-bound the production bf16 ones). Storage stays
    production bf16 either way."""
    import jax
    import jax.numpy as jnp

    from t2omca_tpu.parallel import DataParallel, make_mesh
    from t2omca_tpu.run import Experiment

    n_dev = 8
    with _REC.span("bench.build", leg="prod_hbm"):
        exp = Experiment.build(cfg)
        mesh = make_mesh(n_dev)
        dp = DataParallel(exp, mesh)
        # born-sharded init: shard(init_train_state(0)) holds TWO copies
        # of the ring during the device_put (the measured OOM at
        # ring=16384 on a 125 GiB host — and the same 2x transient a
        # real slice would pay)
        ts = dp.init_sharded(0)
    # production contract: ring donated to insert, state to train_iter
    rollout, insert, train_iter = dp.jitted_programs(donate=True)

    def tree_bytes(tree):
        return sum(x.nbytes for x in jax.tree.leaves(tree)
                   if hasattr(x, "nbytes"))

    gib = 1024 ** 3
    ring_meas = tree_bytes(ts.buffer.storage)
    ring_total = tree_bytes(ts.buffer)          # + PER priorities etc.
    info = exp.env.get_env_info()
    ring_analytic = _episode_bytes_analytic(cfg, info,
                                            cfg.replay.buffer_size)
    print(f"# ring allocated: {ring_meas / gib:.3f} GiB storage "
          f"({ring_total / gib:.3f} with PER state) over {n_dev} devices "
          f"= {ring_total / n_dev / gib:.3f}/device; analytic "
          f"{ring_analytic / gib:.3f} GiB "
          f"({(ring_meas / ring_analytic - 1) * 100:+.1f}%)",
          file=sys.stderr)

    params = ts.learner.params["agent"]
    t0 = time.perf_counter()
    rs, batch, _ = rollout(params, ts.runner, test_mode=False)
    jax.block_until_ready(jax.tree.leaves(batch.reward)[0])
    t_roll = time.perf_counter() - t0
    batch_meas = tree_bytes(batch)
    pre_insert_ring = jax.tree.leaves(ts.buffer.storage)
    ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                    episode=ts.episode + cfg.batch_size_run)
    # donation proof, not shape arithmetic: the donated input buffers must
    # actually be consumed (no 2x-ring transient) — .nbytes comparisons
    # would pass either way
    assert all(x.is_deleted() for x in pre_insert_ring
               if isinstance(x, jax.Array)), \
        "insert must consume (donate) the ring"

    pre_train_ring = jax.tree.leaves(ts.buffer.storage)
    t0 = time.perf_counter()
    ts, tinfo = train_iter(ts, jax.random.PRNGKey(7), jnp.asarray(1000))
    loss = float(jax.device_get(tinfo["loss"]))
    t_train = time.perf_counter() - t0
    assert jnp.isfinite(loss), "train iteration on the production ring"
    assert all(x.is_deleted() for x in pre_train_ring
               if isinstance(x, jax.Array)), \
        "train_iter must consume (donate) the train state"
    ring_after = tree_bytes(ts.buffer.storage)
    assert ring_after == ring_meas, "ring layout changed across train"
    print(f"# fill rollout ({cfg.batch_size_run} lanes x "
          f"{cfg.env_args.episode_limit} steps): {t_roll:.1f}s; train "
          f"iteration (batch {cfg.batch_size}, T="
          f"{cfg.env_args.episode_limit}, remat="
          f"{'on' if cfg.model.remat else 'off'}): {t_train:.1f}s, "
          f"loss {loss:.4f}", file=sys.stderr)

    # the one resident NOT allocated here: the 8192-lane in-flight batch
    prod_envs = 8192
    batch_analytic = _episode_bytes_analytic(cfg, info, prod_envs)
    rec = {
        "metric": "prod_ring_resident_gib",
        "value": round(ring_total / gib, 3),
        "unit": "GiB-allocated",
        "vs_baseline": None,
        "config": 5,
        "ring_episodes": cfg.replay.buffer_size,
        "per_device_gib": round(ring_total / n_dev / gib, 4),
        "analytic_gib": round(ring_analytic / gib, 3),
        "analytic_delta_pct": round((ring_meas / ring_analytic - 1) * 100,
                                    1),
        "fill_batch_gib": round(batch_meas / gib, 4),
        "fill_envs": cfg.batch_size_run,
        "train_step_s": round(t_train, 1),
        "train_loss": round(loss, 5),
        "remat": bool(cfg.model.remat),
        "compute_dtype": cfg.model.dtype,
        "prng": jax.config.jax_default_prng_impl,
        # analytic-only leg, stated as such:
        "rollout_batch_8192_analytic_gib": round(batch_analytic / gib, 3),
    }
    print(json.dumps(_finalize(rec)))
    return 0


def bench_serve(args) -> int:
    """``--serve``: the serving-path measurement (ROADMAP item 5).

    Loads an exported artifact (``python -m t2omca_tpu.serve export``)
    through the production front-end and measures what traffic sees:

    * **p50/p99 decision latency** — per-request wall time of
      ``ServeFrontend.select`` over a deterministic ragged request
      schedule that crosses every bucket boundary (size 1, each bucket,
      each bucket's boundary+1 — the worst padding waste points);
    * **decisions/s/chip** — steady-state agent-decisions per second at
      the largest bucket with the hidden state carried between requests
      (the recurrent-policy serving loop).

    One BENCH-style JSON line; a failure anywhere still emits the
    partial record with the open phase + flight tail (``main_flight``),
    like every training leg. The record carries the live backend —
    a ``--smoke`` (CPU-pinned) serve measurement can never masquerade
    as a chip number."""
    import jax

    from t2omca_tpu.serve.frontend import ServeFrontend

    with _REC.span("bench.build", leg="serve"):
        fe = ServeFrontend.load(args.artifact, dtype=args.serve_dtype,
                                rec=_REC)
    a, d, na = fe.n_agents, fe.obs_dim, fe.n_actions
    rng = np.random.default_rng(0)

    def request(n):
        obs = rng.standard_normal((n, a, d)).astype(np.float32)
        avail = rng.random((n, a, na)) < 0.7
        avail[..., 0] = True            # every agent keeps a legal action
        return obs, avail

    with _REC.span("bench.compile", leg="serve"):
        fe.warmup()                     # one dispatch per bucket

    # ragged schedule crossing every bucket boundary (dedup, sorted)
    sizes = sorted({1, *fe.buckets,
                    *(b + 1 for b in fe.buckets[:-1])})
    reqs = {n: request(n) for n in sizes}
    # enough samples for an honest p99 tail
    reps = max(args.iters, -(-100 // len(sizes)))
    lat_ms = []
    with _REC.span("bench.measure", leg="serve"):
        for _ in range(reps):
            for n in sizes:
                obs, avail = reqs[n]
                t0 = time.perf_counter()
                fe.select(obs, avail)
                lat_ms.append((time.perf_counter() - t0) * 1e3)
        p50, p99 = np.percentile(lat_ms, [50, 99])

        # throughput leg: hidden-carried steady state at the max bucket
        bmax = fe.buckets[-1]
        obs, avail = reqs[bmax]
        _, hidden = fe.select(obs, avail)          # extra warm, fresh h
        k = max(3 * args.iters, 10)
        t0 = time.perf_counter()
        for _ in range(k):
            actions, hidden = fe.select(obs, avail, hidden)
        dt = time.perf_counter() - t0
    decisions = k * bmax * a / dt
    print(f"# serve latency over {len(lat_ms)} requests "
          f"(sizes {sizes}): p50 {p50:.2f} ms, p99 {p99:.2f} ms",
          file=sys.stderr)
    print(f"# serve throughput at bucket {bmax}: "
          f"{decisions:,.0f} decisions/s ({a} agents/request, "
          f"hidden carried)", file=sys.stderr)
    print(json.dumps(_finalize({
        "metric": "serve_decisions_per_sec",
        "value": round(decisions, 1),
        "unit": "decisions/s/chip",
        "vs_baseline": None,
        "p50_ms": round(float(p50), 3),
        "p99_ms": round(float(p99), 3),
        "latency_samples": len(lat_ms),
        "request_sizes": sizes,
        "buckets": fe.buckets,
        "n_agents": a,
        "dtype": args.serve_dtype,
        "backend": jax.default_backend(),
        "artifact": args.artifact,
        "checkpoint_t_env": fe.meta.get("checkpoint", {}).get("t_env"),
    })))
    return 0


def bench_serve_chaos(args) -> int:
    """``--serve --chaos``: the fleet-under-fire measurement (ROADMAP
    item 4 — "p99-under-burst as a ratcheted number instead of a
    hope").

    Drives a :class:`~t2omca_tpu.serve.fleet.ServeFleet` of
    ``--fleet-engines`` share-nothing engines with **bursty
    heavy-tailed open-loop traffic** (Pareto-tailed request sizes;
    exponential arrivals whose rate steps up 5x inside burst windows;
    open-loop = requests are submitted on the clock whether or not
    earlier ones completed — the only honest way to measure shedding)
    while a **fault schedule** runs underneath:

    * engine 0 killed mid-burst (injected non-transient dispatch fault
      → quarantine, bounce, backoff restart, rejoin);
    * one injected dispatch hang on a peer engine (watchdog stall →
      hedge + quarantine);
    * one poisoned hot refresh (nonexistent checkpoint → must be
      REFUSED while serving continues).

    One BENCH-style JSON record: p50/p99 under burst (the ratchet
    value is the p99), shed fraction, engine recovery time, hedge and
    stall counters, the refresh outcome — and ``unresolved``, which a
    correct fleet keeps at exactly 0 (every admitted request completes
    or resolves with an explicit SHED/deadline/error status)."""
    import jax

    from t2omca_tpu.serve.fleet import FleetConfig, ServeFleet
    from t2omca_tpu.utils import resilience

    duration = float(args.chaos_seconds)
    n_eng = int(args.fleet_engines)
    fcfg = FleetConfig(
        queue_depth=32,
        deadline_s=max(2.0, duration / 2.5),
        dispatch_timeout_s=max(0.75, min(2.0, duration / 6.0)),
        restart_backoff_s=0.05, restart_backoff_max_s=0.5,
        ladder_cooldown_s=0.25,
    )
    with _REC.span("bench.build", leg="serve-chaos"):
        fleet = ServeFleet(args.artifact, n_engines=n_eng,
                           dtype=args.serve_dtype, cfg=fcfg,
                           rec=_REC).start()
    try:
        if fleet.serving_engines() == 0:
            st = fleet.stats()
            raise RuntimeError(
                f"no fleet engine reached serving: {st['engines']}")
        with _REC.span("bench.compile", leg="serve-chaos"):
            fleet.warmup()

        fe0 = fleet.engines[0].fe
        a, d, na = fe0.n_agents, fe0.obs_dim, fe0.n_actions
        bmax = fe0.buckets[-1]
        rng = np.random.default_rng(0)

        # request pool: heavy-tailed sizes (Pareto tail past the max
        # bucket exercises the chunking path), one pre-built request
        # per distinct size so the open-loop submitter costs ~nothing
        sizes = np.minimum(1 + rng.pareto(1.1, 4096).astype(np.int64),
                           2 * bmax)
        pool = {}
        for n in np.unique(sizes):
            n = int(n)
            obs = rng.standard_normal((n, a, d)).astype(np.float32)
            avail = rng.random((n, a, na)) < 0.7
            avail[..., 0] = True
            pool[n] = (obs, avail)

        # fault schedule (one-shot each, on the fleet's own chaos hooks)
        kill_at = 0.25 * duration
        refresh_at = 0.40 * duration
        hang_at = 0.55 * duration
        hang_s = fcfg.dispatch_timeout_s + min(1.5, 0.2 * duration)
        hang_engine = 1 % n_eng
        t0 = time.monotonic()
        killed, hung = [], []

        def _fault_schedule(engine, attempt, rid, **kw):
            now = time.monotonic() - t0
            if engine == 0 and not killed and now >= kill_at:
                killed.append(now)
                raise RuntimeError("chaos: engine killed (injected)")
            if engine == hang_engine and not hung and now >= hang_at:
                hung.append(now)
                time.sleep(hang_s)

        resilience.register_fault("fleet.dispatch", _fault_schedule)

        refresh_out = {}

        def _poisoned_refresh():
            refresh_out.update(fleet.refresh(
                os.path.join(args.artifact, "_no_such_checkpoint")))

        poison = threading.Timer(refresh_at, _poisoned_refresh)
        poison.daemon = True
        poison.start()

        # bursty open-loop arrivals: base rate sized to the measured
        # warm dispatch so CPU and TPU runs both saturate in bursts
        t_warm0 = time.perf_counter()
        fleet.select(*pool[min(pool)])
        warm_s = max(time.perf_counter() - t_warm0, 1e-4)
        base_rate = max(10.0, min(200.0, 1.5 * n_eng / warm_s))
        bursts = [(0.2 * duration, 0.3 * duration),
                  (0.5 * duration, 0.65 * duration),
                  (0.8 * duration, 0.9 * duration)]

        def rate_at(t):
            burst = any(lo <= t < hi for lo, hi in bursts)
            return base_rate * (5.0 if burst else 1.0)

        requests = []
        with _REC.span("bench.chaos", leg="serve-chaos"):
            t = 0.0
            i = 0
            while t < duration:
                now = time.monotonic() - t0
                if now < t:
                    time.sleep(min(t - now, 0.05))
                    continue
                n = int(sizes[i % len(sizes)])
                requests.append(fleet.submit(*pool[n]))
                i += 1
                t += rng.exponential(1.0 / rate_at(t))
            # drain: every admitted request must resolve (completion,
            # SHED, deadline or error) — the supervisor's deadline
            # sweep bounds this wait
            results = [r.wait(timeout=fcfg.deadline_s + 2.0)
                       for r in requests]
        poison.join(timeout=30.0)
    finally:
        resilience.clear_faults("fleet.dispatch")
        stats = fleet.stats()
        fleet.stop()

    by = {}
    for r in results:
        by[r.status] = by.get(r.status, 0) + 1
    ok_lat = sorted(r.latency_ms for r in results if r.ok)
    unresolved = sum(1 for r in results
                     if r.status == "error"
                     and "unresolved" in (r.error or ""))
    p50 = p99 = None
    if ok_lat:
        p50, p99 = np.percentile(ok_lat, [50, 99])
    recov = stats["recoveries_s"]
    shed_fraction = by.get("shed", 0) / max(len(results), 1)
    print(f"# chaos traffic: {len(results)} requests over "
          f"{duration:.1f}s ({base_rate:.0f}/s base, 5x bursts) — "
          f"{by.get('ok', 0)} ok, {by.get('shed', 0)} shed, "
          f"{by.get('deadline', 0)} deadline, {by.get('error', 0)} "
          f"error, {unresolved} unresolved", file=sys.stderr)
    print(f"# faults: kill@{killed[0] if killed else None}s "
          f"hang@{hung[0] if hung else None}s "
          f"refresh={refresh_out.get('status')} "
          f"recoveries={recov} "
          f"serving_end={stats['serving']}/{n_eng}", file=sys.stderr)
    print(json.dumps(_finalize({
        "metric": "serve_chaos_p99_ms",
        "value": round(float(p99), 3) if p99 is not None else None,
        "unit": "ms",
        "vs_baseline": None,
        "p50_ms": round(float(p50), 3) if p50 is not None else None,
        "p99_ms": round(float(p99), 3) if p99 is not None else None,
        "requests": len(results),
        "ok": by.get("ok", 0),
        "shed": by.get("shed", 0),
        "deadline": by.get("deadline", 0),
        "errors": by.get("error", 0),
        "unresolved": unresolved,
        "shed_fraction": round(shed_fraction, 4),
        "recovery_s": (round(max(recov), 3) if recov else None),
        "recoveries_s": recov,
        "hedges": stats.get("fleet_hedges_total", 0),
        "stalls": stats.get("fleet_stalls_total", 0),
        "engine_restarts": stats.get("fleet_restarts_total", 0),
        "ejected": stats.get("fleet_ejected_total", 0),
        "ladder_level_end": stats.get("ladder_level", 0),
        "refresh": refresh_out or None,
        "engines": n_eng,
        "engines_serving_end": stats["serving"],
        "duration_s": duration,
        "base_rate_rps": round(base_rate, 1),
        "dtype": args.serve_dtype,
        "backend": jax.default_backend(),
        "artifact": args.artifact,
    }), default=repr))
    return 0


def bench_all(make_cfg, _time, _pipe_rate, args) -> int:
    """``--all``: the full single-chip measurement set in ONE process
    (one process holds the chip). Emits one JSON line per measurement,
    most important first. Every leg is fatal: the first failure
    propagates to ``main_flight`` (partial record, exit 1) with the
    earlier legs' lines already on stdout."""
    import gc

    import jax

    from t2omca_tpu.run import Experiment

    def emit(rec):
        # cumulative per-phase summary (leg meta distinguishes the
        # sub-benches in the span stream; the summary aggregates)
        print(json.dumps(_finalize(rec)), flush=True)

    def rollout_rate(cfg, label, extra=None):
        # each leg carries its own spans (leg=<label> meta); the
        # records embed the CUMULATIVE summary, so a failure in any leg
        # still leaves the earlier legs' phase timings on record
        with _REC.span("bench.build", leg=label):
            exp = Experiment.build(cfg)
            ts = exp.init_train_state(0)
        rollout = jax.jit(exp.runner.run, static_argnames="test_mode")
        params = ts.learner.params["agent"]
        with _REC.span("bench.compile", leg=label):
            rs, batch, _ = rollout(params, ts.runner, test_mode=False)

        def one():
            _, b, _ = rollout(params, rs, test_mode=False)
            return b.reward[0, 0]

        with _REC.span("bench.measure", leg=label):
            dt = _time(one)
        env_steps = cfg.batch_size_run * cfg.env_args.episode_limit
        rec = {
            "metric": "env_steps_per_sec",
            "value": round(env_steps / dt, 1),
            "unit": "env-steps/s/chip",
            "vs_baseline": round(env_steps / dt / 50_000.0, 3),
            "acting": label,
            "n_envs": cfg.batch_size_run,
            "episode_steps": cfg.env_args.episode_limit,
        }

        if args.pipeline:
            rec["pipelined_env_steps_per_sec"] = _pipe_rate(
                rollout, params, rs, env_steps, args.pipeline)
        if jax.config.jax_default_prng_impl != "threefry2x32":
            # read back the live impl, not the flag echo — a broken
            # switch must not be misattributed as an rbg measurement
            rec["prng"] = jax.config.jax_default_prng_impl
        if extra:
            rec.update(extra)
        return rec

    # only claim a BASELINE scale point when unmodified
    cid = lambda n: None if args.envs or args.steps else n

    # 1. headline: config 3, production acting path, both metric halves
    cfg3 = make_cfg("qslice", 3)
    rec = rollout_rate(cfg3, "entity/qslice", {"config": cid(3)})
    rec.update(_train_numbers(cfg3, _time, pipeline_k=args.pipeline))
    emit(rec)
    gc.collect()

    # 2. config 4 train scale (PER + 4096 envs interleave)
    cfg4 = make_cfg("qslice", 4)
    nums = _train_numbers(cfg4, _time, pipeline_k=args.pipeline)
    rec4 = {"metric": "train_steps_per_sec",
            "value": nums.pop("train_steps_per_sec"),
            "unit": "train-steps/s/chip", "vs_baseline": None,
            "config": cid(4)}
    rec4.update(nums)
    emit(rec4)
    gc.collect()

    # 3. acting-path comparison at config 3 (dense = the XLA full forward)
    emit(rollout_rate(make_cfg("dense", 3), "dense", {"config": cid(3)}))
    gc.collect()

    # 3b. PRNG-impl comparison at config 3: leg 1 is the threefry
    #     baseline; rbg routes every draw through the TPU hardware bit
    #     generator (the record carries the live impl)
    emit(rollout_rate(make_cfg("qslice", 3, prng="rbg"),
                      "entity/qslice", {"config": cid(3)}))
    gc.collect()

    # 4. breakdown attribution at config 3 (its own JSON line)
    exp = Experiment.build(cfg3)
    return breakdown(cfg3, exp, exp.init_train_state(0), _time, args)


#: BASELINE.json measurement scale points:
#: (agv, mec, channels, envs, d_model, depth) — config 4 adds PER scale,
#: config 5 is the DP=8 point (needs ≥8 devices)
_CONFIGS = {
    1: dict(agv=4, mec=2, ch=2, envs=1, emb=64, depth=2),
    2: dict(agv=16, mec=4, ch=4, envs=256, emb=128, depth=2),
    3: dict(agv=64, mec=8, ch=8, envs=1024, emb=256, depth=2),
    4: dict(agv=64, mec=8, ch=8, envs=4096, emb=256, depth=2),
    5: dict(agv=256, mec=16, ch=16, envs=8192, emb=256, depth=2),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--config", type=int, choices=sorted(_CONFIGS),
                    default=3,
                    help="BASELINE.json measurement config (default 3, the "
                         "north-star scale point; 4 = PER/train scale, "
                         "5 = the DP=8 point — needs 8 devices)")
    ap.add_argument("--envs", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--profile", default="",
                    help="capture a jax.profiler trace of the timed "
                         "iterations into this directory")
    ap.add_argument("--acting", choices=("qslice", "dense"),
                    default="qslice",
                    help="agent forward for the rollout: qslice (exact "
                         "token-0-only reduction, ops/query_slice — the "
                         "default) or dense (XLA full forward)")
    ap.add_argument("--no-fast-norm", action="store_true",
                    help="sequential per-agent Welford (reference-exact "
                         "normalizer ordering) instead of the batched merge")
    ap.add_argument("--breakdown", action="store_true",
                    help="attribute the slot time: env-only rollout "
                         "(seq vs fast norm), acting-only scan, full rollout")
    ap.add_argument("--train", action="store_true",
                    help="benchmark the learner: train_iter (PER sample -> "
                         "train -> priority update) and the interleaved "
                         "rollout+train loop (BASELINE.json config 4)")
    ap.add_argument("--all", action="store_true",
                    help="comprehensive single-process sweep: default "
                         "rollout+train line, breakdown, qslice/dense "
                         "comparison, threefry/rbg comparison, config-4 "
                         "scale — one backend init, one JSON line per "
                         "measurement")
    ap.add_argument("--hbm", default=None, metavar="CONFIG.yaml",
                    help="print the analytic device-memory budget of "
                         "that configuration file as committed (shape "
                         "arithmetic, no device work; the compiler's own "
                         "sizes come from tests/test_mosaic_compile.py)")
    ap.add_argument("--prod-hbm", action="store_true",
                    help="allocate config-5's PRODUCTION replay ring "
                         "(--ring episodes, T=150, bf16 compact storage) "
                         "on the DP=8 mesh, insert + run one train "
                         "iteration with it co-resident, and cross-check "
                         "the --hbm analytic against real allocated "
                         "bytes (needs 8 devices: a slice, or "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 JAX_PLATFORMS=cpu)")
    ap.add_argument("--ring", type=int, default=16384,
                    help="--prod-hbm ring capacity in episodes "
                         "(default: configs/config5_dp8.yaml's 16384)")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default=None,
                    help="--prod-hbm learner compute dtype (default f32: "
                         "CPU bf16 is emulated ~50x slower, and f32 "
                         "residuals upper-bound bf16; pass bfloat16 on "
                         "a real slice)")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize learner scan forwards in the "
                         "backward pass (long-horizon HBM lever; exact)")
    ap.add_argument("--heads", type=int, default=4,
                    help="agent/mixer head count (d256 standard heads: 4 -> "
                         "head_dim 64, 2 -> head_dim 128 = full MXU lanes)")
    ap.add_argument("--prng", choices=("threefry", "rbg", "unsafe_rbg"),
                    default="threefry",
                    help="PRNG impl for all keys: rbg = the TPU hardware "
                         "bit generator (cheaper for the rollout's many "
                         "small draws; different stream than threefry)")
    ap.add_argument("--serve", action="store_true",
                    help="measure the serving path: load an exported "
                         "artifact (--artifact) through the batched "
                         "front-end and report p50/p99 decision latency "
                         "+ decisions/s/chip (docs/SERVING.md)")
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="--serve: the exported serving artifact "
                         "(python -m t2omca_tpu.serve export)")
    ap.add_argument("--serve-dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="--serve: which param variant to serve")
    ap.add_argument("--chaos", action="store_true",
                    help="--serve: drive the multi-engine FLEET "
                         "(serve/fleet.py) under bursty heavy-tailed "
                         "open-loop traffic plus a fault schedule "
                         "(engine kill mid-burst, injected dispatch "
                         "hang, poisoned refresh) — reports p99 under "
                         "burst, shed fraction and engine recovery "
                         "time (docs/SERVING.md §fleet)")
    ap.add_argument("--fleet-engines", type=int, default=2,
                    help="--serve --chaos: engines in the fleet")
    ap.add_argument("--chaos-seconds", type=float, default=8.0,
                    help="--serve --chaos: open-loop traffic duration")
    ap.add_argument("--kernels", choices=("xla", "pallas", "ab"),
                    default=None,
                    help="attention-kernel A/B leg: measure the DENSE "
                         "rollout under the selected kernels.attention "
                         "mode (xla = einsum path, pallas = fused flash "
                         "kernel; ab = both) — one JSON record per mode "
                         "with the mode in the record (spans summary is "
                         "cumulative across legs, like --all; per-mode "
                         "split via each span's leg= meta)")
    ap.add_argument("--sebulba", action="store_true",
                    help="measure the Sebulba decoupled actor/learner "
                         "split (parallel/sebulba.py): overlapped "
                         "rollout+train over a 1+1 device partition with "
                         "the device-resident trajectory queue, vs the "
                         "serialized single-device loop — one record "
                         "with both rates and the overlap speedup "
                         "(needs >= 2 devices; --smoke forces 2 CPU "
                         "host devices)")
    ap.add_argument("--superstep", type=int, default=None, metavar="K",
                    help="measure the fused training superstep: ONE "
                         "program scanning K rollout->insert->train "
                         "iterations per dispatch (config superstep=K; "
                         "K=1 still fuses the three stages into one "
                         "program). Reports the dispatch-amortized "
                         "env-steps/s including training")
    ap.add_argument("--population", type=int, default=None, metavar="P",
                    help="graftpop experiment-throughput leg: ONE "
                         "vmapped population superstep advancing P "
                         "seed variants per dispatch vs the SAME P "
                         "experiments serialized as P solo dispatches "
                         "(docs/POPULATION.md). Reports experiments_"
                         "per_sec + population_speedup. Composes with "
                         "--kernels pallas|xla (vmap-over-pallas) and "
                         "--sebulba (lockstep split, needs >= 2 "
                         "devices) — the graftlattice legs")
    ap.add_argument("--lattice", action="store_true",
                    help="graftlattice composition matrix (docs/"
                         "POPULATION.md §composition): the population "
                         "axis composed with each other graft axis — "
                         "kernels pallas, a dp=2 mesh, the sebulba "
                         "lockstep split — one record per combo "
                         "(--population picks P, default 4; needs >= 2 "
                         "devices, --smoke forces 2 CPU host devices)")
    ap.add_argument("--pipeline", type=int, default=None, metavar="K",
                    help="also report the steady-state rate over K "
                         "async-chained rollouts with one terminal sync "
                         "(overlaps per-dispatch latency with device "
                         "compute the way the production driver loop "
                         "does); "
                         "defaults to K=4 on full-scale runs, pass 0 "
                         "to disable")
    args = ap.parse_args()
    if args.serve:
        if args.artifact is None:
            ap.error("--serve needs --artifact DIR (an exported serving "
                     "artifact; python -m t2omca_tpu.serve export)")
        if (args.all or args.hbm or args.prod_hbm or args.breakdown
                or args.train or args.superstep is not None
                or args.config != 3):
            ap.error("--serve measures the exported artifact's serving "
                     "path; drop --all/--hbm/--prod-hbm/--breakdown/"
                     "--train/--superstep/--config")
        if args.pipeline:
            ap.error("--serve has its own hidden-carried throughput "
                     "leg; drop --pipeline")
        if args.fleet_engines < 1:
            ap.error("--fleet-engines must be >= 1")
        if args.chaos_seconds <= 0:
            ap.error("--chaos-seconds must be > 0")
    elif args.artifact is not None:
        ap.error("--artifact only applies to --serve")
    elif args.chaos:
        ap.error("--chaos only applies to --serve (the fleet chaos "
                 "traffic leg needs an exported artifact)")
    if args.kernels is not None:
        if (args.all or args.hbm or args.prod_hbm or args.breakdown
                or args.train or args.serve or args.superstep is not None
                or args.config == 5):
            ap.error("--kernels measures the dense rollout under each "
                     "attention-kernel mode; drop --all/--hbm/--prod-hbm/"
                     "--breakdown/--train/--serve/--superstep/--config 5")
    if args.superstep is not None:
        if args.superstep < 1:
            ap.error("--superstep K must be >= 1")
        if (args.all or args.hbm or args.prod_hbm or args.breakdown
                or args.train or args.config == 5):
            ap.error("--superstep measures the fused-dispatch loop on a "
                     "single chip; drop --all/--hbm/--prod-hbm/"
                     "--breakdown/--train/--config 5")
        if args.pipeline:
            ap.error("--superstep already amortizes dispatch inside one "
                     "program; drop --pipeline")
    if args.population is not None:
        if args.population < 2:
            ap.error("--population P must be >= 2 (P=1 is the classic "
                     "loop — measure it with --superstep)")
        if (args.all or args.hbm or args.prod_hbm or args.breakdown
                or args.train or args.serve or args.superstep is not None
                or args.config == 5):
            ap.error("--population measures the vmapped population "
                     "superstep vs the serialized P-run; drop --all/"
                     "--hbm/--prod-hbm/--breakdown/--train/--serve/"
                     "--superstep/--config 5")
        if args.kernels == "ab":
            # graftlattice composes population with ONE kernel mode per
            # run: the record's A/B is vmapped-vs-serialized, not
            # xla-vs-pallas
            ap.error("--population composes with a single kernel mode; "
                     "pick --kernels pallas or --kernels xla (run both "
                     "modes as two invocations, or use --lattice)")
        if args.pipeline:
            ap.error("--population amortizes dispatch across the "
                     "member axis already; drop --pipeline")
    if args.sebulba:
        if (args.all or args.hbm or args.prod_hbm or args.breakdown
                or args.train or args.serve or args.superstep is not None
                or args.kernels is not None or args.config == 5):
            ap.error("--sebulba measures the decoupled actor/learner "
                     "split; drop --all/--hbm/--prod-hbm/--breakdown/"
                     "--train/--serve/--superstep/--kernels/--config 5")
        if args.pipeline:
            ap.error("--sebulba overlaps dispatch across the device "
                     "split already; drop --pipeline")
        # the split needs 2 devices; force 2 CPU host devices while jax
        # is still unimported (no-op on hosts that already expose more —
        # the flag only widens the CPU host platform)
        if "jax" not in sys.modules:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags
                    + " --xla_force_host_platform_device_count=2").strip()
    if args.lattice:
        if (args.all or args.hbm or args.prod_hbm or args.breakdown
                or args.train or args.serve or args.superstep is not None
                or args.kernels is not None or args.sebulba
                or args.config == 5):
            ap.error("--lattice runs its own composition matrix "
                     "(population x pallas / x dp / x sebulba); drop "
                     "the per-leg flags")
        if args.pipeline:
            ap.error("--lattice legs amortize dispatch on their own "
                     "axes; drop --pipeline")
        if args.population is None:
            args.population = 4
        if args.population % 2:
            ap.error("--lattice shards the member axis over a 2-device "
                     "mesh (population-over-dp sub-leg); --population P "
                     "must be even")
        # the dp and sebulba sub-legs need 2 devices (same pre-import
        # widening as --sebulba)
        if "jax" not in sys.modules:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags
                    + " --xla_force_host_platform_device_count=2").strip()
    if args.pipeline is not None and args.pipeline < 0:
        ap.error("--pipeline K must be >= 0")
    if args.pipeline and (args.hbm or args.breakdown or args.prod_hbm):
        # these modes don't measure a chainable dispatch loop; silently
        # ignoring the flag would misattribute records
        ap.error("--pipeline applies to the rollout/train dispatch "
                 "chains (default line, --train, --config 5, --all); "
                 "drop it for --breakdown/--hbm/--prod-hbm")
    if args.pipeline is None:
        # default ON (K=4) wherever a dispatch chain is measured, so the
        # driver's plain `python bench.py` artifact carries the
        # steady-state rate; --pipeline 0 disables. Smoke stays off (the
        # CPU contract tests pin the minimal schema).
        measures_chain = not (args.smoke or args.hbm or args.breakdown
                              or args.prod_hbm or args.serve
                              or args.superstep is not None
                              or args.kernels is not None
                              or args.sebulba
                              or args.population is not None
                              or args.lattice)
        args.pipeline = 4 if measures_chain else 0

    if args.smoke or args.hbm:
        # the two chip-less modes: --smoke rehearses this harness's
        # control flow (CPU pin, Pallas kernels interpreted), --hbm is
        # pure shape arithmetic
        import jax
        jax.config.update("jax_platforms", "cpu")
        if args.smoke:
            from t2omca_tpu.kernels import attention
            attention.INTERPRET = True
    else:
        # the measurement path fails when it finds no chip
        from t2omca_tpu.utils.chip import require_tpu
        require_tpu()

    import jax
    import jax.numpy as jnp

    from t2omca_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    _RECORD_EXTRA["platform"] = jax.default_backend()

    if args.serve:
        # the serving legs need no train config at all — everything
        # (model, buckets, params) comes from the artifact's meta
        if args.chaos:
            return bench_serve_chaos(args)
        return bench_serve(args)

    from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                                   TrainConfig, sanity_check)
    from t2omca_tpu.run import Experiment

    if args.smoke:
        n_envs = args.envs or 8
        steps = args.steps or 8
        cfg = sanity_check(TrainConfig(
            batch_size_run=n_envs,
            prng_impl=args.prng,
            env_args=EnvConfig(agv_num=4, mec_num=2, num_channels=2,
                               episode_limit=steps),
            model=ModelConfig(emb=16, heads=2, depth=1, mixer_emb=16,
                              mixer_heads=2, mixer_depth=1,
                              use_qslice=args.acting != "dense"),
            replay=ReplayConfig(buffer_size=16),
        ))
    else:
        # BASELINE.json measurement scale points; default = config 3, the
        # north-star point (64 AGVs × 8 MEC, 1024 envs, d_model 256).
        # A stand-in for the training configs, not one of them:
        # episode_limit is shortened for the timed program (throughput
        # is per-step) and the ring holds 4 episodes.
        def make_cfg(acting: str, config_id: int, prng: str | None = None):
            c = _CONFIGS[config_id]
            return sanity_check(TrainConfig(
                batch_size_run=args.envs or c["envs"],
                prng_impl=prng or args.prng,
                env_args=EnvConfig(agv_num=c["agv"], mec_num=c["mec"],
                                   num_channels=c["ch"],
                                   episode_limit=args.steps or 32,
                                   fast_norm=not args.no_fast_norm),
                model=ModelConfig(emb=c["emb"], heads=args.heads,
                                  depth=c["depth"],
                                  mixer_emb=c["emb"],
                                  mixer_heads=args.heads,
                                  mixer_depth=c["depth"],
                                  standard_heads=True, dtype="bfloat16",
                                  use_qslice=acting != "dense",
                                  remat=args.remat),
                replay=ReplayConfig(buffer_size=4, store_dtype="bfloat16"),
            ))

        cfg = make_cfg(args.acting, args.config)
        n_envs = cfg.batch_size_run
        steps = cfg.env_args.episode_limit

    def _time(fn, iters=args.iters):
        """median seconds of fn() (fn must return an array to sync on)."""
        fn_times = []
        _sync(fn())   # warm-up beyond compile
        for _ in range(iters):
            t0 = time.perf_counter()
            _sync(fn())
            fn_times.append(time.perf_counter() - t0)
        fn_times.sort()
        return fn_times[len(fn_times) // 2]

    def _pipe_rate(rollout, params, rs, env_steps, k):
        """Steady-state env-steps/s over k async-chained rollouts
        (see _chain_seconds)."""
        def step(rs_):
            rs2, b, _ = rollout(params, rs_, test_mode=False)
            return rs2, b.reward[0, 0]
        return round(env_steps / _chain_seconds(step, rs, k), 1)

    import contextlib

    @contextlib.contextmanager
    def tracing():
        if not args.profile:
            yield
            return
        jax.profiler.start_trace(args.profile)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            print(f"# trace written to {args.profile}", file=sys.stderr,
                  flush=True)

    if args.lattice:
        if jax.device_count() < 2:
            raise SystemExit(
                "--lattice needs >= 2 devices (a slice, or XLA_FLAGS="
                "--xla_force_host_platform_device_count=2 "
                "JAX_PLATFORMS=cpu)")
        with tracing():
            return bench_lattice(cfg, _time, args)

    if args.kernels is not None and args.population is None:
        import dataclasses as _dc

        from t2omca_tpu.config import KernelsConfig

        def make_cfg_kernels(mode: str):
            # dense acting: the kernel switch selects the program the
            # dense rollout dispatches (bench_kernels docstring)
            base = (cfg.replace(model=_dc.replace(cfg.model,
                                                  use_qslice=False))
                    if args.smoke else make_cfg("dense", args.config))
            return base.replace(kernels=KernelsConfig(attention=mode))

        with tracing():
            return bench_kernels(make_cfg_kernels, _time, args)

    if args.sebulba and args.population is None:
        if jax.device_count() < 2:
            raise SystemExit(
                "--sebulba needs >= 2 devices (a slice, or XLA_FLAGS="
                "--xla_force_host_platform_device_count=2 "
                "JAX_PLATFORMS=cpu)")
        with tracing():
            return bench_sebulba(cfg, _time, args)

    if args.superstep is not None:
        with tracing():
            return bench_superstep(cfg, _time, args)

    if args.population is not None:
        if args.sebulba:
            # graftlattice: population x sebulba lockstep
            if jax.device_count() < 2:
                raise SystemExit(
                    "--population --sebulba needs >= 2 devices (a "
                    "slice, or XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=2 JAX_PLATFORMS=cpu)")
            with tracing():
                return bench_population_sebulba(cfg, _time, args)
        with tracing():
            return bench_population(cfg, _time, args)

    if args.prod_hbm:
        if jax.device_count() < 8:
            raise SystemExit(
                "--prod-hbm needs 8 devices (a slice, or "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "JAX_PLATFORMS=cpu)")
        c = _CONFIGS[5]
        n_dev = 8
        envs = max(((args.envs or 64) // n_dev) * n_dev, n_dev)
        ring = -(-args.ring // n_dev) * n_dev
        prod_cfg = sanity_check(TrainConfig(
            batch_size_run=envs, batch_size=32, prng_impl=args.prng,
            env_args=EnvConfig(agv_num=c["agv"], mec_num=c["mec"],
                               num_channels=c["ch"],
                               episode_limit=args.steps or 150),
            model=ModelConfig(emb=c["emb"], heads=args.heads,
                              depth=c["depth"], mixer_emb=c["emb"],
                              mixer_heads=args.heads, mixer_depth=c["depth"],
                              standard_heads=True,
                              dtype=args.dtype or "float32",
                              remat=args.remat),
            replay=ReplayConfig(buffer_size=ring, store_dtype="bfloat16"),
        ))
        return bench_prod_hbm(prod_cfg)

    if args.hbm:
        from t2omca_tpu.config import load_config
        return bench_hbm(load_config(args.hbm), args)

    if args.all:
        if args.smoke:
            raise SystemExit("--all is a full-scale chip mode; drop --smoke")
        if (args.config != 3 or args.acting != "qslice" or args.train
                or args.breakdown or args.prng != "threefry"):
            # --all owns its measurement matrix; silently ignoring these
            # would misattribute records (and a non-default --prng would
            # turn the leg-1 headline into rbg with no threefry baseline)
            raise SystemExit(
                "--all runs its own fixed measurement set (config-3 "
                "headline + config-4 train + qslice/dense + "
                "threefry/rbg + breakdown); drop "
                "--config/--acting/--train/--breakdown/--prng")
        with tracing():
            return bench_all(make_cfg, _time, _pipe_rate, args)

    if args.config == 5:
        # the DP=8 scale point has its own program shape (sharded mesh);
        # bench_dp measures both metric halves (--train flips the headline);
        # --breakdown stays a single-chip mode
        if args.breakdown:
            raise SystemExit(
                "--config 5 measures the DP loop; use configs 1-4 for "
                "--breakdown")
        with tracing():
            return bench_dp(cfg, _time, args)

    if args.train or args.breakdown:
        # whole-mode trace (includes compiles; the default mode traces only
        # the timed iterations)
        with tracing():
            if args.train:   # builds its own Experiment (PER-enabled replay)
                return bench_train(cfg, _time, args)
            exp = Experiment.build(cfg)
            ts = exp.init_train_state(0)
            return breakdown(cfg, exp, ts, _time, args)

    with _REC.span("bench.build"):
        exp = Experiment.build(cfg)
        ts = exp.init_train_state(0)
    rollout = jax.jit(exp.runner.run, static_argnames="test_mode")
    params = ts.learner.params["agent"]

    # compile + warm-up
    t0 = time.perf_counter()
    with _REC.span("bench.compile"):
        rs, batch, stats = rollout(params, ts.runner, test_mode=False)
        _sync(batch.reward[0, 0])
    compile_s = time.perf_counter() - t0
    with _REC.span("bench.warm"):
        rs, batch, stats = rollout(params, rs, test_mode=False)
        _sync(batch.reward[0, 0])
    print(f"# compile+first-run: {compile_s:.1f}s  "
          f"devices={jax.devices()}", file=sys.stderr)

    times = []
    with tracing():
        for _ in range(args.iters):
            t0 = time.perf_counter()
            with _REC.span("bench.measure"):
                rs, batch, stats = rollout(params, rs, test_mode=False)
                _sync(batch.reward[0, 0])
            times.append(time.perf_counter() - t0)
    times.sort()
    dt = times[len(times) // 2]
    env_steps = cfg.batch_size_run * cfg.env_args.episode_limit
    rate = env_steps / dt
    print(f"# median rollout: {dt * 1e3:.1f}ms for {env_steps} env-steps "
          f"({n_envs} envs × {steps} slots, {cfg.env_args.agv_num} AGVs)",
          file=sys.stderr)

    line = {
        "metric": "env_steps_per_sec",
        "value": round(rate, 1),
        "unit": "env-steps/s/chip",
        "vs_baseline": round(rate / 50_000.0, 3),
        # a config id only when the run actually measured that scale point
        # (smoke and --envs/--steps overrides would misattribute the number)
        "config": (None if args.smoke or args.envs or args.steps
                   else args.config),
        "n_envs": n_envs,
        "episode_steps": steps,
        "acting": args.acting,
    }
    if jax.config.jax_default_prng_impl != "threefry2x32":
        # live impl, not the flag echo (see rollout_rate in bench_all)
        line["prng"] = jax.config.jax_default_prng_impl

    if args.pipeline:
        rate_pipe = _pipe_rate(rollout, params, rs, env_steps,
                               args.pipeline)
        line["pipelined_env_steps_per_sec"] = rate_pipe
        print(f"# pipelined (k={args.pipeline}): "
              f"{rate_pipe:.1f} env-steps/s steady-state",
              file=sys.stderr)

    # the north-star metric is BOTH halves ("env-steps/sec/chip + mixer
    # train-steps/sec", BASELINE.json): append the learner measurement to
    # the default line so every driver bench records it. The headline is
    # preserved on stderr first (and the first Experiment's device state
    # dropped); a failing train half is fatal (main_flight: exit 1).
    if not args.smoke:
        print(f"# headline: {json.dumps(line)}", file=sys.stderr, flush=True)
        del ts, rs, batch, stats, rollout, params, exp
        line.update(_train_numbers(cfg, _time, pipeline_k=args.pipeline))

    # per-phase span summary (build / compile / warm / measure
    # + the train half's legs): first_ms isolates the compile,
    # steady_ms the warm rate — the record says where the time went.
    # Set LAST so the train-half spans above are included.
    _finalize(line)
    print(json.dumps(line))
    return 0


def main_flight() -> int:
    """``main()`` with a flight-recorder net: any unhandled failure
    still leaves ONE parseable JSON line on stdout — the partial record
    with the phase it died in (``bench.build`` / ``bench.compile`` /
    ...) and the span tail, so a failed bench says WHERE it died
    instead of leaving a bare traceback on stderr, and exits 1.
    Argparse/SystemExit (usage errors, no TPU) pass through: those
    already print their own diagnostics and no measurement was in
    flight."""
    try:
        return main()
    except KeyboardInterrupt:
        raise
    except Exception as e:  # noqa: BLE001 — the record IS the handler
        # the failing span has already closed (the exception unwound
        # through its __exit__), so fall back from the open-span phase
        # to the most recent span that recorded an error outcome
        phase = _REC.current_phase()
        if phase is None:
            for ev in reversed(_REC.tail()):
                if (ev.get("event") == "span"
                        and str(ev.get("outcome", "")).startswith("error")):
                    phase = ev["phase"]
                    break
        # a crashed --train or --serve run must not file its partial
        # record under the rollout metric
        metric, unit = (("serve_chaos_p99_ms", "ms")
                        if "--serve" in sys.argv and "--chaos" in sys.argv
                        else ("serve_decisions_per_sec",
                              "decisions/s/chip")
                        if "--serve" in sys.argv
                        else ("train_steps_per_sec", "train-steps/s/chip")
                        if "--train" in sys.argv
                        else ("env_steps_per_sec", "env-steps/s/chip"))
        print(f"# bench failed in phase {phase or 'unknown'}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        print(json.dumps(_finalize({
            "metric": metric, "value": None,
            "unit": unit, "vs_baseline": None,
            "error": f"{type(e).__name__}: {e}"[:500],
            "phase": phase,
            "spans_tail": _REC.tail()[-20:],
            # default=repr: a non-JSON span-meta value must degrade,
            # not crash the crash handler and lose the record
        }), default=repr), flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main_flight())
