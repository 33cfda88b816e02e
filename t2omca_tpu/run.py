"""Experiment driver (C4): the train loop with the reference's cadences.

Re-creates ``run``/``run_sequential``/``evaluate_sequential``
(``/root/reference/per_run.py:20-309``) without sacred: config comes from the
frozen-dataclass config tree (``config.py``), experiment identity is the
unique token (``{name}_seed{seed}_{map}_{datetime}``, ``per_run.py:42``), and
sinks are console + TensorBoard + JSONL (M9).

Structure of one iteration (reference ``per_run.py:212-288``):
rollout → insert → (if can_sample ∧ episode gate) sample → train → feed
``|TD|+1e-6`` back as priorities (Q9) → cadenced test/log/checkpoint.
Every device-side stage is a jitted pure function; the Python loop only
sequences them and moves scalars to the logger.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from .components.episode_buffer import (BufferState, PrioritizedReplayBuffer,
                                        ReplayBuffer)
from .config import TrainConfig, sanity_check, unique_token
from .controllers.basic_mac import MAC_REGISTRY
from .envs.registry import make_env
from .learners.qmix_learner import LEARNER_REGISTRY, LearnerState
from .runners import RUNNER_REGISTRY
from .runners.episode_runner import EpisodeRunner
from .runners.parallel_runner import ParallelRunner, RunnerState
from .obs import compiles as obs_compiles
from .obs import memwatch as obs_memwatch
from .obs import pulse as obs_pulse
from .obs import sight as obs_sight
from .obs import spans as obs_spans
from .parallel import distributed as dist
from .utils import elastic, resilience, watchdog
from .utils.checkpoint import (find_checkpoint, load_checkpoint,
                               load_checkpoint_sharded, prune_checkpoints,
                               save_checkpoint, save_checkpoint_shards)
from .utils.logging import Logger
from .utils.profiling import StageTimer, TraceWindow
from .utils.stats import StatsAccumulator
from .utils.timehelper import time_left, time_str


@struct.dataclass
class TrainState:
    """The full checkpointable state (SURVEY.md §5(4): exact resume)."""

    learner: LearnerState
    runner: RunnerState
    buffer: BufferState
    episode: jnp.ndarray      # () int32 — episodes collected


def superstep_eligible(cfg: TrainConfig) -> bool:
    """Whether the fused K-iteration superstep program serves this config
    (the ``ops/query_slice.py`` eligibility-predicate pattern): K > 1
    requested AND the replay ring is device-resident — the host-RAM
    buffer's insert/sample are host calls and cannot live inside one XLA
    program, so ``buffer_cpu_only`` configs keep the classic
    three-program path at any ``superstep`` value."""
    return cfg.superstep > 1 and not cfg.replay.buffer_cpu_only


def sebulba_eligible(cfg: TrainConfig) -> bool:
    """Whether the Sebulba decoupled actor/learner loop serves this
    config (``parallel/sebulba.py``; the ``superstep_eligible``
    predicate pattern): ``sebulba.actor_devices > 0`` opts in, and
    ``sanity_check`` has already rejected the incompatible combinations
    (host-RAM replay, dp_devices, superstep > 1)."""
    return cfg.sebulba.actor_devices > 0


def _strong(tree):
    """Drop weak_type from every chained output: the driver feeds these
    back as inputs, and a weak-typed leaf (e.g. from a Python-scalar
    jnp.where branch) makes the output aval differ from the strong input
    aval — forcing a silent second compile of the whole program on loop
    iteration 2. astype(same-dtype) is a no-op in XLA but strips the
    weak flag."""
    return jax.tree.map(lambda x: x.astype(x.dtype), tree)


def _squeeze0(tree):
    """Drop the leading size-1 population axis from every leaf — the
    P=1 graftpop layout bridge (``population_superstep_program``). Pure
    layout ops; MUST stay the exact inverse of :func:`_expand0` — the
    P=1 bit-parity contract stands on both programs using the same
    bridge."""
    return jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)


def _expand0(tree):
    """Restore the leading population axis ``_squeeze0`` dropped."""
    return jax.tree.map(lambda x: x[None], tree)


@dataclasses.dataclass
class Experiment:
    """Built components + jitted programs for one config."""

    # class-level (not a field): whether any build() in this process has
    # pinned jax_default_prng_impl yet — a later build that CHANGES the
    # impl is the hazardous case worth a RuntimeWarning
    _prng_impl_pinned = False

    cfg: TrainConfig
    env: object
    mac: object
    learner: object
    runner: ParallelRunner
    buffer: ReplayBuffer
    episode_runner: EpisodeRunner

    @classmethod
    def build(cls, cfg: TrainConfig) -> "Experiment":
        cfg = sanity_check(cfg)
        # process-global by necessity: raw PRNGKey arrays carry no impl
        # tag, so every split/draw in the jitted programs resolves the
        # impl from this config. "rbg" = XLA RngBitGenerator, the TPU
        # hardware generator — much cheaper than threefry for the
        # rollout's many small draws. Key shapes differ (4 vs 2 uint32),
        # so checkpoints are impl-specific (shape-validated restore names
        # the mismatch). Only touched when the value actually changes, and
        # a mid-process switch warns loudly: keys made or programs traced
        # under the previous impl (an earlier Experiment build in this
        # process, caller-created keys) mis-resolve under the new one —
        # interleave cross-impl Experiments at your own risk.
        want = {"threefry": "threefry2x32"}.get(cfg.prng_impl, cfg.prng_impl)
        have = jax.config.jax_default_prng_impl
        if have != want:
            if cls._prng_impl_pinned:
                import warnings
                warnings.warn(
                    f"Experiment.build switches jax_default_prng_impl "
                    f"{have!r} -> {want!r} mid-process: PRNG keys and "
                    f"jitted programs from earlier builds in this process "
                    f"resolve against the NEW impl and will break or "
                    f"silently draw different streams; rebuild (or avoid "
                    f"holding) anything created under the old impl",
                    RuntimeWarning, stacklevel=2)
            jax.config.update("jax_default_prng_impl", want)
        cls._prng_impl_pinned = True
        env = make_env(cfg.env_args)
        env_info = env.get_env_info()
        mac = MAC_REGISTRY[cfg.mac].build(cfg, env_info)
        learner = LEARNER_REGISTRY[cfg.learner].build(cfg, mac, env_info)
        runner_cls = RUNNER_REGISTRY[cfg.runner]
        runner = runner_cls(env, mac, cfg)
        from .ops.query_slice import entity_store_eligible
        buf_kw = dict(
            capacity=cfg.replay.buffer_size,
            episode_limit=cfg.env_args.episode_limit,
            n_agents=env_info["n_agents"],
            n_actions=env_info["n_actions"],
            obs_dim=env_info["obs_shape"],
            state_dim=env_info["state_shape"],
            store_dtype=cfg.replay.store_dtype,
        )
        if not cfg.replay.buffer_cpu_only:
            buf_kw["compact_obs"] = entity_store_eligible(cfg)
        if cfg.replay.buffer_cpu_only:
            # host-RAM replay with the device-side PER sample (reference
            # buffer_cpu_only semantics: storage on CPU, samples to
            # device; the priority vector is device-mirrored so index
            # selection + importance weights run as one device program)
            from .components.host_replay import HostReplayBuffer
            buffer = HostReplayBuffer(
                alpha=cfg.replay.per_alpha, beta0=cfg.replay.per_beta,
                t_max=cfg.t_max, prioritized=cfg.replay.prioritized,
                **buf_kw)
        else:
            buf_cls = (PrioritizedReplayBuffer if cfg.replay.prioritized
                       else ReplayBuffer)
            if cfg.replay.prioritized:
                buf_kw.update(alpha=cfg.replay.per_alpha,
                              beta0=cfg.replay.per_beta, t_max=cfg.t_max)
            buffer = buf_cls(**buf_kw)
        episode_runner = EpisodeRunner(env, mac, cfg)
        return cls(cfg=cfg, env=env, mac=mac, learner=learner, runner=runner,
                   buffer=buffer, episode_runner=episode_runner)

    # ------------------------------------------------------------------ state

    @property
    def host_buffer(self) -> bool:
        return getattr(self.buffer, "is_host", False)

    def init_train_state(self, seed: int) -> TrainState:
        k_learner, k_runner = jax.random.split(jax.random.PRNGKey(seed))
        return TrainState(
            learner=self.learner.init_state(k_learner),
            runner=self.runner.init_state(k_runner),
            # host buffers keep their state outside the jitted pytree
            buffer=None if self.host_buffer else self.buffer.init(),
            episode=jnp.zeros((), jnp.int32),
        )

    # ------------------------------------------------------------------ programs

    def jitted_programs(self, constrain_batch=None, constrain_runner=None,
                        constrain_buffer=None, constrain_learner=None,
                        donate: bool = False):
        """→ (rollout, insert, train_iter) jitted programs.

        The ``constrain_*`` hooks are optional identity-shaped functions
        applied to program outputs — the multi-chip path
        (``parallel.DataParallel``) injects ``with_sharding_constraint``
        through them so both paths share one program definition. They
        cover every value the driver loop CHAINS back in as an input
        (episode batches, runner state, replay state, learner state):
        without the output constraints GSPMD is free to choose different
        output shardings than the canonical input placement, and the
        second-and-later iterations of the loop would silently compile
        and run a differently-sharded program.

        ``donate=True`` donates the replay ring to ``insert`` and the train
        state to ``train_iter`` — XLA then updates both in place instead of
        copying the (largest-on-chip) buffer arrays every call. Only for
        callers that never reuse the pre-call state (the ``run_sequential``
        loop replaces it immediately); tests that re-run a program on the
        same inputs must keep the default."""
        runner, buffer, learner, cfg = (self.runner, self.buffer,
                                        self.learner, self.cfg)
        constrain = constrain_batch or (lambda b: b)
        c_runner = constrain_runner or (lambda rs: rs)
        c_buffer = constrain_buffer or (lambda b: b)
        c_learner = constrain_learner or (lambda l: l)

        def _rollout(params, rs, test_mode):
            rs2, batch, stats = runner.run(params, rs, test_mode=test_mode)
            return _strong(c_runner(rs2)), constrain(batch), stats

        rollout = jax.jit(_rollout, static_argnames="test_mode")

        if self.host_buffer:
            # storage lives in host RAM (reference buffer_cpu_only): insert
            # and sample are host calls, only learner.train is jitted
            train = jax.jit(learner.train)

            def insert(_ts_buffer, batch):
                buffer.insert_episode_batch(batch)
                return None

            def train_iter_host(ts: TrainState, key: jax.Array,
                                t_env: jnp.ndarray):
                # host RNG owns the stratum uniforms; key seeds noise/
                # dropout (train ignores it for pure configs). sample()
                # first consumes the PREVIOUS iteration's deferred
                # priority feedback — the |TD| / finite-flag fetch is
                # started asynchronously below and never blocks this
                # iteration; the non-finite guard
                # stays in the flush (a tripped step leaves the
                # priority mirrors untouched). Index selection and
                # importance weights run as ONE device program over the
                # mirrored priority vector (PR 13) — zero sum-tree
                # ctypes crossings on this path
                batch, idx, weights = buffer.sample(cfg.batch_size,
                                                    int(t_env))
                learner_state, info = train(ts.learner, batch, weights,
                                            t_env, ts.episode, key)
                buffer.defer_priority_update(idx, info["td_errors_abs"],
                                             info["all_finite"])
                if cfg.obs.sight.enabled and buffer.prioritized:
                    # host-replay twin of the in-graph PER health read:
                    # pure numpy over the host priority mirror — zero
                    # device traffic on the buffer_cpu_only path
                    info = dict(info, **buffer.sight_priority_info())
                return ts.replace(learner=learner_state), info

            return rollout, insert, train_iter_host

        def _insert(state, batch):
            return _strong(c_buffer(buffer.insert_episode_batch(state,
                                                                batch)))

        insert = jax.jit(_insert, donate_argnums=(0,) if donate else ())

        def _train_iter(ts: TrainState, key: jax.Array, t_env: jnp.ndarray):
            """sample → train → priority feedback, as one program."""
            k_sample, k_learn = jax.random.split(key)
            batch, idx, weights = buffer.sample(
                ts.buffer, k_sample, cfg.batch_size, t_env)
            learner_state, info = learner.train(
                ts.learner, constrain(batch), weights, t_env, ts.episode,
                k_learn)
            # non-finite guard (valid=): a tripped step must not scatter
            # NaN priorities into the ring (they would win every PER
            # draw forever) — the buffer writes back the episodes'
            # EXISTING stored values instead, value-identical to not
            # updating, with no host sync and no full-ring select
            buf = buffer.update_priorities(
                ts.buffer, idx, info["td_errors_abs"] + 1e-6,      # Q9
                valid=info["all_finite"])
            # graftsight PER health: one masked reduce over the
            # post-update priority vector, inside this same program
            # (docs/OBSERVABILITY.md §6 — zero extra dispatches;
            # no-op unless the static gate + prioritized replay apply)
            info = obs_sight.maybe_buffer_info(cfg, info, buf)
            return _strong(ts.replace(learner=c_learner(learner_state),
                                      buffer=c_buffer(buf))), info

        return rollout, insert, jax.jit(
            _train_iter, donate_argnums=(0,) if donate else ())

    def superstep_program(self, k: int, constrain_batch=None,
                          constrain_runner=None, constrain_buffer=None,
                          constrain_learner=None, donate: bool = False):
        """→ jitted ``superstep(ts, keys, t_env0) -> (ts', stacked_stats,
        stacked_infos)`` — the Anakin/Podracer fusion (PAPERS.md): rollout
        → in-place ring insert → gate-checked sample+train as ONE XLA
        program, ``lax.scan``-ed ``k`` iterations per dispatch.

        Amortizes the per-dispatch overhead over ``k`` full train
        iterations, and never
        materializes the ``(B, T+1, ...)`` episode batch between rollout
        and insert: the rollout scan's time-major emission scatters
        straight into the (donated → in-place) replay ring
        (``ReplayBuffer.insert_time_major``).

        Contract with the classic three-program loop (pinned by
        tests/test_superstep.py):

        * the train gate ``episodes_in_buffer >= batch_size AND episode
          >= accumulated_episodes`` is traced arithmetic on the carried
          counters — a ``lax.cond``, so skipped sub-iterations pay no
          train compute;
        * ``keys`` is the ``(k, key)`` stack of per-sub-iteration train
          keys. The driver splits its key stream ONLY for sub-iterations
          whose gate fires (it mirrors the counters host-side, exactly
          like the classic loop's host gate) and passes zeros for skipped
          rows, so the consumed key stream — and therefore training — is
          bit-identical to the K=1 loop;
        * epsilon/beta schedules thread through as functions of the
          carried ``t_env``: sub-iteration ``i`` trains at ``t_env0 +
          (i+1)·B·T``, matching the host counter the classic loop passes;
        * ``stacked_stats``/``stacked_infos`` come back shaped ``(k,
          ...)`` and feed the host accumulators once per dispatch; info
          rows of skipped sub-iterations are aval-matched zeros with
          ``all_finite=True`` (``QMixLearner.train_info_zeros``) and are
          dropped by the driver via its host gate mirror.

        ``donate=True`` donates the full TrainState — ring, learner and
        runner state update in place across the superstep. Host-RAM
        replay configs are ineligible (``superstep_eligible``)."""
        return jax.jit(
            self._superstep_fn(k, constrain_batch, constrain_runner,
                               constrain_buffer, constrain_learner),
            donate_argnums=(0,) if donate else ())

    def _superstep_fn(self, k: int, constrain_batch=None,
                      constrain_runner=None, constrain_buffer=None,
                      constrain_learner=None):
        """The unjitted superstep body — shared by the classic jit
        (``superstep_program``) and the graftpop population vmap
        (``population_superstep_program``). ``spec`` (an optional
        graftpop ``PopulationSpec`` of per-member traced scalars)
        threads the member's epsilon scale into the rollout, its PER
        exponent into the ring writes, and its lr scale into the
        learner update; ``None`` (the classic path) compiles the exact
        pre-population program — every graftprog fingerprint pinned."""
        if self.host_buffer:
            raise ValueError(
                "superstep_program requires the device-resident replay "
                "ring; buffer_cpu_only configs use the three-program "
                "path (superstep_eligible)")
        if k < 1:
            raise ValueError(f"superstep k must be >= 1, got {k}")
        runner, buffer, learner, cfg = (self.runner, self.buffer,
                                        self.learner, self.cfg)
        constrain = constrain_batch or (lambda b: b)
        c_runner = constrain_runner or (lambda rs: rs)
        c_buffer = constrain_buffer or (lambda b: b)
        c_learner = constrain_learner or (lambda l: l)
        steps_per_rollout = cfg.batch_size_run * cfg.env_args.episode_limit

        def _superstep(ts: TrainState, keys: jax.Array,
                       t_env0: jnp.ndarray, spec=None):
            alpha = None if spec is None else spec.per_alpha
            roll_kw = {}
            if spec is not None:
                roll_kw["eps_scale"] = spec.eps_scale
                if cfg.population.scenario_salt:
                    roll_kw["member"] = spec.member

            def _train(op):
                ts, key, t_env = op
                # identical key/arithmetic threading to _train_iter above
                k_sample, k_learn = jax.random.split(key)
                batch, idx, weights = buffer.sample(
                    ts.buffer, k_sample, cfg.batch_size, t_env)
                learner_state, info = learner.train(
                    ts.learner, constrain(batch), weights, t_env,
                    ts.episode, k_learn, spec=spec)
                buf = buffer.update_priorities(
                    ts.buffer, idx, info["td_errors_abs"] + 1e-6,  # Q9
                    valid=info["all_finite"], alpha=alpha)
                return ts.replace(learner=c_learner(learner_state),
                                  buffer=c_buffer(buf)), _sight_buf(info,
                                                                    buf)

            def _sight_buf(info, buf):
                # graftsight PER health, in-graph (the shared definition
                # — see _train_iter). BOTH cond branches route through
                # this so the info pytrees stay aval-identical (the skip
                # branch reads the untouched ring)
                return obs_sight.maybe_buffer_info(cfg, info, buf)

            def _skip(op):
                ts, _, _ = op
                return ts, _sight_buf(
                    learner.train_info_zeros(cfg.batch_size), ts.buffer)

            def _body(ts: TrainState, xs):
                key, t_env = xs
                rs, tm, stats = runner.run_raw(ts.learner.params["agent"],
                                               ts.runner, test_mode=False,
                                               **roll_kw)
                buf = buffer.insert_time_major(ts.buffer, tm, alpha=alpha)
                ts = ts.replace(runner=c_runner(rs), buffer=c_buffer(buf),
                                episode=ts.episode + cfg.batch_size_run)
                gate = (buffer.can_sample(ts.buffer, cfg.batch_size)
                        & (ts.episode >= cfg.accumulated_episodes))
                ts, info = jax.lax.cond(gate, _train, _skip,
                                        (ts, key, t_env))
                return _strong(ts), (stats, _strong(info))

            t_envs = (jnp.asarray(t_env0, jnp.int32)
                      + jnp.arange(1, k + 1, dtype=jnp.int32)
                      * steps_per_rollout)
            ts, (stats, infos) = jax.lax.scan(_body, ts, (keys, t_envs))
            return ts, stats, infos

        return _superstep

    def population_superstep_program(self, k: int, donate: bool = False):
        """→ jitted ``superstep_pop(ts, keys, t_env0, spec) -> (ts',
        stacked_stats, stacked_infos)`` — graftpop (docs/POPULATION.md):
        the SAME fused superstep body vmapped over a leading ``(P,)``
        population axis of the full train state, per-member ``(P, k)``
        key stacks and the :class:`~t2omca_tpu.population.PopulationSpec`
        of per-member hyperparameter scalars. ``t_env0`` stays a shared
        scalar (the counters evolve identically across members). ONE
        donated dispatch advances all P members; outputs come back with
        the extra leading ``(P,)`` axis on every stats/info leaf.

        P=1 deliberately bypasses ``jax.vmap``: the member axis is
        squeezed inside the jit and the UNBATCHED superstep body runs
        directly (axis-restored on the way out — pure layout ops), so a
        single-member population lowers the classic program's exact
        arithmetic and stays BIT-identical to the classic loop. A
        batched rank would not: XLA's batched reduces reassociate f32
        sums (data-dependent 1-ULP drift in gradient accumulations —
        measured on CPU), which is also why P>=2 members pin
        bit-parity only against EACH OTHER (same batched kernel), not
        against their solo runs (docs/POPULATION.md §parity). When the
        P=1 spec is statically NEUTRAL (no grids, no scenario salt, no
        PBT) the spec seams drop out entirely (``spec=None`` into the
        body) — even a value-neutral traced seam (``x*1.0``,
        ``pow(x, traced-default)``) perturbs XLA's fusion choices
        enough to flip a reduce tiling and drift a ULP (measured), and
        the bit-parity contract tolerates zero ULPs."""

        fn = self._superstep_fn(k)
        pc = self.cfg.population
        p = int(pc.size)
        neutral = (p == 1 and not pc.lr and not pc.eps_scale
                   and not pc.per_alpha and not pc.scenario_salt
                   and not pc.pbt.enabled)

        def _superstep_pop(ts: TrainState, keys: jax.Array,
                           t_env0: jnp.ndarray, spec):
            if p == 1:
                out_ts, stats, infos = fn(
                    _squeeze0(ts), jnp.squeeze(keys, 0), t_env0,
                    None if neutral else _squeeze0(spec))
                return _expand0(out_ts), _expand0(stats), _expand0(infos)
            return jax.vmap(
                lambda t, kk, s: fn(t, kk, t_env0, s))(ts, keys, spec)

        return jax.jit(_superstep_pop,
                       donate_argnums=(0,) if donate else ())

    def population_rollout_program(self):
        """→ jitted ``pop_test(params, rs) -> (rs', stats)``: the
        greedy test rollout vmapped over the population axis — serves
        the test cadence of the population driver loop (the episode
        batch is dropped inside the jit, so XLA never materializes
        it). P=1 squeezes instead of vmapping, for the same
        bit-parity reason as ``population_superstep_program``."""
        runner = self.runner
        p = int(self.cfg.population.size)

        def one(params, r):
            r2, _tm, stats = runner.run_raw(params, r, test_mode=True)
            return _strong(r2), stats

        def _pop_test(params, rs):
            if p == 1:
                r2, stats = one(_squeeze0(params), _squeeze0(rs))
                return _expand0(r2), _expand0(stats)
            return jax.vmap(one)(params, rs)

        return jax.jit(_pop_test)


def register_audit_programs(ctx):
    """graftprog registry hook (``analysis/registry.py``): name the
    driver's hot programs once, so the compiled-program auditor and the
    budget baseline (``analysis/programs.json``) can build exactly what
    ``run_sequential`` dispatches. Everything is abstract — eval_shape
    state + ShapeDtypeStruct keys — and ``t_env`` is the driver's own
    weak-typed ``jnp.asarray(int)`` scalar, so the recorded fingerprint
    is the fingerprint of the program the loop actually runs (an aval
    drift between driver and audit surfaces as GP304)."""
    from .analysis.registry import AuditProgram
    exp, ts, k = ctx.exp, ctx.ts_shape, ctx.superstep_k
    rollout, insert, train_iter = exp.jitted_programs(donate=True)
    sup = exp.superstep_program(k, donate=True)
    params, rs = ts.learner.params["agent"], ts.runner
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    keys = jax.ShapeDtypeStruct((k,) + key.shape, key.dtype)
    t_env = jnp.asarray(0)           # weak-typed, like the driver's
    _, batch, _ = jax.eval_shape(
        lambda p, r: rollout(p, r, test_mode=False), params, rs)
    return {
        "rollout": AuditProgram(
            rollout, (params, rs), kwargs=dict(test_mode=False),
            description="parallel env rollout (classic path + test "
                        "cadence)"),
        "insert": AuditProgram(
            insert, (ts.buffer, batch), donate_argnums=(0,),
            description="episode-batch ring insert (classic path, "
                        "donated ring)"),
        "train_iter": AuditProgram(
            train_iter, (ts, key, t_env), donate_argnums=(0,),
            compile=True,
            description="sample -> train -> priority feedback "
                        "(donated TrainState)"),
        "superstep": AuditProgram(
            sup, (ts, keys, t_env), donate_argnums=(0,), compile=True,
            description=f"fused K={k} rollout->insert->train superstep "
                        f"(donated TrainState)"),
        **_kernel_pair_programs(key, t_env),
        **_sight_twin_programs(key, t_env),
        **_population_twin_programs(key, t_env),
    }


def _kernel_pair_programs(key, t_env):
    """The kernel-mode byte-comparison pair (PR 13): the SAME
    ``_train_iter`` lowered under each ``kernels.attention`` mode at the
    kernel audit scale (``registry.kernels_audit_config`` — token counts
    where the logits tensor the flash path eliminates is material).
    Lowered level only; the GP302 ratchet + tests/test_graftprog.py pin
    ``train_iter_pallas`` strictly BELOW ``train_iter_pallas_ref`` —
    the train-path bytes the flash backward exists to remove."""
    from .analysis.registry import AuditProgram, kernels_audit_context
    out = {}
    for mode, name in (("pallas", "train_iter_pallas"),
                       ("xla", "train_iter_pallas_ref")):
        kctx = kernels_audit_context(mode)
        _, _, k_train_iter = kctx.exp.jitted_programs(donate=True)
        out[name] = AuditProgram(
            k_train_iter, (kctx.ts_shape, key, t_env),
            donate_argnums=(0,),
            description=(f"sample -> train -> priority feedback under "
                         f"kernels.attention={mode} at the kernel audit "
                         f"scale — the flash-vs-einsum train-path byte "
                         f"comparison (pallas must stay strictly below "
                         f"the _ref twin)"))
    return out


def _sight_twin_programs(key, t_env):
    """The sight-on twin audit entries (the PR 13 kernel-pair pattern):
    the SAME ``_train_iter``/``_superstep`` lowered under
    ``obs.sight.enabled`` at the shared audit scale
    (``registry.sight_audit_config``). The twins carry their own
    GP301/302 budgets so the diagnostic overhead is itself RATCHETED —
    a sight change that doubles the train step's bytes fails the gate —
    while the sight-OFF fingerprints of
    ``train_iter``/``superstep``/``learner_train``/``dp_superstep``
    stay byte-identical (the static gate compiles out; zero
    re-baseline, pinned by tests/test_sight.py)."""
    from .analysis.registry import AuditProgram, sight_audit_context
    sctx = sight_audit_context()
    exp, ts, k = sctx.exp, sctx.ts_shape, sctx.superstep_k
    _, _, s_train_iter = exp.jitted_programs(donate=True)
    s_sup = exp.superstep_program(k, donate=True)
    keys = jax.ShapeDtypeStruct((k,) + key.shape, key.dtype)
    return {
        "train_iter_sight": AuditProgram(
            s_train_iter, (ts, key, t_env), donate_argnums=(0,),
            description="sample -> train -> priority feedback with the "
                        "graftsight in-graph diagnostics compiled in "
                        "(obs.sight.enabled) — the diagnostic overhead "
                        "ratchet next to the sight-off train_iter"),
        "superstep_sight": AuditProgram(
            s_sup, (ts, keys, t_env), donate_argnums=(0,),
            description=f"fused K={k} superstep with the graftsight "
                        f"diagnostics compiled in — pins the fused-path "
                        f"diagnostic overhead (both lax.cond branches "
                        f"carry the sight info pytree)"),
    }


def _population_twin_programs(key, t_env):
    """The graftpop audit entry (the PR 13/14 twin pattern):
    ``superstep_pop`` — the SAME fused superstep body vmapped over a
    FIXED P=2 population at the shared audit scale
    (``registry.population_audit_config``), ratcheted in programs.json
    so a population-path cost regression fails the gate statically,
    while the population-OFF fingerprints of every existing hot program
    stay byte-identical (the spec seams are ``None``-defaulted — zero
    re-baseline, pinned by the t1 prelude)."""
    import jax as _jax

    from .analysis.registry import (AuditProgram, population_audit_context,
                                    population_kernels_audit_context)
    pctx = population_audit_context()
    exp, k = pctx.exp, pctx.superstep_k
    p = pctx.cfg.population.size
    # the context's ts_shape IS the stacked (ts, spec) aval pair —
    # registry.population_audit_context docstring
    ts_shape, spec_shape = pctx.ts_shape
    prog = exp.population_superstep_program(k, donate=True)
    keys = _jax.ShapeDtypeStruct((p, k) + key.shape, key.dtype)
    # vmap-over-pallas twin (graftlattice): the same population program
    # under kernels.attention=pallas at the KERNEL audit scale — its own
    # context, so neither the xla-mode population baseline above nor the
    # population-OFF pallas baselines move a byte
    pkctx = population_kernels_audit_context()
    pk_ts, pk_spec = pkctx.ts_shape
    pk_prog = pkctx.exp.population_superstep_program(k, donate=True)
    pk_keys = _jax.ShapeDtypeStruct((pkctx.cfg.population.size, k)
                                    + key.shape, key.dtype)
    return {
        "superstep_pop": AuditProgram(
            prog, (ts_shape, keys, t_env, spec_shape),
            donate_argnums=(0,),
            description=f"fused K={k} superstep vmapped over a P={p} "
                        f"population (graftpop — one donated dispatch "
                        f"advances P members; per-member lr/eps/alpha "
                        f"spec leaves)"),
        "superstep_pop_pallas": AuditProgram(
            pk_prog, (pk_ts, pk_keys, t_env, pk_spec),
            donate_argnums=(0,),
            description=f"fused K={k} population superstep with the "
                        f"flash attention kernels vmapped over the "
                        f"P={pkctx.cfg.population.size} member axis "
                        f"(vmap-over-pallas, kernel audit scale — "
                        f"populations use the fused forward+backward "
                        f"kernels)"),
    }


def _host_int(x) -> int:
    """Host mirror of a control counter. Under a population the counter
    is (P,)-stacked but every member's copy evolves identically (same
    batch_size_run, capacity, gates), so member 0's value mirrors the
    whole stacked pytree."""
    return int(np.asarray(jax.device_get(x)).reshape(-1)[0])


class _DriverKit:
    """Shared driver-helper kit (graftlattice, ROADMAP item 2): the
    watchdog stamps, fault-handled dispatch, sync-point classification,
    stall response, flight persist and bounded save-lock discipline that
    ``run_sequential`` and ``run_sebulba`` previously carried as
    acknowledged forked copies (PR 10 known debt). One instance per
    driver; each loop binds locals (``_watched = kit.watched`` …) so
    graftlint's name-keyed call-site phase checks (GL110), the
    fault-injection hooks and the tests see the same wrapper names
    either way.

    Parameterization points — the only behavioral deltas the two loops
    ever had:

    * ``default_wd`` — the watchdog a bare ``watched``/``dispatch``
      call stamps with. The classic loop arms its single watchdog here
      (every device-facing region stamps by default); the sebulba loop
      leaves it ``None`` and passes ``awd=`` explicitly per thread (one
      armed stamp per instance — concurrent threads must not share
      one), so its span-only sites (queue waits bounded by the PEER's
      progress, not device health) stay unstamped.
    * ``t_env_fn`` — the classic loop's cursor closure for sites that
      don't pass ``t=`` explicitly; sebulba always passes ``t=`` from
      whichever thread's cursor applies.
    * ``wake`` — sebulba's queue-condition notifier, fired inside the
      stall response so threads blocked on the queue observe the guard
      trip; ``None`` classically.
    * ``P``/``spec_fn`` — the population stamp wrap: the watchdog's
      emergency save writes the stamped state verbatim, and a bare
      (P,)-stacked TrainState would hit the single-member→population
      migration shim on restore and double-stack, so any full
      TrainState stamp is wrapped into the checkpointable ``PopState``
      (runner-state-only and learner-half stamps pass through — they
      are never emergency-saved).
    """

    _UNSET = object()

    def __init__(self, *, cfg, res, log, rec, mw, sight_mon, guard,
                 model_dir, save_lock, P=0, spec_fn=None, wake=None):
        self.cfg, self.res, self.log, self.rec = cfg, res, log, rec
        self.mw, self.sight_mon, self.guard = mw, sight_mon, guard
        self.model_dir, self.save_lock = model_dir, save_lock
        self.P, self.spec_fn, self.wake = P, spec_fn, wake
        self.default_wd = None      # armed by the driver once built
        self.t_env_fn = lambda: 0   # the classic loop re-binds its cursor
        self.dispatch_faults = 0    # transient dispatch errors seen (stats)

    # ------------------------------------------------------------ telemetry

    def persist_flight(self, path: str) -> None:
        """Flight persist + the memwatch high-water + sight-verdict
        blocks (cached state only — safe on crash/stall paths over a
        wedged backend)."""
        extra = {}
        if self.mw.enabled:
            extra["memwatch"] = self.mw.report()
        if self.sight_mon is not None:
            extra["sight"] = self.sight_mon.report()
        self.rec.persist(path, extra=extra or None)

    def watched(self, phase, state=None, awd=_UNSET, t=None, **meta):
        """One watchdog stamp + graftscope span for a device-facing
        region (no-op context when both are disabled) — keeps the
        wd-None guard, the current-t_env threading, and the telemetry
        pairing in one place instead of at every site. ``meta`` lands
        in the span event (attempt counts, K); the watchdog stamp is
        the OUTER context so a hang inside the span bookkeeping is
        still bounded."""
        if awd is _DriverKit._UNSET:
            awd = self.default_wd
        if t is None:
            t = self.t_env_fn()
        if (self.P and state is not None and hasattr(state, "runner")
                and not hasattr(state, "spec")):
            # population runs stamp the CHECKPOINTABLE PopState, never
            # the bare stacked TrainState (class docstring)
            from . import population as graftpop
            state = graftpop.PopState(ts=state, spec=self.spec_fn())
        w = (awd.watch(phase, t_env=t, state=state)
             if awd is not None else None)
        if self.rec.enabled:
            s = self.rec.span(phase, t_env=t, **meta)
            return obs_spans.stacked(w, s) if w is not None else s
        return w if w is not None else nullcontext()

    # ------------------------------------------------------------ dispatch

    def dispatch(self, phase, fn, state, awd=_UNSET, t=None,
                 retryable=True, **context):
        """One device-facing dispatch: fault-injection hook + watchdog
        heartbeat + bounded in-place retry with backoff (ladder rung 0).
        Transient-classified failures retry ``fn`` with the SAME inputs —
        the callers commit their host mirrors only after success, so a
        retry replays an identical dispatch. Pass ``retryable=False``
        when ``fn`` carries non-idempotent HOST side effects the
        commit-after-success discipline cannot cover (the host-buffer
        path: ``buffer.sample()`` advances the host RNG and the ring
        insert mutates host RAM before a transient h2d/sync failure
        surfaces, and ``state_intact`` can't see host mutations — a
        retry would train on a different batch or double-insert); the
        first transient failure then goes straight to the ladder.
        Deterministic errors propagate immediately (retrying a shape bug
        only delays the real diagnosis); exhausted retries — or a
        failure that already consumed the donated state — raise
        DispatchFailed for the ladder. Deliberately NOT composed from
        watchdog.retry_call: the per-attempt stamp+fire, the donation
        check, and the exhaustion→DispatchFailed conversion don't fit
        its propagate-last-error contract."""
        res = self.res
        if t is None:
            t = self.t_env_fn()
        attempts = (1 + res.dispatch_retries) if retryable else 1
        for attempt in range(1, attempts + 1):
            try:
                with self.watched(phase, state, awd=awd, t=t,
                                  attempt=attempt, **context):
                    # the hook fires INSIDE the watched region: an
                    # injected sleep here is indistinguishable from a
                    # hung dispatch to the watchdog (tests rely on this)
                    resilience.fire(phase, t_env=t, attempt=attempt,
                                    **context)
                    return fn()
            except Exception as e:  # noqa: BLE001 — classified below
                if not watchdog.is_transient(e):
                    raise
                self.dispatch_faults += 1
                if attempt >= attempts or not watchdog.state_intact(state):
                    raise watchdog.DispatchFailed(phase, attempt, e) from e
                delay = watchdog.backoff_delay(attempt, res.retry_backoff_s)
                self.log.warning(f"{phase}: transient dispatch failure "
                                 f"(attempt {attempt}/{attempts}), "
                                 f"retrying in {delay:.2f}s: "
                                 f"{type(e).__name__}: {e}")
                time.sleep(delay)

    def sync_point(self, phase, fn, state, **context):
        """One blocking sync/fetch boundary (run-ahead wait, cadence stat
        fetch): watchdog stamp + fault-injection hook + transient
        classification in one place. On the production path these host
        round-trips are where a device-side wedge or async fault
        actually surfaces, so each must carry a stamp — an unstamped
        blocking fetch is exactly the silent hang this layer exists to
        bound. No in-place retry is possible here (the already-
        dispatched computation's donated inputs are gone and its
        outputs are suspect), so a transient failure raises
        ``DispatchFailed`` for the caller to route to the ladder with
        ``can_degrade=False`` — restore is the only rung that can
        stand; deterministic errors propagate unwrapped. ``context`` is
        handed to the fault-injection hook only."""
        try:
            with self.watched(phase, state):
                resilience.fire(phase, t_env=self.t_env_fn(), **context)
                return fn()
        except Exception as e:  # noqa: BLE001 — classified below
            if not watchdog.is_transient(e):
                raise
            self.dispatch_faults += 1
            raise watchdog.DispatchFailed(phase, 1, e) from e

    # ------------------------------------------------------------ stalls

    def acquire_save_lock(self, where: str) -> bool:
        """BOUNDED acquire shared by every save site: an emergency save
        wedged inside the stalled backend can hold the lock forever, and
        each waiter (watchdog callback, save cadence, exit path) must
        skip with a warning instead of inheriting the hang — resume then
        falls back to the newest published checkpoint."""
        if self.save_lock.acquire(timeout=max(self.res.stall_grace_s,
                                              60.0)):
            return True
        self.log.warning(f"{where}: checkpoint skipped — an emergency "
                         f"save still holds the save lock (wedged "
                         f"backend?); resume falls back to the newest "
                         f"published checkpoint")
        return False

    def stall_response(self, diag, tag: str = "watchdog",
                       save: bool = True) -> None:
        """The watchdog stall callback: flight tail + memwatch + sight
        extras folded into the diagnosis write, guard trip (BEFORE the
        save attempt — the emergency save reads device state over the
        possibly-wedged backend and can block without raising; with
        stall_grace_s=0 a guard tripped only afterwards would never
        trip at all), queue-wait wakeup, then a gated emergency
        checkpoint from the stamped pre-dispatch state. ``save=False``
        is the actor-thread shape: diagnosis + guard trip only — the
        learner (main) thread owns the checkpointable state and writes
        the emergency save on its own exit path. Telemetry extras are
        guarded: a telemetry failure must not abort the callback before
        the diagnosis write and the guard trip — the stall response
        outranks its own decoration. The memwatch/sight blocks are
        host-cached only (``report()``, never ``snapshot()``): the
        stall path must not read the wedged backend it diagnoses."""
        cfg, res, log = self.cfg, self.res, self.log
        extra = {}
        if self.rec.enabled:
            try:
                extra["recent_spans"] = self.rec.tail()
            except Exception:  # noqa: BLE001 — diagnostics only
                log.exception("graftscope: flight tail unavailable")
        if self.mw.enabled:
            extra["memwatch"] = self.mw.report()
        if self.sight_mon is not None:
            extra["sight"] = self.sight_mon.report()
        watchdog.write_diagnosis(diag, self.model_dir, extra=extra or None)
        self.guard.request(tag)
        if self.wake is not None:
            self.wake()              # unblock any queue-condition wait
        # single-process only: save_checkpoint is a lockstep collective
        # sequence in multi-host, and a one-sided save from THIS
        # process's stalled watchdog would hang in sync_global_devices
        # barriers its (healthy, not-saving) peers never enter — wedging
        # the watchdog thread while it holds save_lock. Multi-host
        # stalls still get the diagnosis + guard trip; resume falls back
        # to the last cadence save. A stall during the checkpoint write
        # itself also skips the save (the staging directory is in use by
        # the stalled writer), as does donated-and-consumed state (its
        # buffers are gone).
        if (save and cfg.save_model and res.emergency_checkpoint
                and jax.process_count() == 1
                and not diag.phase.startswith("checkpoint")
                and diag.state is not None
                and watchdog.state_intact(diag.state)):
            # stall callbacks run on their own threads (the monitor
            # keeps watching), so a previous callback wedged inside the
            # stalled backend may still hold the lock — blocking
            # unbounded here would just stack dead threads
            if not self.acquire_save_lock("watchdog emergency save"):
                return
            try:
                save_to = save_checkpoint(
                    self.model_dir, diag.t_env, diag.state,
                    gather_retries=res.dispatch_retries,
                    gather_backoff_s=res.retry_backoff_s)
                log.warning(f"watchdog: emergency checkpoint saved to "
                            f"{save_to}")
            except Exception as e:  # noqa: BLE001 — device may be wedged
                log.warning(f"watchdog: emergency checkpoint failed "
                            f"({e!r}); resume falls back to the last "
                            f"cadence save")
            finally:
                self.save_lock.release()


def run(cfg: TrainConfig, logger: Optional[Logger] = None) -> TrainState:
    """Top-level entry (reference ``run``, ``per_run.py:20-66``): set up the
    unique token and sinks, then train (or evaluate and exit)."""
    logger = logger or Logger()
    cfg = sanity_check(cfg)
    token = unique_token(cfg)
    results_dir = os.path.join(cfg.local_results_path, token)
    if cfg.use_tensorboard:
        logger.setup_tb(os.path.join(
            cfg.local_results_path, "tb_logs", token))
    logger.setup_json(results_dir)
    logger.console_logger.info(f"Experiment token: {token}")

    # graftscope telemetry (docs/OBSERVABILITY.md): NULL_RECORDER when
    # obs.enabled is off — every span below is then a shared no-op
    # context and the driver is behaviorally identical to a build
    # without the obs layer. The Logger history cap applies regardless
    # (the unbounded self.stats growth was a bug, not a behavior).
    logger.max_history = cfg.obs.stats_history
    # every span is also a host event of the same name in the
    # profiler's trace, on the profiler's clock (a flag test while no
    # profiler session is running)
    rec = obs_spans.make_recorder(cfg.obs, results_dir,
                                  annotate=jax.profiler.TraceAnnotation)
    # the program's own compile and cache counters (obs/compiles.py):
    # every compilation is booked to the span it happens in, for this
    # run only, and nothing listens with telemetry off
    with obs_compiles.listening(rec):
        # the backend's start and nothing else (a process that has
        # started it — benchmark/run.py looks for its chips first —
        # passes through)
        with rec.span("backend.init"):
            jax.devices()
        with rec.span("setup.build"):
            exp = Experiment.build(cfg)
        # reference dispatch (per_run.py:192): save_animation alone does
        # NOT divert to evaluation — it enables the in-training animation
        # cadence
        if not (cfg.evaluate or cfg.save_replay):
            return run_sequential(exp, logger, results_dir, rec=rec)
    rec.close()                 # eval path records no further spans
    return evaluate_sequential(exp, logger, results_dir)


def run_sequential(exp: Experiment, logger: Logger,
                   results_dir: str,
                   rec=None) -> TrainState:
    """The train loop (reference ``run_sequential``, ``per_run.py:106-289``)."""
    cfg = exp.cfg
    log = logger.console_logger
    # graftscope span recorder (``run`` passes its own; direct callers —
    # tests, evaluate harnesses — get one from the config here)
    if rec is None:
        rec = obs_spans.make_recorder(
            cfg.obs, results_dir, annotate=jax.profiler.TraceAnnotation)
    if sebulba_eligible(cfg):
        # Sebulba decoupled actor/learner loop (docs/PERF.md): disjoint
        # device meshes + device-resident trajectory queue; its own loop
        # shape below — everything past this point is the fused/classic
        # single-set driver
        return run_sebulba(exp, logger, results_dir, rec=rec)
    env_info = exp.env.get_env_info()
    log.info(f"env_info: {env_info}")

    # ---- graftpop population axis (docs/POPULATION.md) -----------------
    # P > 0 vmaps the WHOLE train state over a leading (P,) axis and
    # drives the loop through ONE donated population superstep per
    # iteration — P seed/hyperparameter variants per dispatch. P = 0
    # (default) leaves every program and this loop byte-identical.
    from . import population as graftpop
    P = graftpop.population_size(cfg)
    spec = graftpop.build_spec(cfg) if P else None
    if P:
        log.info(f"graftpop: population of {P} members per dispatch "
                 f"(seeds {graftpop.member_seeds(cfg)}, "
                 f"pbt={'on' if cfg.population.pbt.enabled else 'off'})")

    # ---- graftpulse live telemetry plane (docs/OBSERVABILITY.md §pulse)
    # obs.pulse_port unset (default) leaves all three as no-op/None —
    # the loop below is byte-identical to a build without the plane
    with rec.span("setup.telemetry"):
        pulse = obs_pulse.make_pulse(cfg.obs, rec=rec, log=log)
        mw = obs_memwatch.make_memwatch(cfg.obs, rec=rec)
        mw.snapshot("startup", t_env=0)
        trc = (obs_pulse.TraceController(
                   results_dir, rec=rec,
                   hub=pulse.hub if pulse is not None else None,
                   n_iterations=cfg.profile_iterations)
               if (rec.enabled or pulse is not None) else None)
        # graftsight learning-health monitor (docs/OBSERVABILITY.md §6):
        # None when obs.sight is off — the loop below is byte-identical.
        # The in-graph half already rode the train programs; this is the
        # host detector pass over the log-cadence fetch. Under a
        # population the detectors run PER MEMBER over the (P,)-leading
        # fetched leaves and the /healthz verdicts name pop<i>
        # (sight.PopulationSightMonitor).
        sight_mon = obs_sight.make_monitor(cfg.obs, logger=logger, rec=rec,
                                           population=P)

    # ---- data parallelism (SURVEY.md §7.2(6)) --------------------------
    # dp_devices > 0 swaps in the mesh-sharded program triple; the loop
    # below is identical either way (same pure functions, GSPMD shardings
    # come from input placement — parallel/mesh.py)
    dp = None
    pop_mesh = None
    if cfg.dp_devices and P:
        # population-over-dp (graftlattice): the mesh shards the LEADING
        # (P,) member axis — whole members per device, no cross-member
        # collectives — so the episode-axis DataParallel wrapper (and
        # its divisibility invariant) does not apply. GSPMD shardings
        # come from input placement exactly like classic dp: the stacked
        # state is device_put with population_shardings below and the
        # unchanged vmapped programs propagate the member axis.
        from .parallel import make_mesh, population_shardings
        with rec.span("setup.programs"):
            pop_mesh = make_mesh(cfg.dp_devices)
        log.info(f"population-over-dp: {P} members sharded over "
                 f"{cfg.dp_devices} devices (mesh axis 'data', "
                 f"{P // cfg.dp_devices} members per device)")
    elif cfg.dp_devices:
        from .parallel import DataParallel, make_mesh
        with rec.span("setup.programs"):
            dp = DataParallel(exp, make_mesh(cfg.dp_devices))
        log.info(f"data-parallel over {cfg.dp_devices} devices "
                 f"(mesh axis 'data')")
    # resolve the resume target FIRST: a checkpoint_path pointing at an
    # empty directory (the enable-resume-from-day-one pattern) is still a
    # fresh start and must take the born-sharded init below
    found = None
    if cfg.checkpoint_path:
        with rec.span("setup.restore", stage="find"):
            found = find_checkpoint(cfg.checkpoint_path, cfg.load_step)
        if found is None:
            log.info(f"no checkpoint found in {cfg.checkpoint_path}")
    with rec.span("setup.init_state"):
        if dp is not None and found is None:
            # fresh DP start: build the state BORN sharded
            # (out_shardings) — the single-device-then-reshard path
            # holds a full extra copy of the replay ring at startup, an
            # OOM at config-5 ring sizes
            ts = dp.init_sharded(cfg.seed)
        elif dp is not None:
            # DP resume: restore each leaf straight onto the mesh — the
            # classic init → load → shard sequence re-creates the same
            # single-device ring transient the born-sharded init exists
            # to avoid (ADVICE r5). elastic.resume_state keeps the rigid
            # load_checkpoint_sharded path when the topology stamp
            # matches and routes population/topology changes through
            # restore_elastic (docs/RESILIENCE.md §6).
            shapes = jax.eval_shape(
                lambda: exp.init_train_state(cfg.seed))
            with rec.span("setup.restore", stage="load"):
                ts, _ = elastic.resume_state(found[0], shapes,
                                             dp.state_shardings(shapes),
                                             verify=False,
                                             topology={"loop": "classic"})
        elif P and found is None:
            # population init: P explicit solo inits stacked — member
            # i's leaves are bit-identical to a solo init at seed_i
            ts, spec = graftpop.init_population(exp, cfg)
        elif P:
            # population RESUME: an abstract template only — P concrete
            # inits here would materialize P replay rings just to be
            # discarded by the load below (the ADVICE-r5 init-then-load
            # transient, ×P). The spec stays concrete: a single-member
            # (v4) checkpoint lifting into this template takes its spec
            # from HERE (the config's grids), not from zero-filled avals.
            ts = jax.eval_shape(
                lambda: graftpop.init_population(exp, cfg))[0]
            spec = graftpop.build_spec(cfg)
        else:
            ts = exp.init_train_state(cfg.seed)
        # per-member driver key streams under a population (each
        # member's stream splits exactly like the classic loop's single
        # one)
        key = graftpop.member_keys(cfg) if P else jax.random.PRNGKey(
            cfg.seed + 1)
    # the driver loop replaces its state right after every call, so the
    # replay ring / train state can be donated (in-place on device)
    with rec.span("setup.programs"):
        rollout, insert, train_iter = (dp or exp).jitted_programs(
            donate=True)

    # fused superstep (config.superstep, docs/SPEC.md §8): K > 1 swaps the
    # three-program iteration for ONE donated program scanning K rollout→
    # insert→train iterations per dispatch; the rollout program above
    # still serves the test/animation cadences. A population ALWAYS
    # drives through the (vmapped) fused program, even at K=1 — one
    # donated dispatch advances all P members. The builder is shared
    # with the degradation ladder's K→1 rung.
    def _build_superstep(k):
        if P:
            return exp.population_superstep_program(k, donate=True)
        return (dp or exp).superstep_program(k, donate=True)

    K = cfg.superstep if superstep_eligible(cfg) else 1
    pop_test = None
    with rec.span("setup.programs"):
        if P:
            K = max(cfg.superstep, 1)
            superstep = _build_superstep(K)
            pop_test = exp.population_rollout_program()
            log.info(f"population superstep: {P} members x {K} "
                     f"iterations per dispatch")
        else:
            superstep = _build_superstep(K) if K > 1 else None
        if cfg.superstep > 1 and K == 1:
            log.info("superstep requested but ineligible (buffer_cpu_only "
                     "keeps the three-program path)")
        elif K > 1 and not P:
            log.info(f"fused superstep: {K} iterations per dispatch")
        # (imports the kernel modules the first trace will need)
        log.info(exp.mac.describe_acting(cfg.batch_size_run,
                                         jax.default_backend()))

    def _ckpt_state():
        """What checkpoints hold: the bare TrainState classically, the
        (state, spec) PopState under a population (the spec is
        PBT-mutable and must resume with the members it shaped)."""
        return graftpop.PopState(ts=ts, spec=spec) if P else ts

    t_env = 0
    # ---- resume (reference :159-189, Q13: t_env cursor restored) ----
    if found is not None:
        dirname, step = found
        if P:
            # population resume: the checkpoint is a PopState (or a
            # v4 single-member state the migration shim lifts to
            # P=stacked — utils/checkpoint._migrate_raw). A stamped
            # P-mismatch (grow/shrink since the save) routes through
            # restore_elastic via elastic.resume_state.
            with rec.span("setup.restore", stage="load"):
                ps, _ = elastic.resume_state(dirname, _ckpt_state(),
                                             verify=False,
                                             topology={"loop": "classic"})
            ts, spec = ps.ts, ps.spec
        elif dp is None:
            # find_checkpoint already hashed this candidate — skip
            # re-verify (the DP path restored sharded above)
            with rec.span("setup.restore", stage="load"):
                ts, _ = elastic.resume_state(dirname, ts, verify=False,
                                             topology={"loop": "classic"})
        t_env = step
        new_t = (jnp.full((P,), step, jnp.int32) if P
                 else jnp.asarray(step, jnp.int32))
        if dp is not None:
            # keep the canonical replicated placement — a fresh
            # single-device scalar here would hand the first dispatch a
            # different input aval than every later iteration
            new_t = jax.device_put(new_t, ts.runner.t_env.sharding)
        ts = ts.replace(runner=ts.runner.replace(t_env=new_t))
        log.info(f"resumed from {dirname} at t_env={step}")

    if pop_mesh is not None:
        # population-over-dp placement: shard every leaf (state AND
        # spec) on the leading member axis. Fresh and resumed states
        # both route through here — the single device_put is the whole
        # parallelization, because the vmapped programs are rank-
        # polymorphic over placement (GSPMD propagates the member
        # sharding through the batched graph). Members never
        # communicate: control state matches replication bit-exactly,
        # floats at ULP scale (partitioning retiles batched reduces —
        # see parallel/mesh.py population_shardings).
        ts = jax.device_put(ts, population_shardings(pop_mesh, ts))
        spec = jax.device_put(spec, population_shardings(pop_mesh, spec))

    model_dir = os.path.join(cfg.local_results_path, "models",
                             os.path.basename(results_dir))

    # ---- resilience (docs/RESILIENCE.md) -------------------------------
    res = cfg.resilience
    # SIGTERM/SIGINT → flag; the loop polls it once per iteration and
    # performs the orderly exit below (emergency checkpoint + exit 0)
    guard = (resilience.ShutdownGuard.install() if res.handle_signals
             else resilience.ShutdownGuard())
    nonfinite_streak = 0            # consecutive tripped train steps
    nonfinite_total = 0
    restores = 0                    # guard-triggered checkpoint restores
    # coordinated preemption (docs/RESILIENCE.md §6): once the guard
    # trips, every host negotiates ONE cut step (stop_at); stop_ok=False
    # means a peer died mid-negotiation and the exit path must degrade
    # to the per-host shard save (no collectives over a corpse)
    stop_at = None
    stop_ok = True

    def _save_topology():
        """The topology stamp every save carries (meta.json) — what a
        later resume compares its own shape against. The member ranking
        (best first, from the host-side EMA returns when every member
        has one) is what an elastic population SHRINK keeps."""
        topo = {"loop": "classic"}
        if dp is not None or pop_mesh is not None:
            topo["mesh_shape"] = [int(cfg.dp_devices)]
        if P:
            ema = getattr(train_acc, "member_return_ema", None)
            if ema and all(v is not None for v in ema):
                topo["member_ranking"] = sorted(
                    range(P), key=lambda m: ema[m], reverse=True)
        return topo

    # ---- hang detection + degradation ladder (RESILIENCE.md §5) --------
    # The watchdog's stall callback runs in the WATCHDOG thread — the main
    # thread is blocked inside the stalled call — so the emergency
    # checkpoint comes from the pre-dispatch state stamped with the
    # heartbeat: complete and consistent, because the dispatch that would
    # have superseded it never finished. A stall during the checkpoint
    # write itself skips the save (the staging directory is in use by the
    # stalled writer); donated-and-consumed state is skipped too (its
    # buffers are gone — resume falls back to the last cadence save).
    # serializes the watchdog thread's emergency save against the main
    # thread's cadence/exit saves: both stage into the same tmp.<t_env>
    # directory, and a bounded wd.stop() join can hand control back to
    # the main thread while the watchdog's save is still mid-write
    save_lock = threading.Lock()

    # graftlattice shared driver kit: the flight-persist, save-lock,
    # stall-response, watchdog-stamp and fault-handled-dispatch bodies
    # shared with run_sebulba (_DriverKit above) — bound to the local
    # names every call site (and graftlint GL110's name-keyed phase
    # check) keys on. The classic loop's shape: one armed watchdog
    # stamps every device-facing region by default, and the loop's own
    # t_env cursor threads into every stamp/span.
    kit = _DriverKit(cfg=cfg, res=res, log=log, rec=rec, mw=mw,
                     sight_mon=sight_mon, guard=guard,
                     model_dir=model_dir, save_lock=save_lock,
                     P=P, spec_fn=lambda: spec)
    kit.t_env_fn = lambda: t_env
    _persist_flight = kit.persist_flight
    _acquire_save_lock = kit.acquire_save_lock
    _on_stall = kit.stall_response

    wd = None
    if res.dispatch_timeout > 0:
        wd = watchdog.Watchdog(
            res.dispatch_timeout, on_stall=_on_stall,
            grace_s=res.stall_grace_s, exit_code=res.stall_exit_code,
            first_timeout_s=res.first_dispatch_timeout).start()
        log.info(f"dispatch watchdog armed: timeout="
                 f"{res.dispatch_timeout}s (first occurrence of each "
                 f"phase: {res.first_dispatch_timeout or 'unbounded'}, "
                 f"compile exemption), hard-exit grace="
                 f"{res.stall_grace_s}s (exit {res.stall_exit_code})")
    # arm the kit: bare _watched/_dispatch calls stamp this watchdog
    kit.default_wd = wd
    ladder = watchdog.DegradationLadder(res.max_restores)
    if pulse is not None:
        # live health/heartbeat surface: the watchdog rows are read per
        # scrape (visible while the main thread is wedged), and
        # /healthz flips to degraded the moment a stall fires or the
        # shutdown guard trips
        if wd is not None:
            pulse.wire_watchdog(wd)
        pulse.wire_guard(guard)
        pulse.set("superstep_k", K)
        pulse.set("backend_info", 1, backend=jax.default_backend())
        if sight_mon is not None:
            # one /healthz check per RL-health detector: the endpoint
            # flips 503 naming the verdict (sight-<detector>) the
            # moment the host pass trips it
            sight_mon.wire_pulse(pulse.hub)

    # one watchdog stamp + graftscope span per device-facing region
    # (_DriverKit.watched: wd-None guard, t_env threading, PopState
    # wrap, telemetry pairing — shared with run_sebulba)
    _watched = kit.watched

    last_test_t = t_env - cfg.test_interval - 1
    last_log_t = t_env
    last_save_t = t_env if t_env else -cfg.save_model_interval - 1
    start_time = last_time = time.time()
    last_log_time = None     # set at the first flush: the first window is
    # dominated by the rollout/train compiles (~30s+ on chip) and would
    # log a wildly-low throughput outlier
    start_t = last_T = t_env
    n_test_runs = max(1, cfg.test_nepisode // cfg.batch_size_run)
    # Q10 rounded quota; a population tests all P members per dispatch,
    # so the accumulator's total-episode quota scales by P
    test_quota = n_test_runs * cfg.batch_size_run * max(P, 1)
    train_infos = []
    # terminal-info stat accumulation (reference parallel_runner.py:202-231;
    # population=P adds the per-member pop<i>_* aggregation on the same
    # fold fetch — utils/stats.py)
    train_acc = StatsAccumulator(population=P)
    test_acc = StatsAccumulator(population=P)
    last_runner_log_t = t_env
    # in-training animation cadence (reference per_run.py:258-263)
    last_anim_t = -cfg.animation_interval - 1
    er_rs = None
    # tracing/profiling (SURVEY.md §5(1)): per-stage wall-clock into the
    # metric stream + optional jax.profiler trace window over the hot loop
    timer = StageTimer()
    tracer = TraceWindow(cfg.profile_dir, cfg.profile_start,
                         cfg.profile_iterations)
    # run header for the report CLI: the shapes that scale graftprog's
    # audit-config budgets to this run (obs/report.py). Set-up ends and
    # the loop starts here: the mark carries what the process has
    # compiled so far (obs/compiles.py)
    if rec.enabled:
        from .envs.registry import scenario_config
        rec.mark("run", t_env=t_env, backend=jax.default_backend(),
                 batch_size_run=cfg.batch_size_run,
                 episode_limit=cfg.env_args.episode_limit,
                 batch_size=cfg.batch_size, superstep=K,
                 host_buffer=exp.host_buffer, population=P,
                 scenario=scenario_config(cfg.env_args).kind,
                 **rec.totals())
    # per-stage barriers for honest attribution; tracing implies them
    # (an un-synced trace window would capture dispatch, not execution)
    sync_stages = cfg.profile_stages or bool(cfg.profile_dir)

    # ---- async dispatch ------------------------------------------------
    # Every control scalar of this loop evolves deterministically: the
    # rollout scan always runs episode_limit slots (termination is
    # time-limit-only, envs/mec_offload.py step), so t_env advances by
    # exactly B·T per train rollout; the episode counter by B; the replay
    # ring fill by min(+B, capacity). Tracking them host-side removes
    # every blocking device→host fetch from the loop body, which would
    # otherwise serialize the driver on the host link.
    # The loop then only blocks at its natural cadences (stat flush, log,
    # test, checkpoint), letting the host enqueue ahead of the device.
    steps_per_rollout = cfg.batch_size_run * cfg.env_args.episode_limit

    episode = _host_int(ts.episode)                    # restored on resume
    buffer_filled = (0 if exp.host_buffer else
                     _host_int(ts.buffer.episodes_in_buffer))
    buffer_capacity = 0 if exp.host_buffer else exp.buffer.capacity
    inflight = deque()              # rollout outputs not yet waited on

    # ---- fault-handled dispatch + ladder plumbing (RESILIENCE.md §5) ---
    # one device-facing dispatch: fault-injection hook + watchdog
    # heartbeat + bounded in-place retry (ladder rung 0) — shared body
    # in _DriverKit.dispatch; transient-failure counts accumulate in
    # kit.dispatch_faults for the log cadence below
    _dispatch = kit.dispatch

    def _restore_checkpoint(dirname, step):
        """Reload a published checkpoint and re-sync every host-side
        mirror of device state — shared by the non-finite escalation and
        the degradation ladder's restore rung."""
        nonlocal ts, t_env, episode, buffer_filled, train_infos
        nonlocal last_test_t, last_log_t, last_runner_log_t, last_save_t
        nonlocal nonfinite_streak, train_acc, spec
        if P:
            # population restore: the checkpoint holds a PopState; the
            # live ts only contributes structure/shape metadata
            ps = load_checkpoint(dirname, _ckpt_state(), verify=False)
            ts, spec = ps.ts, ps.spec
            new_t = jnp.full((P,), step, jnp.int32)
            if pop_mesh is not None:
                # re-shard onto the member axis (same placement as the
                # startup path — a single-device restore mid-run would
                # hand the next dispatch differently-placed inputs)
                ts = jax.device_put(ts, population_shardings(pop_mesh,
                                                             ts))
                spec = jax.device_put(
                    spec, population_shardings(pop_mesh, spec))
                new_t = jax.device_put(new_t,
                                       ts.runner.t_env.sharding)
        elif dp is not None:
            # same born-sharded restore as the resume path: the live ts
            # only contributes shape metadata (its donated leaves may
            # already be deleted), and the single-device load → shard
            # sequence would re-create the ring OOM mid-run (ADVICE r5)
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), ts)
            ts = load_checkpoint_sharded(dirname, shapes,
                                         dp.state_shardings(shapes),
                                         verify=False)
            new_t = jax.device_put(jnp.asarray(step, jnp.int32),
                                   ts.runner.t_env.sharding)
        else:
            ts = load_checkpoint(dirname, ts, verify=False)
            new_t = jnp.asarray(step, jnp.int32)
        ts = ts.replace(runner=ts.runner.replace(t_env=new_t))
        # re-sync every host-side mirror of device state
        t_env = step
        episode = _host_int(ts.episode)
        if not exp.host_buffer:
            buffer_filled = _host_int(ts.buffer.episodes_in_buffer)
        inflight.clear()
        train_infos = []
        # the restored state predates whatever streak was counted — a
        # stale streak would double-count the replayed steps (the ladder
        # restore shares this path, not just the non-finite escalation)
        nonfinite_streak = 0
        # drop pending stats too: their device refs belong to the
        # rolled-back (possibly poisoned) computation, and the replayed
        # iterations will re-push them — flushing the stale ones would
        # both double-count episodes and re-raise the fault at the next
        # cadence fetch, outside any routing. The fetch tally survives
        # the reset: stat_fetches is logged as a cumulative round-trip
        # counter and must not go backwards across a restore
        fetches = train_acc.fetches
        train_acc = StatsAccumulator(population=P)
        train_acc.fetches = fetches
        if exp.host_buffer:
            # same hazard for the host-replay deferred priority refs:
            # they came from the rolled-back train step
            exp.buffer.drop_pending_update()
        last_test_t = last_log_t = t_env
        last_runner_log_t = last_save_t = t_env

    def _dispatch_ladder(df: watchdog.DispatchFailed,
                         can_degrade: Optional[bool] = None) -> None:
        """Rungs above in-place retry: superstep K→1 (smaller blast
        radius), restore the last good checkpoint, abort with the
        captured diagnosis. Mutates the loop shape; callers ``continue``
        after it returns (their host mirrors were never committed, so the
        abandoned dispatch leaves no trace). Pass ``can_degrade=False``
        from boundaries where degrading cannot help — a failure surfacing
        at a sync/fetch point means the already-dispatched computation
        (or its output state) is suspect, so only restore can stand."""
        nonlocal K, superstep
        # a dispatch whose donated inputs were consumed mid-failure left
        # ts unusable — degrading and continuing would dereference
        # deleted arrays; only the restore rung can stand on it (the
        # deleted leaves still carry shape metadata, which is all the
        # load_checkpoint template needs)
        if can_degrade is None:
            can_degrade = (K > 1 and res.degrade_superstep
                           and watchdog.state_intact(ts))
        action = ladder.next_action(can_degrade=can_degrade)
        logger.log_stat("dispatch_failures", ladder.failures, t_env)
        # ladder actions are span-stream events too: the flight tail
        # then shows retry exhaustion -> rung taken in causal order
        rec.mark("ladder", action=action, phase=df.phase, t_env=t_env,
                 failures=ladder.failures)
        if action == "degrade":
            log.warning(f"degradation ladder: {df} — falling back "
                        f"superstep K={K} -> 1 ({ladder.describe()})")
            K = 1
            # a population still drives through the (vmapped) fused
            # program — rebuild it at K=1 instead of dropping to the
            # three-program path, which has no population rank
            superstep = _build_superstep(1) if P else None
            logger.log_stat("superstep_k", 1, t_env)
            return
        if action == "restore":
            good = find_checkpoint(model_dir) if cfg.save_model else None
            if good is not None:
                log.warning(f"degradation ladder: {df} — restoring last "
                            f"good checkpoint {good[0]} "
                            f"({ladder.describe()})")
                _restore_checkpoint(*good)
                return
            # no checkpoint to stand on: fall through to abort
        # abort rung: persist the flight tail next to the checkpoints
        # (the stall-diagnosis merge covers hangs; this covers failures)
        _persist_flight(os.path.join(model_dir, "flight_recorder.json"))
        # consume the stall diagnosis only on abort: a degrade/restore
        # rung leaves it for the guard-triggered exit log (the causal
        # "stalled call eventually returned" chain) or a later abort
        diag = wd.take_diagnosis() if wd is not None else None
        raise RuntimeError(
            f"dispatch failure exhausted the degradation ladder at "
            f"t_env={t_env} ({ladder.describe()})"
            + (f"; stall diagnosis: {diag.message()}" if diag else "")
            + ("" if cfg.save_model else
               "; no checkpoints exist to restore (save_model off)")
            + f" — last failure: {df}") from df

    def _sync_point(phase, fn, **context):
        """One blocking sync/fetch boundary (run-ahead wait, cadence
        stat fetch) — shared body in ``_DriverKit.sync_point``. Stays a
        local def (not a bare bound method) so the stamp always carries
        the loop's CURRENT ``ts``: the state local is rebound across
        restores and donated dispatches, and an early capture would
        stamp deleted buffers."""
        return kit.sync_point(phase, fn, ts, **context)

    # signal handlers are process-global state: restore them on
    # EVERY exit (normal, preemption, divergence abort)
    try:
        while t_env <= cfg.t_max:
            # fault-injection hook + preemption poll (docs/RESILIENCE.md):
            # the signal handler only sets a flag; the orderly exit —
            # emergency checkpoint, resume hint, exit 0 — happens here, at an
            # iteration boundary where ts is a complete consistent state.
            # Under superstep K>1 this is a DISPATCH boundary: the poll,
            # every cadence, and every checkpoint land between fused
            # dispatches, so a preemption loses at most K iterations and a
            # restored checkpoint always resumes at a K-aligned t_env
            resilience.fire("driver.iteration", t_env=t_env, guard=guard,
                            ts=ts, key=key, train_infos=train_infos)
            # coordinated preemption (docs/RESILIENCE.md §6): propagate a
            # PEER's announced shutdown into the local guard, then
            # negotiate the one cut step all hosts share. Hosts behind
            # the consensus keep stepping (lockstep dp trajectories make
            # every host's t_env reach stop_at) so the collective
            # emergency save below runs at one t_env on every host.
            if not guard.triggered and dist.peer_shutdown_requested():
                guard.request("peer")
            if guard.triggered:
                if stop_at is None:
                    dist.announce_shutdown(t_env)
                    with rec.span("preempt.barrier", t_env=t_env):
                        stop_at, stop_ok = dist.negotiate_stop_step(
                            t_env, res.preempt_barrier_timeout_s)
                if not stop_ok or t_env >= stop_at:
                    break
            if pulse is not None:
                pulse.tick_iteration(t_env, episode)
            if trc is not None:
                # on-demand trace trigger (PULSE_TRACE file / /trace
                # endpoint): one os.path.exists when idle
                trc.poll(t_env)
            tracer.maybe_start(t_env)
            if superstep is not None:
                # ------------ fused superstep (one dispatch = K iters) ------
                # mirror the control scalars host-side for each of the K
                # sub-iterations: they evolve deterministically (see the
                # async-dispatch note above), so the host knows exactly
                # which sub-iterations train — it splits the driver key
                # stream ONLY for those (bit-identical threading to the
                # K=1 loop's conditional split) and keeps their stacked
                # info rows, dropping the zero rows of skipped ones.
                # Computed from snapshots and COMMITTED only after the
                # dispatch succeeds: an in-place retry (or a ladder rung
                # abandoning this dispatch) replays the identical key
                # stream, preserving bit-parity with the K=1 loop.
                # Under a population (P > 0) `key` is a LIST of P member
                # streams: the gate mirror is computed ONCE (the
                # counters evolve identically across members) and each
                # member's stream splits exactly like the classic
                # loop's single one — member 0's consumed stream IS the
                # solo run's, the bit-parity contract.
                # host work between the boundary and the dispatch (the key
                # splits enqueue tiny device programs; nothing blocks)
                with rec.span("driver.prepare", t_env=t_env):
                    ep2, fill2 = episode, buffer_filled
                    key2 = list(key) if P else key
                    key_rows, gated = [], []
                    for _ in range(K):
                        ep2 += cfg.batch_size_run
                        fill2 = min(fill2 + cfg.batch_size_run,
                                    buffer_capacity)
                        g = (fill2 >= cfg.batch_size
                             and ep2 >= cfg.accumulated_episodes)
                        gated.append(g)
                        if P:
                            if g:
                                row = []
                                for m in range(P):
                                    key2[m], k_s = jax.random.split(key2[m])
                                    row.append(k_s)
                                key_rows.append(jnp.stack(row))
                            else:
                                key_rows.append(jnp.zeros(
                                    (P,) + key2[0].shape, key2[0].dtype))
                        elif g:
                            key2, k_sample = jax.random.split(key2)
                            key_rows.append(k_sample)
                        else:
                            key_rows.append(jnp.zeros_like(key2))
                def _fused(ts=ts, key_rows=key_rows):
                    if P:
                        # (P, K, 2) — the vmapped program maps axis 0,
                        # each member scanning its own (K,) key rows
                        keys = jnp.stack(key_rows, axis=1)
                        if pop_mesh is not None:
                            # member-axis placement for the key stack
                            # too: the dispatched program must see the
                            # same input shardings as the audited
                            # pop_dp_superstep twin (a replicated key
                            # input would lower a different SPMD
                            # program than the one ratcheted)
                            keys = jax.device_put(
                                keys, population_shardings(pop_mesh,
                                                           keys))
                        ts2, stats, infos = superstep(
                            ts, keys, jnp.asarray(t_env), spec)
                    else:
                        ts2, stats, infos = superstep(
                            ts, jnp.stack(key_rows), jnp.asarray(t_env))
                    if sync_stages:
                        # inside the dispatched fn so the barrier (where
                        # a device-side wedge actually surfaces) is
                        # covered by the watchdog stamp + retry, like
                        # _roll/_train_once below
                        jax.block_until_ready(stats.epsilon)
                    return ts2, stats, infos
                try:
                    with timer.stage("superstep"):
                        ts, stats, infos = _dispatch("dispatch.superstep",
                                                     _fused, ts, k=K)
                except watchdog.DispatchFailed as df:
                    _dispatch_ladder(df)
                    continue
                with rec.span("driver.account", t_env=t_env):
                    key, episode, buffer_filled = key2, ep2, fill2
                    t_env += K * steps_per_rollout
                    for i, g in enumerate(gated):
                        if g:
                            # population infos carry the leading (P,)
                            # member axis; the scan's (K,) axis is the
                            # next one
                            train_infos.append(jax.tree.map(
                                (lambda x, i=i: x[:, i]) if P
                                else (lambda x, i=i: x[i]), infos))
            else:
                # ------------ rollout (no grad by construction) -------------
                def _roll(ts=ts):
                    rs, batch, stats = rollout(ts.learner.params["agent"],
                                               ts.runner, test_mode=False)
                    ts = ts.replace(runner=rs,
                                    buffer=insert(ts.buffer, batch),
                                    episode=ts.episode + cfg.batch_size_run)
                    if sync_stages:
                        jax.block_until_ready(rs.t_env)
                    return ts, stats
                try:
                    with timer.stage("rollout"):
                        # host-buffer rollouts insert into host RAM
                        # inside fn — not replayable in place
                        ts, stats = _dispatch("dispatch.rollout", _roll,
                                              ts,
                                              retryable=not exp.host_buffer)
                except watchdog.DispatchFailed as df:
                    _dispatch_ladder(df)
                    continue
                t_env += steps_per_rollout
                episode += cfg.batch_size_run
                buffer_filled = min(buffer_filled + cfg.batch_size_run,
                                    buffer_capacity)

                # ------------ train gate (reference :220-238) ---------------
                if exp.host_buffer:
                    can = exp.buffer.can_sample(cfg.batch_size)
                else:
                    can = buffer_filled >= cfg.batch_size
                if can and episode >= cfg.accumulated_episodes:
                    with rec.span("driver.prepare", t_env=t_env):
                        key2, k_sample = jax.random.split(key)

                    # NB: not named `_train` — graftlint's traced-region
                    # discovery is name-keyed per module, and `_train` is
                    # the lax.cond branch inside superstep_program
                    def _train_once(ts=ts, k_sample=k_sample):
                        ts, info = train_iter(ts, k_sample,
                                              jnp.asarray(t_env))
                        if sync_stages:
                            jax.block_until_ready(info["loss"])
                        return ts, info
                    try:
                        with timer.stage("train"):
                            # host-buffer sampling advances the host RNG
                            # inside fn — not replayable in place
                            ts, info = _dispatch(
                                "dispatch.train", _train_once, ts,
                                retryable=not exp.host_buffer)
                    except watchdog.DispatchFailed as df:
                        _dispatch_ladder(df)
                        continue
                    key = key2
                    train_infos.append(info)
            # shared accounting for both loop shapes: ONE stats push per
            # dispatch (per-rollout (B,) or stacked (K, B) — the
            # accumulator flattens), then the dispatch run-ahead bound:
            # block on the dispatch from two back (TPU executes in
            # dispatch order, so this caps live episode batches while
            # still double-buffering host↔device)
            # the accumulator push folds with a blocking device fetch
            # every FOLD_EVERY rollouts — a sync point like any other
            try:
                _sync_point("fetch.train_stats",
                            lambda: train_acc.push(stats))
            except watchdog.DispatchFailed as df:
                _dispatch_ladder(df, can_degrade=False)
                continue
            inflight.append(stats.epsilon)
            if len(inflight) > 2:
                # the steady-state blocking point of the async loop: a
                # device-side wedge surfaces HERE, not at the dispatch
                # call
                try:
                    _sync_point("dispatch.wait",
                                lambda: jax.block_until_ready(
                                    inflight.popleft()))
                except watchdog.DispatchFailed as df:
                    _dispatch_ladder(df, can_degrade=False)
                    continue
            tracer.tick(logger, t_env)
            if trc is not None:
                trc.tick(logger, t_env)

            # train-stat cadence: runner_log_interval, epsilon alongside
            # (reference parallel_runner.py:215-219). Deliberately after the
            # train dispatch: at configs where B·T ≥ the interval this flush
            # fires every iteration, and its blocking stat fetch then overlaps
            # the already-enqueued train step instead of serializing it.
            if t_env - last_runner_log_t >= cfg.runner_log_interval:
                def _flush_train_stats():
                    train_acc.flush(logger, t_env)
                    # cached by the flush's own fold — no second fetch
                    logger.log_stat("epsilon", train_acc.epsilon, t_env)
                try:
                    _sync_point("fetch.train_stats", _flush_train_stats)
                except watchdog.DispatchFailed as df:
                    _dispatch_ladder(df, can_degrade=False)
                    continue
                last_runner_log_t = t_env

            # ---------------- test cadence (reference :240-256) ----------------
            if (t_env - last_test_t) / cfg.test_interval >= 1.0:
                log.info(f"t_env: {t_env} / {cfg.t_max}")
                log.info(
                    f"Estimated time left: "
                    f"{time_left(last_time, last_T, t_env, cfg.t_max)}. "
                    f"Time passed: {time_str(time.time() - start_time)}")
                last_time, last_T = time.time(), t_env

                try:
                    with timer.stage("test"):
                        for _ in range(n_test_runs):
                            # one _dispatch per rollout (stamp + hook +
                            # retry) — a single stamp spanning all
                            # n_test_runs (plus the flush's sink I/O)
                            # would overrun a per-dispatch-sized timeout
                            # on a perfectly healthy test cadence
                            def _test_roll(ts=ts):
                                if P:
                                    # vmapped greedy rollout: every
                                    # member evaluates in the SAME
                                    # dispatch (the population cost
                                    # profile — never P fetches)
                                    return pop_test(
                                        ts.learner.params["agent"],
                                        ts.runner)
                                rs, _, s = rollout(
                                    ts.learner.params["agent"], ts.runner,
                                    test_mode=True)
                                return rs, s
                            rs, s = _dispatch("dispatch.test", _test_roll,
                                              ts)
                            if pop_mesh is not None:
                                # pin the runner back to the member-axis
                                # placement (no-op when GSPMD already
                                # propagated it): the next superstep's
                                # input shardings must not drift with
                                # XLA's output-sharding choices
                                rs = jax.device_put(
                                    rs, population_shardings(pop_mesh,
                                                             rs))
                            ts = ts.replace(runner=rs)
                            # the push's periodic device fold is a
                            # blocking fetch like the train-side one —
                            # stamped + routed the same way (its
                            # DispatchFailed lands in the except below)
                            _sync_point("fetch.test_stats",
                                        lambda s=s: test_acc.push(s))
                            # Q10: flush only on the exact rounded quota
                            # (the flush fetch is a sync point; its
                            # DispatchFailed lands in the except below)
                            if test_acc.n_episodes == test_quota:
                                _sync_point(
                                    "fetch.test_stats",
                                    lambda: test_acc.flush(logger, t_env,
                                                           prefix="test_"))
                except watchdog.DispatchFailed as df:
                    # drop the partial cadence: a leftover sub-quota
                    # accumulation would miss the exact-quota flush on
                    # every later cadence; degrading can't help a test
                    # rollout, only restore can
                    test_acc = StatsAccumulator(population=P)
                    _dispatch_ladder(df, can_degrade=False)
                    continue
                last_test_t = t_env

            # ---------------- animation cadence (reference :258-263) -----------
            if (cfg.save_animation
                    and (t_env - last_anim_t) / cfg.animation_interval >= 1.0):
                er = exp.episode_runner
                if er_rs is None:
                    er_rs = er.init_state(jax.random.PRNGKey(cfg.seed + 3))
                er_rs, _, _, traj = er.run(ts.learner.params["agent"], er_rs,
                                           test_mode=True,
                                           capture_trajectory=True)
                p = er.save_animation(
                    traj, os.path.join(results_dir, f"animation_{t_env}.gif"))
                if p:
                    log.info(f"animation saved to {p}")
                last_anim_t = t_env

            # ---------------- save cadence (reference :265-279) ----------------
            if cfg.save_model and (t_env - last_save_t) >= cfg.save_model_interval:
                # watchdog covers the (possibly multi-host-collective)
                # write; transient gather/filesystem faults retry with
                # backoff — deterministic errors still propagate. The
                # stamp wraps EACH attempt, not the whole retry loop: a
                # dispatch_timeout sized for one save must not be eaten
                # by attempt 1's failure + backoff sleep and then
                # misdiagnose a succeeding attempt 2 as a stall
                def _save_once():
                    with _watched("checkpoint.save", ts):
                        # this cadence runs in the tail of the very
                        # iteration whose stall fired it (the guard poll
                        # at the loop top comes later) — the watchdog's
                        # emergency save may well hold the lock
                        if not _acquire_save_lock("save cadence"):
                            return None
                        try:
                            # population checkpoints hold the PopState
                            # (stacked state + the PBT-mutable spec)
                            return save_checkpoint(
                                model_dir, t_env, _ckpt_state(),
                                gather_retries=res.dispatch_retries,
                                gather_backoff_s=res.retry_backoff_s,
                                topology=_save_topology())
                        finally:
                            save_lock.release()
                # retry only single-process: in multi-host the save is a
                # lockstep collective sequence, and a ONE-SIDED transient
                # failure (say process 0's file write) retried on that
                # process alone would re-enter barriers its peers already
                # left — deadlock or a cross-step checkpoint. Symmetric
                # transport faults are retried one level down (the
                # per-leaf allgather in utils/checkpoint.py, in lockstep).
                save_to = watchdog.retry_call(
                    _save_once,
                    attempts=(1 + res.dispatch_retries
                              if jax.process_count() == 1 else 1),
                    backoff_s=res.retry_backoff_s,
                    label="checkpoint.save")
                if save_to is not None:
                    log.info(f"Saving models to {save_to}")
                    if res.keep_last:
                        prune_checkpoints(model_dir, res.keep_last,
                                          res.keep_every)
                    # checkpoint gather is a transient-HBM event worth
                    # a memwatch boundary of its own (no-op when off)
                    mw.snapshot("checkpoint.save", t_env=t_env)
                    # advance the cadence only on a real save: a
                    # lock-skipped attempt (None) retries next iteration
                    # instead of silently widening the data-loss window
                    # by a full save interval right after a stall event
                    last_save_t = t_env
                    if P and cfg.population.pbt.enabled:
                        # PBT exploit/explore (docs/POPULATION.md): at
                        # save boundaries ONLY, after the save — the
                        # published checkpoint holds the pre-PBT
                        # population, so a restored run is self-
                        # consistent (it re-warms the host-side
                        # ranking EMA from fresh flushes and may no-op
                        # this boundary rather than replay it — the
                        # EMA is deliberately not checkpointed). The
                        # ranking signal is the accumulator's
                        # per-member return EMA (riding the existing
                        # fold fetch — the only device work here is
                        # pbt_step's one gather when members copy).
                        ts, spec, pbt_info = graftpop.pbt_step(
                            cfg, ts, spec,
                            train_acc.member_return_ema, t_env)
                        if pbt_info is not None:
                            logger.log_stat("pbt_copies",
                                            len(pbt_info["copied"]),
                                            t_env)
                            rec.mark("pbt", t_env=t_env, **pbt_info)
                            log.info(f"graftpop PBT: exploited "
                                     f"{pbt_info['copied']} at "
                                     f"t_env={t_env}")

            # ---------------- log cadence (reference :283-286) ------------------
            if (t_env - last_log_t) >= cfg.log_interval:
                if train_infos:
                    # non-finite guard escalation: ONE blocking fetch for all
                    # flags since the last cadence — the async dispatch
                    # pipeline never syncs per train step. Deliberately after
                    # the save cadence: the checkpoint written just above
                    # (params finite by construction — tripped steps are
                    # no-ops) is the state the restore wants.
                    # ONE stamped region for the whole cadence fetch
                    # (flags + the last info row): a wedge surfacing at
                    # either device_get must fire the watchdog, and a
                    # transient error routes through the ladder (the
                    # fetched-from state is suspect — restore only)
                    def _fetch_infos():
                        flags = np.asarray(jax.device_get(
                            [i["all_finite"] for i in train_infos]))
                        return flags, jax.device_get(train_infos[-1])
                    try:
                        flags, last = _sync_point("fetch.train_infos",
                                                  _fetch_infos,
                                                  train_infos=train_infos)
                    except watchdog.DispatchFailed as df:
                        _dispatch_ladder(df, can_degrade=False)
                        continue
                    # host-only from here: the fetch above was the
                    # blocking part
                    with rec.span("driver.log", t_env=t_env):
                        if P:
                            # (n, P) member flags: a train step counts as
                            # finite only when EVERY member's update was —
                            # one poisoned member is a restore-worthy event
                            # exactly like a solo NaN (the stacked state is
                            # one checkpoint)
                            flags = flags.reshape(len(train_infos), -1)\
                                         .all(axis=1)
                        for ok in flags:
                            if ok:
                                nonfinite_streak = 0
                            else:
                                nonfinite_streak += 1
                                nonfinite_total += 1
                        if not flags.all():
                            logger.log_stat("nonfinite_steps", nonfinite_total,
                                            t_env)
                            # non-finite trip: event + flight persist, so a
                            # later divergence abort has the phase history
                            # leading up to the first trip on disk already
                            rec.mark("nonfinite", t_env=t_env,
                                     streak=nonfinite_streak,
                                     total=nonfinite_total)
                            _persist_flight(os.path.join(
                                results_dir, "flight_recorder.json"))
                            log.warning(
                                f"non-finite loss/grads in "
                                f"{int((~flags).sum())}/{len(flags)} train "
                                f"steps "
                                f"since last log (streak={nonfinite_streak}, "
                                f"total={nonfinite_total}); parameter updates "
                                f"were skipped")
                        for k in ("loss", "grad_norm", "td_error_abs",
                                  "q_taken_mean", "target_mean"):
                            if P:
                                # aggregate row = population mean; per-
                                # member rows (pop<i>_*) only at P > 1 so a
                                # P=1 run keeps the solo metric stream
                                v = np.asarray(last[k], np.float64)
                                logger.log_stat(k, float(v.mean()), t_env)
                                if P > 1:
                                    for m in range(P):
                                        logger.log_stat(f"pop{m}_{k}",
                                                        float(v[m]), t_env)
                            else:
                                logger.log_stat(k, float(last[k]), t_env)
                        # a catalog trunk's routing counters of the last
                        # update (models/trunk.moe_counters), same fetch
                        for k in sorted(last):
                            if k.startswith("moe_"):
                                logger.log_stat(
                                    k, float(np.mean(last[k])), t_env)
                        if sight_mon is not None:
                            # graftsight detector pass over the SAME fetched
                            # info (no extra device traffic; the monitor
                            # logs the sight_* stats at full fidelity). A
                            # fresh trip persists the flight ring like a
                            # non-finite trip does — the post-mortem then
                            # carries the verdict even if the run dies later
                            with rec.span("sight.detect", t_env=t_env):
                                trips = sight_mon.observe(last, t_env)
                            if trips:
                                log.warning(
                                    f"graftsight: detector(s) tripped at "
                                    f"t_env={t_env}: {', '.join(trips)} — "
                                    f"/healthz degraded; run `python -m "
                                    f"t2omca_tpu.obs learning "
                                    f"{results_dir}` for the read")
                                _persist_flight(os.path.join(
                                    results_dir, "flight_recorder.json"))
                        train_infos = []
                    if (res.nonfinite_tolerance
                            and nonfinite_streak >= res.nonfinite_tolerance):
                        found = (find_checkpoint(model_dir)
                                 if cfg.save_model else None)
                        if found is None or restores >= res.max_restores:
                            raise RuntimeError(
                                f"training diverged: {nonfinite_streak} "
                                f"consecutive non-finite train steps at "
                                f"t_env={t_env} (last loss="
                                f"{float(np.mean(last['loss']))}, grad_norm="
                                f"{float(np.mean(last['grad_norm']))}), and "
                                + (f"restore limit reached (resilience."
                                   f"max_restores={res.max_restores})"
                                   if found is not None else
                                   "no valid checkpoint exists to restore "
                                   "(save_model off or none written yet)")
                                + " — the NaN source is persistent; inspect "
                                "lr/grad_norm_clip/td_loss before rerunning")
                        dirname, step = found
                        log.warning(
                            f"non-finite streak hit resilience."
                            f"nonfinite_tolerance={res.nonfinite_tolerance}; "
                            f"restoring last good checkpoint {dirname} "
                            f"(restore {restores + 1}/{res.max_restores})")
                        _restore_checkpoint(dirname, step)
                        restores += 1
                        nonfinite_streak = 0
                        continue
                with rec.span("driver.log", t_env=t_env):
                    if kit.dispatch_faults:
                        # ladder visibility: cumulative transient dispatch
                        # errors (in-place retries included); per-escalation
                        # counters land in _dispatch_ladder as they happen
                        logger.log_stat("dispatch_faults",
                                        kit.dispatch_faults, t_env)
                    if rec.enabled:
                        # device-fetch accounting (utils/stats.py): how many
                        # blocking stat round-trips the cadences have cost
                        logger.log_stat("stat_fetches",
                                        train_acc.fetches + test_acc.fetches,
                                        t_env)
                    logger.log_stat("episode", episode, t_env)
                    # wall-clock throughput including everything (train,
                    # logging, cadences) — the honest live rate; the async
                    # loop makes the per-stage timings dispatch-enqueue
                    # times unless profile_stages is on (and they are
                    # logged only then, below)
                    now = time.time()
                    if last_log_time is not None:
                        rate = ((t_env - last_log_t)
                                / max(now - last_log_time, 1e-9))
                        logger.log_stat("env_steps_per_sec", rate, t_env)
                        if pulse is not None:
                            pulse.set("env_steps_per_sec", rate)
                    last_log_time = now
                    # memwatch phase boundary + the live-plane cadence
                    # gauges (both no-ops when the plane is off)
                    pulse_snap = mw.snapshot("log", t_env=t_env)
                    if pulse is not None:
                        pulse.set("nonfinite_streak", nonfinite_streak)
                        pulse.set("nonfinite_total", nonfinite_total)
                        pulse.set("dispatch_faults", kit.dispatch_faults)
                        pulse.set("ladder_failures", ladder.failures)
                        pulse.set("restores", restores)
                        pulse.set("superstep_k", K)
                        pulse.set_memwatch(pulse_snap)
                    if sync_stages:
                        # without the per-stage barrier a stage's wall
                        # clock is enqueue time, not device time: not
                        # logged under a name that reads as device time
                        timer.log_and_reset(logger, t_env)
                    logger.print_recent_stats()
                    last_log_t = t_env

    except BaseException as e:
        # crash path: leave the same causal trail a stall does — the
        # flight tail with the failing span's phase/outcome last
        # (best-effort no-ops when telemetry is off; never masks ``e``)
        rec.mark("crash", t_env=t_env,
                 error=f"{type(e).__name__}: {e}"[:300])
        _persist_flight(os.path.join(results_dir, "flight_recorder.json"))
        rec.close()                     # flush the spans.jsonl tail too
        raise
    finally:
        # stop the watchdog FIRST: the hard-exit grace timer must not be
        # able to kill the process while the orderly emergency checkpoint
        # below is being written
        if wd is not None:
            wd.stop()
        guard.uninstall()
        if pulse is not None:
            pulse.close()               # bounded; never hangs the exit

    if guard.triggered:
        # ---- preemption path: lose at most one iteration ---------------
        # SIGTERM (or watchdog guard trip) is a flight-persist trigger:
        # the preempted run's last phases survive even if the emergency
        # checkpoint below cannot be written
        rec.mark("shutdown", t_env=t_env, signame=guard.signame or "")
        _persist_flight(os.path.join(results_dir, "flight_recorder.json"))
        stall = wd.take_diagnosis() if wd is not None else None
        if stall is not None:
            log.warning(f"watchdog: {stall.message()} — the stalled call "
                        f"eventually returned; exiting with the diagnosis "
                        f"persisted to {model_dir}/stall_diagnosis.json")
        log.warning(f"shutdown requested ({guard.signame}) at "
                    f"t_env={t_env} — stopping gracefully")
        if cfg.save_model and res.emergency_checkpoint:
            # a watchdog-thread emergency save may still be mid-write if
            # wd.stop()'s bounded join gave up on it — both stage into
            # the same tmp.<t_env> directory, and an unbounded wait
            # would hang the exit forever (the watchdog and its grace
            # timer are already stopped)
            if _acquire_save_lock("preemption exit"):
                save_to = None
                # the watchdog and its grace timer are stopped, so this
                # save is the one device-facing call left with no bound:
                # wedged device→host fetches block without raising and
                # retry_call only bounds failures — arm a hard deadline
                # (watchdog-armed runs only: dispatch_timeout unset
                # keeps today's behavior) so a wedged backend costs the
                # stall exit code, not a silent forever-hang in the
                # exit path
                deadline = (watchdog.ExitDeadline(
                                max(res.stall_grace_s, 60.0),
                                res.stall_exit_code,
                                label="preemption-exit emergency "
                                      "checkpoint")
                            if wd is not None else nullcontext())
                try:
                    with deadline:
                        if stop_ok:
                            # same single-process-only retry policy as
                            # the cadence save (a one-sided retry of the
                            # lockstep multi-host collective would
                            # deadlock its peers) — and an orderly
                            # preemption exit must STAY orderly: a save
                            # that still fails degrades to the per-host
                            # shard save below instead of turning the
                            # exit-0 resume hint into a crash
                            try:
                                save_to = watchdog.retry_call(
                                    lambda: save_checkpoint(
                                        model_dir, t_env, _ckpt_state(),
                                        gather_retries=res.dispatch_retries,
                                        gather_backoff_s=res.retry_backoff_s,
                                        topology=_save_topology()),
                                    attempts=(1 + res.dispatch_retries
                                              if jax.process_count() == 1
                                              else 1),
                                    backoff_s=res.retry_backoff_s,
                                    label="checkpoint.emergency")
                            except Exception:  # noqa: BLE001
                                log.exception(
                                    "collective emergency checkpoint "
                                    "failed (a peer died mid-gather?) — "
                                    "degrading to the per-host shard "
                                    "save")
                        if save_to is None:
                            # degraded exit (docs/RESILIENCE.md §6): the
                            # peer barrier failed or the collective save
                            # died — write THIS host's addressable shard
                            # only (no collectives, cannot hang on a
                            # dead peer); restore_elastic reassembles
                            # the set, find_checkpoint skips it unless
                            # every shard landed
                            with rec.span("checkpoint.shard_save",
                                          t_env=t_env):
                                save_to = save_checkpoint_shards(
                                    model_dir, t_env, _ckpt_state(),
                                    topology=_save_topology())
                except Exception:  # noqa: BLE001 — exit path stays orderly
                    log.exception(
                        "emergency checkpoint failed on the preemption "
                        "exit — resume falls back to the newest "
                        "published checkpoint")
                finally:
                    save_lock.release()
                if save_to is not None:
                    if res.keep_last:
                        prune_checkpoints(model_dir, res.keep_last,
                                          res.keep_every)
                    log.info(f"emergency checkpoint saved to {save_to}")
        log.info(f"resume with checkpoint_path={model_dir} (newest valid "
                 f"step selected automatically)")
    else:
        log.info("Finished Training")
    rec.close()
    return ts


def run_sebulba(exp: Experiment, logger: Logger, results_dir: str,
                rec=None) -> TrainState:
    """The Sebulba decoupled train loop (ROADMAP item 2, docs/PERF.md §
    decoupled pipeline): rollout and training on DISJOINT device sets
    with a bounded device-resident trajectory queue between them, so
    neither phase idles the other's devices.

    Two host threads orchestrate dispatches (no value ever comes to
    host except at the same cadences the classic loop syncs at):

    * the **actor thread** runs ``actor_step`` (the shared ``run_raw``
      rollout definition) on the actor mesh, pushes each time-major
      emission into the queue (``queue.put`` — an async device-to-device
      copy + one scatter per leaf into the slot ring), adopts freshly
      published params under the ``sebulba.staleness`` bound
      (``params.sync``), and owns the test cadence (it owns the rollout
      program and the runner state, exactly like the classic loop's
      shared-runner test rollouts);
    * the **learner (main) thread** consumes batches (``queue.get`` —
      slot gather scattered straight into the replay ring via
      ``insert_time_major``), mirrors the train gate host-side and
      splits the key stream EXACTLY like the classic loop, trains
      (``learner.dispatch``), publishes params back to the actor mesh,
      and owns the log/save cadences, the non-finite escalation, the
      degradation ladder and every exit path.

    Failure routing: both threads route dispatches through the
    watchdog-stamped retry helper (each thread has its OWN watchdog —
    one armed stamp per instance); exhausted retries and actor-thread
    failures land in the shared ladder, whose rungs here are restore
    (tear down the actor thread, reload the newest checkpoint, restart
    a fresh epoch) and abort — there is no superstep to degrade.
    A stall on either side writes the diagnosis and trips the
    ShutdownGuard, so a wedged learner dispatch still ends with the
    actor thread exiting and a resumable checkpoint on disk
    (tests/test_sebulba.py chaos scenario).

    Lockstep mode (``queue_slots=1, staleness=0``) serializes
    rollout→insert→train exactly like the classic K=1 loop and is
    bit-identical to it (pinned by test on a forced multi-device CPU
    host)."""
    cfg = exp.cfg
    sb = cfg.sebulba
    log = logger.console_logger
    if rec is None:
        rec = obs_spans.make_recorder(
            cfg.obs, results_dir, annotate=jax.profiler.TraceAnnotation)

    # ---- graftpop population axis over the decoupled loop ---------------
    # (graftlattice, docs/POPULATION.md §composition): P > 0 stacks a
    # leading (P,) member axis onto BOTH halves of the split state and
    # vmaps every sebulba program over it (parallel/sebulba.py). Only
    # lockstep queues are legal (sanity_check): the queue serializes
    # rollout→insert→train exactly like the classic population loop, so
    # the host loop below needs no per-member control flow — counters,
    # gates and cadences mirror member 0 (every member's control
    # counters evolve identically; _host_int).
    from . import population as graftpop
    P = graftpop.population_size(cfg)
    # graftpulse plane (same off-state contract as the classic loop);
    # the decoupled layout is the one Podracer says lives or dies on
    # utilization you can see live — queue depth, staleness, idle time
    with rec.span("setup.telemetry"):
        pulse = obs_pulse.make_pulse(cfg.obs, rec=rec, log=log)
        mw = obs_memwatch.make_memwatch(cfg.obs, rec=rec)
        mw.snapshot("startup", t_env=0)
        # on-demand trace trigger, driven from the learner (main)
        # thread — the profiler window captures whole-process device
        # activity, so one driver is enough and the /trace route works
        # on decoupled runs exactly like classic ones
        trc = (obs_pulse.TraceController(
                   results_dir, rec=rec,
                   hub=pulse.hub if pulse is not None else None,
                   n_iterations=cfg.profile_iterations)
               if (rec.enabled or pulse is not None) else None)

        # graftsight monitor (learner-thread cadence pass; same
        # off-state contract as the classic loop)
        sight_mon = obs_sight.make_monitor(cfg.obs, logger=logger,
                                           rec=rec, population=P)

    from .parallel.sebulba import make_sebulba
    with rec.span("setup.programs"):
        seb = make_sebulba(exp)
    spec = seb.spec
    lockstep = sb.queue_slots == 1 and sb.staleness == 0
    log.info(f"sebulba decoupled loop: {sb.actor_devices} actor + "
             f"{sb.learner_devices} learner devices, queue_slots="
             f"{sb.queue_slots}, staleness={sb.staleness}"
             + (" (lockstep)" if lockstep else ""))
    if P:
        log.info(f"graftpop × sebulba: population of {P} members vmapped "
                 f"over the decoupled programs (seeds "
                 f"{graftpop.member_seeds(cfg)}, member axis sharded "
                 f"over each device set)")

    res = cfg.resilience
    guard = (resilience.ShutdownGuard.install() if res.handle_signals
             else resilience.ShutdownGuard())
    model_dir = os.path.join(cfg.local_results_path, "models",
                             os.path.basename(results_dir))
    save_lock = threading.Lock()
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    n_test_runs = max(1, cfg.test_nepisode // cfg.batch_size_run)
    test_quota = n_test_runs * cfg.batch_size_run * max(P, 1)
    buffer_capacity = exp.buffer.capacity

    with rec.span("setup.programs"):
        actor_step, queue_put, queue_get, learner_step = seb.programs()

    # ---- cross-thread cells (all access under `cond` unless noted) ----
    cond = threading.Condition()
    cell = {"rs": None,          # latest post-rollout runner state handle
            "rs_t_env": 0,       # the actor's env-step cursor at it
            "params": None,      # latest published acting params (actor mesh)
            "version": 0,        # publish counter
            "q": None}           # the queue handle (threaded linearly)
    counters = {"put": 0, "got": 0, "consumed": 0, "started": 0}
    idle = {"actor_s": 0.0, "learner_s": 0.0}   # cumulative blocked time
    stop_event = threading.Event()   # epoch teardown (restore/exit)
    actor_failure = []               # DispatchFailed escaped from the actor
    nonfinite_streak = 0
    nonfinite_total = 0
    restores = 0
    # coordinated preemption (docs/RESILIENCE.md §6): stop_ok=False
    # after a failed peer negotiation degrades the exit to the per-host
    # shard save. Sebulba cuts at its own t_env (sanity_check rejects
    # sebulba×dp, so there is no multi-host sebulba to step in lockstep
    # toward a consensus cut).
    stop_at = None
    stop_ok = True

    # ---- shared driver-helper kit (graftlattice) ----------------------
    # default_wd stays None: each thread passes awd= explicitly (one
    # armed stamp per watchdog instance), and the queue waits bounded by
    # the PEER's progress stay span-only; wake= lets the stall response
    # unblock either thread's queue-condition wait.
    def _wake():
        with cond:
            cond.notify_all()
    kit = _DriverKit(cfg=cfg, res=res, log=log, rec=rec, mw=mw,
                     sight_mon=sight_mon, guard=guard,
                     model_dir=model_dir, save_lock=save_lock,
                     P=P, spec_fn=lambda: spec, wake=_wake)
    _persist_flight = kit.persist_flight
    _acquire_save_lock = kit.acquire_save_lock
    _watched = kit.watched
    _dispatch = kit.dispatch

    # ---- resume target ------------------------------------------------
    found = None
    if cfg.checkpoint_path:
        with rec.span("setup.restore", stage="find"):
            found = find_checkpoint(cfg.checkpoint_path, cfg.load_step)
        if found is None:
            log.info(f"no checkpoint found in {cfg.checkpoint_path}")

    def _snapshot_state():
        """The latest complete joined TrainState (for stamps and
        saves): learner half from the main thread's handles, runner
        half from the actor's published post-rollout handle."""
        with cond:
            rs = cell["rs"]
        return seb.join(rs, state_cell["ls"]) if rs is not None else None

    state_cell = {"ls": None}        # learner-side handle (main thread owns)

    # learner-side stall: full kit response (diagnosis + guard trip +
    # bounded emergency save from the stamped pre-dispatch state);
    # actor-side: diagnosis + guard trip only — the learner (main)
    # thread owns the checkpointable state and writes the emergency
    # save on its own exit path
    _on_stall = kit.stall_response

    def _on_actor_stall(diag: watchdog.StallDiagnosis) -> None:
        kit.stall_response(diag, tag="watchdog-actor", save=False)

    wd = wd_actor = None
    if res.dispatch_timeout > 0:
        wd = watchdog.Watchdog(
            res.dispatch_timeout, on_stall=_on_stall,
            grace_s=res.stall_grace_s, exit_code=res.stall_exit_code,
            first_timeout_s=res.first_dispatch_timeout).start()
        wd_actor = watchdog.Watchdog(
            res.dispatch_timeout, on_stall=_on_actor_stall,
            grace_s=res.stall_grace_s, exit_code=res.stall_exit_code,
            first_timeout_s=res.first_dispatch_timeout).start()
        log.info(f"dispatch watchdogs armed (actor + learner): timeout="
                 f"{res.dispatch_timeout}s, grace={res.stall_grace_s}s")
    ladder = watchdog.DegradationLadder(res.max_restores)
    if pulse is not None:
        if wd is not None:
            pulse.wire_watchdog(wd, side="learner")
        if wd_actor is not None:
            pulse.wire_watchdog(wd_actor, side="actor")
        pulse.wire_guard(guard)
        pulse.set("backend_info", 1, backend=jax.default_backend())
        pulse.set("queue_slots", sb.queue_slots)
        pulse.set("staleness_bound", sb.staleness)
        if sight_mon is not None:
            sight_mon.wire_pulse(pulse.hub)

    # ---- stat accumulators (actor pushes, both flush at cadences) -----
    train_acc = StatsAccumulator(population=P)
    test_acc = StatsAccumulator(population=P)

    def _stopping() -> bool:
        return stop_event.is_set() or guard.triggered

    # ---- the actor thread body ----------------------------------------
    def _actor_loop(rs, t_env0):
        """Rollout producer: staleness-bounded params adoption → rollout
        → queue put, plus the test cadence. Exits on quota, stop_event,
        guard trip, or an escaped DispatchFailed (recorded for the main
        thread's ladder)."""
        a_t = t_env0
        last_test_t = a_t - cfg.test_interval - 1
        last_runner_log_t = t_env0
        try:
            while a_t <= cfg.t_max and not _stopping():
                # params.sync: adopt the newest published params, but
                # never act more than `staleness` batches ahead of the
                # learner's last processed batch (0 = lockstep). Span
                # only, no watchdog stamp: this wait is bounded by the
                # LEARNER's progress, not device health — a slow train
                # step must read as actor idle time, never as a stall
                with _watched("params.sync", t=a_t):
                    resilience.fire("params.sync", t_env=a_t)
                    with cond:
                        while (counters["started"] - counters["consumed"]
                               > sb.staleness and not _stopping()):
                            t0 = time.monotonic()
                            cond.wait(0.05)
                            idle["actor_s"] += time.monotonic() - t0
                        params = cell["params"]
                if _stopping():
                    break

                def _roll(rs=rs, params=params):
                    rs2, tm, stats = actor_step(params, rs,
                                                test_mode=False)
                    # the actor thread's natural barrier: it has nothing
                    # else to do, and blocking here makes actor.dispatch
                    # spans the honest device rollout time
                    jax.block_until_ready(stats.epsilon)  # graftlint: disable=GL105
                    return rs2, tm, stats
                rs, tm, stats = _dispatch("actor.dispatch", _roll, rs,
                                          awd=wd_actor, t=a_t)
                a_t += spr
                with cond:
                    counters["started"] += 1
                    cell["rs"], cell["rs_t_env"] = rs, a_t
                _dispatch("fetch.train_stats",
                          lambda: train_acc.push(stats), None,
                          awd=wd_actor, t=a_t, retryable=False)

                # queue.put: wait for a free slot (backpressure), then
                # d2d-copy the emission and scatter it into the slot
                # ring. Span only (no stamp): a full queue is the
                # learner being slower, i.e. actor idle — not a stall
                with _watched("queue.put", t=a_t):
                    resilience.fire("queue.put", t_env=a_t)
                    tm_l = seb.to_learner(tm)
                    with cond:
                        while (counters["put"] - counters["got"]
                               >= sb.queue_slots and not _stopping()):
                            t0 = time.monotonic()
                            cond.wait(0.05)
                            idle["actor_s"] += time.monotonic() - t0
                        if _stopping():
                            break
                        slot = counters["put"] % sb.queue_slots
                        cell["q"] = queue_put(
                            cell["q"], jnp.asarray(slot, jnp.int32), tm_l)
                        counters["put"] += 1
                        cond.notify_all()

                # train-stat cadence (classic: runner_log_interval)
                if a_t - last_runner_log_t >= cfg.runner_log_interval:
                    def _flush_train_stats():
                        train_acc.flush(logger, a_t)
                        logger.log_stat("epsilon", train_acc.epsilon, a_t)
                    _dispatch("fetch.train_stats", _flush_train_stats,
                              None, awd=wd_actor, t=a_t, retryable=False)
                    last_runner_log_t = a_t

                # test cadence (the actor owns the rollout program and
                # the runner state, like the classic loop's test rolls)
                if (a_t - last_test_t) / cfg.test_interval >= 1.0:
                    # drain the pipeline first and adopt the freshest
                    # params: the classic loop evaluates AFTER the
                    # current iteration's train step, so the test
                    # rollouts here must see every produced batch
                    # trained (lockstep bit-parity depends on it; for
                    # overlapped configs it briefly drains the queue —
                    # the same serialization the classic cadence pays)
                    with _watched("params.sync", t=a_t):
                        resilience.fire("params.sync", t_env=a_t)
                        with cond:
                            while (counters["consumed"]
                                   < counters["started"]
                                   and not _stopping()):
                                t0 = time.monotonic()
                                cond.wait(0.05)
                                idle["actor_s"] += time.monotonic() - t0
                            params = cell["params"]
                    for _ in range(n_test_runs):
                        if _stopping():
                            break

                        def _test_roll(rs=rs, params=params):
                            rs2, _, s = actor_step(params, rs,
                                                   test_mode=True)
                            return rs2, s
                        rs, s = _dispatch("dispatch.test", _test_roll,
                                          rs, awd=wd_actor, t=a_t)
                        _dispatch("fetch.test_stats",
                                  lambda s=s: test_acc.push(s), None,
                                  awd=wd_actor, t=a_t, retryable=False)
                        if test_acc.n_episodes == test_quota:
                            _dispatch(
                                "fetch.test_stats",
                                lambda: test_acc.flush(logger, a_t,
                                                       prefix="test_"),
                                None, awd=wd_actor, t=a_t,
                                retryable=False)
                    with cond:
                        cell["rs"], cell["rs_t_env"] = rs, a_t
                    last_test_t = a_t
        except watchdog.DispatchFailed as df:
            log.warning(f"actor thread: {df} — handing to the ladder")
            with cond:
                actor_failure.append(df)
                cond.notify_all()
        except Exception as e:  # noqa: BLE001 — surfaced to the ladder
            log.exception("actor thread failed")
            with cond:
                actor_failure.append(
                    watchdog.DispatchFailed("actor.dispatch", 1, e))
                cond.notify_all()
        finally:
            with cond:
                cond.notify_all()    # wake a learner waiting on the queue

    # ---- state init / resume ------------------------------------------
    t_env = 0

    def _ckpt_state(ts_):
        """What checkpoints hold: the bare TrainState classically, the
        (state, spec) PopState under a population — the classic loop's
        checkpoint contract, so either driver resumes the other's
        saves."""
        return graftpop.PopState(ts=ts_, spec=spec) if P else ts_

    def _save_topology():
        """The topology stamp every sebulba save carries (meta.json) —
        symmetric with the classic loop's, so a classic resume of a
        sebulba save (or vice versa) sees the loop-shape change and
        logs/routes it (docs/RESILIENCE.md §6)."""
        topo = {"loop": "sebulba",
                "sebulba": {"actor_devices": sb.actor_devices,
                            "learner_devices": sb.learner_devices}}
        if P:
            ema = getattr(train_acc, "member_return_ema", None)
            if ema and all(v is not None for v in ema):
                topo["member_ranking"] = sorted(
                    range(P), key=lambda m: ema[m], reverse=True)
        return topo

    def _place(found_):
        """(rs, ls, t_env) freshly initialized or restored. The restore
        streams each leaf STRAIGHT onto its mesh
        (``load_checkpoint_sharded`` with an abstract eval_shape
        template — per-leaf ``device_put``, so the two halves land on
        their disjoint meshes with no full-state single-device
        transient; the classic DP resume's ADVICE-r5 reasoning, which
        matters doubly here because this is also the mid-run ladder
        restore path, where the live sharded state still holds HBM)."""
        if found_ is None:
            return (*seb.init_states(cfg.seed), 0)
        dirname, step = found_
        if P:
            # population resume: the checkpoint is a PopState (or a v4
            # single-member state the migration shim lifts to the
            # stacked template — utils/checkpoint._migrate_raw).
            # Abstract ts template only (P concrete inits would
            # materialize P replay rings just to be discarded). The
            # restored spec is ignored in favor of the program-baked
            # one: pbt × sebulba is rejected (sanity_check), so the
            # spec is config-determined and the two are identical.
            shapes = jax.eval_shape(
                lambda: graftpop.init_population(exp, cfg))[0]
            ps, _ = elastic.resume_state(dirname, _ckpt_state(shapes),
                                         verify=False,
                                         topology={"loop": "sebulba"})
            rs, ls = seb.place(ps.ts)
            rs = rs.replace(t_env=jax.device_put(
                jnp.full((P,), step, jnp.int32), rs.t_env.sharding))
            log.info(f"resumed population from {dirname} at "
                     f"t_env={step}")
            return rs, ls, step
        shapes = jax.eval_shape(lambda: exp.init_train_state(cfg.seed))
        rs_shape, ls_shape = seb.split_shapes(shapes)
        ts = elastic.resume_state(
            dirname, shapes,
            seb.join(seb.runner_shardings(rs_shape),
                     seb.learner_shardings(ls_shape)),
            verify=False, topology={"loop": "sebulba"})[0]
        rs, ls = seb.split_shapes(ts)
        # keep the canonical placement for the restored cursor
        rs = rs.replace(t_env=jax.device_put(
            jnp.asarray(step, jnp.int32), rs.t_env.sharding))
        log.info(f"resumed from {dirname} at t_env={step}")
        return rs, ls, step

    # fresh: both halves initialized on their meshes; resume: the
    # abstract templates and the load straight onto the meshes
    with rec.span("setup.init_state" if found is None
                  else "setup.restore", stage="place"):
        # per-member driver key streams under a population (each
        # member's stream splits exactly like the classic loop's single
        # one)
        key = graftpop.member_keys(cfg) if P else jax.random.PRNGKey(
            cfg.seed + 1)
        rs0, ls, t_env = _place(found)

    if rec.enabled:
        rec.mark("run", t_env=t_env, backend=jax.default_backend(),
                 batch_size_run=cfg.batch_size_run,
                 episode_limit=cfg.env_args.episode_limit,
                 batch_size=cfg.batch_size, superstep=1,
                 host_buffer=False, sebulba=True, population=P,
                 actor_devices=sb.actor_devices,
                 learner_devices=sb.learner_devices,
                 queue_slots=sb.queue_slots, staleness=sb.staleness,
                 **rec.totals())

    last_log_t = t_env
    last_save_t = t_env if t_env else -cfg.save_model_interval - 1
    start_time = time.time()
    last_log_time = None
    train_infos = []
    episode = _host_int(ls.episode)
    buffer_filled = _host_int(ls.buffer.episodes_in_buffer)
    state_cell["ls"] = ls

    def _epoch(rs, t_env0):
        """One actor-thread lifetime: spawn the producer, consume until
        the quota is drained (or a guard trip / ladder rung ends it).
        Returns ``'done' | 'failed'`` — 'failed' hands the recorded
        DispatchFailed to the caller's ladder."""
        nonlocal ls, t_env, episode, buffer_filled, key, train_infos
        nonlocal nonfinite_streak, nonfinite_total
        nonlocal last_log_t, last_save_t, last_log_time
        nonlocal stop_at, stop_ok
        stop_event.clear()
        with cond:
            actor_failure.clear()   # same discipline as its append sites
            counters.update(put=0, got=0, consumed=0, started=0)
            cell["q"] = seb.init_queue()
            cell["rs"], cell["rs_t_env"] = rs, t_env0
            cell["params"] = seb.publish_params(ls.learner.params["agent"])
            cell["version"] = 0
        actor = threading.Thread(target=_actor_loop, args=(rs, t_env0),
                                 daemon=True, name="t2omca-sebulba-actor")
        actor.start()
        failed = None
        try:
            while not guard.triggered:
                resilience.fire("driver.iteration", t_env=t_env,
                                guard=guard)
                # coordinated preemption (docs/RESILIENCE.md §6):
                # propagate a peer's announced shutdown, then negotiate
                # once and cut HERE — the actor thread exits on the
                # trigger, so the learner cannot step toward a later
                # consensus target (and sanity_check rejects sebulba×dp,
                # so there is no multi-host sebulba peer to align with;
                # the negotiation only decides collective-vs-shard save)
                if not guard.triggered and dist.peer_shutdown_requested():
                    guard.request("peer")
                if guard.triggered:
                    if stop_at is None:
                        dist.announce_shutdown(t_env)
                        with rec.span("preempt.barrier", t_env=t_env):
                            stop_at, stop_ok = dist.negotiate_stop_step(
                                t_env, res.preempt_barrier_timeout_s)
                    break
                if pulse is not None:
                    pulse.tick_iteration(t_env, episode)
                if trc is not None:
                    trc.poll(t_env)
                # queue.get: wait for an item (or producer exit), then
                # gather the slot straight into the replay ring. Span
                # only (no stamp): an empty queue is the actor being
                # slower, i.e. learner idle — not a stall; the consume
                # dispatch itself is an async enqueue whose faults
                # surface at the stamped learner.dispatch/fetch
                # boundaries
                got_item = False
                with _watched("queue.get", t=t_env):
                    resilience.fire("queue.get", t_env=t_env)
                    with cond:
                        while (counters["put"] == counters["got"]
                               and actor.is_alive() and not actor_failure
                               and not _stopping()):
                            t0 = time.monotonic()
                            cond.wait(0.05)
                            idle["learner_s"] += time.monotonic() - t0
                        if actor_failure:
                            failed = actor_failure[0]
                            break
                        if counters["put"] > counters["got"]:
                            slot = counters["got"] % sb.queue_slots
                            ls2, q2 = queue_get(
                                ls, cell["q"],
                                jnp.asarray(slot, jnp.int32))
                            ls, cell["q"] = ls2, q2
                            counters["got"] += 1
                            got_item = True
                            cond.notify_all()
                if failed is not None or (not got_item):
                    break               # producer finished (or failed)
                state_cell["ls"] = ls
                t_env += spr
                episode += cfg.batch_size_run
                buffer_filled = min(buffer_filled + cfg.batch_size_run,
                                    buffer_capacity)

                # train gate: the classic loop's host mirror + key split
                if (buffer_filled >= cfg.batch_size
                        and episode >= cfg.accumulated_episodes):
                    if P:
                        # per-member key streams: each member's stream
                        # splits exactly like the classic loop's single
                        # one (lockstep bit-parity with the classic
                        # population loop depends on it)
                        key2, rows = list(key), []
                        for m in range(P):
                            key2[m], k_s = jax.random.split(key2[m])
                            rows.append(k_s)
                        k_sample = jnp.stack(rows)
                    else:
                        key2, k_sample = jax.random.split(key)

                    def _train_once(ls=ls, k_sample=k_sample):
                        ls2, info = learner_step(ls, k_sample,
                                                 jnp.asarray(t_env))
                        return ls2, info
                    ls, info = _dispatch("learner.dispatch", _train_once,
                                         _snapshot_state(), awd=wd,
                                         t=t_env)
                    key = key2
                    train_infos.append(info)
                    state_cell["ls"] = ls

                # params.sync: publish the (possibly) fresh params back
                # to the actor mesh and advance the staleness window
                # (an async device-to-device copy — the stamp bounds
                # only the enqueue)
                with _watched("params.sync", awd=wd, t=t_env):
                    resilience.fire("params.sync", t_env=t_env)
                    new_params = seb.publish_params(
                        ls.learner.params["agent"])
                with cond:
                    cell["params"] = new_params
                    cell["version"] += 1
                    counters["consumed"] += 1
                    cond.notify_all()

                _cadences()
                if trc is not None:
                    trc.tick(logger, t_env)
            return ("failed", failed) if failed is not None else \
                ("done", None)
        except watchdog.DispatchFailed as df:
            return "failed", df
        finally:
            stop_event.set()
            with cond:
                cond.notify_all()
            actor.join(timeout=30.0)
            if actor.is_alive():
                log.warning("actor thread did not exit within 30s "
                            "(wedged dispatch?) — continuing teardown; "
                            "the daemon thread dies with the process")

    def _cadences():
        """Save + log cadences (learner thread; the actor owns the
        test/runner-log cadences)."""
        nonlocal last_save_t, last_log_t, last_log_time, train_infos
        nonlocal nonfinite_streak, nonfinite_total
        if cfg.save_model and (t_env - last_save_t) >= cfg.save_model_interval:
            def _save_once():
                with _watched("checkpoint.save", state_cell["ls"], awd=wd,
                              t=t_env):
                    if not _acquire_save_lock("save cadence"):
                        return None
                    try:
                        return save_checkpoint(
                            model_dir, t_env,
                            _ckpt_state(_snapshot_state()),
                            gather_retries=res.dispatch_retries,
                            gather_backoff_s=res.retry_backoff_s,
                            topology=_save_topology())
                    finally:
                        save_lock.release()
            save_to = watchdog.retry_call(
                _save_once, attempts=1 + res.dispatch_retries,
                backoff_s=res.retry_backoff_s, label="checkpoint.save")
            if save_to is not None:
                log.info(f"Saving models to {save_to}")
                if res.keep_last:
                    prune_checkpoints(model_dir, res.keep_last,
                                      res.keep_every)
                last_save_t = t_env

        if (t_env - last_log_t) >= cfg.log_interval:
            if train_infos:
                def _fetch_infos():
                    flags = np.asarray(jax.device_get(  # graftlint: disable=GL105
                        [i["all_finite"] for i in train_infos]))
                    return flags, jax.device_get(train_infos[-1])  # graftlint: disable=GL105
                flags, last = _dispatch("fetch.train_infos",
                                        _fetch_infos, None, awd=wd,
                                        t=t_env, retryable=False)
                if P:
                    # (n, P) member flags: a train step counts as
                    # finite only when EVERY member's update was —
                    # one poisoned member is a restore-worthy event
                    # exactly like a solo NaN (the stacked state is
                    # one checkpoint)
                    flags = flags.reshape(len(train_infos), -1)\
                                 .all(axis=1)
                for ok in flags:
                    if ok:
                        nonfinite_streak = 0
                    else:
                        nonfinite_streak += 1
                        nonfinite_total += 1
                if not flags.all():
                    logger.log_stat("nonfinite_steps", nonfinite_total,
                                    t_env)
                    rec.mark("nonfinite", t_env=t_env,
                             streak=nonfinite_streak,
                             total=nonfinite_total)
                    log.warning(
                        f"non-finite loss/grads in "
                        f"{int((~flags).sum())}/{len(flags)} train steps "
                        f"since last log (streak={nonfinite_streak})")
                for k in ("loss", "grad_norm", "td_error_abs",
                          "q_taken_mean", "target_mean"):
                    if P:
                        # aggregate row = population mean; per-member
                        # rows (pop<i>_*) only at P > 1 so a P=1 run
                        # keeps the solo metric stream (the classic
                        # population cadence's shape)
                        v = np.asarray(last[k], np.float64)
                        logger.log_stat(k, float(v.mean()), t_env)
                        if P > 1:
                            for m in range(P):
                                logger.log_stat(f"pop{m}_{k}",
                                                float(v[m]), t_env)
                    else:
                        logger.log_stat(k, float(last[k]), t_env)
                if sight_mon is not None:
                    # classic-loop contract: detector pass on the same
                    # fetch, flight persist on a fresh trip
                    with rec.span("sight.detect", t_env=t_env):
                        trips = sight_mon.observe(last, t_env)
                    if trips:
                        log.warning(
                            f"graftsight: detector(s) tripped at "
                            f"t_env={t_env}: {', '.join(trips)} — "
                            f"/healthz degraded")
                        _persist_flight(os.path.join(
                            results_dir, "flight_recorder.json"))
                train_infos = []
                if (res.nonfinite_tolerance
                        and nonfinite_streak >= res.nonfinite_tolerance):
                    raise _NonFiniteEscalation(nonfinite_streak)
            with cond:
                depth = counters["put"] - counters["got"]
                ahead = counters["started"] - counters["consumed"]
            logger.log_stat("queue_depth", depth, t_env)
            logger.log_stat("actor_idle_s", round(idle["actor_s"], 3),
                            t_env)
            logger.log_stat("learner_idle_s",
                            round(idle["learner_s"], 3), t_env)
            if rec.enabled:
                rec.mark("sebulba", t_env=t_env, queue_depth=depth,
                         actor_idle_s=round(idle["actor_s"], 3),
                         learner_idle_s=round(idle["learner_s"], 3))
            if kit.dispatch_faults:
                logger.log_stat("dispatch_faults", kit.dispatch_faults,
                                t_env)
            logger.log_stat("episode", episode, t_env)
            now = time.time()
            rate = None
            if last_log_time is not None:
                rate = ((t_env - last_log_t)
                        / max(now - last_log_time, 1e-9))
                logger.log_stat("env_steps_per_sec", rate, t_env)
            last_log_time = now
            pulse_snap = mw.snapshot("log", t_env=t_env)
            if pulse is not None:
                # the decoupled loop's live utilization surface: queue
                # depth, params staleness in flight, both sides' idle
                if rate is not None:
                    pulse.set("env_steps_per_sec", rate)
                pulse.set("queue_depth", depth)
                pulse.set("staleness_in_flight", ahead)
                pulse.set("actor_idle_seconds", round(idle["actor_s"], 3))
                pulse.set("learner_idle_seconds",
                          round(idle["learner_s"], 3))
                pulse.set("nonfinite_streak", nonfinite_streak)
                pulse.set("dispatch_faults", kit.dispatch_faults)
                pulse.set("ladder_failures", ladder.failures)
                pulse.set("restores", restores)
                pulse.set_memwatch(pulse_snap)
            logger.print_recent_stats()
            last_log_t = t_env

    # ---- epochs: run; a ladder restore reloads and re-enters ----------
    try:
        while True:
            try:
                status, failed = _epoch(rs0, t_env)
            except _NonFiniteEscalation as nf:
                status, failed = "failed", watchdog.DispatchFailed(
                    "learner.dispatch", 1, nf)
            if status == "done" or guard.triggered:
                break
            # ladder: no superstep to degrade — restore or abort
            action = ladder.next_action(can_degrade=False)
            logger.log_stat("dispatch_failures", ladder.failures, t_env)
            rec.mark("ladder", action=action, phase=failed.phase,
                     t_env=t_env, failures=ladder.failures)
            good = (find_checkpoint(model_dir) if cfg.save_model
                    else None)
            if action == "restore" and good is not None:
                log.warning(f"degradation ladder: {failed} — restoring "
                            f"last good checkpoint {good[0]} "
                            f"({ladder.describe()})")
                rs0, ls, t_env = _place(good)
                state_cell["ls"] = ls
                episode = _host_int(ls.episode)
                buffer_filled = _host_int(ls.buffer.episodes_in_buffer)
                train_infos = []
                nonfinite_streak = 0
                fetches = train_acc.fetches
                train_acc = StatsAccumulator(population=P)
                train_acc.fetches = fetches
                # the torn-down actor thread may have died mid-test-
                # cadence: a partial accumulation would miss the
                # exact-quota flush on every later cadence (the classic
                # loop's test-failure reset, same reasoning)
                tfetches = test_acc.fetches
                test_acc = StatsAccumulator(population=P)
                test_acc.fetches = tfetches
                restores += 1
                last_log_t = last_save_t = t_env
                continue
            _persist_flight(os.path.join(model_dir,
                                         "flight_recorder.json"))
            diag = wd.take_diagnosis() if wd is not None else None
            raise RuntimeError(
                f"sebulba dispatch failure exhausted the degradation "
                f"ladder at t_env={t_env} ({ladder.describe()})"
                + (f"; stall diagnosis: {diag.message()}" if diag else "")
                + f" — last failure: {failed}") from failed
    except BaseException as e:
        rec.mark("crash", t_env=t_env,
                 error=f"{type(e).__name__}: {e}"[:300])
        _persist_flight(os.path.join(results_dir, "flight_recorder.json"))
        rec.close()
        raise
    finally:
        stop_event.set()
        with cond:
            cond.notify_all()
        if wd is not None:
            wd.stop()
        if wd_actor is not None:
            wd_actor.stop()
        guard.uninstall()
        if pulse is not None:
            pulse.close()

    ts = _snapshot_state() or seb.join(rs0, ls)
    if guard.triggered:
        rec.mark("shutdown", t_env=t_env, signame=guard.signame or "")
        _persist_flight(os.path.join(results_dir, "flight_recorder.json"))
        stall = (wd.take_diagnosis() if wd is not None else None) or \
                (wd_actor.take_diagnosis() if wd_actor is not None
                 else None)
        if stall is not None:
            log.warning(f"watchdog: {stall.message()} — diagnosis "
                        f"persisted to {model_dir}/stall_diagnosis.json")
        log.warning(f"shutdown requested ({guard.signame}) at "
                    f"t_env={t_env} — stopping gracefully")
        if cfg.save_model and res.emergency_checkpoint \
                and watchdog.state_intact(ts):
            if _acquire_save_lock("preemption exit"):
                save_to = None
                deadline = (watchdog.ExitDeadline(
                                max(res.stall_grace_s, 60.0),
                                res.stall_exit_code,
                                label="sebulba exit emergency checkpoint")
                            if wd is not None else nullcontext())
                try:
                    with deadline:
                        if stop_ok:
                            try:
                                save_to = watchdog.retry_call(
                                    lambda: save_checkpoint(
                                        model_dir, t_env, _ckpt_state(ts),
                                        gather_retries=res.dispatch_retries,
                                        gather_backoff_s=res.retry_backoff_s,
                                        topology=_save_topology()),
                                    attempts=1 + res.dispatch_retries,
                                    backoff_s=res.retry_backoff_s,
                                    label="checkpoint.emergency")
                            except Exception:  # noqa: BLE001
                                log.exception(
                                    "collective emergency checkpoint "
                                    "failed on the sebulba exit — "
                                    "degrading to the per-host shard "
                                    "save")
                        if save_to is None:
                            # degraded exit (docs/RESILIENCE.md §6):
                            # write this host's addressable shard only —
                            # no collectives, cannot hang on a dead peer
                            with rec.span("checkpoint.shard_save",
                                          t_env=t_env):
                                save_to = save_checkpoint_shards(
                                    model_dir, t_env, _ckpt_state(ts),
                                    topology=_save_topology())
                except Exception:  # noqa: BLE001 — exit stays orderly
                    log.exception("emergency checkpoint failed on the "
                                  "sebulba exit path")
                finally:
                    save_lock.release()
                if save_to is not None:
                    log.info(f"emergency checkpoint saved to {save_to}")
        log.info(f"resume with checkpoint_path={model_dir} (newest valid "
                 f"step selected automatically)")
    else:
        log.info("Finished Training")
        log.info(f"sebulba totals: actor idle {idle['actor_s']:.2f}s, "
                 f"learner idle {idle['learner_s']:.2f}s, "
                 f"wall {time.time() - start_time:.2f}s")
    rec.close()
    return ts


class _NonFiniteEscalation(RuntimeError):
    """Internal control flow: the non-finite streak hit
    ``resilience.nonfinite_tolerance`` inside the sebulba log cadence —
    routed through the epoch ladder (restore rung) exactly like a
    persistent dispatch failure."""

    def __init__(self, streak: int):
        super().__init__(f"training diverged: {streak} consecutive "
                         f"non-finite train steps")


def evaluate_sequential(exp: Experiment, logger: Logger,
                        results_dir: str) -> TrainState:
    """Eval/replay/benchmark entry (reference ``evaluate_sequential``,
    ``per_run.py:74-101``): greedy episodes on the single-env runner, with
    optional replay (npz), animation (gif) and benchmark CSV export."""
    cfg = exp.cfg
    log = logger.console_logger
    ts = exp.init_train_state(cfg.seed)
    if cfg.checkpoint_path:
        found = find_checkpoint(cfg.checkpoint_path, cfg.load_step)
        if found is not None:
            dirname, step = found
            from .utils.checkpoint import (CheckpointFormatError,
                                           load_learner_state)
            try:
                ts = load_checkpoint(dirname, ts, verify=False)
                log.info(f"loaded full state from {dirname}")
            except CheckpointFormatError:
                raise        # unreadable format: no fallback applies
            except ValueError as e:
                # eval config differs from the training config (other
                # env-lane count, dense-vs-compact replay, DP shapes):
                # fall back to the learner subtree — the reference's
                # model-only checkpoint semantics (per_run.py:185-187)
                log.info(f"full-state restore rejected ({e}); trying "
                         f"model-only restore")
                ts = load_learner_state(dirname, ts)
                log.info(f"loaded learner (model-only) from {dirname}; "
                         f"runner state starts fresh")

    er = exp.episode_runner
    rs = er.init_state(jax.random.PRNGKey(cfg.seed + 2))
    params = ts.learner.params["agent"]

    trajs = []
    returns = []
    for ep in range(cfg.test_nepisode):
        rs, batch, stats, traj = er.run(params, rs, test_mode=True,
                                        capture_trajectory=True)
        trajs.append(traj)
        returns.append(float(np.sum(jax.device_get(stats.episode_return))))
    log.info(f"eval over {len(returns)} episodes: "
             f"return_mean={np.mean(returns):.3f} ± {np.std(returns):.3f}")
    logger.log_stat("test_return_mean", float(np.mean(returns)), 0)

    # reference per_run.py:85,92: in full-evaluate mode only every
    # ``animation_interval_evaluation``-th episode is rendered/animated
    anim_every = max(cfg.animation_interval_evaluation, 1)
    anim_eps = [i for i in range(len(trajs))
                if not cfg.evaluate or i % anim_every == 0]
    if cfg.save_replay:
        for i in anim_eps:
            p = er.save_replay(trajs[i],
                               os.path.join(results_dir,
                                            f"replay_episode_{i}.npz"))
        log.info(f"replays saved to {results_dir} ({len(anim_eps)} episodes)")
    if cfg.save_animation:
        for i in anim_eps:
            p = er.save_animation(
                trajs[i], os.path.join(results_dir,
                                       f"animation_episode_{i}.gif"))
        if p:
            log.info(f"animations saved to {results_dir} "
                     f"({len(anim_eps)} episodes)")
    if cfg.benchmark_mode:
        # reference exports CSVs only in benchmark mode (per_run.py:96-101)
        p = er.benchmark_csv(trajs, os.path.join(results_dir,
                                                 "benchmark.csv"))
        log.info(f"benchmark CSV saved to {p}")
    return ts


if __name__ == "__main__":          # `python -m t2omca_tpu.run train ...`
    import sys

    from .__main__ import main
    sys.exit(main())
