"""Vectorized rollout runner — the TPU replacement for the subprocess farm.

Re-creates ``ParallelRunner`` (``/root/reference/parallel_runner.py:13-287``,
C3) with the Anakin/PureJaxRL pattern (SURVEY.md §7.1): instead of
``batch_size_run`` daemon processes exchanging pickled NumPy over Pipes, the
pure-functional env is ``jax.vmap``-ed over the env axis and ``lax.scan``-ed
over episode time, with MAC action selection fused into the same XLA program.
"runner↔env communication" is a function call inside one compiled program —
the entire IPC tier (``env_worker``, ``CloudpickleWrapper``, the five-message
Pipe protocol, ``:234-287``) has no equivalent because nothing crosses a
process boundary.

Semantics preserved:

* per-env independent streams: worker ``i`` gets ``seed + i`` (Q8) → here
  ``jax.random.split`` of a per-rollout key, one subkey per env lane;
* per-env Welford obs normalizers persist across episodes (reference: one
  per subprocess lifetime; here carried in ``RunnerState`` and threaded back
  into ``env.reset``) and update even in test mode (Q4);
* actions recorded into the episode at the pre-step slot (Q15);
* time-limit termination recorded as non-terminal for bootstrapping (Q7):
  ``terminated & ~info.episode_limit``;
* stats summed over envs and episodes, logged as ``<k>_mean = v/n`` with the
  same keys (``parallel_runner.py:202-231``, §5.5 metric contract);
* epsilon logged from the selector schedule (``:217-218``).

The env in this build terminates only at ``episode_limit``, so every lane
runs exactly ``T`` slots and ``filled`` is all-ones — the general masks are
still produced for parity with the M4 scheme.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
from flax import struct

from ..components.episode_buffer import CompactEntityObs, TimeMajorEpisodes
from ..config import TrainConfig
from ..controllers.basic_mac import BasicMAC
from ..envs.mec_offload import EnvParams, EnvState, MultiAgvOffloadingEnv
from ..envs.normalization import (RewardScaleState, reset_reward_scale,
                                  scale_reward)
from ..envs.registry import make_scenario_distribution

#: fold_in salt for the per-rollout scenario-sampling key: the sampler
#: key is folded OFF the rollout key, never split from it — splitting
#: would re-pair the threefry counters of the existing reset/scan split
#: and silently change every env stream even for the fixed default
#: scenario (the graftworld bit-parity contract, tests/test_graftworld.py)
_SCENARIO_SALT = 0x5CE7


@struct.dataclass
class RunnerState:
    """Cross-episode carried state (one vmap lane = one reference worker)."""

    env_states: EnvState      # batched (B, ...) — holds the persistent norms
    key: jnp.ndarray          # PRNG key
    t_env: jnp.ndarray        # () int32 — global env-step cursor
    # per-lane reward-scaling state (envs/normalization.RewardScaleState;
    # active only under env_args.reward_scaling, but always carried so the
    # checkpoint pytree is config-independent)
    rscale: RewardScaleState
    # per-lane scenario instances (graftworld EnvParams, batched (B, ...)):
    # the knobs the CURRENT episode of each lane runs under, resampled
    # from the config's ScenarioDistribution at every rollout start.
    # Carried so (a) checkpoints record the active scenarios, (b) the
    # data-parallel/sebulba placement rules shard them with their lanes
    # (parallel/mesh.py, parallel/sebulba.py)
    env_params: EnvParams


@struct.dataclass
class RolloutStats:
    """Per-rollout stats with the reference's terminal-info semantics
    (``/root/reference/parallel_runner.py:168-170,226-231``): the logged
    ``<k>_mean`` keys aggregate the info dict of the TERMINAL step only
    (the reference collects ``final_env_infos`` at termination and sums
    those), not per-step sums. All info fields here are the terminal-step
    values per env lane; ``episode_return``/``episode_length`` feed
    ``return_mean`` and ETA accounting."""

    episode_return: jnp.ndarray            # (B,) summed reward (return_mean)
    episode_length: jnp.ndarray            # (B,)
    reward: jnp.ndarray                    # (B,) terminal-step values below
    delay_reward: jnp.ndarray              # (B,)
    overtime_penalty: jnp.ndarray          # (B,)
    channel_utilization_rate: jnp.ndarray  # (B,)
    conflict_ratio: jnp.ndarray            # (B,)
    episode_limit: jnp.ndarray             # (B,) terminated-by-time-limit
    task_completion_rate: jnp.ndarray      # (B,)
    task_completion_delay: jnp.ndarray     # (B,)
    deadline_miss_rate: jnp.ndarray        # (B,)
    epsilon: jnp.ndarray                   # ()
    # per-lane scenario-family tag (graftworld): which family slice each
    # episode ran under — the stats accumulators group the terminal-info
    # aggregation by it (per-slice generalization eval, utils/stats.py)
    scenario: jnp.ndarray                  # (B,) int32
    # a catalog trunk's routing counters over the whole rollout
    # (models/trunk.moe_counters: moe_pairs_held / _routed / _load_max /
    # _dropped, scalars); empty — no leaves — for every other agent
    moe: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ParallelRunner:
    env: MultiAgvOffloadingEnv
    mac: BasicMAC
    cfg: TrainConfig

    @property
    def batch_size(self) -> int:
        return self.cfg.batch_size_run

    @property
    def compact_store(self) -> bool:
        """Store the factored entity obs instead of the flattened tensor
        (ops/query_slice.entity_store_eligible)."""
        from ..ops.query_slice import entity_store_eligible
        return entity_store_eligible(self.cfg)

    def get_env_info(self) -> Dict[str, int]:
        return self.env.get_env_info()

    @property
    def scenario(self):
        """The config's scenario distribution (graftworld) — a frozen,
        hashable dataclass the jitted rollout closes over as static
        structure; built on demand (cheap: pure dataclass assembly)."""
        return make_scenario_distribution(self.cfg.env_args)

    def _sample_scenarios(self, key: jax.Array,
                          member=None) -> EnvParams:
        """One EnvParams instance per lane, from a ``fold_in`` side key
        (see ``_SCENARIO_SALT``): each lane draws its own scenario with
        zero extra dispatches — the sampling is part of the rollout
        program. ``member`` (a traced graftpop member index, only under
        ``population.scenario_salt``) folds a per-member salt into the
        sampler key so vmapped members draw different scenario
        instances from the same distribution
        (envs/graftworld.member_scenario_key); ``None`` keeps the
        pre-population key chain bit-identical."""
        scn = self.scenario
        k = jax.random.fold_in(key, _SCENARIO_SALT)
        if member is not None:
            from ..envs.graftworld import member_scenario_key
            k = member_scenario_key(k, member)
        keys = jax.random.split(k, self.batch_size)
        return jax.vmap(lambda k: scn.sample(k, self.env))(keys)

    # ------------------------------------------------------------------ state

    def init_state(self, key: jax.Array) -> RunnerState:
        """Initial env states; norms start fresh (as at subprocess spawn).
        ``env_args.seed`` is folded into the key chain (Q8: the reference
        hands worker ``i`` ``seed + i``; here one fold_in re-seeds the whole
        per-lane split, so two configs differing only in env seed roll
        different worlds)."""
        key = jax.random.fold_in(key, self.cfg.env_args.seed)
        key, k_reset = jax.random.split(key)
        env_params = self._sample_scenarios(k_reset)
        states, *_ = jax.vmap(self.env.reset)(
            jax.random.split(k_reset, self.batch_size), None, env_params)
        return RunnerState(
            env_states=states, key=key,
            t_env=jnp.zeros((), jnp.int32),
            rscale=RewardScaleState.create(gamma=self.cfg.gamma,
                                           dim=self.batch_size),
            env_params=env_params)

    def _moe_counters(self, aux_seq, env_steps: int) -> dict:
        """The rollout's ``moe_*`` counters from the per-step ``aux`` of
        ``BasicMAC.act`` (a catalog trunk's routed pairs, stacked over the
        scan); ``{}`` where the agent routes nothing."""
        if not aux_seq:
            return {}
        from ..models.trunk import moe_counters
        a = self.mac.n_agents
        with jax.named_scope("act.forward"):
            return moe_counters(aux_seq, env_steps * a * (a + 1),
                                self.mac.trunk)

    # ------------------------------------------------------------------ rollout

    def run(self, params, rs: RunnerState, test_mode: bool = False,
            capture: bool = False, eps_scale=None, member=None):
        """One synchronous batched episode. Pure → jittable; ``test_mode``
        (greedy selection) and ``capture`` are static Python bools.

        With ``capture=True`` a fourth return value carries the per-step
        visualization fields (pre-step AGV positions, serving MECs, ACKs) as
        ``(T, B, ...)`` arrays — the same scan emits them, so the trajectory
        is exactly the episode in the returned batch (no re-run, no drift)."""
        out = self.run_raw(params, rs, test_mode=test_mode, capture=capture,
                           eps_scale=eps_scale, member=member)
        if capture:
            new_rs, tm, stats, viz = out
            return new_rs, tm.to_batch(), stats, viz
        new_rs, tm, stats = out
        return new_rs, tm.to_batch(), stats

    def run_raw(self, params, rs: RunnerState, test_mode: bool = False,
                capture: bool = False, eps_scale=None, member=None):
        """``run`` minus the episode-batch assembly: returns the scan's
        time-major emission (``TimeMajorEpisodes``) so the fused superstep
        can scatter it straight into the replay ring without ever
        materializing the ``(B, T+1, ...)`` batch. ``run`` itself is
        ``run_raw`` + ``to_batch()`` — one rollout definition for both
        paths.

        ``eps_scale``/``member`` are the graftpop per-member seams
        (traced scalars from the PopulationSpec the population
        superstep vmaps over): the epsilon-schedule multiplier and the
        scenario-sampler member salt. ``None`` defaults keep every
        pre-population caller's program byte-identical."""
        b, t_len = self.batch_size, self.env.cfg.episode_limit
        key, k_reset, k_scan = jax.random.split(rs.key, 3)
        # qslice weight folds are loop-invariant: do them once per rollout,
        # not once per scan step (no-op on other acting paths)
        params = self.mac.prepare_acting_params(params)

        # graftworld: every lane samples a fresh scenario instance at
        # episode start (per-lane EnvParams, one traced program for the
        # whole distribution — fixed/uniform/mixture alike). The sampler
        # key folds off rs.key so the env/action key streams are
        # untouched (bit-parity at the fixed default scenario)
        with jax.named_scope("rollout.reset"):
            env_params = self._sample_scenarios(rs.key, member=member)

            # reset every lane, carrying each lane's Welford normalizer (Q4)
            reset_keys = jax.random.split(k_reset, b)
            env_states, obs, gstate, avail = jax.vmap(self.env.reset)(
                reset_keys, rs.env_states.norm, env_params)

        hidden = self.mac.init_hidden(b)

        compact_store = self.compact_store
        sd = jnp.dtype(self.cfg.replay.store_dtype)

        def obs_store(env_states, obs, compact):
            """Pre-step observation in its storage form (Q15 slot). Compact
            leaves stay f32 even under store_dtype=bf16: they are raw
            UN-normalized features (O(1e4) data sizes), where bf16 error is
            amplified ~|mean|/std by the learner's re-normalization — and
            at ~1/20th the footprint of the dense obs there is nothing
            worth saving."""
            if not compact_store:
                return obs.astype(sd)
            rows, _, mean, std = compact
            return CompactEntityObs(
                rows=rows,
                mec_index=env_states.mec_index.astype(jnp.int8),
                mean=mean, std=std)

        # reward scaling (env_args.reward_scaling): the discounted-return
        # accumulator resets each episode, the running std persists (C2
        # RewardScaling semantics). Train rollouts only — eval batches are
        # never trained on, and updating the std from greedy episodes
        # would perturb the training scale across test cadences.
        scale_on = self.cfg.env_args.reward_scaling and not test_mode
        rscale0 = reset_reward_scale(rs.rscale)

        def step_fn(carry, key_t):
            env_states, obs, gstate, avail, hidden, t_env, rscale = carry
            k_act, k_env = jax.random.split(key_t)
            # entity-table acting / compact storage: the factored obs is a
            # pure function of the carried env state (same post-update norm
            # stats the carried obs was normalized with), so recompute it
            # here instead of widening the carry
            with jax.named_scope("env.obs"):
                compact = (jax.vmap(self.env.compact_obs)(env_states,
                                                          env_params)
                           if self.mac.use_entity_tables or compact_store
                           else None)
            actions, hidden, eps, aux = self.mac.act(
                params, obs, avail, hidden, k_act, t_env,
                test_mode=test_mode, compact=compact, eps_scale=eps_scale)
            # Q15: the action is recorded with the pre-step observation.
            # Cast to the storage dtype here so the scan stacks the compact
            # representation (the f32 episode stack is the HBM hot spot);
            # avail narrows to bool — it is a predicate, and bool storage
            # makes arithmetic misuse a type error
            with jax.named_scope("rollout.store"):
                pre = (obs_store(env_states, obs, compact),
                       gstate.astype(sd), avail > 0, actions)
            viz = ((env_states.pos, env_states.mec_index)
                   if capture else None)
            with jax.named_scope("env.step"):
                env_states, reward, terminated, info, obs, gstate, avail = \
                    jax.vmap(self.env.step)(
                        env_states, actions, jax.random.split(k_env, b),
                        env_params)
            with jax.named_scope("rollout.store"):
                if scale_on:
                    rscale, rec_reward = scale_reward(rscale, reward)
                else:
                    rec_reward = reward
            env_terminal = terminated & ~info.episode_limit        # Q7
            ys = (pre, reward, rec_reward, env_terminal, info, eps,
                  (viz + (env_states.last_ack,)) if capture else (), aux)
            t_env = t_env + jnp.where(jnp.asarray(test_mode), 0, b)
            return (env_states, obs, gstate, avail, hidden, t_env,
                    rscale), ys

        carry = (env_states, obs, gstate, avail, hidden, rs.t_env, rscale0)
        carry, ys = jax.lax.scan(step_fn, carry, jax.random.split(k_scan, t_len))
        env_states, last_obs, last_gstate, last_avail, _, t_env, rscale = carry
        (pre, reward, rec_reward, env_terminal, info, eps, viz_seq,
         aux_seq) = ys
        obs_seq, gstate_seq, avail_seq, action_seq = pre

        with jax.named_scope("env.obs"):
            last_compact = (jax.vmap(self.env.compact_obs)(env_states,
                                                           env_params)
                            if compact_store else None)
        with jax.named_scope("rollout.store"):
            last_obs_store = obs_store(env_states, last_obs, last_compact)
            tm = TimeMajorEpisodes(
                obs=obs_seq,
                state=gstate_seq,
                avail_actions=avail_seq,
                actions=action_seq,
                reward=rec_reward,   # scaled under reward_scaling; else raw
                terminated=env_terminal,
                last_obs=last_obs_store,
                last_state=last_gstate.astype(sd),
                last_avail=last_avail > 0,
            )

        last = lambda x: x[-1]             # terminal-step info values
        stats = RolloutStats(
            episode_return=reward.sum(axis=0),
            episode_length=jnp.full((b,), t_len, jnp.float32),
            reward=last(reward),
            delay_reward=last(info.delay_reward),
            overtime_penalty=last(info.overtime_penalty),
            channel_utilization_rate=last(info.channel_utilization_rate),
            conflict_ratio=last(info.conflict_ratio),
            episode_limit=last(info.episode_limit).astype(jnp.float32),
            task_completion_rate=last(info.task_completion_rate),
            task_completion_delay=last(info.task_completion_delay),
            deadline_miss_rate=last(info.deadline_miss_rate),
            epsilon=eps[-1],
            scenario=env_params.family,
            moe=self._moe_counters(aux_seq, t_len * b),
        )
        new_rs = RunnerState(env_states=env_states, key=key, t_env=t_env,
                             rscale=rscale if scale_on else rs.rscale,
                             env_params=env_params)
        if capture:
            pos_seq, mec_seq, ack_seq = viz_seq
            viz = {"pos": pos_seq, "mec_index": mec_seq, "acks": ack_seq,
                   "actions": action_seq, "reward": reward, "info": info}
            return new_rs, tm, stats, viz
        return new_rs, tm, stats
