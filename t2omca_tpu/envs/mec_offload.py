"""Multi-AGV task-offloading environment as a pure function of pytrees.

TPU-native re-creation of ``MultiAgvOffloadingEnv``
(``/root/reference/environment_multi_mec.py:9-471``, C1): every 5 ms slot each
AGV either computes its head-of-queue job locally (action 0) or transmits it
over one of ``num_channels`` uplink channels to its serving MEC (actions
1..C); two AGVs picking the same channel under the same MEC collide (quirk
Q14: channels are reusable across MECs). Reward trades offload-latency
savings against deadline misses.

Where the reference is a Python object farmed out to subprocesses over Pipes
(``parallel_runner.py:21-32``), this is a ``reset``/``step`` pair of pure
functions over an ``EnvState`` pytree: ``jax.vmap`` gives thousands of envs
per chip, ``lax.scan`` gives the episode time axis, and the whole rollout
fuses into one XLA program — there is no IPC tier to replace.

Semantics preserved exactly (SURVEY.md §2.1/§7.5):

* step pipeline order (``:309-366``): one-hot last_action → per-MEC bincount
  collision resolution (counts>1 zeroed) → ACK ∈ {0 local, 1 success, −1
  collision} → reward (uses *pre-teleport* positions) → per-agent update
  (teleport mobility Q6, queue pop/age/expire/generate) → terminal info.
* reward branches (``:229-293``): see ``_reward``; the ``access_reward`` is
  computed but excluded from the returned reward (quirk Q3).
* observations: per-agent ``[last_ack, agent_inf(5)]`` or entity mode
  ``[ack_onehot(3), agent_inf(5), is_self]`` rows masked to same-MEC agents
  (``:148-182``); obs pass through a per-env Welford normalizer updated on
  every call including evaluation (Q4/Q5).
* job queues: the reference's Python lists with mid-list deletion
  (``:300-307``) become fixed-shape ``(max_jobs,)`` masked arrays with
  identical within-slot ordering — pop head → age all → drop expired →
  maybe generate (SURVEY.md §7.4(1)); ``max_jobs = latency_max/5 + 1``
  (bound stated at ``:90``).

Missing-module contracts supplied here (SURVEY.md §2.3): M1 (MEC/AGV/Job as
arrays; parameter values pinned in docs/SPEC.md), M2 (CRITIC, ``critic.py``),
M13 (uniform point in a circle), C2 (normalization as carried state).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..components.transforms import one_hot
from ..config import EnvConfig
from .critic import critic
from .normalization import (NormState, apply_norm, normalize,
                            normalize_batch, select_update,
                            welford_update_batch_factored)


def _round(x: jnp.ndarray, decimals: int = 0) -> jnp.ndarray:
    """Banker's rounding, matching python/numpy ``round`` in the reference."""
    return jnp.round(x, decimals)


@struct.dataclass
class EnvParams:
    """Per-instance scenario knobs (graftworld, docs/ENVS.md): every leaf
    is a jnp array, so the pytree vmaps alongside :class:`EnvState` — one
    compiled ``reset``/``step`` serves every scenario in a sampled
    distribution with zero extra dispatches and no per-family recompile
    (the JaxMARL/NAVIX parameterized-env pattern, PAPERS.md).

    The **default values are exactly the fixed scenario the physics
    constants below encode**: every knob enters the math as a
    multiply-by-1 / add-0 / all-true-mask neutral element, so
    ``env.default_params()`` reproduces the pre-graftworld env
    BIT-identically (pinned by tests/test_graftworld.py goldens). Knob
    groups:

    * fleet size — ``n_active`` of the static ``agv_num`` maximum; the
      rest are padded agents (no jobs, action 0 only, zero reward, a
      unique negative ``mec_index`` sentinel so they are invisible in
      every same-MEC visibility/collision structure);
    * channel fading / interference — linear SNR multiplier +
      additive interference power on the noise floor;
    * MEC placement & AGV mobility — placement stretch and per-step
      teleport probability (1.0 = the reference's always-teleport Q6);
    * job-arrival regime — base Bernoulli rate plus a sinusoidal
      surge modulation (non-stationary traffic);
    * deadline distribution — per-instance deadline budget (bounded by
      the static ``latency_max_ms``, which fixes the queue shape);
    * heterogeneous fleets — per-AGV compute/transmit capability
      scales (the first (A,)-shaped knobs);
    * ``family`` — the scenario-family tag carried through rollout
      stats for per-slice generalization eval (utils/stats.py).
    """

    n_active: jnp.ndarray           # () int32 — active AGVs (rest padded)
    gain_scale: jnp.ndarray         # () f32 — linear channel-gain multiplier
    interference_w: jnp.ndarray     # () f32 — adversarial interference [W]
    mec_scale: jnp.ndarray          # () f32 — MEC placement stretch
    teleport_prob: jnp.ndarray      # () f32 — per-step AGV teleport prob
    job_prob: jnp.ndarray           # () f32 — base job-arrival rate
    surge_amp: jnp.ndarray          # () f32 — traffic-surge amplitude
    surge_period: jnp.ndarray       # () f32 — surge period [slots]
    deadline_ms: jnp.ndarray        # () f32 — job deadline budget
    mec_compute_scale: jnp.ndarray  # () f32 — MEC compute-cap multiplier
    compute_scale: jnp.ndarray      # (A,) f32 — per-AGV compute capability
    tx_scale: jnp.ndarray           # (A,) f32 — per-AGV transmit power
    family: jnp.ndarray             # () int32 — scenario family/bucket id

    def agent_mask(self, n_agents: int) -> jnp.ndarray:
        """(A,) bool — True for active agents, False for padded ones."""
        return jnp.arange(n_agents) < self.n_active


@struct.dataclass
class EnvState:
    """Per-env dynamic state (one vmap lane = one reference subprocess env)."""

    time_slot: jnp.ndarray        # () int32
    mec_index: jnp.ndarray        # (A,) int32 — serving MEC per AGV
    pos: jnp.ndarray              # (A, 2) float32 — AGV positions [m]
    job_data: jnp.ndarray         # (A, J) float32 — data sizes [bits]
    job_deadline: jnp.ndarray     # (A, J) float32 — remaining deadline [ms]
    job_valid: jnp.ndarray        # (A, J) bool
    last_ack: jnp.ndarray         # (A,) int32 ∈ {-1, 0, 1}
    last_action: jnp.ndarray      # (A,) int32
    task_num: jnp.ndarray         # (A,) int32 — jobs generated
    task_success: jnp.ndarray     # (A,) int32 — jobs finished in deadline
    remain_delay: jnp.ndarray     # (A,) float32 — completion-delay accumulator
    norm: NormState               # obs Welford stats (shared across agents, Q4)


@struct.dataclass
class StepInfo:
    """Fixed-key ``info`` dict equivalent (SURVEY.md §5.5 metric contract)."""

    reward: jnp.ndarray
    delay_reward: jnp.ndarray
    overtime_penalty: jnp.ndarray
    channel_utilization_rate: jnp.ndarray
    conflict_ratio: jnp.ndarray
    episode_limit: jnp.ndarray          # bool: terminated due to time limit
    task_completion_rate: jnp.ndarray   # valid when episode_limit
    task_completion_delay: jnp.ndarray  # valid when episode_limit
    # deadline-miss rate: generated jobs neither completed in deadline nor
    # still queued, / generated (graftworld per-slice eval metric — counts
    # late local/offload completions AND queue-expired drops exactly once)
    deadline_miss_rate: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class MultiAgvOffloadingEnv:
    """Static physics + topology; hashable, so ``jit`` can close over it.

    Physics constants are the reference's (``environment_multi_mec.py:49-57``);
    M1 parameter values (compute caps, transmit power, job distribution) are
    the pinned spec of docs/SPEC.md.
    """

    cfg: EnvConfig

    # ---- constants (reference :49-54)
    computation_cycles: float = 31250.0   # cycles/bit
    bandwidth: float = 5e6                # Hz
    noise_power: float = 1e-11            # W
    path_loss_base: float = 3.0           # NB reference uses base-3, not dB→10
    channel_gain_db: float = 5.0
    t_length: float = 5.0                 # ms/slot

    # ---- derived sizes
    @property
    def n_agents(self) -> int:
        return self.cfg.agv_num

    @property
    def n_mec(self) -> int:
        return self.cfg.mec_num

    @property
    def n_actions(self) -> int:
        return self.cfg.num_channels + 1

    @property
    def max_jobs(self) -> int:
        # latency_max/5 + 1 (reference :90): a job survives ≤ latency_max/5
        # slots after its generation slot, and ≤1 job is generated per slot.
        return int(self.cfg.latency_max_ms / self.t_length) + 1

    @property
    def obs_entity_feats(self) -> int:
        return 9  # ack_onehot(3) + agent_inf(5) + is_self(1)

    @property
    def state_entity_feats(self) -> int:
        # ack_onehot(3) + agent_inf(5); with state_last_action the per-agent
        # action one-hot joins the state (reference env_info arithmetic
        # divides the flat state length by n_agents, :435-438)
        if self.cfg.state_last_action:
            return 8 + self.n_actions
        return 8

    @property
    def obs_dim(self) -> int:
        if self.cfg.obs_entity_mode:
            return self.n_agents * self.obs_entity_feats
        return 6  # [last_ack, agent_inf(5)]

    @property
    def state_dim(self) -> int:
        return self.n_agents * self.state_entity_feats

    def default_params(self) -> EnvParams:
        """The fixed reference scenario as an :class:`EnvParams` instance:
        every knob is the neutral element of the expression it enters, so
        running with these is bit-identical to the pre-graftworld env
        (pinned golden digests in tests/test_graftworld.py)."""
        a = self.n_agents
        return EnvParams(
            n_active=jnp.asarray(a, jnp.int32),
            gain_scale=jnp.asarray(1.0, jnp.float32),
            interference_w=jnp.asarray(0.0, jnp.float32),
            mec_scale=jnp.asarray(1.0, jnp.float32),
            teleport_prob=jnp.asarray(1.0, jnp.float32),
            job_prob=jnp.asarray(self.cfg.job_prob, jnp.float32),
            surge_amp=jnp.asarray(0.0, jnp.float32),
            surge_period=jnp.asarray(40.0, jnp.float32),
            deadline_ms=jnp.asarray(self.cfg.latency_max_ms, jnp.float32),
            mec_compute_scale=jnp.asarray(1.0, jnp.float32),
            compute_scale=jnp.ones((a,), jnp.float32),
            tx_scale=jnp.ones((a,), jnp.float32),
            family=jnp.asarray(0, jnp.int32),
        )

    def _p(self, params: "EnvParams | None") -> EnvParams:
        """Resolve the optional ``params`` argument: None = the fixed
        default scenario (keeps every pre-graftworld call site valid)."""
        return self.default_params() if params is None else params

    def mec_positions(self, params: "EnvParams | None" = None) -> jnp.ndarray:
        """MECs on a line at spacing 2*radius (reference :23-28), stretched
        by ``params.mec_scale`` (1.0 = reference placement, bit-exact)."""
        r = self.cfg.mec_radius_m
        xs = np.arange(self.n_mec) * (2 * r) + r
        ys = np.full(self.n_mec, r)
        base = jnp.asarray(np.stack([xs, ys], axis=1), jnp.float32)
        if params is None:
            return base
        return base * params.mec_scale

    # ------------------------------------------------------------------ helpers

    def _mec_lookup(self, table: jnp.ndarray,
                    mec_index: jnp.ndarray) -> jnp.ndarray:
        """Each agent's row of a per-MEC ``table (n_mec, ...)`` → ``(A, ...)``,
        as a one-hot select over the ``n_mec`` rows rather than a gather: a
        TPU gather fetches its A rows one after another, the select is
        element-wise work that fuses into its consumer. Exactly one term of the sum is the
        table's entry and the rest are exact zeros, so the result carries
        the table's bits. An index outside ``[0, n_mec)`` — a padded agent's
        sentinel (``_pad_sentinel``) — matches no row and reads zeros."""
        hit = mec_index[:, None] == jnp.arange(self.n_mec)            # (A, M)
        hit = hit.reshape(hit.shape + (1,) * (table.ndim - 1))
        return jnp.where(hit, table[None], 0).sum(axis=1)

    def _random_positions(self, key: jax.Array, mec_index: jnp.ndarray,
                          params: EnvParams) -> jnp.ndarray:
        """M13: uniform point inside the serving MEC's communication circle."""
        k1, k2 = jax.random.split(key)
        a = self.n_agents
        u = jax.random.uniform(k1, (a,))
        theta = jax.random.uniform(k2, (a,), maxval=2 * np.pi)
        rad = self.cfg.communication_range_m * jnp.sqrt(u)
        offset = jnp.stack([rad * jnp.cos(theta), rad * jnp.sin(theta)], axis=1)
        return self._mec_lookup(self.mec_positions(params), mec_index) \
            + offset

    def _local_delay(self, data: jnp.ndarray, decimals: int,
                     params: EnvParams) -> jnp.ndarray:
        """Local compute delay in ms (reference :127, :247-248); the cap is
        scaled per-AGV by ``params.compute_scale`` (heterogeneous fleets).
        The knob divides the reference expression as a TRAILING step:
        XLA rewrites the reference's divide-by-constant caps into
        reciprocal multiplies, so folding the scale into the divisor
        would change the lowering (and the bits) even at scale=1 —
        appending ``/ scale`` keeps the default path's ops identical
        (/1.0 is exact) and the parity goldens green."""
        return _round(self.computation_cycles * data
                      / self.cfg.user_compute_cap * 1000.0
                      / params.compute_scale, decimals)

    def _offload_delay(self, data: jnp.ndarray, pos: jnp.ndarray,
                       mec_index: jnp.ndarray,
                       params: EnvParams) -> jnp.ndarray:
        """Shannon-rate transmit delay + MEC compute delay in ms
        (reference ``calculate_offload_delay`` :106-121). Note the quirk kept
        verbatim: path-loss linearization uses base ``self.path_loss`` (=3),
        i.e. ``3 ** (-dB/10)``, not ``10 ** (-dB/10)`` (:112). graftworld
        knobs enter as TRAILING neutral operations on the reference
        expressions (multiply by 1 / divide by 1, exact): per-AGV transmit
        scale and channel-fading gain multiply the reference SNR,
        interference divides it by ``1 + I/N0`` (algebraically the lifted
        noise floor ``N0 + I``), MEC compute delay divides by the cap
        scale — so the default (1/1/0/1) path runs the reference ops
        bit-identically (see the ``_local_delay`` lowering note). The
        serving MEC's position is a one-hot select (``_mec_lookup``): a
        padded agent's sentinel reads the origin, a dead value behind the
        ``has_job`` gate of every caller."""
        gain_lin = 10.0 ** (self.channel_gain_db / 10.0)
        d = jnp.linalg.norm(
            pos - self._mec_lookup(self.mec_positions(params), mec_index),
            axis=-1)
        pl_db = 128.1 + 37.6 * jnp.log10(d + 0.1)
        pl_lin = self.path_loss_base ** (-pl_db / 10.0)
        snr = (gain_lin * self.cfg.transmit_power_w * pl_lin
               / self.noise_power
               * params.gain_scale * params.tx_scale
               / (1.0 + params.interference_w / self.noise_power))
        rate = self.bandwidth * jnp.log2(1.0 + snr)
        transmit = data / rate * 1000.0
        compute = (self.computation_cycles * data
                   / self.cfg.mec_compute_cap) * 1000.0 \
            / params.mec_compute_scale
        return _round(transmit + compute, 2)

    def _agent_inf(self, state: EnvState, params: EnvParams) -> jnp.ndarray:
        """Per-agent feature rows ``[data_size, data_delay, offload_delay,
        remaining_delay, buffer_length]`` (reference ``get_agent_inf``
        :123-146), zeros for empty buffers (padded agents never hold a
        job, so their rows are zero by the same gate)."""
        has_job = state.job_valid[:, 0]
        data = state.job_data[:, 0]
        inf = jnp.stack([
            data,
            self._local_delay(data, 0, params),
            self._offload_delay(data, state.pos, state.mec_index, params),
            state.job_deadline[:, 0],
            state.job_valid.sum(axis=1).astype(jnp.float32),
        ], axis=1)
        return jnp.where(has_job[:, None], inf, 0.0)

    @staticmethod
    def _ack_onehot(last_ack: jnp.ndarray) -> jnp.ndarray:
        """ack_mapping {-1:[1,0,0], 0:[0,1,0], 1:[0,0,1]} (reference :7);
        built with the M15 OneHot transform."""
        return one_hot(last_ack + 1, 3)

    # ------------------------------------------------------------------ obs/state

    def _entity_parts(self, state: EnvState, params: EnvParams
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Factored entity obs pieces: feature ``rows (A, 8)`` and the
        ``same_mec (A, A)`` visibility mask. Padded agents carry a unique
        negative ``mec_index`` sentinel (set at reset/teleport), so the
        equality mask makes them visible only to themselves — the SAME
        rule the compact-entity storage path reconstructs from the stored
        ``mec_index`` (ops/query_slice.py), with zero schema change."""
        inf = self._agent_inf(state, params)
        ack1h = self._ack_onehot(state.last_ack)
        rows = jnp.concatenate([ack1h, inf], axis=1)               # (A, 8)
        same_mec = state.mec_index[:, None] == state.mec_index[None, :]
        return rows, same_mec

    def _raw_obs(self, state: EnvState, params: EnvParams) -> jnp.ndarray:
        """(A, obs_dim) pre-normalization observations."""
        if self.cfg.obs_entity_mode:
            a = self.n_agents
            rows, same_mec = self._entity_parts(state, params)
            ent = jnp.where(same_mec[:, :, None],
                            jnp.broadcast_to(rows[None], (a, a, 8)), 0.0)
            is_self = jnp.eye(a)[:, :, None]       # diagonal is always same-MEC
            ent = jnp.concatenate([ent, is_self], axis=2)          # (A, A, 9)
            return ent.reshape(a, a * self.obs_entity_feats)
        inf = self._agent_inf(state, params)
        return jnp.concatenate(
            [state.last_ack[:, None].astype(jnp.float32), inf], axis=1)

    def get_obs(self, state: EnvState, params: "EnvParams | None" = None,
                update_norm: bool = True) -> Tuple[EnvState, jnp.ndarray]:
        """Normalized per-agent observations. Default path: the Welford
        state is updated agent-by-agent in order, each agent normalized with
        the statistics *after its own update* — exactly the reference's
        sequential ``[self.obs_norm(self.get_obs_agent(i)) for i in
        range(n)]`` (``:184-186``, quirks Q4/Q5). With ``cfg.fast_norm`` the
        A-step sequential scan (the env-step serialization bottleneck at 64
        agents) becomes one order-free batched merge; equivalence-tolerance
        test in ``tests/test_normalization.py``."""
        params = self._p(params)
        if self.cfg.fast_norm and self.cfg.obs_entity_mode:
            # statistics from the FACTORED form (O(A·F), exact up to
            # reassociation — normalization.welford_update_batch_factored);
            # the normalized obs tensor is still produced from the
            # materialized raw matrix, but when no consumer reads it (the
            # entity-table acting + compact-storage stack) XLA dead-code
            # eliminates the whole O(A²) materialization from the rollout
            with jax.named_scope("env.normalizer"):
                rows, same_mec = self._entity_parts(state, params)
                norm = select_update(
                    state.norm,
                    welford_update_batch_factored(state.norm, rows,
                                                  same_mec),
                    update_norm)
            with jax.named_scope("env.obs"):
                obs = apply_norm(norm, self._raw_obs(state, params))
            return state.replace(norm=norm), obs

        with jax.named_scope("env.obs"):
            raw = self._raw_obs(state, params)

        # the dense normalizers update and apply in one call: both under
        # the normalizer's name
        with jax.named_scope("env.normalizer"):
            if self.cfg.fast_norm:
                norm, obs = normalize_batch(state.norm, raw,
                                            update=update_norm)
                return state.replace(norm=norm), obs

            def body(carry: NormState, x):
                carry, y = normalize(carry, x, update=update_norm)
                return carry, y

            norm, obs = jax.lax.scan(body, state.norm, raw)
            return state.replace(norm=norm), obs

    def compact_obs(self, state: EnvState,
                    params: "EnvParams | None" = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                               jnp.ndarray]:
        """Factored form of the entity observation for the entity-table
        acting path (``ops/query_slice.agent_forward_qslice_entity``):
        ``(rows (A, 8), same_mec (A, A) bool, mean (A, 9), std (A, 9))``.

        The full entity obs (``_raw_obs``) is ``A`` copies of the same 8
        feature rows under the same-MEC visibility mask plus an is-self
        diagonal; with ``fast_norm`` every agent row is normalized by the
        SAME per-position statistics (one shared ``NormState``, Q4), so
        ``(rows, mask, stats)`` reconstructs every agent's normalized obs
        exactly (pinned in tests/test_entity_tables.py). Must be called on
        the post-``get_obs`` state (its ``norm`` already updated) — the
        runner calls it on the state ``step``/``reset`` returned. Only
        valid for ``obs_entity_mode`` + ``fast_norm`` (the sequential
        normalizer gives each agent different prefix statistics)."""
        assert self.cfg.obs_entity_mode and self.cfg.fast_norm
        rows, same_mec = self._entity_parts(state, self._p(params))
        a = self.n_agents
        mean = state.norm.mean.reshape(a, self.obs_entity_feats)
        std = state.norm.std.reshape(a, self.obs_entity_feats)
        return rows, same_mec, mean, std

    def get_state(self, state: EnvState,
                  params: "EnvParams | None" = None) -> jnp.ndarray:
        """Global state: all-agent ACK one-hots ++ all-agent agent_inf rows,
        flattened (reference ``get_state`` :188-204); not normalized. With
        ``state_last_action`` the per-agent action one-hots are prepended —
        the reference declares the flag (:11) and keeps the concat slot
        commented (:196); wiring it preserves that config surface."""
        params = self._p(params)
        ack1h = self._ack_onehot(state.last_ack)
        inf = self._agent_inf(state, params)
        parts = [ack1h.reshape(-1), inf.reshape(-1)]
        if self.cfg.state_last_action:
            # M15 OneHot: the reference stores np.eye(n_actions)[actions]
            # (:318) and would concat it here (:196)
            parts.insert(0, one_hot(state.last_action,
                                    self.n_actions).reshape(-1))
        return jnp.concatenate(parts)

    def get_avail_actions(self, state: EnvState,
                          params: "EnvParams | None" = None) -> jnp.ndarray:
        """(A, n_actions) availability (reference :61-82): empty buffer ⇒ only
        action 0; ``edge_only`` forbids local compute when a job exists.
        Padded agents are masked to action 0 EVERYWHERE — they can never
        hold a job (the generator is mask-gated), but the explicit mask
        pins the invariant against any future job-path change."""
        params = self._p(params)
        has_job = state.job_valid[:, 0]
        idle_only = jnp.concatenate(
            [jnp.ones((self.n_agents, 1)),
             jnp.zeros((self.n_agents, self.n_actions - 1))], axis=1)
        if self.cfg.edge_only:
            busy = jnp.concatenate(
                [jnp.zeros((self.n_agents, 1)),
                 jnp.ones((self.n_agents, self.n_actions - 1))], axis=1)
        else:
            busy = jnp.ones((self.n_agents, self.n_actions))
        avail = jnp.where(has_job[:, None], busy, idle_only)
        mask = params.agent_mask(self.n_agents)
        return jnp.where(mask[:, None], avail, idle_only).astype(jnp.int32)

    def get_critic_score(self, state: EnvState, key: jax.Array,
                         params: "EnvParams | None" = None) -> jnp.ndarray:
        """CRITIC indicator matrix [task_prior, queueing-delay ratio,
        buffer-fill ratio] (+1e-6-scale noise) → per-agent scores (reference
        ``get_critic_score`` :84-104). ``task_prior`` is 1.0 for all AGVs in
        the released slice's single-type fleet (docs/SPEC.md); queueing delay
        is ``latency_max - remaining_deadline`` of the head job. The
        queueing-delay ratio is against the instance's deadline budget
        (``params.deadline_ms``, = latency_max at default); the fill
        ratio keeps the STATIC latency_max — it is the queue-capacity
        bound, a shape property. Padded agents score zero through the
        has-job gate (they never hold a job)."""
        params = self._p(params)
        has_job = state.job_valid[:, 0]
        lm = params.deadline_ms
        prior = jnp.where(has_job, 1.0, 0.0)
        # reciprocal-multiply, not division: XLA lowers the reference's
        # divide-by-constant-lm to exactly this form, so the traced-lm
        # default stays bit-identical (tests/test_graftworld.py goldens)
        delay_q = jnp.where(has_job,
                            (lm - state.job_deadline[:, 0]) * (1.0 / lm),
                            0.0)
        fill = jnp.where(
            has_job,
            state.job_valid.sum(axis=1)
            / (self.cfg.latency_max_ms / self.t_length + 1), 0.0)
        mat = jnp.stack([prior, delay_q, fill], axis=1)
        noise = 1e-6 * _round(jax.random.uniform(
            key, mat.shape, minval=0.9, maxval=1.1), 2)
        return critic(mat + noise)

    # ------------------------------------------------------------------ queues

    def _generate_jobs(self, state: EnvState, key: jax.Array,
                       params: EnvParams) -> EnvState:
        """``AGV.generate_job`` (M1 spec): with prob ``job_prob`` append a job
        ``(data ~ U[min,max] bits, deadline = params.deadline_ms)``; count it
        in ``task_num``. graftworld regime knobs: the arrival rate is the
        instance's ``job_prob`` modulated by a sinusoidal surge
        (non-stationary traffic; ``amp=0`` multiplies by exactly 1), and
        padded agents never generate (mask-gated). Defaults keep the
        Bernoulli draw bit-identical — same uniform draw, same threshold
        value."""
        k1, k2 = jax.random.split(key)
        a, j = self.n_agents, self.max_jobs
        p_eff = jnp.clip(
            params.job_prob
            * (1.0 + params.surge_amp
               * jnp.sin(2.0 * np.pi * state.time_slot.astype(jnp.float32)
                         / params.surge_period)), 0.0, 1.0)
        gen = jax.random.bernoulli(k1, p_eff, (a,)) \
            & params.agent_mask(a)
        data_new = jax.random.uniform(
            k2, (a,), minval=self.cfg.data_size_min,
            maxval=self.cfg.data_size_max)
        cnt = state.job_valid.sum(axis=1)
        slot = (jnp.arange(j)[None, :] == cnt[:, None]) & gen[:, None] \
            & (cnt[:, None] < j)
        return state.replace(
            job_data=jnp.where(slot, data_new[:, None], state.job_data),
            job_deadline=jnp.where(slot, params.deadline_ms,
                                   state.job_deadline),
            job_valid=state.job_valid | slot,
            task_num=state.task_num + gen.astype(jnp.int32),
        )

    def _pad_sentinel(self, mec_index: jnp.ndarray,
                      params: EnvParams) -> jnp.ndarray:
        """Give every padded agent a UNIQUE negative serving-MEC index.
        One representation covers every padding consumer: the same-MEC
        equality mask makes padded agents visible only to themselves (and
        the compact-entity store reconstructs the identical visibility
        from the stored ``mec_index`` with no schema change), and the
        collision histogram's ``one_hot`` maps out-of-range indices to
        zero rows, so padded agents never occupy a channel or count
        toward utilization — and every per-MEC table lookup is the same
        one-hot select (``_mec_lookup``), which reads zeros for them where
        a gather would wrap onto some real row. All-active (the default)
        selects the real indices bit-identically."""
        a = self.n_agents
        return jnp.where(params.agent_mask(a), mec_index,
                         -1 - jnp.arange(a, dtype=mec_index.dtype))

    def _update_users(self, state: EnvState, ack: jnp.ndarray,
                      key: jax.Array, params: EnvParams) -> EnvState:
        """``update_users`` per agent (reference :295-307), vectorized:
        teleport mobility (Q6), then pop head on ACK≠−1, age all deadlines by
        5 ms, drop expired, maybe generate. Ordering is load-bearing
        (SURVEY.md §7.4(1)). graftworld mobility: each agent teleports with
        ``params.teleport_prob`` (1.0 = the reference's unconditional
        teleport — the gate draw comes from a ``fold_in`` side key, so the
        reference key stream and the selected values are bit-identical)."""
        k_mec, k_pos, k_gen = jax.random.split(key, 3)

        # Q6: i.i.d. teleport, serving MEC redrawn uniformly. The teleport
        # gate key is folded off the parent key, NOT split from it — a
        # fourth split would re-pair the threefry counters and change
        # every draw above even at the default
        new_mec = jax.random.randint(k_mec, (self.n_agents,), 0, self.n_mec)
        new_pos = self._random_positions(k_pos, new_mec, params)
        tel = jax.random.uniform(
            jax.random.fold_in(key, 7), (self.n_agents,)) \
            < params.teleport_prob
        new_mec = jnp.where(tel, new_mec, state.mec_index)
        new_pos = jnp.where(tel[:, None], new_pos, state.pos)
        new_mec = self._pad_sentinel(new_mec, params)

        # pop head job where ACK != -1 (local compute or successful offload)
        popped = (ack != -1) & state.job_valid[:, 0]
        shift = lambda arr, fill: jnp.concatenate(
            [arr[:, 1:], jnp.full_like(arr[:, :1], fill)], axis=1)
        data = jnp.where(popped[:, None], shift(state.job_data, 0.0),
                         state.job_data)
        deadline = jnp.where(popped[:, None],
                             shift(state.job_deadline, 0.0),
                             state.job_deadline)
        valid = jnp.where(popped[:, None], shift(state.job_valid, False),
                          state.job_valid)

        # age all remaining jobs by one slot; drop expired (deadline <= 0)
        deadline = deadline - self.t_length
        keep = valid & (deadline > 0)
        # compact survivors to the front in FIFO order: destination slot =
        # exclusive prefix count of kept jobs (cumsum is monotone over the
        # source order, so stability is free), realized as a one-hot gather
        # matmul — cheaper on TPU than a stable argsort's sorting network
        dest = jnp.cumsum(keep, axis=1) - 1                   # (A, J)
        j = self.max_jobs
        gather = (jnp.where(keep, dest, -1)[:, :, None]
                  == jnp.arange(j)[None, None, :])            # (A, Jsrc, Jdst)
        # HIGHEST precision: the default TPU matmul runs the MXU in bf16,
        # which would lossily round job payload sizes every step — the
        # compaction must stay an exact permutation like the take_along_axis
        # it replaces
        gf = gather.astype(jnp.float32)
        hp = jax.lax.Precision.HIGHEST
        data = jnp.einsum("aj,ajd->ad", data, gf, precision=hp)
        deadline = jnp.einsum("aj,ajd->ad", deadline, gf, precision=hp)
        valid = gather.any(axis=1)

        state = state.replace(mec_index=new_mec, pos=new_pos, job_data=data,
                              job_deadline=deadline, job_valid=valid)
        return self._generate_jobs(state, k_gen, params)

    # ------------------------------------------------------------------ reward

    def _reward(self, state: EnvState, ack: jnp.ndarray, params: EnvParams
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, EnvState]:
        """Reference ``get_reward`` (:229-293), vectorized over the six
        branches. Uses pre-teleport positions and pre-update queues. Also
        applies the task_success/remain_delay counter side-effects the
        reference performs inside the reward pass. Padded agents
        contribute exactly zero: they never hold a job, so every branch
        mask is False for them. The per-miss penalty and the completion-
        delay bookkeeping use the instance's deadline budget
        (``params.deadline_ms`` — the value every job was stamped with)."""
        has_job = state.job_valid[:, 0]
        data = state.job_data[:, 0]
        deadline = state.job_deadline[:, 0]
        lm = params.deadline_ms

        local_delay = self._local_delay(data, 2, params)      # round(x, 2)
        offload_delay = self._offload_delay(data, state.pos,
                                            state.mec_index, params)

        is_local = has_job & (ack == 0)
        is_collision = has_job & (ack == -1)
        is_offload = has_job & (ack == 1)

        local_ok = is_local & (deadline - local_delay > 0)
        local_miss = is_local & ~(deadline - local_delay > 0)
        collision_expiring = is_collision & (deadline - self.t_length <= 0)
        offload_ok = is_offload & (deadline - offload_delay > 0)
        offload_miss = is_offload & ~(deadline - offload_delay > 0)

        delay_reward = jnp.where(is_offload, local_delay - offload_delay,
                                 0.0).sum()
        overtime = (jnp.where(local_miss | collision_expiring | offload_miss,
                              lm, 0.0)).sum()

        success = local_ok | offload_ok
        finish_delay = jnp.where(local_ok, local_delay, offload_delay)
        new_success = state.task_success + success.astype(jnp.int32)
        new_remain = state.remain_delay + jnp.where(
            success, lm - deadline + finish_delay, 0.0)

        reward = delay_reward - overtime                       # Q3: access_reward unused
        state = state.replace(task_success=new_success, remain_delay=new_remain)
        return reward, delay_reward, overtime, state

    # ------------------------------------------------------------------ API

    def reset(self, key: jax.Array, norm: NormState | None = None,
              params: "EnvParams | None" = None
              ) -> Tuple[EnvState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """→ (state, obs, global_state, avail_actions). Mirrors reference
        ``reset``/``reset_user`` (:206-227): fresh positions, empty buffers,
        one ``generate_job`` call, zero ACK/last_action; obs normalizer
        persists across resets (it lives for the life of the subprocess in
        the reference — pass the previous episode's ``norm`` to carry it).
        ``params`` selects the scenario instance (graftworld, docs/ENVS.md);
        None = the fixed default scenario, bit-identical to pre-graftworld."""
        params = self._p(params)
        k_mec, k_pos, k_gen = jax.random.split(key, 3)
        a, j = self.n_agents, self.max_jobs
        # positions from the draw itself, the sentinel after (the order
        # ``_update_users`` teleports in): a padded agent starts inside a
        # real MEC's circle, not around the origin its sentinel selects
        mec_draw = jax.random.randint(k_mec, (a,), 0, self.n_mec)
        state = EnvState(
            time_slot=jnp.zeros((), jnp.int32),
            mec_index=self._pad_sentinel(mec_draw, params),
            pos=self._random_positions(k_pos, mec_draw, params),
            job_data=jnp.zeros((a, j), jnp.float32),
            job_deadline=jnp.zeros((a, j), jnp.float32),
            job_valid=jnp.zeros((a, j), bool),
            last_ack=jnp.zeros((a,), jnp.int32),
            last_action=jnp.zeros((a,), jnp.int32),
            task_num=jnp.zeros((a,), jnp.int32),
            task_success=jnp.zeros((a,), jnp.int32),
            remain_delay=jnp.zeros((a,), jnp.float32),
            norm=NormState.create(self.obs_dim) if norm is None else norm,
        )
        state = self._generate_jobs(state, k_gen, params)
        state, obs = self.get_obs(state, params)
        return (state, obs, self.get_state(state, params),
                self.get_avail_actions(state, params))

    def fresh_norm(self, state: EnvState) -> EnvState:
        return state.replace(norm=NormState.create(self.obs_dim))

    def step(self, state: EnvState, actions: jnp.ndarray, key: jax.Array,
             params: "EnvParams | None" = None, update_norm: bool = True
             ) -> Tuple[EnvState, jnp.ndarray, jnp.ndarray, StepInfo,
                        jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """→ (state', reward, terminated, info, obs', global_state', avail').

        The reference worker protocol returns next-step obs/state/avail with
        the current-step reward (``parallel_runner.py:247-256``); this fuses
        both into one call. ``params`` is the lane's scenario instance —
        constant through the episode, resampled at reset by the runner's
        scenario distribution (graftworld). No gather anywhere in it: the
        lookups by serving MEC and by action are one-hot selects
        (``_mec_lookup``; pinned by tests/test_env.py's lowering test)."""
        params = self._p(params)
        mask = params.agent_mask(self.n_agents)
        actions = actions.astype(jnp.int32)

        # per-MEC collision resolution (reference :319-326; Q14). The
        # (mec, action) histogram is a one-hot einsum rather than a
        # scatter-add: one MXU matmul instead of A serialized scatter
        # updates per env; f32 accumulation is exact for counts < 2^24.
        mec1h = one_hot(state.mec_index, self.n_mec)          # (A, M)
        act1h = one_hot(actions, self.n_actions)              # (A, C)
        counts = jnp.einsum("am,ac->mc", mec1h, act1h,
                            precision=jax.lax.Precision.HIGHEST
                            ).astype(jnp.int32)
        masked = jnp.where(counts > 1, 0, counts)
        # utilization sums ALL slots incl. action-0 (reference :327-329 quirk)
        utilization = masked.sum() / (self.cfg.num_channels * self.n_mec)

        # the agent's own cell of the histogram: its MEC's row, then its
        # action's column — both one-hot selects (``_mec_lookup``), no gather
        chosen = jnp.where(act1h > 0,
                           self._mec_lookup(masked, state.mec_index),
                           0).sum(axis=1)
        # explicit int32: a weak-typed ack in the carried state would give
        # the rollout program weak output avals and force a second compile
        # when the driver chains the state back in. Padded agents are
        # pinned to ack 0 — their sentinel mec_index selects no row of the
        # histogram, so the raw lookup reads 0 and would say "collision"
        ack = jnp.where(actions == 0, 0,
                        jnp.where(chosen == 1, 1, -1)).astype(jnp.int32)
        ack = jnp.where(mask, ack, 0)
        # reciprocal-multiply over the ACTIVE count: the reference's
        # ``.mean()`` lowers div-by-constant-A to exactly this form, so
        # the all-active default is bit-identical while padded scenarios
        # divide by the true fleet size
        conflict_ratio = (ack == -1).astype(jnp.float32).sum() \
            * (1.0 / params.n_active.astype(jnp.float32))

        state = state.replace(
            time_slot=state.time_slot + 1,
            last_action=actions,
            last_ack=ack,
        )

        reward, delay_reward, overtime, state = self._reward(state, ack,
                                                             params)
        state = self._update_users(state, ack, key, params)

        terminated = state.time_slot >= self.cfg.episode_limit
        tn = state.task_num.sum()
        ts = state.task_success.sum()
        # deadline misses = generated − completed-in-deadline − still
        # queued: late local/offload completions and queue-expired drops
        # each leave the queue exactly once, so each missed job is
        # counted exactly once (per-slice eval metric, docs/ENVS.md)
        queued = state.job_valid.sum()
        info = StepInfo(
            reward=reward,
            delay_reward=delay_reward,
            overtime_penalty=overtime,
            channel_utilization_rate=utilization,
            conflict_ratio=conflict_ratio,
            episode_limit=terminated,
            task_completion_rate=ts / jnp.maximum(tn, 1),
            task_completion_delay=state.remain_delay.sum()
            / jnp.maximum(ts, 1),
            deadline_miss_rate=(tn - ts - queued) / jnp.maximum(tn, 1),
        )

        state, obs = self.get_obs(state, params, update_norm=update_norm)
        return (state, reward, terminated, info, obs,
                self.get_state(state, params),
                self.get_avail_actions(state, params))

    def get_env_info(self) -> Dict[str, int]:
        """Reference ``get_env_info`` (:421-439); copied onto args by the
        driver (``per_run.py:112-114``)."""
        info = {
            "state_shape": self.state_dim,
            "obs_shape": self.obs_dim,
            "n_actions": self.n_actions,
            "n_agents": self.n_agents,
            "episode_limit": self.cfg.episode_limit,
            "n_entities": self.n_agents,
        }
        if self.cfg.obs_entity_mode:
            info["obs_entity_feats"] = self.obs_entity_feats
        if self.cfg.state_entity_mode:
            info["state_entity_feats"] = self.state_entity_feats
        return info
