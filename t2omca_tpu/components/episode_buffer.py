"""Episode batch + replay buffers as device-resident pytrees (M4).

Re-creates the contracts of the unreleased ``components/episode_buffer``
(``EpisodeBatch`` / ``ReplayBuffer`` / ``PrioritizedReplayBuffer``, imported
at ``/root/reference/parallel_runner.py:3`` and ``/root/reference/per_run.py:16``;
contracts pinned in SURVEY.md §2.3 M4) — but where the reference keeps a
torch-tensor dict on CPU/GPU and slices it with Python, here the whole buffer
is a fixed-shape pytree living in device HBM and every operation (insert,
sample, priority update) is a pure jittable function. Sampling never leaves
the chip, so the rollout→insert→sample→train loop compiles into a handful of
XLA programs with no host round-trips.

Scheme (reference ``per_run.py:119-133``): ``state (T+1, S)``, per-agent
``obs (T+1, A, O)``, ``avail_actions (T+1, A, n_actions)``, ``actions (T, A)``,
``reward (T,)``, ``terminated (T,)``, ``filled (T,)``. The trailing
timestep T of obs/state/avail is the bootstrap observation (the reference
stores ``episode_limit + 1`` steps per episode, ``per_run.py:143-146``).
``actions_onehot`` (M15) is materialized on demand by the consumer, not
stored.

Prioritized replay: per-*episode* priorities (the reference samples whole
episodes and feeds back one ``|TD|+1e-6`` priority per sampled episode,
``per_run.py:224-238``, Q9). Instead of a sequential sum-tree — hostile to
XLA — sampling uses stratified inverse-CDF over the normalized priority
distribution (SURVEY.md §7.4(4)): O(capacity) vectorized ops, exact for the
β-weighted expectation, fine at the reference's buffer sizes (≤ a few
thousand episodes).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class CompactEntityObs:
    """Factored entity observation (``env.compact_obs``) as episode storage:
    ~``obs_dim/(rows+stats)`` ≈ 20× smaller than the flattened ``(A, A·F)``
    obs it reconstructs exactly (same-MEC visibility × shared per-position
    normalization affine; ops/query_slice.agent_forward_qslice_entity
    consumes it directly, tests/test_entity_tables.py pins the
    reconstruction)."""

    rows: jnp.ndarray       # (B, T+1, A, F-1) — raw entity feature rows
    mec_index: jnp.ndarray  # (B, T+1, A) int8 — visibility = same-MEC
    mean: jnp.ndarray       # (B, T+1, A, F) f32 — per-position Welford mean
    std: jnp.ndarray        # (B, T+1, A, F) f32


@struct.dataclass
class EpisodeBatch:
    """One (batch of) episode(s): arrays shaped ``(B, T(+1), ...)``."""

    obs: jnp.ndarray            # (B, T+1, A, obs_dim) float32 — or a
                                # CompactEntityObs pytree (compact storage)
    state: jnp.ndarray          # (B, T+1, state_dim) float32
    avail_actions: jnp.ndarray  # (B, T+1, A, n_actions) bool (storage; a
                                # predicate — arithmetic misuse is a type
                                # error by construction)
    actions: jnp.ndarray        # (B, T, A) int32
    reward: jnp.ndarray         # (B, T) float32
    terminated: jnp.ndarray     # (B, T) bool — env-terminal, time-limit excluded (Q7)
    filled: jnp.ndarray         # (B, T) bool

    @property
    def batch_size(self) -> int:
        return jax.tree.leaves(self.obs)[0].shape[0]

    @property
    def max_seq_length(self) -> int:
        return self.actions.shape[1]

    def max_t_filled(self) -> jnp.ndarray:
        """Longest filled prefix across the batch (reference
        ``per_run.py:226-227`` truncates the sampled batch to it; with static
        shapes we keep full length and rely on the masks instead)."""
        return self.filled.sum(axis=1).max()


@struct.dataclass
class TimeMajorEpisodes:
    """Rollout-scan emission BEFORE episode-batch assembly: the ``(T, B,
    ...)`` stacked per-step outputs plus the ``(B, ...)`` bootstrap step.
    The fused superstep path (``run.Experiment.superstep_program``)
    scatters these straight into the replay ring
    (``ReplayBuffer.insert_time_major``) without ever materializing the
    concatenated ``(B, T+1, ...)`` episode batch (a batch→copy HBM
    round-trip). The
    classic path assembles the same values into an ``EpisodeBatch`` via
    ``to_batch()`` (bit-identical contents either way)."""

    obs: jnp.ndarray            # (T, B, A, obs) storage-cast — or a
                                # CompactEntityObs pytree, time-major
    state: jnp.ndarray          # (T, B, state_dim) storage-cast
    avail_actions: jnp.ndarray  # (T, B, A, n_actions) bool
    actions: jnp.ndarray        # (T, B, A) int32
    reward: jnp.ndarray         # (T, B) float32 (train-recorded reward)
    terminated: jnp.ndarray     # (T, B) bool (env-terminal, Q7)
    last_obs: jnp.ndarray       # (B, A, obs) bootstrap step — or compact
    last_state: jnp.ndarray     # (B, state_dim)
    last_avail: jnp.ndarray     # (B, A, n_actions) bool

    @property
    def batch_size(self) -> int:
        return self.actions.shape[1]

    def to_batch(self) -> EpisodeBatch:
        """Assemble the classic ``(B, T(+1), ...)`` episode batch."""
        b, t = self.actions.shape[1], self.actions.shape[0]
        bt = lambda x: jnp.swapaxes(x, 0, 1)
        cat_last = lambda seq, last: jax.tree.map(
            lambda s, l: jnp.concatenate([bt(s), l[:, None]], axis=1),
            seq, last)
        return EpisodeBatch(
            obs=cat_last(self.obs, self.last_obs),
            state=cat_last(self.state, self.last_state),
            avail_actions=cat_last(self.avail_actions, self.last_avail),
            actions=bt(self.actions),
            reward=bt(self.reward),
            terminated=bt(self.terminated),
            filled=jnp.ones((b, t), bool),
        )


@struct.dataclass
class BufferState:
    """Ring buffer over episodes + PER priorities, all device-resident."""

    storage: EpisodeBatch       # arrays (capacity, T(+1), ...)
    insert_pos: jnp.ndarray     # () int32 — next ring slot
    episodes_in_buffer: jnp.ndarray  # () int32
    # (capacity,) float32 — stored PRE-EXPONENTIATED: p^alpha for the
    # prioritized buffer (exponentiation happens once per priority WRITE
    # — O(batch) at update, O(1) at insert — instead of over the full
    # capacity on every sample; bit-identical probabilities, same op on
    # the same inputs), raw p for the uniform buffer (which never
    # samples by priority)
    priorities: jnp.ndarray
    max_priority: jnp.ndarray   # () float32 — running max of RAW priorities


def _zeros_like_episode(n_agents: int, n_actions: int, obs_dim: int,
                        state_dim: int, t: int, batch: int,
                        store_dtype=jnp.float32,
                        compact_obs: bool = False) -> EpisodeBatch:
    if compact_obs:
        f = obs_dim // n_agents        # entity feats (entity-mode layout)
        # compact leaves stay f32 regardless of store_dtype: raw features
        # + statistics, where bf16 error would be amplified by the
        # learner's re-normalization (see ParallelRunner.obs_store)
        obs = CompactEntityObs(
            rows=jnp.zeros((batch, t + 1, n_agents, f - 1), jnp.float32),
            mec_index=jnp.zeros((batch, t + 1, n_agents), jnp.int8),
            mean=jnp.zeros((batch, t + 1, n_agents, f), jnp.float32),
            std=jnp.zeros((batch, t + 1, n_agents, f), jnp.float32),
        )
    else:
        obs = jnp.zeros((batch, t + 1, n_agents, obs_dim), store_dtype)
    return EpisodeBatch(
        obs=obs,
        state=jnp.zeros((batch, t + 1, state_dim), store_dtype),
        avail_actions=jnp.zeros((batch, t + 1, n_agents, n_actions), bool),
        actions=jnp.zeros((batch, t, n_agents), jnp.int32),
        reward=jnp.zeros((batch, t), jnp.float32),
        terminated=jnp.zeros((batch, t), bool),
        filled=jnp.zeros((batch, t), bool),
    )


@dataclasses.dataclass(frozen=True)
class ReplayBuffer:
    """Uniform episode replay (the reference's commented-out default,
    ``per_run.py:135-141``). All methods are pure: ``state' = f(state, ...)``."""

    capacity: int               # episodes (reference buffer_size)
    episode_limit: int
    n_agents: int
    n_actions: int
    obs_dim: int
    state_dim: int
    store_dtype: str = "float32"   # obs/state storage dtype (HBM budget)
    compact_obs: bool = False      # CompactEntityObs storage (entity mode)

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(f"buffer capacity must be >= 1, got "
                             f"{self.capacity}")

    def init(self) -> BufferState:
        return BufferState(
            storage=_zeros_like_episode(
                self.n_agents, self.n_actions, self.obs_dim, self.state_dim,
                self.episode_limit, self.capacity,
                jnp.dtype(self.store_dtype), compact_obs=self.compact_obs),
            insert_pos=jnp.zeros((), jnp.int32),
            episodes_in_buffer=jnp.zeros((), jnp.int32),
            priorities=jnp.zeros((self.capacity,), jnp.float32),
            max_priority=jnp.ones((), jnp.float32),
        )

    def _ring_slots(self, state: BufferState, b: int) -> jnp.ndarray:
        """Target slots for ``b`` incoming episodes, with the shared
        capacity guard — ONE source for both insert paths (their ring
        bookkeeping must stay bit-identical: superstep K=1 parity,
        docs/SPEC.md §8)."""
        if b > self.capacity:
            # ring indices would repeat within one scatter and XLA's order
            # for duplicate indices is unspecified → arbitrary contents
            raise ValueError(
                f"insert batch of {b} episodes exceeds buffer capacity "
                f"{self.capacity}; raise replay.buffer_size above "
                f"batch_size_run")
        return (state.insert_pos + jnp.arange(b)) % self.capacity

    def _insert_priority(self, state: BufferState,
                         alpha: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """STORED priority stamped on freshly inserted episodes: the raw
        running max here; the prioritized subclass pre-exponentiates
        (one scalar pow per insert — the storage convention). ``alpha``
        (a traced scalar) overrides the static exponent — the graftpop
        per-member PER-alpha seam; ``None`` (every pre-population
        caller) is byte-identical to the static path."""
        del alpha
        return state.max_priority

    def _ring_advance(self, state: BufferState, storage: EpisodeBatch,
                      idx: jnp.ndarray, b: int,
                      alpha: Optional[jnp.ndarray] = None) -> BufferState:
        """Post-insert bookkeeping shared by both insert paths: advance
        the ring cursor/fill and stamp new episodes at the running max
        priority (standard PER; reference feeds real |TD| back after the
        first sample, Q9)."""
        return state.replace(
            storage=storage,
            insert_pos=(state.insert_pos + b) % self.capacity,
            episodes_in_buffer=jnp.minimum(
                state.episodes_in_buffer + b, self.capacity),
            priorities=state.priorities.at[idx].set(
                self._insert_priority(state, alpha)),
        )

    def insert_episode_batch(self, state: BufferState,
                             batch: EpisodeBatch,
                             alpha: Optional[jnp.ndarray] = None
                             ) -> BufferState:
        """Ring-insert ``B`` episodes; overwrites oldest when full (the
        reference's EpisodeBatch ring semantics)."""
        with jax.named_scope("replay.insert"):
            b = batch.batch_size
            idx = self._ring_slots(state, b)
            # cast to the ring's storage dtypes (int32-avail producers stay
            # legal; scatter dtype mismatches become hard errors in newer JAX)
            storage = jax.tree.map(
                lambda s, x: s.at[idx].set(x.astype(s.dtype)), state.storage,
                batch)
            return self._ring_advance(state, storage, idx, b, alpha)

    def insert_time_major(self, state: BufferState,
                          tm: TimeMajorEpisodes,
                          alpha: Optional[jnp.ndarray] = None
                          ) -> BufferState:
        """Ring-insert straight from the rollout scan's time-major
        emission: ONE scatter per leaf via a combined ``(slot, t)``
        index map. The former path did two scatters per (T+1)-length
        leaf (steps 0..T-1 from the scan stack, step T from the
        bootstrap) and paid a ``(T, B, ...) -> (B, T, ...)`` transpose
        of every stacked leaf to line the updates up with the ring
        layout. Here the updates stay TIME-MAJOR — the scan stack and
        the bootstrap step concatenate along the existing time axis
        (no transpose, and XLA fuses the concat into the scatter's
        update operand) — and a 2-D index grid scatters row ``(t, b)``
        straight to ring element ``(slots[b], t)`` in one writeback.
        The eliminated transpose + second scatter pass are the insert
        bytes the GP302 ratchet pins DOWN on the compiled superstep
        program. Contents are bit-identical to
        ``insert_episode_batch(state, tm.to_batch())`` — the fused
        superstep relies on that for K=1 parity."""
        with jax.named_scope("replay.insert"):
            b = tm.batch_size
            idx = self._ring_slots(state, b)
            t1 = self.episode_limit + 1
            # combined index map shared by every (T+1)-leaf scatter: update
            # row (t, b) lands at ring element (slots[b], t)
            t_grid = jnp.broadcast_to(jnp.arange(t1)[:, None], (t1, b))
            s_grid = jnp.broadcast_to(idx[None, :], (t1, b))

            def put_tp1(s, seq, last):
                """(cap, T+1, ...) leaf ← one scatter of the time-major
                (T+1, B, ...) updates (scan stack ++ bootstrap step)."""
                upd = jnp.concatenate([seq, last[None]], axis=0)
                return s.at[s_grid, t_grid].set(upd.astype(s.dtype))

            def put_t(s, seq):
                """(cap, T, ...) leaf ← one scatter of the time-major
                (T, B, ...) scan stack (same combined index map, first T
                rows — no transpose here either)."""
                return s.at[s_grid[:-1], t_grid[:-1]].set(seq.astype(s.dtype))

            st = state.storage
            storage = st.replace(
                obs=jax.tree.map(put_tp1, st.obs, tm.obs, tm.last_obs),
                state=put_tp1(st.state, tm.state, tm.last_state),
                avail_actions=put_tp1(st.avail_actions, tm.avail_actions,
                                      tm.last_avail),
                actions=put_t(st.actions, tm.actions),
                reward=put_t(st.reward, tm.reward),
                terminated=put_t(st.terminated, tm.terminated),
                filled=st.filled.at[idx].set(True),
            )
            return self._ring_advance(state, storage, idx, b, alpha)

    def can_sample(self, state: BufferState, batch_size: int) -> jnp.ndarray:
        return state.episodes_in_buffer >= batch_size

    def _gather(self, state: BufferState, idx: jnp.ndarray) -> EpisodeBatch:
        return jax.tree.map(lambda s: s[idx], state.storage)

    def sample(self, state: BufferState, key: jax.Array, batch_size: int,
               t_env: jnp.ndarray = 0
               ) -> Tuple[EpisodeBatch, jnp.ndarray, jnp.ndarray]:
        """→ (batch, idx, weights). Uniform without replacement (weights = 1),
        same return signature as PER so the driver is agnostic
        (``per_run.py:224``)."""
        with jax.named_scope("replay.sample"):
            del t_env
            n = state.episodes_in_buffer
            # top-batch_size of random scores over valid slots ≡ sampling
            # without replacement with static shapes (caller gates on
            # can_sample)
            scores = jax.random.uniform(key, (self.capacity,))
            scores = jnp.where(jnp.arange(self.capacity) < n, scores,
                               -jnp.inf)
            _, idx = jax.lax.top_k(scores, batch_size)
            return self._gather(state, idx), idx, jnp.ones((batch_size,))

    def update_priorities(self, state: BufferState, idx: jnp.ndarray,
                          priorities: jnp.ndarray,
                          valid: Optional[jnp.ndarray] = None,
                          alpha: Optional[jnp.ndarray] = None
                          ) -> BufferState:
        del idx, priorities, valid, alpha
        return state  # uniform: no-op


@dataclasses.dataclass(frozen=True)
class PrioritizedReplayBuffer(ReplayBuffer):
    """Proportional PER over episodes (reference ``per_run.py:143-146``):
    ``P(i) ∝ p_i^alpha``, importance weights ``(N·P(i))^-β`` normalized by
    their max, β annealed linearly from ``per_beta`` to 1 over ``t_max`` env
    steps (the ctor's ``t_max`` argument)."""

    alpha: float = 0.6
    beta0: float = 0.4
    t_max: int = 1

    def _insert_priority(self, state: BufferState,
                         alpha: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        # storage convention: stored values are pre-exponentiated, so
        # the fresh-episode stamp is max^alpha (one scalar pow per
        # insert; bit-identical to exponentiating at sample time). A
        # traced `alpha` is the graftpop per-member exponent — the same
        # pow on the same values at the config default, so the
        # population path is value-identical to the static one.
        return state.max_priority ** (self.alpha if alpha is None
                                      else alpha)

    def _probs(self, state: BufferState) -> jnp.ndarray:
        # stored values are ALREADY p^alpha (pre-exponentiated at
        # insert/update — O(batch) writes), so sampling is a masked
        # normalize instead of an O(capacity) pow every draw
        valid = jnp.arange(self.capacity) < state.episodes_in_buffer
        p = jnp.where(valid, state.priorities, 0.0)
        return p / jnp.maximum(p.sum(), 1e-12)

    def sample(self, state: BufferState, key: jax.Array, batch_size: int,
               t_env: jnp.ndarray = 0
               ) -> Tuple[EpisodeBatch, jnp.ndarray, jnp.ndarray]:
        with jax.named_scope("replay.sample"):
            probs = self._probs(state)
            cdf = jnp.cumsum(probs)
            # stratified inverse-CDF: one uniform per equal-mass stratum
            u = (jnp.arange(batch_size)
                 + jax.random.uniform(key, (batch_size,))) / batch_size
            idx = jnp.searchsorted(cdf, u * cdf[-1], side="left")
            idx = jnp.clip(idx, 0, self.capacity - 1)

            beta = self.beta0 + (1.0 - self.beta0) * jnp.clip(
                jnp.asarray(t_env, jnp.float32) / self.t_max, 0.0, 1.0)
            n = jnp.maximum(state.episodes_in_buffer, 1).astype(jnp.float32)
            w = (n * jnp.maximum(probs[idx], 1e-12)) ** (-beta)
            w = w / jnp.maximum(w.max(), 1e-12)
            return self._gather(state, idx), idx, w

    def update_priorities(self, state: BufferState, idx: jnp.ndarray,
                          priorities: jnp.ndarray,
                          valid: Optional[jnp.ndarray] = None,
                          alpha: Optional[jnp.ndarray] = None
                          ) -> BufferState:
        """Feed RAW |TD|+1e-6 back for the sampled episodes (Q9); the
        stored form is pre-exponentiated (``p^alpha``, one O(batch) pow
        here instead of O(capacity) per sample). Duplicate indices
        resolve to one of the written values (XLA scatter), matching
        the reference's last-write-wins dict update.

        ``valid`` (optional () bool) is the non-finite guard seam: when
        False the write degenerates to the episodes' EXISTING stored
        values and the running max is untouched — value-identical to
        not updating, with no host sync and no full-ring select (the
        guard the driver used to inline; it moved here when the storage
        went pre-exponentiated, so the fallback reads stored-space
        values).

        ``alpha`` (optional traced scalar) overrides the static
        exponent — the graftpop per-member PER-alpha seam (each vmapped
        member's ring then stores ``p^alpha_i`` consistently across
        insert-stamp, feedback and sample-normalize)."""
        with jax.named_scope("replay.priority"):
            pa = priorities ** (self.alpha if alpha is None else alpha)
            new_max = jnp.maximum(state.max_priority, priorities.max())
            if valid is not None:
                pa = jnp.where(valid, pa, state.priorities[idx])
                new_max = jnp.where(valid, new_max, state.max_priority)
            return state.replace(
                priorities=state.priorities.at[idx].set(pa),
                max_priority=new_max,
            )
