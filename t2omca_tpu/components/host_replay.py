"""Host-RAM prioritized replay with a device-side stratified PER sample.

The reference's ``buffer_cpu_only`` flag keeps replay on CPU and moves only
sampled batches to the accelerator (``/root/reference/per_run.py:143-146``,
``:229-230``). This is that mode for the TPU framework: episode storage in
pinned host NumPy (capacity bounded by RAM, not HBM) while the PER *index*
lives on device — a mirrored ``(capacity,)`` f32 priority vector sampled by
one jitted stratified inverse-CDF program (the episode-buffer formulation,
``components/episode_buffer.PrioritizedReplayBuffer.sample``). The
pre-PR-13 implementation kept priorities in a C++ sum-tree
(``native/sumtree.cpp``) and paid a ctypes crossing per sample; the
steady-state sample path now runs ZERO sum-tree calls — priority writes are
O(batch) scatters into both mirrors, sampling is one device dispatch whose
importance weights feed the train step without ever visiting the host. The
``PySumTree``/``NativeSumTree`` classes remain as the reference formulation
the parity tests pin sampled indices and weights against
(``tests/test_host_replay.py``).

Same method surface as the device buffers (insert / can_sample / sample /
update_priorities) so the driver only branches on ``is_host`` to skip
jitting the buffer stages. Sampling semantics match the device PER:
stratified inverse-CDF over ``p^alpha``, importance weights ``(N·P)^-beta``
max-normalized, beta annealed to 1 over ``t_max`` (Q9 priorities flow back
per sampled episode).

Priorities are stored-space ``p^alpha`` at f32 — the device mirror cannot
hold the old tree's f64, and |TD|-scale priorities fit f32 with orders of
headroom; the host mirror keeps the SAME f32 values so the two can never
drift (pinned by test). ``max_priority`` tracking stays a host f64 float.
"""

from __future__ import annotations

import ctypes
import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .episode_buffer import EpisodeBatch


class PySumTree:
    """NumPy sum-tree formulation. Since PR 13 this is no longer on the
    live sample path — it survives (with ``NativeSumTree``) as the
    reference formulation the device-sample parity tests pin indices
    and weights against."""

    def __init__(self, cap: int):
        self.cap = cap
        self.leaf = np.zeros(cap, np.float64)

    def set_batch(self, idx, pri):
        self.leaf[idx] = pri

    def get(self, idx):
        return self.leaf[idx]

    def total(self):
        return float(self.leaf.sum())

    def sample(self, us: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        n = len(us)
        cdf = np.cumsum(self.leaf)
        u = (np.arange(n) + us) / n * cdf[-1]
        idx = np.minimum(np.searchsorted(cdf, u, side="right"),
                         self.cap - 1)
        return idx.astype(np.int64), self.leaf[idx]


class NativeSumTree:
    """ctypes wrapper over native/sumtree.cpp (extern "C" ABI). Parity
    reference only since PR 13 (see ``PySumTree``) — the steady-state
    sample path runs zero ctypes crossings."""

    def __init__(self, cap: int):
        from ..native import load_sumtree
        self._lib = load_sumtree()
        self.cap = 1
        while self.cap < cap:
            self.cap *= 2
        self._ptr = self._lib.sumtree_create(self.cap)
        if not self._ptr:
            raise MemoryError("sumtree_create failed")

    def __del__(self):
        lib = getattr(self, "_lib", None)
        ptr = getattr(self, "_ptr", None)
        if lib is not None and ptr:
            lib.sumtree_free(ptr)

    def set_batch(self, idx, pri):
        idx = np.ascontiguousarray(idx, np.int64)
        pri = np.ascontiguousarray(pri, np.float64)
        self._lib.sumtree_set_batch(
            self._ptr, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            pri.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(idx))

    def get(self, idx):
        # one batched ctypes crossing (native sumtree_get_batch) instead
        # of a per-element Python loop over sumtree_get — the FFI call
        # overhead dominated the old path at any realistic batch size
        idx = np.ascontiguousarray(np.atleast_1d(idx), np.int64)
        out = np.empty(len(idx), np.float64)
        self._lib.sumtree_get_batch(
            self._ptr, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(idx), out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return out

    def total(self):
        return self._lib.sumtree_total(self._ptr)

    def sample(self, us: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        n = len(us)
        us = np.ascontiguousarray(us, np.float64)
        out_idx = np.empty(n, np.int64)
        out_pri = np.empty(n, np.float64)
        self._lib.sumtree_sample(
            self._ptr, us.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n, out_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out_pri.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return out_idx, out_pri


# ------------------------------------------------- device sample programs
#
# Module-level jitted programs shared by every HostReplayBuffer instance
# (one compile per (capacity, batch) aval set). The priority vector is the
# only state they touch; everything else stays in host RAM.

@functools.partial(jax.jit, donate_argnums=(0,))
def _mirror_set(pri: jnp.ndarray, idx: jnp.ndarray,
                vals: jnp.ndarray) -> jnp.ndarray:
    """O(batch) scatter into the device priority mirror (donated: XLA
    updates the capacity-length vector in place instead of copying it
    per insert/priority-feedback)."""
    return pri.at[idx].set(vals)


def _valid_mass(pri: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Zero the unfilled (and any poisoned) tail: only slots < n carry
    sampling mass, exactly like the device buffer's ``_probs`` mask —
    garbage beyond the fill line can never leak into indices or
    weights."""
    return jnp.where(jnp.arange(pri.shape[0]) < n, pri, 0.0)


def _weights_from_mass(p: jnp.ndarray, idx: jnp.ndarray, n: jnp.ndarray,
                       beta: jnp.ndarray) -> jnp.ndarray:
    """Max-normalized ``(N·P)^-beta`` over the already-masked priority
    mass — ONE definition consumed by both the live sample program and
    the sum-tree parity reference, so weight parity is pinned through
    the identical lowering rather than through cross-library float
    accidents (numpy and XLA powf differ in the last ulp). The
    ``p.sum()`` here is deliberately NOT replaced by the sampler's
    ``cdf[-1]``: the standalone ``_importance_weights`` entry point has
    no cdf, and the two reductions associate differently in f32 — one
    extra O(capacity) reduce buys the bit-identical shared form."""
    probs = p[idx] / jnp.maximum(p.sum(), 1e-12)
    nn = jnp.maximum(n, 1).astype(jnp.float32)
    w = (nn * jnp.maximum(probs, 1e-12)) ** (-beta)
    return w / jnp.maximum(w.max(), 1e-12)


@jax.jit
def _importance_weights(pri: jnp.ndarray, idx: jnp.ndarray,
                        n: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """Standalone entry point over the RAW mirror (tests evaluate it at
    the sum-tree's own sampled indices)."""
    return _weights_from_mass(_valid_mass(pri, n), idx, n, beta)


@jax.jit
def _stratified_sample(pri: jnp.ndarray, us: jnp.ndarray, n: jnp.ndarray,
                       beta: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The sum-tree's stratified inverse-CDF sample as one device
    program: one uniform per equal-mass stratum, ``searchsorted`` over
    the masked priority cdf (side="right", the tree-descent convention),
    clamped to the last VALID slot so the exact-right-edge float
    artifact (``u·total == total``) resolves inside the fill line — the
    ctypes path redrew there instead; with zero mass on the invalid tail
    the clamp is the only reachable difference and it is measure-zero.
    Indices are pinned bit-equal to ``NativeSumTree``/``PySumTree``
    sampling at the same uniforms (tests/test_host_replay.py)."""
    p = _valid_mass(pri, n)
    cdf = jnp.cumsum(p)
    bs = us.shape[0]
    u = (jnp.arange(bs, dtype=jnp.float32) + us) / bs * cdf[-1]
    idx = jnp.searchsorted(cdf, u, side="right")
    idx = jnp.minimum(idx, jnp.maximum(n - 1, 0))
    return idx, _weights_from_mass(p, idx, n, beta)


@dataclasses.dataclass
class HostReplayBuffer:
    """Prioritized episode replay in host RAM (reference buffer_cpu_only)."""

    capacity: int
    episode_limit: int
    n_agents: int
    n_actions: int
    obs_dim: int
    state_dim: int
    alpha: float = 0.6
    beta0: float = 0.4
    t_max: int = 1
    store_dtype: str = "float32"
    prioritized: bool = True
    is_host: bool = True

    def __post_init__(self):
        t, cap = self.episode_limit, self.capacity
        if self.store_dtype == "bfloat16":
            import ml_dtypes  # ships with jax
            sd = np.dtype(ml_dtypes.bfloat16)
        else:
            sd = np.dtype(self.store_dtype)
        self._storage = EpisodeBatch(
            obs=np.zeros((cap, t + 1, self.n_agents, self.obs_dim), sd),
            state=np.zeros((cap, t + 1, self.state_dim), sd),
            avail_actions=np.zeros((cap, t + 1, self.n_agents,
                                    self.n_actions), bool),
            actions=np.zeros((cap, t, self.n_agents), np.int32),
            reward=np.zeros((cap, t), np.float32),
            terminated=np.zeros((cap, t), bool),
            filled=np.zeros((cap, t), bool),
        )
        # stored-space p^alpha, twin-mirrored: host f32 (checkpoint /
        # introspection / parity tests) and device f32 (the sample
        # program's operand). Writes go through _set_priorities so the
        # two can never drift; the device copy is donated in place.
        self._pri = np.zeros(cap, np.float32)
        self._pri_dev = jnp.zeros(cap, jnp.float32)
        self._pos = 0
        self._count = 0
        self._max_priority = 1.0
        self._rng = np.random.default_rng(0)
        # deferred priority feedback (run.py host path): (idx, td_ref,
        # finite_ref) device refs whose host fetch is consumed at the
        # NEXT sample instead of blocking the train iteration
        self._pending_update = None

    # ------------------------------------------------------------- protocol

    def insert_episode_batch(self, batch: EpisodeBatch) -> None:
        # consume any deferred priority feedback BEFORE the insert can
        # overwrite its slots: on ring wrap-around the deferred idx may be
        # exactly the slots this batch reuses, and flushing after would
        # stamp the EVICTED episodes' |TD| onto the fresh episodes
        # (which must start at max_priority) — flushing here keeps the
        # priority mirrors byte-identical to the old synchronous order
        self.flush_priority_updates()
        host = jax.device_get(batch)
        b = host.obs.shape[0]
        idx = (self._pos + np.arange(b)) % self.capacity
        for name in ("obs", "state", "avail_actions", "actions", "reward",
                     "terminated", "filled"):
            getattr(self._storage, name)[idx] = np.asarray(
                getattr(host, name), getattr(self._storage, name).dtype)
        if self.prioritized:
            self._set_priorities(idx, np.full(
                b, self._max_priority ** self.alpha, np.float32))
        self._pos = int((self._pos + b) % self.capacity)
        self._count = int(min(self._count + b, self.capacity))

    def _set_priorities(self, idx: np.ndarray, vals: np.ndarray) -> None:
        """O(batch) priority write into BOTH mirrors (same f32 values —
        the host array is the device vector's byte-twin)."""
        self._pri[idx] = vals
        self._pri_dev = _mirror_set(self._pri_dev, jnp.asarray(idx),
                                    jnp.asarray(vals))

    def can_sample(self, batch_size: int) -> bool:
        return self._count >= batch_size

    def defer_priority_update(self, idx: np.ndarray, td_ref, finite_ref
                              ) -> None:
        """Asynchronous replacement for the post-train ``update_priorities``
        call: start the device→host copies NOW (non-blocking) and stash
        the refs; the fetch is consumed by ``flush_priority_updates`` at
        the next ``sample`` — by which point a full rollout has executed
        and the copy has long landed, so the ``np.asarray`` there is a
        wait-free read instead of a blocking ``jax.device_get``. The
        sampling distribution sees each step's |TD| one iteration late —
        the same deferral the device path's async dispatch pipeline
        already has."""
        if not self.prioritized:
            return
        self.flush_priority_updates()      # at most one in flight
        for ref in (td_ref, finite_ref):
            start = getattr(ref, "copy_to_host_async", None)
            if start is not None:
                start()
        self._pending_update = (np.asarray(idx, np.int64), td_ref,
                                finite_ref)

    def drop_pending_update(self) -> None:
        """Abandon deferred priority feedback WITHOUT consuming it. The
        driver's checkpoint restore calls this when the train step that
        produced the refs was rolled back — fetching them would stamp the
        abandoned computation's |TD| into the priority mirrors, or
        re-raise a fault from a poisoned device array outside any ladder
        routing."""
        self._pending_update = None

    def flush_priority_updates(self) -> None:
        """Consume the deferred priority feedback, if any. A tripped
        (non-finite) train step leaves the priority mirrors untouched —
        NaN mass would corrupt the sampling cdf permanently."""
        if self._pending_update is None:
            return
        idx, td_ref, finite_ref = self._pending_update
        self._pending_update = None
        if bool(np.asarray(jax.device_get(finite_ref))):
            td = np.asarray(jax.device_get(td_ref), np.float64)
            self.update_priorities(idx, td + 1e-6)             # Q9

    def _beta(self, t_env: int) -> np.float32:
        """Annealed beta, computed host-side (t_env is a host int on
        this path) and cast ONCE to the f32 the sample program runs in."""
        return np.float32(self.beta0 + (1.0 - self.beta0) * min(
            max(float(t_env) / self.t_max, 0.0), 1.0))

    def sample(self, batch_size: int, t_env: int
               ) -> Tuple[EpisodeBatch, np.ndarray, np.ndarray]:
        self.flush_priority_updates()
        n = self._count
        if self.prioritized:
            # host RNG keeps the stratum uniforms (one tiny h2d per
            # sample); index selection + importance weights run as ONE
            # device program over the mirrored priority vector — the
            # steady-state path executes zero sum-tree ctypes calls.
            # The uniforms are cast to f32 BEFORE use so the parity
            # reference (the f64 sum-tree formulation) sees the exact
            # same values under lossless promotion.
            us = self._rng.random(batch_size).astype(np.float32)
            idx_dev, w = _stratified_sample(
                self._pri_dev, jnp.asarray(us),
                jnp.asarray(n, jnp.int32), jnp.asarray(self._beta(t_env)))
            # the episode gather reads host RAM, so the indices must
            # come home — a batch_size-int fetch, the path's one
            # unavoidable d2h (the weights stay on device and feed the
            # train step directly)
            idx = np.asarray(jax.device_get(idx_dev), np.int64)
        else:
            idx = self._rng.choice(n, size=batch_size, replace=False)
            w = jax.numpy.ones(batch_size, jax.numpy.float32)
        batch = jax.tree.map(lambda s: jax.numpy.asarray(s[idx]),
                             self._storage)
        return batch, idx, w

    def sight_priority_info(self) -> dict:
        """graftsight PER health over the HOST priority mirror (pure
        numpy — the buffer_cpu_only path pays zero device traffic for
        the read; run.py's host train path appends it to train_info)."""
        from ..obs.sight import buffer_sight_info_host
        return buffer_sight_info_host(self._pri, self._count)

    def update_priorities(self, idx: np.ndarray,
                          priorities: np.ndarray) -> None:
        if not self.prioritized:
            return
        pri = np.asarray(jax.device_get(priorities), np.float64)
        self._max_priority = float(max(self._max_priority, pri.max()))
        self._set_priorities(np.asarray(idx, np.int64),
                             (pri ** self.alpha).astype(np.float32))
