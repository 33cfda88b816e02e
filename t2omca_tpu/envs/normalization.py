"""Running normalization as pure functions on carried state (C2).

Re-creates ``/root/reference/normalization.py`` with its two quirks
(SURVEY.md §7.5):

* **Q5** — the Welford update's *first* sample sets ``std = x`` (not 0)
  (``normalization.py:16-18``), so the first normalized output is exactly 0
  via ``(x - x)/(x + 1e-8)``.
* **Q4** — the observation normalizer is updated on every call, including
  evaluation (``environment_multi_mec.py:184-186``); callers here decide by
  passing ``update``.

The reference keeps one mutable ``Normalization`` object per env subprocess;
here the statistics are a ``NormState`` pytree carried inside ``EnvState`` so
each vmapped env keeps independent statistics (SURVEY.md §7.4(3)).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class NormState:
    """Welford running statistics (reference ``RunningMeanStd``)."""

    n: jnp.ndarray       # scalar int32 sample count
    mean: jnp.ndarray    # (dim,)
    s: jnp.ndarray       # (dim,) sum of squared deviations
    std: jnp.ndarray     # (dim,)

    @classmethod
    def create(cls, dim: int) -> "NormState":
        # three DISTINCT zero buffers, not one shared array: a freshly
        # created state may be donated whole (the fused superstep donates
        # the full TrainState), and XLA rejects donating the same buffer
        # through two leaves ("donate twice in Execute")
        z = lambda: jnp.zeros((dim,), jnp.float32)
        return cls(n=jnp.zeros((), jnp.int32), mean=z(), s=z(), std=z())


def welford_update(state: NormState, x: jnp.ndarray) -> NormState:
    """One ``RunningMeanStd.update`` step (``normalization.py:12-22``)."""
    n1 = state.n + 1
    first = n1 == 1
    new_mean = jnp.where(first, x, state.mean + (x - state.mean) / n1)
    new_s = jnp.where(first, state.s,
                      state.s + (x - state.mean) * (x - new_mean))
    new_std = jnp.where(first, x, jnp.sqrt(new_s / n1))   # Q5: first std = x
    return NormState(n=n1, mean=new_mean, s=new_s, std=new_std)


def normalize(state: NormState, x: jnp.ndarray,
              update=True) -> Tuple[NormState, jnp.ndarray]:
    """``Normalization.__call__`` (``normalization.py:29-35``): optionally
    update, then normalize with the (post-update) statistics. ``update`` may
    be a Python bool or a traced scalar bool (so evaluation rollouts can flip
    it inside one jitted program)."""
    state = select_update(state, welford_update(state, x), update)
    return state, apply_norm(state, x)


def welford_update_batch(state: NormState, xs: jnp.ndarray) -> NormState:
    """Order-free batched Welford: merge ``A`` samples ``xs (A, dim)`` into
    the running statistics in ONE update (Chan et al. parallel combine).

    Algebraically identical to ``A`` sequential ``welford_update`` calls for
    ``n >= 1`` (the merge recurrences telescope); the only deviations from
    the reference's sequential per-agent loop
    (``/root/reference/environment_multi_mec.py:184-186``) are (a) the Q5
    first-sample ``std = x`` quirk is skipped when starting from ``n == 0``
    (std becomes the true batch std immediately) and (b) callers normalize
    every sample with the post-merge statistics rather than each sample with
    its own prefix — an ``O(A/n)`` transient that vanishes as ``n`` grows
    (equivalence-tolerance test: ``tests/test_normalization.py``).

    This replaces an ``A``-step sequential scan of tiny updates on the env
    hot path with one batched op (the scan was the env-step serialization
    bottleneck at 64 agents)."""
    a = xs.shape[0]
    bmean = xs.mean(axis=0)
    bs = ((xs - bmean) ** 2).sum(axis=0)
    return _welford_merge(state, a, bmean, bs)


def _welford_merge(state: NormState, a: int, bmean: jnp.ndarray,
                   bs: jnp.ndarray) -> NormState:
    """Chan-style merge of precomputed batch statistics (count ``a``,
    mean ``bmean``, sum of squared deviations ``bs``)."""
    n1 = state.n + jnp.asarray(a, state.n.dtype)
    # correction terms in f32: the int32 product n·A would wrap after
    # ~2^31/A samples and poison the variance with NaNs
    nf = state.n.astype(jnp.float32)
    bnf = jnp.float32(a)
    n1f = nf + bnf
    delta = bmean - state.mean
    # state.n == 0 ⇒ the merge reduces to the batch statistics exactly
    new_mean = state.mean + delta * bnf / n1f
    new_s = state.s + bs + delta ** 2 * (nf * bnf / n1f)
    new_std = jnp.sqrt(new_s / n1f)
    return NormState(n=n1, mean=new_mean, s=new_s, std=new_std)


def welford_update_batch_factored(state: NormState, rows: jnp.ndarray,
                                  same_mec: jnp.ndarray) -> NormState:
    """``welford_update_batch`` over the ENTITY-STRUCTURED batch without
    materializing it: the ``A`` samples are the rows of the entity obs
    matrix, whose position ``(j, f<F-1)`` holds ``same_mec[i, j] *
    rows[j, f]`` and whose last feature is the is-self indicator δ_ij
    (``envs/mec_offload._raw_obs``). Batch mean and squared-deviation sums
    reduce to closed forms in the per-entity visible count — O(A·F) work
    instead of O(A²·F):

        cnt_j   = Σ_i same_mec[i, j]
        bmean   = rows_j · cnt_j / A
        bs      = cnt_j (rows_j − bmean)² + (A − cnt_j) bmean²
        is-self: bmean = 1/A,  bs = (A−1)/A

    Exact up to float reassociation vs the materialized update
    (tests/test_normalization.py)."""
    a = rows.shape[0]
    cnt = same_mec.sum(axis=0).astype(jnp.float32)            # (A,)
    frac = (cnt / a)[:, None]
    bmean_f = rows * frac                                     # (A, F-1)
    bs_f = (cnt[:, None] * (rows - bmean_f) ** 2
            + (a - cnt)[:, None] * bmean_f ** 2)
    bmean_s = jnp.full((a, 1), 1.0 / a, jnp.float32)
    bs_s = jnp.full((a, 1), (a - 1.0) / a, jnp.float32)
    bmean = jnp.concatenate([bmean_f, bmean_s], axis=1).reshape(-1)
    bs = jnp.concatenate([bs_f, bs_s], axis=1).reshape(-1)
    return _welford_merge(state, a, bmean, bs)


def select_update(state: NormState, updated: NormState,
                  update) -> NormState:
    """Pick the updated statistics per the ``update`` flag, which may be a
    Python bool or a traced scalar bool (one shared implementation for the
    sequential, batched, and factored paths)."""
    if isinstance(update, bool):
        return updated if update else state
    u = jnp.asarray(update)
    return jax.tree.map(lambda p, q: jnp.where(u, p, q), updated, state)


def apply_norm(state: NormState, xs: jnp.ndarray) -> jnp.ndarray:
    """The normalization affine shared by every path (reference
    ``Normalization.__call__`` epsilon)."""
    return (xs - state.mean) / (state.std + 1e-8)


def normalize_batch(state: NormState, xs: jnp.ndarray,
                    update=True) -> Tuple[NormState, jnp.ndarray]:
    """Batched counterpart of ``normalize``: one order-free merge of all
    rows, every row normalized with the post-merge statistics."""
    state = select_update(state, welford_update_batch(state, xs), update)
    return state, apply_norm(state, xs)


@struct.dataclass
class RewardScaleState:
    """``RewardScaling`` carried state (``normalization.py:38-52``): a
    discounted return whose running std divides rewards. Imported by the
    reference env but never instantiated in the released slice — provided for
    capability parity."""

    norm: NormState
    r: jnp.ndarray       # discounted return accumulator
    gamma: float = struct.field(pytree_node=False, default=0.99)

    @classmethod
    def create(cls, gamma: float, dim: int = 1) -> "RewardScaleState":
        return cls(norm=NormState.create(dim),
                   r=jnp.zeros((dim,), jnp.float32), gamma=gamma)


def scale_reward(state: RewardScaleState,
                 x: jnp.ndarray) -> Tuple[RewardScaleState, jnp.ndarray]:
    r = state.gamma * state.r + x
    norm = welford_update(state.norm, r)
    y = x / (norm.std + 1e-8)
    return RewardScaleState(norm=norm, r=r, gamma=state.gamma), y


def reset_reward_scale(state: RewardScaleState) -> RewardScaleState:
    return RewardScaleState(norm=state.norm, r=jnp.zeros_like(state.r),
                            gamma=state.gamma)
