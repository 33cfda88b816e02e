"""Plain reference of the replay ring's bookkeeping and of proportional
prioritised sampling over episodes (Schaul et al. 2016; the source's
``per_run.py``): new episodes are stamped with the running maximum
priority, ``P(i) ~ p_i^alpha``, one stratified draw per batch row,
importance weights ``(N P(i))^-beta`` normalised by their maximum, and
``|TD| + 1e-6`` fed back for the sampled episodes."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ring_slots(insert_pos: int, lanes: int, capacity: int):
    return (insert_pos + jnp.arange(lanes)) % capacity


def stamp_inserted(pri, max_priority, slots, alpha: float):
    """Stored priorities are ``p^alpha``; new episodes get the maximum."""
    return pri.at[slots].set(max_priority ** alpha)


def probabilities(pri, filled: int):
    p = jnp.where(jnp.arange(pri.shape[0]) < filled, pri, 0.0)
    return p / jnp.maximum(p.sum(), 1e-12)


def sample(pri, filled: int, key, batch: int):
    """One stratified proportional draw → indices ``(batch,)``."""
    cdf = jnp.cumsum(probabilities(pri, filled))
    u = (jnp.arange(batch) + jax.random.uniform(key, (batch,))) / batch
    idx = jnp.searchsorted(cdf, u * cdf[-1], side="left")
    return jnp.clip(idx, 0, pri.shape[0] - 1)


def importance_weights(pri, filled: int, idx, beta):
    probs = probabilities(pri, filled)
    n = jnp.maximum(filled, 1).astype(jnp.float32)
    w = (n * jnp.maximum(probs[idx], 1e-12)) ** (-beta)
    return w / jnp.maximum(w.max(), 1e-12)


def feed_back(pri, max_priority, idx, td_abs, alpha: float):
    raw = td_abs + 1e-6
    return (pri.at[idx].set(raw ** alpha),
            jnp.maximum(max_priority, raw.max()))
