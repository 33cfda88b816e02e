"""Action selectors (part of M7, the unreleased controllers package).

Two modes, matching the reference's flag values (SURVEY.md §5.6
``action_selector``):

* ``epsilon_greedy`` — linear-decay epsilon over ``epsilon_anneal_time`` env
  steps; with prob ε a uniformly random *available* action, else the argmax
  over available actions. Test mode forces ε = 0 (greedy), the PyMARL
  convention this codebase forks.
* ``noisy-new`` — NoisyNet exploration (``/root/reference/transf_agent.py:37-39``):
  exploration lives in the agent's noisy output layer, so selection is pure
  greedy over available actions in both train and test mode.

Everything is a pure function of ``(key, t_env)`` — no mutable selector
object; the runner logs ``epsilon(t_env)`` directly (quirk parity with
``parallel_runner.py:217-218``).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from .schedules import DecayThenFlatSchedule

_UNAVAIL = -jnp.inf


def masked_argmax(q: jnp.ndarray, avail: jnp.ndarray) -> jnp.ndarray:
    """Greedy action over available ones; unavailable Q-values are masked to
    -inf before the argmax (the MAC masking contract, SURVEY.md §2.3 M7)."""
    return jnp.argmax(jnp.where(avail > 0, q, _UNAVAIL), axis=-1)


def random_avail(key: jax.Array, avail: jnp.ndarray) -> jnp.ndarray:
    """Uniform sample over available actions via the Gumbel trick (shape-static,
    vmap-safe — replaces torch ``Categorical(avail).sample()``)."""
    g = jax.random.gumbel(key, avail.shape)
    return jnp.argmax(jnp.where(avail > 0, g, _UNAVAIL), axis=-1)


@dataclasses.dataclass(frozen=True)
class EpsilonGreedySelector:
    schedule: DecayThenFlatSchedule

    def epsilon(self, t_env: jnp.ndarray, test_mode: bool,
                eps_scale=None) -> jnp.ndarray:
        """``eps_scale`` (optional traced scalar) multiplies the
        schedule's epsilon — the graftpop per-member exploration knob
        (``population.eps_scale``). ``None`` (every pre-population
        caller) is byte-identical; 1.0 is bitwise-neutral."""
        eps = self.schedule.eval(t_env)
        if eps_scale is not None:
            eps = eps * eps_scale
        return jnp.where(jnp.asarray(test_mode), 0.0, eps)

    def select(self, key: jax.Array, q: jnp.ndarray, avail: jnp.ndarray,
               t_env: jnp.ndarray, test_mode: bool = False,
               eps_scale=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """q, avail: ``(..., n_actions)`` → (actions ``(...)``, epsilon)."""
        with jax.named_scope("act.select"):
            eps = self.epsilon(t_env, test_mode, eps_scale)
            k_coin, k_rand = jax.random.split(key)
            explore = jax.random.uniform(k_coin, q.shape[:-1]) < eps
            actions = jnp.where(explore, random_avail(k_rand, avail),
                                masked_argmax(q, avail))
            return actions, eps


@dataclasses.dataclass(frozen=True)
class NoisySelector:
    """Greedy selection; exploration comes from the agent's NoisyLinear head."""

    schedule: DecayThenFlatSchedule  # kept so `.epsilon` still logs (always 0)

    def epsilon(self, t_env: jnp.ndarray, test_mode: bool,
                eps_scale=None) -> jnp.ndarray:
        return jnp.zeros(())

    def select(self, key: jax.Array, q: jnp.ndarray, avail: jnp.ndarray,
               t_env: jnp.ndarray, test_mode: bool = False,
               eps_scale=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        # NoisyNet exploration lives in the q-head, so the population
        # eps knob has nothing to scale here
        del key, eps_scale
        with jax.named_scope("act.select"):
            return masked_argmax(q, avail), jnp.zeros(())


SELECTOR_REGISTRY = {
    "epsilon_greedy": EpsilonGreedySelector,
    "noisy-new": NoisySelector,
}
