"""Measure the uniform-random-legal-actions baseline for a config.

The learning gates (tests/test_learning_curve.py) compare a trained
policy's final evals against ``random_return_mean + 2*std`` — this script
produces that JSON for any scale point (the config-1 artifact
``runs/config1_full/random_baseline.json`` predates it; this is the
reproducible producer).

Usage:
    JAX_PLATFORMS=cpu python scripts/random_baseline.py \
        [--episodes 24] [--seed 0] [key=value config overrides...]
e.g. the config-2 point:
    ... scripts/random_baseline.py env_args.agv_num=16 env_args.mec_num=4 \
        env_args.num_channels=4
"""

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from t2omca_tpu.config import load_config  # noqa: E402
from t2omca_tpu.envs.registry import make_env  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args()

    cfg = load_config(overrides=tuple(args.overrides))
    env = make_env(cfg.env_args)
    b, t_len = args.episodes, cfg.env_args.episode_limit

    def episode(key):
        k_reset, k_scan = jax.random.split(key)
        state, obs, gstate, avail = env.reset(k_reset)

        def body(carry, k):
            state, avail = carry
            k_act, k_step = jax.random.split(k)
            # uniform over LEGAL actions per agent (gumbel-max over the
            # avail mask — exact uniform on the legal set)
            g = jax.random.gumbel(k_act, avail.shape)
            actions = jnp.argmax(jnp.where(avail > 0, g, -jnp.inf), axis=-1)
            state, reward, _term, info, _obs, _gs, avail2 = env.step(
                state, actions, k_step)
            return (state, avail2), (reward, info.conflict_ratio,
                                     info.task_completion_rate)

        keys = jax.random.split(k_scan, t_len)
        _, (rew, cr, tcr) = jax.lax.scan(body, (state, avail), keys)
        return rew.sum(), cr[-1], tcr[-1]

    keys = jax.random.split(jax.random.PRNGKey(args.seed), b)
    rets, crs, tcrs = jax.jit(jax.vmap(episode))(keys)
    rets = np.asarray(rets)
    out = {
        "random_return_mean": float(rets.mean()),
        "random_return_std": float(rets.std()),
        "random_task_completion_rate": float(np.asarray(tcrs).mean()),
        "random_conflict_ratio": float(np.asarray(crs).mean()),
        "episodes": b,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
