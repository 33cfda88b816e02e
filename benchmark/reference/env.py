"""Plain reference of the deterministic part of the MEC-offloading env's
transition (hj5717/T2OMCA ``environment_multi_mec.py``: ``step``,
``get_reward``, ``get_avail_actions``): what one step must return for the
recorded pre-step observation and the recorded joint action. Mobility and
job arrivals are random and are not followed; ACKs, reward and
availability are functions of what the ring stores.

Feature row of an AGV (``get_agent_inf`` behind the ACK one-hot):
``[ack=-1, ack=0, ack=1, data_bits, local_delay_ms(round 0),
offload_delay_ms(round 2), remaining_deadline_ms, buffer_length]``.
"""

from __future__ import annotations

import jax.numpy as jnp

CYCLES_PER_BIT = 31250.0
T_SLOT_MS = 5.0


def acks(mec, actions):
    """Per-MEC collision resolution: action 0 is local compute (ACK 0);
    a channel chosen by exactly one AGV of a MEC succeeds (ACK 1); a
    channel chosen by several collides (ACK -1). mec, actions ``(..., A)``."""
    same = ((mec[..., :, None] == mec[..., None, :])
            & (actions[..., :, None] == actions[..., None, :]))
    mine = same.sum(-1)              # AGVs of my MEC on my channel, me included
    return jnp.where(actions == 0, 0, jnp.where(mine == 1, 1, -1))


def reward(rows, ack, *, user_compute_cap: float, latency_max_ms: float,
           dtype=jnp.float32):
    """Reward of one step, summed over AGVs: saved delay of successful
    offloads minus ``latency_max`` per missed deadline. rows ``(..., A, 8)``,
    ack ``(..., A)`` → ``(...)``. ``dtype`` below float32 is the control."""
    rows = rows.astype(dtype)
    data, offload, deadline, buf = (rows[..., 3], rows[..., 5],
                                    rows[..., 6], rows[..., 7])
    has_job = buf > 0
    local = jnp.round(jnp.asarray(CYCLES_PER_BIT, dtype) * data
                      / jnp.asarray(user_compute_cap, dtype) * 1000.0, 2)
    is_local = has_job & (ack == 0)
    is_coll = has_job & (ack == -1)
    is_off = has_job & (ack == 1)
    miss = ((is_local & ~(deadline - local > 0))
            | (is_coll & (deadline - T_SLOT_MS <= 0))
            | (is_off & ~(deadline - offload > 0)))
    gain = jnp.where(is_off, local - offload, 0).astype(dtype).sum(-1)
    lost = jnp.where(miss, jnp.asarray(latency_max_ms, dtype), 0).sum(-1)
    return (gain - lost).astype(jnp.float32)


def avail(rows, n_actions: int):
    """An AGV with an empty buffer may only idle (action 0); with a job
    every action is open. → ``(..., A, n_actions)`` bool."""
    has_job = rows[..., 7] > 0
    return has_job[..., None] | (jnp.arange(n_actions) == 0)
