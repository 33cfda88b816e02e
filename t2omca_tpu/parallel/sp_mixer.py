"""Sequence-parallel mixer forward — the consumer of ``ring_attention``.

At the config-5 scale point (256 AGVs × 16 MECs, BASELINE.json) the mixer's
token axis is ``n_entities + n_agents + 3`` = 515 tokens; beyond that —
entity-token models with thousands of entities — the (b, h, T, T) attention
matrix and the token activations outgrow one chip. This module runs
``TransformerMixer``'s exact forward math (``models/mixer.py``, quirks
Q1/Q2/Q11/Q12 included) with the TOKEN axis sharded across a mesh axis:

* embedding / LayerNorm / FFN are token-local → run unchanged per shard;
* attention runs as ``ring_attention`` (K/V rotate over ICI via
  ``lax.ppermute``; the full T×T score matrix never exists on any device);
* layer-0 key pinning (``transformer.py:126,140`` threading) is preserved —
  every depth attends against the sharded layer-0 token blocks;
* the hypernet readout (Q11: weights read off the LAST ``3`` positional
  output tokens plus one per agent) happens after the (small) output gather.

The functions read the SAME flax param tree the dense module owns — no
separate parameters, no checkpoint divergence (same pattern as
``ops/query_slice``). Dense-equivalence is asserted on the virtual 8-device
mesh in ``tests/test_ring_attention.py``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.mixer import TransformerMixer
from .ring_attention import ring_attention

LN_EPS = 1e-6   # flax nn.LayerNorm default, matches models/transformer.py


def _ln(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.maximum((x32 * x32).mean(axis=-1, keepdims=True)
                      - mean * mean, 0.0)
    y = (x32 - mean) * jax.lax.rsqrt(var + LN_EPS)
    return (y * scale + bias).astype(x.dtype)


def _sp_transformer(tf_params, tokens, valid, *, heads: int, depth: int,
                    head_dim: int, axis: str) -> jnp.ndarray:
    """Runs INSIDE shard_map. tokens ``(B, T_local, E)`` — the local block
    of the token axis; ``valid (T_local,)`` marks real (non-pad) tokens.
    Mirrors ``models/transformer.py`` with keys pinned to layer-0 tokens."""
    b, t_loc, e = tokens.shape
    dt = tokens.dtype                 # compute dtype (mixer.dtype, cast by caller)
    k0 = tokens                       # layer-0 key pinning
    kv_mask = jnp.broadcast_to(valid[None, None, :], (b, heads, t_loc))
    x = tokens
    scale = head_dim ** -0.25         # Q1: applied to queries AND keys
    w = lambda p_: p_.astype(dt)

    for i in range(depth):
        bp = tf_params[f"block_{i}"]
        at = bp["attention"]
        split = lambda z, wk: (z @ w(wk)).reshape(b, t_loc, heads, head_dim
                                                  ).transpose(0, 2, 1, 3)
        q = split(x, at["toqueries"]["kernel"]) * scale
        k = split(k0, at["tokeys"]["kernel"]) * scale
        v = split(k0, at["tovalues"]["kernel"])

        ctx = ring_attention(q, k, v, axis, kv_mask)   # (B, H, T_loc, D)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t_loc, heads * head_dim)
        attended = (ctx @ w(at["unifyheads"]["kernel"])
                    + w(at["unifyheads"]["bias"]))

        # Q2: post-LN residuals; FFN is token-local
        x1 = _ln(attended + x, bp["norm1"]["scale"], bp["norm1"]["bias"])
        ff = jnp.maximum(x1 @ w(bp["ff1"]["kernel"]) + w(bp["ff1"]["bias"]),
                         0.0)
        ff = ff @ w(bp["ff2"]["kernel"]) + w(bp["ff2"]["bias"])
        x = _ln(ff + x1, bp["norm2"]["scale"], bp["norm2"]["bias"])
    return x


def mixer_apply_sp(mixer: TransformerMixer, variables, qvals: jnp.ndarray,
                   hidden_states: jnp.ndarray, hyper_weights: jnp.ndarray,
                   states: jnp.ndarray, obs: jnp.ndarray, mesh: Mesh,
                   axis: str = "sp") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for ``mixer.apply`` (deterministic, dropout=0) with the token
    axis sharded over ``mesh[axis]``. Same signature tail and returns:
    ``(q_tot (b,1,1), hyper_tokens (b,3,emb))``."""
    p = variables["params"]
    b = qvals.shape[0]
    n_sp = mesh.shape[axis]

    # ---- token construction, exactly models/mixer.py:71-81 ----
    if mixer.state_entity_mode:
        inputs = states.reshape(b, mixer.n_entities, mixer.feat_dim)
    else:   # Q12: all agents' obs entities
        inputs = obs.reshape(b, mixer.n_agents * mixer.n_entities,
                             mixer.feat_dim)
    # compute dtype mirrors the dense module (flax Dense/Transformer with
    # dtype=mixer.dtype): bf16 perf mode keeps token activations and the
    # ring's K/V traffic in bf16; LN statistics and the hypernet readout
    # stay f32 either way
    dt = mixer.dtype
    fe = p["feat_embedding"]
    embs = inputs.astype(dt) @ fe["kernel"].astype(dt) + fe["bias"].astype(dt)
    tokens = jnp.concatenate(
        [embs, hidden_states.astype(dt), hyper_weights.astype(dt)], axis=1)
    t = tokens.shape[1]

    # pad the token axis to a multiple of the axis size; padded keys are
    # excluded from every softmax via the ring kv mask
    tp = -(-t // n_sp) * n_sp
    if tp != t:
        tokens = jnp.pad(tokens, [(0, 0), (0, tp - t), (0, 0)])
    valid = jnp.arange(tp) < t

    head_dim = mixer.emb // mixer.heads if mixer.standard_heads else mixer.emb
    inner = functools.partial(_sp_transformer, heads=mixer.heads,
                              depth=mixer.depth, head_dim=head_dim,
                              axis=axis)
    out = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(None, axis, None), P(axis)),
        out_specs=P(None, axis, None),
        check_vma=False,
    )(p["transformer"], tokens, valid)
    out = out[:, :t, :].astype(jnp.float32)

    # ---- hypernet readout, exactly models/mixer.py:91-104 (Q11) ----
    a, e = mixer.n_agents, mixer.emb
    w1 = mixer.pos_func(out[:, -3 - a:-3, :])
    b1 = out[:, -3, :].reshape(b, 1, e)
    w2 = mixer.pos_func(out[:, -2, :].reshape(b, e, 1))
    hb = p["hyper_b2"]
    b2 = jnp.maximum(out[:, -1, :] @ hb["kernel"] + hb["bias"],
                     0.0).reshape(b, 1, 1)
    hidden = jax.nn.elu(jnp.matmul(qvals, w1) + b1)
    y = jnp.matmul(hidden, w2) + b2
    if "out_gate" in p:        # zero_init_gate configs (models/mixer.py)
        y = y * p["out_gate"]
    return y, out[:, -3:, :]
