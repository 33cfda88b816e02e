"""Plain reference of the T2OMCA networks (hj5717/T2OMCA: ``transformer.py``,
``transf_agent.py``, ``n_transf_mixer.py``) in ``jax.numpy`` and float32.

Nothing of the program is imported. The published mathematics, written the
straightforward way: every token of every block is computed (dense), keys
and values of every block are projected from the layer-0 tokens, the
block is post-LN with the residual on the query input, the agent's hidden
state is token 0, the mixer reads its hyper-network weights off the
trailing tokens.

``prec`` selects the compute precision, as ``model.dtype`` does in the
program: every operand of a contraction AND every activation a layer
hands on (projections, logits, attention weights, LayerNorm outputs,
feed-forward activations, residual sums) is rounded to it.

* ``"f32"``  — the reference: float32, ``highest`` matmul precision;
* ``"bf16"`` — the precision both configurations state
  (``model.dtype: bfloat16``): what a sound program may read;
* ``"fp8"``  — float8 (e4m3), the step below bfloat16: the *control* that
  the comparison must fail.

Accumulation inside a contraction, the statistics of softmax and
LayerNorm, the Q head, the mixer's read-out and the loss stay float32 in
all three, as they do in the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6            # flax.linen.LayerNorm default, the program's


def quant(x, prec: str):
    """Round a contraction operand to ``prec``, as float32. The rounding
    is straight-through for the backward pass (cotangents stay float32:
    cast down they overflow float8's range, and the program too keeps its
    accumulations in float32)."""
    if prec == "f32":
        return x
    if prec == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif prec == "fp8":
        fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
        r = (jnp.clip(x, -fmax, fmax).astype(jnp.float8_e4m3fn)
             .astype(jnp.float32))
    else:
        raise ValueError(f"unknown precision {prec!r}")
    return x + jax.lax.stop_gradient(r - x)


def mm(spec: str, a, b, prec: str):
    """One contraction, float32 accumulate, operands at ``prec``."""
    return jnp.einsum(spec, quant(a, prec), quant(b, prec),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dense(p, x, prec):
    y = mm("...i,io->...o", x, p["kernel"], prec)
    return quant(y + p["bias"] if "bias" in p else y, prec)


def layer_norm(p, x, prec):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return quant((x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"]
                 + p["bias"], prec)


def attention(p, q, k, heads: int, standard_heads: bool, prec):
    """Multi-head attention; queries and keys each scaled by
    ``head_dim ** -0.25`` (the source's geometry). ``standard_heads``:
    head_dim = emb // heads, else every head has the full emb."""
    b, tq, e = q.shape
    tk = k.shape[1]
    d = e // heads if standard_heads else e
    kk = dense(p["tokeys"], k, prec).reshape(b, tk, heads, d) * d ** -0.25
    qq = dense(p["toqueries"], q, prec).reshape(b, tq, heads, d) * d ** -0.25
    vv = dense(p["tovalues"], k, prec).reshape(b, tk, heads, d)
    logits = quant(mm("bqhd,bkhd->bhqk", quant(qq, prec), quant(kk, prec),
                      prec), prec)
    attn = quant(jax.nn.softmax(logits, axis=-1), prec)
    out = quant(mm("bhqk,bkhd->bqhd", attn, vv, prec), prec)
    return dense(p["unifyheads"], out.reshape(b, tq, heads * d), prec)


def block(p, q, k, heads, standard_heads, prec):
    x = layer_norm(p["norm1"], quant(attention(
        p["attention"], q, k, heads, standard_heads, prec) + q, prec), prec)
    ff = dense(p["ff2"], jax.nn.relu(dense(p["ff1"], x, prec)), prec)
    return layer_norm(p["norm2"], quant(ff + x, prec), prec)


def transformer(p, tokens, heads, depth, standard_heads, prec):
    """Every block attends its evolving queries against the LAYER-0
    tokens (the source threads the original keys through the stack)."""
    x = tokens
    for i in range(depth):
        x = block(p[f"block_{i}"], x, tokens, heads, standard_heads, prec)
    return x


def entity_obs(rows, mec_index, mean, std):
    """The normalised entity observation every agent sees, from its
    factored storage: agent ``i`` sees entity ``j``'s 8 feature rows iff
    both are served by the same MEC, plus an is-self flag; every position
    is normalised by running statistics shared by all observers.
    rows ``(..., A, 8)``, mec_index ``(..., A)``, mean/std ``(..., A, 9)``
    → ``(..., A, A, 9)``."""
    a = rows.shape[-2]
    same = mec_index[..., :, None] == mec_index[..., None, :]
    ent = jnp.where(same[..., None], rows[..., None, :, :], 0.0)
    is_self = jnp.broadcast_to(jnp.eye(a, dtype=rows.dtype)[..., None],
                               ent.shape[:-1] + (1,))
    raw = jnp.concatenate([ent, is_self], axis=-1)
    return (raw - mean[..., None, :, :]) / (std[..., None, :, :] + 1e-8)


def agent_forward(p, obs, hidden, *, heads, depth, standard_heads,
                  prec="f32"):
    """obs ``(B, A, N, F)`` normalised entity tokens, hidden ``(B, A, E)``
    → (q ``(B, A, n_actions)``, hidden' ``(B, A, E)``)."""
    b, a, n, f = obs.shape
    e = hidden.shape[-1]
    emb = dense(p["feat_embedding"], obs.reshape(b * a, n, f), prec)
    tokens = jnp.concatenate([quant(hidden.reshape(b * a, 1, e), prec), emb],
                             axis=1)
    out = transformer(p["transformer"], tokens, heads, depth,
                      standard_heads, prec)
    h = out[:, 0, :]
    # the program keeps the Q head in float32 at every setting
    q = dense(p["q_basic"], h, "f32")
    return q.reshape(b, a, -1), h.reshape(b, a, e)


def mixer_forward(p, qvals, hiddens, hyper, state_entities, *, n_agents,
                  heads, depth, standard_heads, prec="f32"):
    """qvals ``(B, A)``, hiddens ``(B, A, E)``, hyper ``(B, 3, E)``,
    state_entities ``(B, N, F)`` → (q_tot ``(B,)``, hyper')."""
    emb = dense(p["feat_embedding"], state_entities, prec)
    tokens = jnp.concatenate([emb, quant(hiddens, prec), quant(hyper, prec)],
                             axis=1)
    out = transformer(p["transformer"], tokens, heads, depth,
                      standard_heads, prec)
    w1 = jnp.abs(out[:, -3 - n_agents:-3, :])                 # (B, A, E)
    b1 = out[:, -3, :]
    w2 = jnp.abs(out[:, -2, :])
    b2 = jax.nn.relu(dense(p["hyper_b2"], out[:, -1, :], "f32"))[:, 0]
    hid = jax.nn.elu(jnp.einsum("ba,bae->be", qvals, w1,
                                precision=jax.lax.Precision.HIGHEST) + b1)
    y = (hid * w2).sum(-1) + b2
    return y, out[:, -3:, :]
