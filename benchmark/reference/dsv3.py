"""Plain reference of a ``deepseek_v3`` decoder trunk (kanana-2's family:
kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``; what the config
is silent on follows transformers' ``deepseek_v3`` modelling code) as the
QMIX agent's token stack, in ``jax.numpy`` and float32 at ``highest``
matmul precision. Nothing of the program is imported; no kernel, no wide
product, no cast tree: one head at a time, every expert held here over
every token in a Python loop, weighted by the routing weight.

Per agent-step the sequence is the agent's ``A`` entity tokens (the
9 -> d ``feat_embedding`` of the normalised entity observation) followed
by the hidden token carried from the step before, at positions
``p = 0 ... A``. With ``N`` RMSNorm, held layer ``l`` with input ``h``:

* ``u = N(h; input_norm)``; ``q = u W_q`` in heads of ``nope + rope``:
  ``[q_nope | q_rope]``. There is no query latent (``q_lora_rank`` null);
* multi-head LATENT attention: ``u W_kva`` is split into ONE latent ``c``
  (``latent`` wide) and ONE rotary key ``k_r`` (``rope`` wide) a token —
  split BEFORE any norm; ``N(c; kv_norm) W_kvb`` gives every head
  ``[k_nope (nope) | v (value)]``;
* rotary positions on ``q_rope`` and ``k_r`` only, INTERLEAVED: the pair
  ``(x_2i, x_2i+1)`` turns by the angle ``p * theta ** (-2 i / rope)``.
  (transformers de-interleaves q and k alike into the half-split layout
  and rotates there: the same permutation on both sides of ``q . k``,
  which is therefore what is written here.) ``k_r`` is rotated once and
  read by all heads; head ``j``'s key is ``[k_nope_j | k_r]``;
* ``o_j = softmax(q_j k_j^T * (nope + rope) ** -0.5 + causal) v_j`` — the
  whole causal prefix, no window, no scale correction (``rope_scaling``
  null); ``a = h + concat_j(o_j) W_o`` with ``W_o`` over ``value`` a
  head. No bias anywhere;
* ``m = N(a; post_norm)``; a ``dense`` layer: ``f = W_down (silu(W_gate
  m) * W_up m)``; an ``experts`` layer: ``s = sigmoid(m W_r)`` over all
  experts (float32), the ``top_k`` of ``s + e_score_correction_bias``
  kept (the bias chooses, it does not weigh; ``n_group = topk_group =
  1``: no group-limited selection), ``r_e = routed_scaling_factor * s_e /
  (sum of kept s + 1e-20)``, ``f = Shared(m) + sum over kept and held e
  of r_e Expert_e(m)`` — experts SwiGLU, the ``n_shared_experts`` shared
  ones ONE SwiGLU of their summed width;
* ``y = a + f`` (pre-norm residuals: no norm on a sublayer's output);
* after the last layer the final RMSNorm's last token, float32, is the
  carried hidden state, the mixer's input and the Q head's.

**The share.** ``trunk`` gives the heads and experts held: the parameters
are that share's (``q_heads`` of the published heads' columns of ``W_q``
and ``W_kvb`` and rows of ``W_o``; experts ``expert_offset ...
expert_offset + experts_held - 1`` of ``experts``); ``W_kva`` and its
norm, the shared experts and a dense layer's feed-forward are whole. The
router scores all ``experts``; what the absent experts and heads would
add is left out, and that partial result goes on to the next layer — as
in the program. ``attention`` and ``feed_forward`` return the sublayer
sums: over all shares (latent, shared experts and dense feed-forward
counted once) those add up to the uncut layer's.

Departures from the published model: no vocabulary (embedding table,
output head), a sequence of ``A + 1`` tokens with no cache (so the
latent is never cached and ``W_kvb`` never absorbed: this is the training
form), the hidden token as recurrence; ``expert_bias``
(``e_score_correction_bias``) is a given parameter (its update from the
load is training-loop state the model's equations do not hold).

The router (``afmoe.routing``: sigmoid scores, biased selection,
renormalisation, scale), ``swiglu``, ``rms`` and the mixer are the
reference modules' that are there; ``prec`` as in
``benchmark/reference/model.py`` (operands and handed-on activations
rounded to it; accumulation, softmax and norm statistics, the router,
the final norm and the Q head float32 at every setting).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model
from .afmoe import rms, routing, swiglu
from .model import dense, mm, quant


def rotate_pairs(x, theta):
    """RoPE on ``x (S, n, D)`` at positions ``0 ... n-1``, as published
    for ``rope_interleave``: the pair ``(x_2i, x_2i+1)`` is the complex
    number ``x_2i + i x_2i+1``, multiplied by ``exp(i p theta^(-2i/D))``."""
    s, n, d = x.shape
    ang = (jnp.arange(n)[:, None]
           * theta ** (-2.0 * jnp.arange(d // 2) / d)[None, :])
    pair = x.reshape(s, n, d // 2, 2)
    z = (pair[..., 0] + 1j * pair[..., 1]) * jnp.exp(1j * ang)[None]
    return jnp.stack([z.real, z.imag], axis=-1).reshape(s, n, d)


def attention(p, u, *, trunk, prec):
    """``u (S, n, d)`` normed → this share's ``W_o``-projected latent
    attention, one query head at a time."""
    s, n, _ = u.shape
    hq, nope, rope = trunk["q_heads"], trunk["nope"], trunk["rope"]
    value, rank = trunk["value"], trunk["latent"]
    q = quant(mm("snd,de->sne", u, p["wq"], prec), prec
              ).reshape(s, n, hq, nope + rope)
    down = quant(mm("snd,de->sne", u, p["wkv_a"], prec), prec)
    c, k_r = down[..., :rank], down[..., rank:]      # split, THEN the norm
    c = quant(rms(p["kv_norm"], c, trunk["eps"]), prec)
    kv = quant(mm("snr,re->sne", c, p["wkv_b"], prec), prec
               ).reshape(s, n, hq, nope + value)
    k_r = quant(rotate_pairs(k_r, trunk["theta"]), prec)   # once, for all
    pos = jnp.arange(n)
    seen = pos[:, None] >= pos[None, :]
    heads = []
    for j in range(hq):
        q_r = quant(rotate_pairs(q[:, :, j, nope:], trunk["theta"]), prec)
        q_j = jnp.concatenate([q[:, :, j, :nope], q_r], axis=-1)
        k_j = jnp.concatenate([kv[:, :, j, :nope], k_r], axis=-1)
        logits = mm("sqd,skd->sqk", q_j, k_j, prec) * (nope + rope) ** -0.5
        w = quant(jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), -1), prec)
        heads.append(quant(mm("sqk,skd->sqd", w, kv[:, :, j, nope:], prec),
                           prec))
    return mm("sne,ed->snd", jnp.concatenate(heads, axis=-1), p["wo"], prec)


def feed_forward(p, m, *, trunk, layer: int, prec):
    """``m (N, d)`` normed (float32, un-rounded: the router reads it so)
    → a dense layer's SwiGLU, or the shared experts' plus the held
    experts' weighted sum."""
    x = quant(m, prec)
    if trunk["layers"][layer] == "dense":
        return swiglu(p["dense_gate"], p["dense_up"], p["dense_down"], x,
                      prec)
    weights = routing(p, m, trunk=trunk)
    out = swiglu(p["shared_gate"], p["shared_up"], p["shared_down"], x, prec)
    for e in range(trunk["experts_held"]):
        out = out + (weights[:, trunk["expert_offset"] + e, None]
                     * swiglu(p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                              x, prec))
    return out


def layer_forward(p, h, *, trunk, layer: int, prec):
    s, n, d = h.shape
    eps = trunk["eps"]
    u = quant(rms(p["input_norm"], h, eps), prec)
    a = quant(h + attention(p, u, trunk=trunk, prec=prec), prec)
    m = rms(p["post_norm"], a, eps).reshape(s * n, d)
    f = feed_forward(p, m, trunk=trunk, layer=layer, prec=prec)
    return quant(a + f.reshape(s, n, d), prec)


def agent_forward(p, obs, hidden, *, trunk, prec="f32"):
    """obs ``(B, A, N, F)`` normalised entity tokens, hidden ``(B, A, d)``
    → (q ``(B, A, n_actions)``, hidden' ``(B, A, d)``)."""
    b, a, n, f = obs.shape
    e = hidden.shape[-1]
    emb = dense(p["feat_embedding"], obs.reshape(b * a, n, f), prec)
    h = jnp.concatenate([emb, quant(hidden.reshape(b * a, 1, e), prec)],
                        axis=1)                        # hidden token LAST
    for i in range(len(trunk["layers"])):
        h = layer_forward(p["transformer"][f"layer_{i}"], h, trunk=trunk,
                          layer=i, prec=prec)
    out = rms(p["transformer"]["norm"], h[:, -1, :], trunk["eps"])
    q = dense(p["q_basic"], out, "f32")
    return q.reshape(b, a, -1), out.reshape(b, a, e)


def unroll_agent(p, batch, *, sizes, trunk, prec: str = "f32"):
    """The agent over the steps of ``batch`` (rows, mec, mean, std,
    time-major), its hidden token carried from zero → (Q-values ``(steps,
    B, A, n_actions)``, hidden ``(steps, B, A, d)``)."""
    _, b, a = batch["mec"].shape

    def step(h, xs):
        q, h = agent_forward(p, model.entity_obs(*xs), h, trunk=trunk,
                             prec=prec)
        return h, (q, h)
    _, (qs, hs) = jax.lax.scan(
        jax.checkpoint(step), jnp.zeros((b, a, sizes["emb"]), jnp.float32),
        (batch["rows"], batch["mec"], batch["mean"], batch["std"]))
    return qs, hs


def episode_loss(params, target_params, batch, weights, *, sizes, trunk,
                 gamma: float, prec: str = "f32", half_batch: bool = False):
    """The QMIX episode loss as ``benchmark/reference/qmix.py`` states it
    (double-Q under the availability mask, both recurrent streams from
    t = 0, the target mixer over all T+1 steps with outputs [1:] as
    bootstraps, importance-weighted masked MSE) with this trunk as the
    agent; the mixer is T2OMCA's (``model.mixer_forward``).
    → (loss, aux)."""
    mkw = dict(n_agents=sizes["n_agents"], heads=sizes["mixer_heads"],
               depth=sizes["mixer_depth"],
               standard_heads=sizes["standard_heads"], prec=prec)
    t1, b, a = batch["mec"].shape
    kw = dict(sizes=sizes, trunk=trunk, prec=prec)
    qs, hs = unroll_agent(params["agent"], batch, **kw)
    tqs, ths = jax.lax.stop_gradient(
        unroll_agent(target_params["agent"], batch, **kw))
    chosen = jnp.take_along_axis(
        qs[:-1], batch["actions"][..., None], axis=-1)[..., 0]
    best = jnp.argmax(jnp.where(batch["avail"], qs, -jnp.inf), axis=-1)
    target_max = jnp.take_along_axis(tqs, best[..., None], axis=-1)[..., 0]
    state_ent = batch["state"].reshape(t1, b, a, -1)

    def unroll_mixer(p, qv, hid, ent):
        def step(hyper, xs):
            y, hyper = model.mixer_forward(p, xs[0], xs[1], hyper, xs[2],
                                           **mkw)
            return hyper, y
        return jax.lax.scan(
            jax.checkpoint(step),
            jnp.zeros((b, 3, sizes["mixer_emb"]), jnp.float32),
            (qv, hid, ent))[1]

    q_tot = unroll_mixer(params["mixer"], chosen, hs[:-1], state_ent[:-1])
    target_q_tot = jax.lax.stop_gradient(unroll_mixer(
        target_params["mixer"], target_max, ths, state_ent))[1:]
    mask = batch["filled"].astype(jnp.float32)
    if half_batch:
        mask = mask * (jnp.arange(b) < b // 2)[None, :]
    targets = (batch["reward"] + gamma
               * (1.0 - batch["terminated"].astype(jnp.float32))
               * target_q_tot)
    td = (q_tot - targets) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (weights[None, :] * td ** 2).sum() / denom
    return loss, {
        "td_errors_abs": jnp.abs(td).sum(0) / jnp.maximum(mask.sum(0), 1.0),
        "q_taken_mean": (chosen.mean(-1) * mask).sum() / denom,
        "target_mean": (targets * mask).sum() / denom,
    }
