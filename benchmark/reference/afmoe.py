"""Plain reference of an ``afmoe`` decoder trunk (Trinity's family:
arcee-ai/Trinity-Mini ``config.json`` and the ``modeling_afmoe.py`` beside
it) as the QMIX agent's token stack, in ``jax.numpy`` and float32 at
``highest`` matmul precision. Nothing of the program is imported; no
kernel, no wide product, no cast tree: every expert held here is computed
over every token in a Python loop and weighted by the routing weight.

Per agent-step the sequence is the agent's ``A`` entity tokens (the
9 -> d ``feat_embedding`` of the normalised entity observation) followed
by the hidden token carried from the step before, at positions
``0 ... A``. With ``N`` RMSNorm, held layer ``l`` with input ``h``:

* ``u = N(h; input_norm)``; ``q, k, v = u W_q, u W_k, u W_v`` in heads of
  ``head_dim``; q and k are RMS-normed over ``head_dim`` (one scale
  vector each) BEFORE any rotation; a ``sliding`` layer rotates q and k
  (RoPE, the rotate-half form ``x cos + rot(x) sin``) and masks keys
  ``window`` or more positions back, a ``full`` layer has no positional
  encoding and reads the whole causal prefix; scale ``head_dim ** -0.5``;
* the heads' output is gated per element by ``sigmoid(u W_g)``, then
  ``W_o``; ``a = h + N(. ; attn_out_norm)`` — the norm on the sublayer's
  OUTPUT, here this share's partial sum;
* ``m = N(a; post_norm)``; a ``dense`` layer:
  ``f = W_down (silu(W_gate m) * W_up m)``; an ``experts`` layer:
  ``s = sigmoid(m W_r)`` over all experts (float32), the ``top_k`` of
  ``s + expert_bias`` kept (the bias chooses, it does not weigh),
  ``r_e = route_scale * s_e / (sum of kept s + 1e-20)``,
  ``f = Shared(m) + sum over kept and held e of r_e Expert_e(m)``, every
  expert and the shared one SwiGLU;
* ``y = a + N(f; ff_out_norm)``;
* after the last layer the final RMSNorm's last token, float32, is the
  carried hidden state, the mixer's input and the Q head's.

**The share.** ``trunk`` gives the heads and experts held: the parameters
are that share's (``q_heads`` query heads over ``kv_heads`` key/value
heads, experts ``expert_offset ... expert_offset + experts_held - 1`` of
``experts``); the shared expert and a dense layer's feed-forward are
whole. The router scores all ``experts``; what the absent experts and
heads would add is left out, the two output norms act on the partial
sums, and that is what goes on — as in the program. ``attention`` and
``feed_forward`` return the sublayer sums BEFORE their norms: over all
shares (the shared expert once) those add up to the uncut layer's.

Departures from the published model: no vocabulary (embedding table with
muP's multiplier, output head), a sequence of ``A + 1`` tokens with no
cache, the hidden token as recurrence; ``expert_bias`` is a given
parameter (its update from the load is training-loop state the model's
equations do not hold).

``prec`` as in ``benchmark/reference/model.py`` (operands and handed-on
activations rounded to it; accumulation, softmax and norm statistics,
the router, the output norms' results, the final norm and the Q head
float32 at every setting).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model
from .model import dense, mm, quant

_HI = jax.lax.Precision.HIGHEST


def rms(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotate(x, theta):
    """RoPE on ``x (S, n, H, D)`` at positions ``0 ... n-1``, rotate-half:
    ``x cos + rot(x) sin`` with ``rot(x) = [-x_2, x_1]`` on the halves and
    the angle ``p * theta ** (-2 j / D)`` repeated over both halves."""
    n, d = x.shape[1], x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2) / d)
    ang = jnp.arange(n)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def routing(p, m, *, trunk):
    """``m (N, d)`` → ``(N, experts)`` weights: sigmoid scores, the
    ``top_k`` of ``score + expert_bias`` kept (found one maximum at a
    time), the kept scores over their sum times ``route_scale``; zero
    elsewhere."""
    scores = jax.nn.sigmoid(jnp.dot(m, p["router"], precision=_HI))
    chooser = scores + p["expert_bias"]
    keep = jnp.zeros(scores.shape, bool)
    for _ in range(trunk["top_k"]):
        best = jnp.argmax(jnp.where(keep, -jnp.inf, chooser), axis=-1)
        keep = keep | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    kept = jnp.where(keep, scores, 0.0)
    return trunk["route_scale"] * kept / (kept.sum(-1, keepdims=True) + 1e-20)


def attention(p, u, *, trunk, layer: int, prec):
    """``u (S, n, d)`` normed → this share's gated, ``W_o``-projected
    attention BEFORE its output norm, one query head at a time."""
    s, n, _ = u.shape
    d, hq, hkv = trunk["head_dim"], trunk["q_heads"], trunk["kv_heads"]
    eps = trunk["eps"]
    split = lambda w, heads: quant(mm(                       # noqa: E731
        "snd,de->sne", u, w, prec), prec).reshape(s, n, heads, d)
    q, k, v = split(p["wq"], hq), split(p["wk"], hkv), split(p["wv"], hkv)
    q = quant(rms(p["q_norm"], q, eps), prec)
    k = quant(rms(p["k_norm"], k, eps), prec)
    pos = jnp.arange(n)
    back = pos[:, None] - pos[None, :]                       # query - key
    seen = back >= 0
    if trunk["layers"][layer][1] == "sliding":
        q = quant(rotate(q, trunk["theta"]), prec)
        k = quant(rotate(k, trunk["theta"]), prec)
        seen = seen & (back < trunk["window"])
    heads = []
    for j in range(hq):
        g = j // (hq // hkv)                 # the key/value head it reads
        logits = mm("sqd,skd->sqk", q[:, :, j], k[:, :, g], prec) * d ** -0.5
        w = quant(jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), -1), prec)
        heads.append(quant(mm("sqk,skd->sqd", w, v[:, :, g], prec), prec))
    gate = jax.nn.sigmoid(mm("snd,de->sne", u, p["wg"], prec))
    gated = quant(jnp.concatenate(heads, axis=-1) * gate, prec)
    return mm("sne,ed->snd", gated, p["wo"], prec)


def swiglu(gate, up, down, m, prec):
    act = quant(jax.nn.silu(mm("nd,df->nf", m, gate, prec))
                * mm("nd,df->nf", m, up, prec), prec)
    return mm("nf,fd->nd", act, down, prec)


def feed_forward(p, m, *, trunk, layer: int, prec):
    """``m (N, d)`` normed (float32, un-rounded: the router reads it so)
    → the feed-forward sum BEFORE its output norm: a dense layer's
    SwiGLU, or the shared expert plus the held experts' weighted sum."""
    x = quant(m, prec)
    if trunk["layers"][layer][0] == "dense":
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
    att = attention(p, u, trunk=trunk, layer=layer, prec=prec)
    a = quant(h + rms(p["attn_out_norm"], att, eps), prec)
    m = rms(p["post_norm"], a, eps).reshape(s * n, d)
    f = feed_forward(p, m, trunk=trunk, layer=layer, prec=prec)
    return quant(a + rms(p["ff_out_norm"], f, eps).reshape(s, n, d), prec)


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
