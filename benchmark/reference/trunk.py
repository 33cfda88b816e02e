"""Plain reference of a catalog decoder trunk as the QMIX agent's token
stack (SmallThinker's layer; PowerInfer/SmallThinker-21BA3B-Instruct
``config.json`` and the family's description), in ``jax.numpy`` and
float32 at ``highest`` matmul precision. Nothing of the program is
imported; no kernel, no sort, no grouped product: every expert held here
is computed over every token and masked by the routing weight.

Per agent-step the sequence is the agent's ``A`` entity tokens (the
9 -> d ``feat_embedding`` of the normalised entity observation) followed
by the hidden token carried from the step before, at positions
``0 ... A``. For layer ``l`` with input ``h``:

* router, float32, on the layer's input before any norm ("router placed
  before attention"): softmax over all experts, the ``top_k`` largest
  kept and renormalised to sum 1;
* ``a = h + W_o GQA(RMSNorm(h))``: causal grouped-query attention, scale
  ``head_dim ** -0.5``; a layer the layout marks rotates q and k (RoPE,
  written here as multiplication by ``exp(i * position * frequency)`` on
  the complex pairs ``(x_j, x_{j + D/2})``) and masks keys ``window`` or
  more positions back, an unmarked layer has no positional encoding and
  reads the whole prefix. No biases, no q/k norm;
* ``y = a + sum_e r_e W_down,e (relu(W_gate,e m) * W_up,e m)``,
  ``m = RMSNorm(a)``;
* after the last layer the final RMSNorm's last token, float32, is the
  carried hidden state, the mixer's input and the Q head's.

**The share.** ``trunk`` gives the heads and experts held: the parameters
are that share's (``q_heads`` query heads over ``kv_heads`` key/value
heads, experts ``expert_offset ... expert_offset + experts_held - 1`` of
``experts``). The router scores all ``experts``; what the absent experts
and heads would add is left out, and that partial result goes on to the
next layer — as in the program. With every head and expert held this is
the uncut layer.

Departures from the published model, all forced by what the tokens are:
no vocabulary (embedding table, output head), a sequence of ``A + 1``
tokens with no cache, the hidden token as recurrence.

``prec`` as in ``benchmark/reference/model.py`` (operands and handed-on
activations rounded to it; accumulation, softmax and norm statistics,
the router, the final norm and the Q head float32 at every setting).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model
from .model import dense, mm, quant

_HI = jax.lax.Precision.HIGHEST


def rms_norm(scale, x, eps, prec):
    return quant(x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
                 * scale, prec)


def rotate(x, theta):
    """RoPE on ``x (S, n, H, D)`` at positions ``0 ... n-1``: the pair
    ``(x_j, x_{j + D/2})`` as a complex number times ``exp(i p f_j)``,
    ``f_j = theta ** (-2 j / D)``."""
    n, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half) / d)
    turn = jnp.exp(1j * jnp.arange(n)[:, None] * freq[None, :])
    z = (x[..., :half] + 1j * x[..., half:]) * turn[None, :, None, :]
    return jnp.concatenate([z.real, z.imag], axis=-1).astype(x.dtype)


def routing(w_router, h, top_k: int):
    """``h (N, d)`` → ``(N, experts)`` weights: softmax over all experts,
    the ``top_k`` largest kept (found one maximum at a time) and
    renormalised; zero elsewhere."""
    probs = jax.nn.softmax(jnp.dot(h, w_router, precision=_HI), axis=-1)
    keep = jnp.zeros(probs.shape, bool)
    for _ in range(top_k):
        best = jnp.argmax(jnp.where(keep, -1.0, probs), axis=-1)
        keep = keep | jax.nn.one_hot(best, probs.shape[-1], dtype=bool)
    kept = jnp.where(keep, probs, 0.0)
    return kept / kept.sum(-1, keepdims=True)


def attention(p, x, *, trunk, layer: int, prec):
    """``x (S, n, d)`` normed → this share's ``W_o``-projected attention,
    one query head at a time."""
    s, n, _ = x.shape
    d, hq, hkv = trunk["head_dim"], trunk["q_heads"], trunk["kv_heads"]
    split = lambda w, heads: quant(mm(                       # noqa: E731
        "snd,de->sne", x, w, prec), prec).reshape(s, n, heads, d)
    q, k, v = split(p["wq"], hq), split(p["wk"], hkv), split(p["wv"], hkv)
    if trunk["rope"][layer]:
        q, k = (quant(rotate(q, trunk["theta"]), prec),
                quant(rotate(k, trunk["theta"]), prec))
    pos = jnp.arange(n)
    back = pos[:, None] - pos[None, :]                       # query - key
    seen = back >= 0
    if trunk["window"][layer]:
        seen = seen & (back < trunk["window"][layer])
    heads = []
    for j in range(hq):
        g = j // (hq // hkv)                 # the key/value head it reads
        logits = mm("sqd,skd->sqk", q[:, :, j], k[:, :, g], prec) * d ** -0.5
        w = quant(jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), -1), prec)
        heads.append(quant(mm("sqk,skd->sqd", w, v[:, :, g], prec), prec))
    return mm("sne,ed->snd", jnp.concatenate(heads, axis=-1), p["wo"], prec)


def experts(p, m, weights, *, trunk, prec):
    """``m (N, d)`` normed, ``weights (N, experts)`` → the held experts'
    weighted sum, each held expert over every token."""
    out = jnp.zeros(m.shape, jnp.float32)
    for e in range(trunk["experts_held"]):
        act = quant(jax.nn.relu(mm("nd,df->nf", m, p["w_gate"][e], prec))
                    * mm("nd,df->nf", m, p["w_up"][e], prec), prec)
        out = out + (weights[:, trunk["expert_offset"] + e, None]
                     * mm("nf,fd->nd", act, p["w_down"][e], prec))
    return out


def layer_forward(p, h, *, trunk, layer: int, prec):
    s, n, d = h.shape
    weights = routing(p["router"], h.reshape(s * n, d), trunk["top_k"])
    a = quant(h + attention(p, rms_norm(p["input_norm"], h, trunk["eps"],
                                        prec),
                            trunk=trunk, layer=layer, prec=prec), prec)
    m = rms_norm(p["post_norm"], a, trunk["eps"], prec)
    return quant(a + experts(p, m.reshape(s * n, d), weights, trunk=trunk,
                             prec=prec).reshape(s, n, d), prec)


def agent_forward(p, obs, hidden, *, trunk, prec="f32"):
    """obs ``(B, A, N, F)`` normalised entity tokens, hidden ``(B, A, d)``
    → (q ``(B, A, n_actions)``, hidden' ``(B, A, d)``)."""
    b, a, n, f = obs.shape
    e = hidden.shape[-1]
    emb = dense(p["feat_embedding"], obs.reshape(b * a, n, f), prec)
    h = jnp.concatenate([emb, quant(hidden.reshape(b * a, 1, e), prec)],
                        axis=1)                        # hidden token LAST
    for i in range(trunk["layers"]):
        h = layer_forward(p["transformer"][f"layer_{i}"], h, trunk=trunk,
                          layer=i, prec=prec)
    out = rms_norm(p["transformer"]["norm"], h[:, -1, :], trunk["eps"],
                   "f32")
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
    """The QMIX episode loss of ``benchmark/reference/qmix.py`` (double-Q
    under the availability mask, both recurrent streams from t = 0, the
    target mixer over all T+1 steps with outputs [1:] as bootstraps,
    importance-weighted masked MSE) with this trunk as the agent; the
    mixer is T2OMCA's (``model.mixer_forward``). → (loss, aux)."""
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
