"""A catalog decoder trunk as the transformer agent's token stack.

``model.trunk`` replaces the T2OMCA blocks of ``TransformerAgent`` with
the decoder layers of a public language model at their published widths.
Three families are written down in ``config.py`` under their own
published key names (``TrunkConfig``: SmallThinker's;
``AfmoeTrunkConfig``: Trinity's, ``model_type: afmoe``;
``DeepseekV3TrunkConfig``: kanana-2's, ``model_type: deepseek_v3``); all
resolve to one ``TrunkSpec`` (``tk.spec``), and that is all this module
reads: what a layer IS is a set of mechanisms — two attention kinds among
them, grouped-query and latent — and no branch here tests a family or a
model's name.
The language model's embedding table and output head have no counterpart:
tokens are the agent's ``A`` entity rows (``feat_embedding``) followed by
the hidden token that carries memory, outputs are ``q_basic`` of that
token.

Per agent-step the sequence is ``x = [E(e_1) … E(e_A), h_{t-1}]`` at
positions ``0 … A``; the hidden token is LAST so that causal attention
lets it read every entity. With ``N`` RMSNorm (float32 statistics), a
layer with input ``h`` is

    u = N(h; input_norm)
    grouped-query [kv_latent 0]:
      q, k, v = u W_q, u W_k, u W_v         grouped-query heads of head_dim
      q, k = N_head(q), N_head(k)           [qk_norm] over head_dim, pre-RoPE
      q, k = RoPE(q), RoPE(k)               [layer.rope]
    latent [kv_latent > 0]:
      q = u W_q                           heads of [qk_nope_dim | qk_rope_dim]
      c, k_r = split(u W_kva)               ONE latent and ONE rotary key a
                                            token, read by every head
      k_n, v = split(N(c; kv_norm) W_kvb)   heads of [qk_nope_dim | v_head_dim]
      q_r, k_r = RoPE(q_r), RoPE(k_r)       [layer.rope] the rotary part only,
                                            pairs (2i, 2i+1) [rope_interleave]
      q kᵀ = q_n k_nᵀ + q_r k_rᵀ            d = qk_nope_dim + qk_rope_dim
    o = softmax(q kᵀ/√d + causal [∧ i-j < layer.window]) v       float32
    att = (o [⊙ σ(u W_g): attn_gate]) W_o
    a = h + [N(att; attn_out_norm): sandwich_norm | att]
    m = N(a; post_norm)
    f = dense:  W_down (act(W_gate m) ⊙ W_up m)            [layer.dense_width]
        routed: Σ_{e ∈ top-k} r_e Expert_e(m) [+ Shared(m): shared_width]
    y = a + [N(f; ff_out_norm): sandwich_norm | f]

and after the last layer ``h_t = N(y)[last]`` in float32. The router
(float32, all experts) reads ``h`` un-normed [router_reads_input] or
``m``; its weights are the softmax over the kept logits
[router_scores softmax], or sigmoid scores with the top-k taken of
``score + expert_bias`` [router_bias: the bias enters the SELECTION only,
so its gradient is exactly zero], the kept scores divided by their sum
[route_norm] and multiplied by ``route_scale``. The top-k is taken one
maximum at a time, as one-hot planes over the experts, and read through
them (``route``): ``jax.lax.top_k``'s ids in its order, with no sort,
gather or scatter in the compiled program. ``act`` is ``expert_act``
(relu | silu) in every feed-forward.

**One chip's share.** The layer is told which experts and heads this chip
holds (``experts_held``, ``heads_held``, ``share_index``): the router
scores all experts and keeps its ``k`` a token, the chip computes the
token-expert pairs whose expert it holds, and ``W_o`` contracts the heads
it holds; a shared expert, a dense layer's feed-forward and latent
attention's down-projection with its norm are held whole (every chip of
the group computes them alike). There is no exchange here
— the partial sums are what the next layer reads, in the program and in
its references alike (``benchmark/reference/trunk.py``,
``benchmark/reference/afmoe.py``, ``benchmark/reference/dsv3.py``) — and
no code stands in for the absent chips. Under pre-norm residuals the
shares' layer outputs add up to the uncut layer; under sandwich norms the
output norms are not linear, so the shares add up at the two SUBLAYER
sums (``att`` and ``f``, before their norms), and each share normalises
its own partial sum
(``tests/test_trunk.py``, ``tests/test_trunk_afmoe.py``).

**Routing is dropless, and its time does not depend on the routing.**
Every held expert runs over every token as one wide product, and each
token's result is weighted by its routing weight for that expert (zero for
an expert the token did not choose) before the down projection sums the
experts. All shapes are static and no pair can be left out under any skew.
A grouped product over the held pairs alone (``jax.lax.ragged_dot``) costs
less when the load is even, but its time follows the load: the entity
tokens are few distinct vectors (an entity of another MEC is a masked
row), an untrained router sends like tokens to the same experts, and
how many of those this chip holds is the seed's draw — the same
program ran 32 to 59 s a period by seed
(PERF.md par.6). One path, one cost, for every family.

Everything is plain ``jax.numpy`` over the parameter tree (the
``ops/query_slice.py`` pattern); ``TrunkAgent`` is the flax face that
declares the tree and serves ``BasicMAC.forward``.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

#: parameter leaves that stay float32 at every compute dtype: the router
#: and its selection bias (a rounded logit flips the top-k choice), the
#: norms' scales, the Q head
KEEP_F32 = ("router", "expert_bias", "input_norm", "post_norm", "norm",
            "q_norm", "k_norm", "attn_out_norm", "ff_out_norm", "kv_norm",
            "q_basic")

#: ``TrunkSpec.expert_act`` → the gate's activation in every feed-forward
_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}

#: float32 contractions that must not fall to the chip's default
#: (bfloat16-pass) precision
_HI = jax.lax.Precision.HIGHEST


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm over the last axis, float32 statistics → float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions over ``x (S, n, H, D)``, positions ``0 … n - 1``:
    the half-split convention (pairs ``(i, i + D/2)`` rotate together),
    angles in float32."""
    n, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_pairs(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """``rope`` in the interleaved convention: the pairs ``(2i, 2i + 1)``
    rotate together, by ``position · theta^(-2i/D)``. Each lane's partner
    is its neighbour, read by a shift of the lanes — no ``(D/2, 2)``
    reshape, whose minor axis of 2 a TPU pads to a whole tile."""
    n, d = x.shape[1], x.shape[-1]
    lane = jnp.arange(d)
    inv = theta ** (-(lane - lane % 2).astype(jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    partner = jnp.where(lane % 2 == 0, -jnp.roll(x32, -1, axis=-1),
                        jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + partner * sin).astype(x.dtype)


def attention_mask(n: int, window: int) -> jnp.ndarray:
    """``(n, n)`` bool: query ``i`` reads key ``j`` iff ``j <= i`` and,
    with a window, ``i - j < window`` (``window <= 0``: the whole
    prefix)."""
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    ok = j <= i
    return ok & (i - j < window) if window > 0 else ok


def grouped_scores(lp: dict, x: jnp.ndarray, sp, ls, dtype):
    """Grouped-query attention's scaled logits and values of the normed
    ``x (S, n, d)`` → (float32 ``(S, kv heads, group, n, n)``, ``v (S, n,
    kv heads, head_dim)``): every key/value head its own ``W_k`` / ``W_v``;
    RMSNorm on q and k per head [qk_norm]; RoPE over the whole head."""
    s, n, _ = x.shape
    hq, hkv, d = sp.heads_held, sp.kv_heads_held, sp.head_dim
    proj = lambda w, heads: jnp.dot(                         # noqa: E731
        x, w.astype(dtype), preferred_element_type=jnp.float32
    ).astype(dtype).reshape(s, n, heads, d)
    q, k, v = proj(lp["wq"], hq), proj(lp["wk"], hkv), proj(lp["wv"], hkv)
    if sp.qk_norm:
        q = rms_norm(q, lp["q_norm"], sp.rms_norm_eps).astype(dtype)
        k = rms_norm(k, lp["k_norm"], sp.rms_norm_eps).astype(dtype)
    if ls.rope:
        q, k = rope(q, sp.rope_theta), rope(k, sp.rope_theta)
    q = q.reshape(s, n, hkv, hq // hkv, d)
    logits = jnp.einsum("sqhgd,skhd->shgqk", q, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    return logits, v


def latent_kv(lp: dict, x: jnp.ndarray, sp, dtype):
    """What latent attention does that grouped-query attention does not:
    the normed ``x (S, n, d)`` down to ONE latent and ONE rotary key a
    token (``W_kva``, whole in every share), the latent's RMSNorm
    (float32 statistics) and its up-projection to the held heads'
    no-position keys and values → (``k_nope (S, n, H, qk_nope_dim)``,
    ``k_rope (S, n, qk_rope_dim)`` un-rotated, ``v (S, n, H,
    v_head_dim)``)."""
    s, n, _ = x.shape
    with jax.named_scope("agent.latent"):
        down = jnp.dot(x, lp["wkv_a"].astype(dtype),
                       preferred_element_type=jnp.float32).astype(dtype)
        c = rms_norm(down[..., :sp.kv_latent], lp["kv_norm"],
                     sp.rms_norm_eps).astype(dtype)
        kv = jnp.dot(c, lp["wkv_b"].astype(dtype),
                     preferred_element_type=jnp.float32).astype(dtype)
        kv = kv.reshape(s, n, sp.heads_held, sp.qk_nope_dim + sp.value_dim)
    return (kv[..., :sp.qk_nope_dim], down[..., sp.kv_latent:],
            kv[..., sp.qk_nope_dim:])


def latent_scores(lp: dict, x: jnp.ndarray, sp, ls, dtype):
    """Latent attention's scaled logits and values of the normed ``x`` —
    shaped as ``grouped_scores``' with a group of one: a query head is
    ``[no-position | rotary]``, its key the head's own no-position key
    beside the ONE rotary key every head reads; RoPE (the interleaved
    pairing under ``rope_interleave``) turns the rotary parts alone."""
    s, n, _ = x.shape
    hq, nope = sp.heads_held, sp.qk_nope_dim
    q = jnp.dot(x, lp["wq"].astype(dtype), preferred_element_type=jnp.float32
                ).astype(dtype).reshape(s, n, hq, sp.head_dim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    k_nope, k_rope, v = latent_kv(lp, x, sp, dtype)
    if ls.rope:
        turn = rope_pairs if sp.rope_interleave else rope
        q_rope = turn(q_rope, sp.rope_theta)
        k_rope = turn(k_rope[:, :, None, :], sp.rope_theta)[:, :, 0, :]
    logits = (jnp.einsum("sqhd,skhd->shqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("sqhd,skd->shqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32))
    return (logits * sp.head_dim ** -0.5)[:, :, None], v


def attention_part(lp: dict, h: jnp.ndarray, tk, layer: int,
                   dtype) -> jnp.ndarray:
    """This share's ``W_o · attention(RMSNorm(h))`` of the spec's kind —
    grouped-query (``grouped_scores``) or latent (``latent_scores``) —
    with the spec's extras (the output gate) — ``h (S, n, d)`` → float32
    ``(S, n, d)``, before any output norm. Softmax in float32 at every
    dtype (``n`` is tens)."""
    s, n, _ = h.shape
    sp = tk.spec
    ls = sp.layers[layer]
    x = rms_norm(h, lp["input_norm"], sp.rms_norm_eps).astype(dtype)
    scores = latent_scores if sp.kv_latent else grouped_scores
    logits, v = scores(lp, x, sp, ls, dtype)
    logits = jnp.where(attention_mask(n, ls.window), logits, -jnp.inf)
    attn = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("shgqk,skhd->sqhgd", attn, v,
                     preferred_element_type=jnp.float32)
    out = out.astype(dtype).reshape(s, n, sp.heads_held * sp.value_dim)
    if sp.attn_gate:
        gate = jax.nn.sigmoid(jnp.dot(x, lp["wg"].astype(dtype),
                                      preferred_element_type=jnp.float32))
        out = (out * gate).astype(dtype)
    return jnp.dot(out, lp["wo"].astype(dtype),
                   preferred_element_type=jnp.float32)


def route(w_router: jnp.ndarray, h: jnp.ndarray, tk, bias=None
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing of ``h (N, d)`` over ALL experts, float32 → (weights
    ``(N, k)``, expert ids ``(N, k)``), ids and their order those of
    ``jax.lax.top_k`` (largest first; equal values: lower id first).
    Softmax scores: softmax over every expert then renormalised over the
    kept ones is the softmax over the kept logits. Sigmoid scores: the
    top-k is taken of ``score + bias`` (``bias (experts,)``, selection
    only — the weights are the unbiased scores'), renormalised over the
    kept ones (``route_norm``) and scaled.

    The selection is ``k`` unrolled passes over the ``(N, E)`` plane —
    the row's first maximum as a one-hot over the lanes, that lane masked
    to ``-inf`` for the next pass — and every value that follows is read
    through those one-hots as a masked sum, whose transpose is a masked
    broadcast: no sort, gather or scatter. On a TPU a sort orders all
    ``E`` lanes to keep ``k``, and a gather or scatter moves its
    elements one after another (PERF.md par.5)."""
    sp = tk.spec
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=_HI)
    softmax = sp.router_scores == "softmax"
    values = logits if softmax else jax.nn.sigmoid(logits)
    # what is ranked carries no gradient: the ids are integers
    ranked = jax.lax.stop_gradient(
        values if bias is None else values + bias.astype(jnp.float32))
    lanes = jax.lax.broadcasted_iota(jnp.int32, ranked.shape, 1)
    kept, idx = [], []
    for _ in range(sp.top_k):
        first = jnp.argmax(ranked, axis=-1)     # ties: the lower lane
        hot = lanes == first[:, None]
        ranked = jnp.where(hot, -jnp.inf, ranked)
        kept.append(jnp.where(hot, values, 0.0).sum(axis=-1))
        idx.append(first)
    weights, idx = jnp.stack(kept, axis=-1), jnp.stack(idx, axis=-1)
    if softmax:
        return jax.nn.softmax(weights, axis=-1), idx
    if sp.route_norm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * sp.route_scale, idx


def held_weights(weights: jnp.ndarray, idx: jnp.ndarray, tk) -> jnp.ndarray:
    """Routing ``weights, idx (N, k)`` → float32 ``(N, experts_held)``:
    each token's weight for every expert held here, zero for one it did
    not choose. A pair whose expert is held elsewhere matches no column."""
    sp = tk.spec
    chosen = ((idx - sp.expert_offset)[:, :, None]
              == jnp.arange(sp.experts_held))
    return jnp.where(chosen, weights[:, :, None], 0.0).sum(axis=1)


def wide_experts(lp: dict, dtype):
    """The held experts' kernels as three wide matrices at ``dtype``:
    gate and up ``(e, d, f)`` → ``(d, e·f)``, down ``(e, f, d)`` →
    ``(e·f, d)`` — one plain product each over all held experts. The
    relayout moves every weight, so ``cast_weights`` makes it once outside
    a scan; kernels that are already wide pass through."""
    gate, up, down = lp["w_gate"], lp["w_up"], lp["w_down"]
    if gate.ndim == 3:
        e, d, f = gate.shape
        wide = lambda w: jnp.swapaxes(w, 0, 1).reshape(d, e * f)  # noqa
        gate, up, down = wide(gate), wide(up), down.reshape(e * f, d)
    return gate.astype(dtype), up.astype(dtype), down.astype(dtype)


def experts_part(lp: dict, m: jnp.ndarray, per: jnp.ndarray, dtype,
                 act=jax.nn.relu) -> jnp.ndarray:
    """This share's ``sum_e r_e W_down,e (act(W_gate,e m) * W_up,e m)``:
    ``m (N, d)``, ``per (N, experts_held)`` of ``held_weights`` → float32
    ``(N, d)``. Every held expert over every token; the routing weight
    scales the activations, and the down projection contracts experts and
    expert width together, so the sum over experts is the product's own
    float32 accumulation."""
    gate, up, down = wide_experts(lp, dtype)
    x = m.astype(dtype)
    n, e = per.shape
    g = act(jnp.dot(x, gate, preferred_element_type=dtype))
    u = jnp.dot(x, up, preferred_element_type=jnp.float32)
    weight = jnp.broadcast_to(per[:, :, None], (n, e, gate.shape[1] // e))
    hidden = (g * u * weight.reshape(n, -1)).astype(dtype)
    return jnp.dot(hidden, down, preferred_element_type=jnp.float32)


def gated_ffn(lp: dict, prefix: str, m: jnp.ndarray, dtype, act
              ) -> jnp.ndarray:
    """A whole gated feed-forward ``W_down (act(W_gate m) * W_up m)`` of
    the kernels ``<prefix>_gate / _up / _down`` (a shared expert, a dense
    layer's feed-forward): ``m (N, d)`` → float32 ``(N, d)``, at the
    roundings of ``experts_part``."""
    x = m.astype(dtype)
    g = act(jnp.dot(x, lp[prefix + "_gate"].astype(dtype),
                    preferred_element_type=dtype))
    u = jnp.dot(x, lp[prefix + "_up"].astype(dtype),
                preferred_element_type=jnp.float32)
    return jnp.dot((g * u).astype(dtype), lp[prefix + "_down"].astype(dtype),
                   preferred_element_type=jnp.float32)


def _routing(lp: dict, x: jnp.ndarray, tk):
    """The router over ``x (N, d)`` → (``held_weights``, the layer's
    aux)."""
    sp = tk.spec
    with jax.named_scope("agent.router"):
        # a router without a selection bias is called as it always was
        kw = {"bias": lp["expert_bias"]} if sp.router_bias else {}
        weights, idx = route(lp["router"], x, tk, **kw)
        per = held_weights(weights, idx, tk)
        lo = sp.expert_offset
        aux = {"load": (per > 0).sum(axis=0).astype(jnp.int32),
               "held": ((idx >= lo) & (idx < lo + sp.experts_held)
                        & (weights > 0)).sum().astype(jnp.int32)}
    return per, aux


def trunk_layer(lp: dict, h: jnp.ndarray, tk, layer: int, dtype):
    """One decoder layer over ``h (S, n, d)`` → (``y (S, n, d)`` in
    ``dtype``, aux). A routed layer's aux is ``{"load": (experts_held,)
    pairs that entered the product per held expert, "held": pairs the
    router sent to an expert id in this share's range}`` — two counts of
    the same pairs from the expert ids, by the product's own mask and by
    the range; a dense layer's is ``None``."""
    s, n, d = h.shape
    sp = tk.spec
    ls = sp.layers[layer]
    eps, act = sp.rms_norm_eps, _ACT[sp.expert_act]
    per = aux = None
    if not ls.dense_width and sp.router_reads_input:
        per, aux = _routing(lp, h.reshape(s * n, d), tk)
    with jax.named_scope("agent.attention"):
        residual = h.astype(jnp.float32)
        att = attention_part(lp, h, tk, layer, dtype)
        if sp.sandwich_norm:
            att = rms_norm(att, lp["attn_out_norm"], eps)
        a = (residual + att).astype(dtype)
    if ls.dense_width:
        with jax.named_scope("agent.dense"):
            m = rms_norm(a, lp["post_norm"], eps).reshape(s * n, d)
            residual = a.astype(jnp.float32)
            f = gated_ffn(lp, "dense", m, dtype, act)
            if sp.sandwich_norm:
                f = rms_norm(f, lp["ff_out_norm"], eps)
            y = (residual + f.reshape(s, n, d)).astype(dtype)
        return y, None
    with jax.named_scope("agent.experts"):
        m = rms_norm(a, lp["post_norm"], eps).reshape(s * n, d)
    if per is None:
        per, aux = _routing(lp, m, tk)
    shared = None
    if sp.shared_width:
        with jax.named_scope("agent.shared"):
            shared = gated_ffn(lp, "shared", m, dtype, act)
    with jax.named_scope("agent.experts"):
        residual = a.astype(jnp.float32)
        f = experts_part(lp, m, per, dtype, act)
        if shared is not None:
            f = f + shared
        if sp.sandwich_norm:
            f = rms_norm(f, lp["ff_out_norm"], eps)
        y = (residual + f.reshape(s, n, d)).astype(dtype)
    return y, aux


def entity_tokens(rows: jnp.ndarray, same_mec: jnp.ndarray,
                  mean: jnp.ndarray, std: jnp.ndarray) -> jnp.ndarray:
    """The normalised entity observation every agent sees, rebuilt from
    its factored form (``env.compact_obs`` / compact entity storage):
    ``rows (B, A, 8)``, ``same_mec (B, A, A)``, ``mean/std (B, A, 9)`` →
    ``(B, A, A, 9)``, observer-major — ``envs/mec_offload._raw_obs``
    under the shared ``fast_norm`` affine."""
    a = rows.shape[-2]
    ent = jnp.where(same_mec[..., None],
                    rows.astype(jnp.float32)[:, None, :, :], 0.0)
    is_self = jnp.broadcast_to(jnp.eye(a, dtype=jnp.float32)[..., None],
                               ent.shape[:-1] + (1,))
    raw = jnp.concatenate([ent, is_self], axis=-1)
    return (raw - mean[:, None]) / (std[:, None] + 1e-8)


def cast_weights(params: dict, dtype) -> dict:
    """The matmul weights at the compute dtype, ONCE, outside any scan
    that runs the forward (every step would otherwise read the float32
    leaves and round them again), the experts' kernels in their wide
    layout (``wide_experts``); ``KEEP_F32`` leaves stay float32.
    Differentiable (casts and a relayout)."""
    def cast(path, x):
        names = {getattr(k, "key", None) for k in path}
        return x if names & set(KEEP_F32) else x.astype(dtype)
    p = params.get("params", params)
    out = jax.tree_util.tree_map_with_path(cast, p)
    layers = {}
    for name, lp in out["transformer"].items():
        if isinstance(lp, dict) and "w_gate" in lp:
            gate, up, down = wide_experts(lp, dtype)
            lp = dict(lp, w_gate=gate, w_up=up, w_down=down)
        layers[name] = lp
    return dict(out, transformer=layers)


def _embed(p: dict, obs: jnp.ndarray, dtype) -> jnp.ndarray:
    """``obs (B, A, N, F)`` → entity tokens ``(B·A, N, d)``."""
    b, a, n_ent, f = obs.shape
    with jax.named_scope("agent.embed"):
        fe = p["feat_embedding"]
        return (jnp.dot(obs.reshape(b * a, n_ent, f).astype(dtype),
                        fe["kernel"].astype(dtype),
                        preferred_element_type=jnp.float32)
                + fe["bias"].astype(jnp.float32)).astype(dtype)


def _head(p: dict, last: jnp.ndarray, tk, shape):
    """The final norm of the hidden token's output ``(S, d)`` and the Q
    head, float32 → (q, hidden') shaped ``shape + (·,)``."""
    with jax.named_scope("agent.head"):
        h_new = rms_norm(last, p["transformer"]["norm"], tk.rms_norm_eps)
        qb = p["q_basic"]
        q = (jnp.dot(h_new, qb["kernel"].astype(jnp.float32))
             + qb["bias"].astype(jnp.float32))
    return q.reshape(shape + (-1,)), h_new.reshape(shape + (-1,))


def agent_forward_trunk(variables: dict, obs: jnp.ndarray,
                        hidden: jnp.ndarray, *, tk, dtype):
    """``obs (B, A, A, 9)`` normalised entity tokens, ``hidden (B, A, d)``
    → (q ``(B, A, n_actions)`` float32, hidden' ``(B, A, d)`` float32,
    aux) with ``aux`` the ROUTED layers' (``trunk_layer``) stacked:
    ``{"load": (routed layers, experts_held), "held": (routed layers,)}``
    — the sources of the ``moe_*`` counters (``moe_counters``). The one
    entry: acting calls it a step, the learner scans it (``unroll``)."""
    p = variables.get("params", variables)
    b, a = obs.shape[:2]
    h = jnp.concatenate(
        [_embed(p, obs, dtype),
         hidden.reshape(b * a, 1, -1).astype(dtype)], axis=1)
    auxes = []
    for layer in range(tk.num_hidden_layers):
        h, aux = trunk_layer(p["transformer"][f"layer_{layer}"], h, tk,
                             layer, dtype)
        if aux is not None:
            auxes.append(aux)
    aux = jax.tree.map(lambda *x: jnp.stack(x), *auxes)
    return _head(p, h[:, -1, :], tk, (b, a)) + (aux,)


def unroll(variables: dict, obs_tm: jnp.ndarray, hidden: jnp.ndarray, *,
           tk, dtype, wrap=lambda f: f):
    """The agent over the steps of ``obs_tm (T, B, A, A, 9)``, its hidden
    token carried from ``hidden`` → (q ``(T, B, A, n_actions)``, hiddens
    ``(T, B, A, d)``, aux stacked over the steps): a scan of
    ``agent_forward_trunk``. ``wrap`` wraps the body (``jax.checkpoint``
    under ``model.remat``)."""
    def step(h, obs):
        q, h, aux = agent_forward_trunk(variables, obs, h, tk=tk,
                                        dtype=dtype)
        return h, (q, h, aux)

    _, out = jax.lax.scan(wrap(step), hidden, obs_tm)
    return out


def moe_counters(aux: dict, tokens: int, tk) -> dict:
    """The four counters of the training info rows and the rollout stats
    from the ``aux`` of the forwards they cover, stacked over any leading
    axes (a scan's steps) and summed here (``tokens``: the tokens those
    forwards routed, a static count); routed layers only — a dense layer
    routes nothing. ``moe_load_max``: per layer the
    busiest held expert's pairs, summed over the layers, so that
    ``moe_load_max / moe_pairs_held`` is ``1 / experts_held`` under an
    even load. ``moe_dropped``: pairs the router sent to this share's
    range of expert ids less pairs that entered the product with their
    weight (``trunk_layer``'s two counts) — 0 unless the product's mask
    leaves a held pair out."""
    load = aux["load"].astype(jnp.float32)
    load = load.reshape((-1,) + load.shape[-2:]).sum(axis=0)
    return {
        "moe_pairs_held": load.sum(),
        "moe_pairs_routed": jnp.asarray(
            float(tokens) * tk.spec.top_k * tk.spec.expert_layers,
            jnp.float32),
        "moe_load_max": load.max(axis=-1).sum(),
        "moe_dropped": aux["held"].astype(jnp.float32).sum() - load.sum(),
    }


MOE_COUNTERS = ("moe_pairs_held", "moe_pairs_routed", "moe_load_max",
                "moe_dropped")


class _Dense(nn.Module):
    """``{"kernel", "bias"}`` of an ``nn.Dense``, declared and handed
    back (the forward is ``agent_forward_trunk``'s)."""
    inputs: int
    features: int

    @nn.compact
    def __call__(self) -> dict:
        return {"kernel": self.param("kernel", nn.initializers.lecun_normal(),
                                     (self.inputs, self.features)),
                "bias": self.param("bias", nn.initializers.zeros,
                                   (self.features,))}


class _Layer(nn.Module):
    """One layer's parameters: this chip's share, by what the spec says
    the layer has."""
    trunk: Any
    layer: int

    @nn.compact
    def __call__(self) -> dict:
        sp = self.trunk.spec
        ls = sp.layers[self.layer]
        d, f, e = sp.hidden_size, sp.expert_width, sp.experts_held
        hq, hkv = sp.heads_held * sp.head_dim, sp.kv_heads_held * sp.head_dim
        init = nn.initializers.lecun_normal()
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        ones = nn.initializers.ones
        out = {
            "input_norm": self.param("input_norm", ones, (d,)),
            "wq": self.param("wq", init, (d, hq)),
        }
        if sp.kv_latent:
            # the down-projection and its norm whole; the held heads'
            # columns of the up-projection, [no-position key | value] a head
            rank, up = sp.kv_latent, sp.qk_nope_dim + sp.value_dim
            out["wkv_a"] = self.param("wkv_a", init,
                                      (d, rank + sp.qk_rope_dim))
            out["kv_norm"] = self.param("kv_norm", ones, (rank,))
            out["wkv_b"] = self.param("wkv_b", init,
                                      (rank, sp.heads_held * up))
        else:
            out["wk"] = self.param("wk", init, (d, hkv))
            out["wv"] = self.param("wv", init, (d, hkv))
        out["wo"] = self.param("wo", init,
                               (sp.heads_held * sp.value_dim, d))
        out["post_norm"] = self.param("post_norm", ones, (d,))
        if sp.qk_norm:
            out["q_norm"] = self.param("q_norm", ones, (sp.head_dim,))
            out["k_norm"] = self.param("k_norm", ones, (sp.head_dim,))
        if sp.attn_gate:
            out["wg"] = self.param("wg", init, (d, hq))
        if sp.sandwich_norm:
            out["attn_out_norm"] = self.param("attn_out_norm", ones, (d,))
            out["ff_out_norm"] = self.param("ff_out_norm", ones, (d,))

        def ffn(prefix, width):
            out[prefix + "_gate"] = self.param(prefix + "_gate", init,
                                               (d, width))
            out[prefix + "_up"] = self.param(prefix + "_up", init,
                                             (d, width))
            out[prefix + "_down"] = self.param(prefix + "_down", init,
                                               (width, d))
        if ls.dense_width:
            ffn("dense", ls.dense_width)
            return out
        out.update({
            "router": self.param("router", init, (d, sp.experts)),
            "w_gate": self.param("w_gate", per_expert, (e, d, f)),
            "w_up": self.param("w_up", per_expert, (e, d, f)),
            "w_down": self.param("w_down", per_expert, (e, f, d)),
        })
        if sp.router_bias:
            # a checkpoint's selection bias is not zero, and at zero a
            # fault in how it enters would be invisible: N(0, 0.02^2)
            out["expert_bias"] = self.param(
                "expert_bias", nn.initializers.normal(0.02), (sp.experts,))
        if sp.shared_width:
            ffn("shared", sp.shared_width)
        return out


class _Stack(nn.Module):
    trunk: Any

    @nn.compact
    def __call__(self) -> dict:
        tk = self.trunk
        out = {f"layer_{i}": _Layer(tk, i, name=f"layer_{i}")()
               for i in range(tk.num_hidden_layers)}
        out["norm"] = self.param("norm", nn.initializers.ones,
                                 (tk.hidden_size,))
        return out


class TrunkAgent(nn.Module):
    """The flax face of ``agent_forward_trunk``: declares the parameter
    tree (this chip's share of every layer, under ``transformer`` as the
    T2OMCA stack is) and serves the dense-obs ``BasicMAC.forward``
    contract of ``TransformerAgent``."""

    n_agents: int
    n_entities: int
    feat_dim: int
    emb: int
    n_actions: int
    trunk: Any
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, hidden_state: jax.Array,
                 deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
        b, a, _ = inputs.shape
        tree = {
            "feat_embedding": _Dense(self.feat_dim, self.emb,
                                     name="feat_embedding")(),
            "transformer": _Stack(self.trunk, name="transformer")(),
            "q_basic": _Dense(self.emb, self.n_actions, name="q_basic")(),
        }
        q, h, _ = agent_forward_trunk(
            tree, inputs.reshape(b, a, self.n_entities, self.feat_dim),
            hidden_state, tk=self.trunk, dtype=self.dtype)
        return q, h

    def initial_hidden(self, batch_size: int) -> jax.Array:
        return jnp.zeros((batch_size, self.n_agents, self.emb))
