"""Query-slice agent forward: compute ONLY the hidden token's row.

An exact algebraic reduction of ``TransformerAgent.__call__``, exploiting two
structural facts of the reference architecture (both pinned by parity tests):

1. **Keys are layer-0-pinned.** Every block attends its evolving queries
   against the ORIGINAL embedded tokens — blocks return ``k`` unchanged
   (``/root/reference/transformer.py:126,140``; ``models/transformer.py``
   "Key threading"). So token ``i``'s output at depth ``L`` depends only on
   token ``i``'s own query path and the shared layer-0 keys: information
   never flows token→token→token across layers.
2. **Only token 0 is consumed.** The agent reads ``out[:, 0]`` as the new
   hidden state and Q-head input (``/root/reference/transf_agent.py:71``);
   the other ``n_entities`` output rows are dead.

Therefore the attention-output / unify / LayerNorm / FFN work for every
entity token is dead computation — ~``(T-1)/T`` ≈ 98% of block FLOPs at the
64-agent scale. This path carries a single query row (token 0) through the
stack and contracts the key/value projections away entirely:

* ``logits_h = (q_h·s)·(k0 Wk_h·s)^T = x0 (Wq_h Wk_h^T s^2) k0^T`` — fold
  ``Wqk_h = Wq_h Wk_h^T s^2`` (E×E per head, computed once from the weights,
  O(params) not O(tokens)), so keys are never materialized.
* ``attended = Σ_h softmax(logits_h) (k0 Wv_h) Wu_h = Σ_h (attn_h k0) Wvu_h``
  with ``Wvu_h = Wv_h Wu_h`` — values are never materialized either.

Per sequence the block cost drops from O(T·E²·ff) to O(E²·ff + H·T·E): at the
north-star scale (T=65, E=256) a ~50× FLOP reduction with bit-compatible
semantics (float reassociation only; equivalence pinned to the flax module in
``tests/test_qslice.py``, including gradients — the reduction is exact, so
the learner can unroll through it too).

All ops are fat ``(S, ·)×(·, ·)`` matmuls over the folded batch×agent axis
plus two bandwidth-bound batched contractions against ``k0`` — no Pallas
needed; XLA fuses the rest. Numerics conventions: f32 accumulation, f32
LayerNorm statistics, softmax in f32 for the f32 parity mode and bf16 for
the perf mode (mirroring ``models/transformer.py:101-105``).

Forward-compatible with gradient flow: everything here is plain jnp, so
``jax.grad`` through it yields the same gradients as the dense module (same
function, different association).

**Two associations of the entity-table forward.** The fold above is made for
the obs path (``transformer_rows``), whose keys are per-sequence
``(S, T, E)`` tensors: not projecting them is the whole gain, and it holds at
either head geometry. ``agent_forward_qslice_entity`` contracts against
per-env TABLES instead, and there the fold only pays where a head is as wide
as the embedding (``head_dim == emb``, the reference's Q1 geometry). With
``standard_heads`` (``head_dim = emb / heads``) every folded query and context
row is ``heads`` times wider than the mathematics needs, so that forward
projects queries, keys and values as the dense module does — the entity
tables' keys and values straight from the nine normalised features, the
embedding being affine (``_fold_entity_heads``) — and contracts at head width
(``_entity_attention_heads``). One sum, two associations, chosen at trace time
from ``head_dim`` and ``emb`` alone.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

LN_EPS = 1e-6   # flax nn.LayerNorm default


def _ln(x32: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray
        ) -> jnp.ndarray:
    """f32 fast-variance LayerNorm over the last axis (flax-compatible)."""
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + LN_EPS)
    return (x32 - mean) * inv * scale + bias


#: marker key of a pre-folded parameter tree (see ``fold_transformer``)
FOLDED = "__qslice_folded__"


def agent_qslice_eligible(cfg) -> bool:
    """Single source of truth for agent-side eligibility: the reduction
    needs a deterministic transformer STACK (no dropout mask inside the
    blocks). NoisyLinear is fine: the noise lives only in the q-head
    (``models/agent.py:64-66``), which applies AFTER the sliced stack —
    ``_q_head`` samples it from an explicit key (round 5; previously
    noisy configs were excluded wholesale, which forced the reference's
    own selector onto the dense path). Consumers: ``BasicMAC.build`` and
    ``QMixLearner`` (both acting and learner unrolls share it)."""
    return (cfg.model.use_qslice
            and cfg.agent == "transformer"
            # a catalog trunk (models/trunk.py) pins nothing to layer 0
            # and consumes every token: there is no slice to take
            and cfg.model.trunk is None
            and cfg.model.dropout == 0.0)


def entity_obs_factored(cfg) -> bool:
    """The factored form ``env.compact_obs`` gives (feature rows, MEC
    ids, shared statistics) reconstructs every agent's normalised obs
    exactly: the entity observation mode (the factored structure IS the
    entity obs), the batched normalizer (the sequential one gives each
    observer different prefix statistics), and no entity-count override
    (the rows are the env's own agents)."""
    return (cfg.env_args.obs_entity_mode
            and cfg.env_args.fast_norm
            and cfg.model.n_entities_obs == 0)


def trunk_compact_eligible(cfg) -> bool:
    """A catalog trunk reads the factored observation: its MAC and its
    learner's unroll rebuild the entity tokens from the compact rows
    (``models/trunk.entity_tokens``) and run the dense token path — the
    compact ring layout without the sliced forward."""
    return (cfg.model.trunk is not None and cfg.agent == "transformer"
            and entity_obs_factored(cfg))


def entity_tables_eligible(cfg) -> bool:
    """Entity-table eligibility: needs the ``use_entity_tables`` kill
    switch on (it covers BOTH acting and the learner's compact-storage
    unroll), the qslice agent path, and the factored observation
    (``entity_obs_factored``)."""
    return (cfg.model.use_entity_tables
            and agent_qslice_eligible(cfg)
            and entity_obs_factored(cfg))


def entity_store_eligible(cfg) -> bool:
    """Compact entity episode STORAGE eligibility: the learner must be
    able to unroll from the factored rows — through the entity-table
    forward (the sliced T2OMCA agent, ``entity_tables_eligible``) or by
    rebuilding the tokens for the dense path (a catalog trunk,
    ``trunk_compact_eligible``); storage does not imply the sliced
    forward — the mixer must not consume stored obs (Q12 fallback needs
    the full tensor), and the host-RAM buffer keeps the plain layout (its
    escape-hatch use case predates the 20× shrink)."""
    return (cfg.replay.compact_entity_store
            and (entity_tables_eligible(cfg) or trunk_compact_eligible(cfg))
            and cfg.env_args.state_entity_mode
            and not cfg.replay.buffer_cpu_only
            # the stored mec_index narrows to int8
            # (runners/parallel_runner.py obs_store); ids are 0..mec_num-1,
            # so any id past 127 would alias and corrupt reconstructed
            # same-MEC visibility
            and cfg.env_args.mec_num <= 128)


def mixer_qslice_eligible(cfg) -> bool:
    """Mixer-side eligibility: deterministic transformer mixer only (only
    the last ``n_agents+3`` output rows are consumed, models/mixer.py)."""
    return (cfg.model.use_qslice
            and cfg.mixer == "transformer"
            and cfg.model.dropout == 0.0)


def _fold_block(bp: dict, emb: int, heads: int, head_dim: int,
                dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold the block's attention projections (f32, O(E²·H·D) — independent
    of the token/batch axes).

    Returns ``wqk (E, H·E)`` with the Q1 dual ``head_dim**-0.25`` scaling
    folded in, and ``wvu (H·E, E)``.
    """
    at = bp["attention"]
    wq = at["toqueries"]["kernel"].astype(jnp.float32)   # (E, H·D)
    wk = at["tokeys"]["kernel"].astype(jnp.float32)
    wv = at["tovalues"]["kernel"].astype(jnp.float32)
    wu = at["unifyheads"]["kernel"].astype(jnp.float32)  # (H·D, E)
    h, d, e = heads, head_dim, emb
    wq_h = wq.reshape(e, h, d)
    wk_h = wk.reshape(e, h, d)
    wv_h = wv.reshape(e, h, d)
    wu_h = wu.reshape(h, d, e)
    # Q1: queries AND keys are each scaled by d**-0.25 → d**-0.5 on logits
    wqk = jnp.einsum("ehd,fhd->ehf", wq_h, wk_h) * (d ** -0.5)   # (E, H, E)
    wvu = jnp.einsum("ehd,hdf->hef", wv_h, wu_h)                 # (H, E, E)
    return (wqk.reshape(e, h * e).astype(dtype),
            wvu.reshape(h * e, e).astype(dtype))


def _fold_entity_heads(p: dict, *, head_dim: int, depth: int, dtype) -> list:
    """Per-block kernels of the HEAD-WIDTH entity-table forward
    (``head_dim < emb``; ``_entity_attention_heads``): the dense module's
    own four projections — ``wq`` / ``wk`` ``(E, H·D)`` each carrying its
    Q1 ``head_dim**-0.25``, ``wv``, ``wu (H·D, E)`` with ``u_bias`` — plus
    the entity
    tables' keys and values as affine maps of the NINE normalised
    features, ``wek = we · wk`` ``(9, H·D)`` with ``bek = be · wk``
    (``wev`` / ``bev`` likewise): the embedding is affine, so no
    ``(B, A, E)`` table needs projecting. O(params), differentiable."""
    fe = p["feat_embedding"]
    we = fe["kernel"].astype(jnp.float32)                          # (9, E)
    be = fe["bias"].astype(jnp.float32)
    scale = head_dim ** -0.25
    hi = jax.lax.Precision.HIGHEST
    out = []
    for i in range(depth):
        at = p["transformer"][f"block_{i}"]["attention"]
        with jax.named_scope("agent.attention"):
            wk = at["tokeys"]["kernel"].astype(jnp.float32) * scale
            wv = at["tovalues"]["kernel"].astype(jnp.float32)
            out.append({
                "wq": (at["toqueries"]["kernel"].astype(jnp.float32)
                       * scale).astype(dtype),
                "wk": wk.astype(dtype), "wv": wv.astype(dtype),
                "wu": at["unifyheads"]["kernel"].astype(dtype),
                "u_bias": at["unifyheads"]["bias"],
                "wek": jnp.dot(we, wk, precision=hi).astype(dtype),
                "bek": jnp.dot(be, wk, precision=hi),
                "wev": jnp.dot(we, wv, precision=hi).astype(dtype),
                "bev": jnp.dot(be, wv, precision=hi)})
    return out


def fold_transformer(tf_params: dict, *, emb: int, heads: int,
                     head_dim: int, depth: int, dtype) -> dict:
    """Pre-fold every block's attention projections ONCE. The fold is
    differentiable (einsums of the raw kernels), so gradients flow back to
    the original parameters unchanged. Callers whose forward sits inside a
    ``lax.scan`` body (rollout step, learner unroll) should fold OUTSIDE the
    scan and pass the result through — relying on XLA's loop-invariant code
    motion to hoist the fold dots is not guaranteed."""
    blocks = []
    for i in range(depth):
        bp = tf_params[f"block_{i}"]
        with jax.named_scope("agent.attention"):
            wqk, wvu = _fold_block(bp, emb, heads, head_dim, dtype)
        blocks.append({"wqk": wqk, "wvu": wvu,
                       "u_bias": bp["attention"]["unifyheads"]["bias"],
                       "n1": bp["norm1"], "n2": bp["norm2"],
                       "ff1": bp["ff1"], "ff2": bp["ff2"]})
    return {FOLDED: True, "blocks": blocks}


def transformer_rows(tf_folded: dict, k0: jnp.ndarray, x0: jnp.ndarray, *,
                     emb: int, heads: int, depth: int,
                     dtype=jnp.float32, attn_impl: str = "xla"
                     ) -> jnp.ndarray:
    """Carry ``R`` query rows through ``depth`` pre-folded blocks against
    the pinned layer-0 keys ``k0 (S, T, E)``. ``x0 (S, R, E)`` must be the
    slice of ``k0`` rows whose outputs are consumed (agent: row 0; mixer:
    the last ``n_agents+3`` rows). Returns the final rows ``(S, R, E)`` in
    f32.

    ``attn_impl`` is the ``kernels.attention`` switch for THIS forward:
    ``"pallas"`` routes the ``R·H`` sliced query rows through the flash
    kernel (``kernels/attention.py``) as one head-free attention —
    batch ``S``, query axis ``R·H``, the shared ``k0`` as both keys and
    values — so neither the ``(S, R·H, T)`` logits tensor nor (under
    ``jax.grad``) its backward recompute ever reach HBM. The learner
    unrolls pass the config switch; acting/serving callers keep the
    default (the rollout's per-step attention is Q=H rows — too small
    for the tiling to pay — and the serving artifact's lowering must
    never depend on a training-run perf knob). Numerics: the kernel
    keeps f32 softmax statistics at every dtype, so the bf16 mode is
    *better*-conditioned than the einsum branch below (which softmaxes
    in bf16); f32 matches to reassociation (tests/test_kernels.py)."""
    s, r, _ = x0.shape
    for i in range(depth):
        bp = tf_folded["blocks"][i]
        wqk, wvu = bp["wqk"], bp["wvu"]
        with jax.named_scope("agent.attention"):
            # logits over all T keys for each head, keys never materialized
            qp = jnp.dot(x0.reshape(s * r, emb), wqk,
                         preferred_element_type=jnp.float32)
            qp = qp.astype(dtype).reshape(s, r * heads, emb)
            if attn_impl == "pallas":
                # fused flash kernel over the R·H sliced rows: the folded
                # wqk already carries the d**-0.5 logit scaling, k0 doubles
                # as keys AND values (the qslice identity: ctx = attn·k0,
                # wvu applies after), no mask/causal structure
                from ..kernels.attention import flash_attention
                ctx = flash_attention(qp[:, None], k0[:, None],
                                      k0[:, None])[:, 0]        # (S, R·H, E)
                ctx = ctx.astype(dtype).reshape(s * r, heads * emb)
            else:
                logits = jax.lax.dot_general(
                    qp, k0, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)  # (S, R·H, T)
                # parity mode keeps f32 softmax; bf16 perf mode stays in bf16
                # (mirrors models/transformer.py:101-105)
                if dtype == jnp.float32:
                    attn = jax.nn.softmax(logits, axis=-1)
                else:
                    attn = jax.nn.softmax(logits.astype(dtype), axis=-1)
                attn = attn.astype(dtype)
                ctx = jax.lax.dot_general(
                    attn, k0, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)  # (S, R·H, E)
                ctx = ctx.astype(dtype).reshape(s * r, heads * emb)
            attended = (jnp.dot(ctx, wvu, preferred_element_type=jnp.float32)
                        + bp["u_bias"].astype(jnp.float32))  # (S·R, E) f32
        x0 = _block_tail(bp, attended,
                         x0.reshape(s * r, emb), dtype).reshape(s, r, emb)

    return x0.astype(jnp.float32)


def _block_tail(bp: dict, attended: jnp.ndarray, x0_flat: jnp.ndarray,
                dtype) -> jnp.ndarray:
    """Post-attention block tail shared by both query-slice forwards:
    Q2 post-LN residuals + FFN, f32 statistics.
    ``attended (N, E)`` f32, ``x0_flat (N, E)`` in compute dtype."""
    with jax.named_scope("agent.ff"):
        x1 = _ln(attended + x0_flat.astype(jnp.float32),
                 bp["n1"]["scale"].astype(jnp.float32),
                 bp["n1"]["bias"].astype(jnp.float32))
        hid = jnp.dot(x1.astype(dtype), bp["ff1"]["kernel"].astype(dtype),
                      preferred_element_type=jnp.float32)
        hid = jnp.maximum(hid + bp["ff1"]["bias"].astype(jnp.float32), 0.0)
        y = jnp.dot(hid.astype(dtype), bp["ff2"]["kernel"].astype(dtype),
                    preferred_element_type=jnp.float32)
        y = y + bp["ff2"]["bias"].astype(jnp.float32)
        x2 = _ln(y + x1,
                 bp["n2"]["scale"].astype(jnp.float32),
                 bp["n2"]["bias"].astype(jnp.float32))
        return x2.astype(dtype)


def _q_head(qb: dict, h_new: jnp.ndarray,
            noise_key: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply the Q head to ``(S, E)`` f32 hidden rows. ``qb`` is either
    the dense ``q_basic`` params ({kernel, bias}) or NoisyLinear params
    ({w_mu, w_sigma, b_mu, b_sigma} — ``models/noisy.py``).

    ``noise_key=None`` is the deterministic path (mu weights — exactly
    NoisyLinear's eval mode, so test-mode equivalence with the dense
    module is bit-for-reassociation). With a key, ONE factored-Gaussian
    draw perturbs the weights for the whole call — the dense module's
    one-draw-per-forward semantics (all agents share the draw; per-agent
    diversity comes through each agent's h). The raw key is split here
    (in/out factors) rather than run through flax's path-folded
    ``make_rng``, so the NOISE STREAM differs from the flax module's for
    the same key — identical distribution, different sample; documented
    in docs/SPEC.md §7 (use_qslice row)."""
    with jax.named_scope("agent.head"):
        if "kernel" in qb:
            return (jnp.dot(h_new, qb["kernel"].astype(jnp.float32))
                    + qb["bias"].astype(jnp.float32))
        w = qb["w_mu"].astype(jnp.float32)
        b = qb["b_mu"].astype(jnp.float32)
        if noise_key is not None:
            from ..models.noisy import noisy_weights
            w, b = noisy_weights(w, qb["w_sigma"].astype(jnp.float32),
                                 b, qb["b_sigma"].astype(jnp.float32),
                                 noise_key)
        return jnp.dot(h_new, w) + b


def fold_agent_params(variables: dict, *, emb: int, heads: int, depth: int,
                      standard_heads: bool = False, dtype=jnp.float32
                      ) -> dict:
    """Pre-fold an agent param tree for ``agent_forward_qslice``. Call once
    OUTSIDE any scan whose body runs the forward (rollout step fn, learner
    unroll); the result is an ordinary pytree."""
    if FOLDED in variables:
        return variables
    p = variables["params"]
    head_dim = emb // heads if standard_heads else emb
    tree = {FOLDED: True,
            "fe": p["feat_embedding"],
            "tf": fold_transformer(p["transformer"], emb=emb, heads=heads,
                                   head_dim=head_dim, depth=depth,
                                   dtype=dtype),
            "qb": p["q_basic"]}
    if head_dim < emb:
        # heads narrower than emb: the entity-table forward contracts at
        # head width instead of through wqk / wvu (dead leaves in every
        # program that runs the obs path)
        tree["ent"] = _fold_entity_heads(p, head_dim=head_dim, depth=depth,
                                         dtype=dtype)
    return tree


def agent_forward_qslice(variables: dict, inputs: jnp.ndarray,
                         hidden_state: jnp.ndarray, *,
                         n_entities: int, feat_dim: int, emb: int,
                         heads: int, depth: int, n_actions: int,
                         standard_heads: bool = False,
                         dtype=jnp.float32,
                         noise_key: jnp.ndarray | None = None,
                         attn_impl: str = "xla"
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for ``TransformerAgent.apply`` (dropout=0; noisy heads
    supported via ``noise_key`` — see ``_q_head``):
    inputs ``(B, A, obs)``, hidden ``(B, A, emb)`` → (q, hidden').
    Accepts either the raw flax variables or a ``fold_agent_params`` tree.
    ``attn_impl`` selects the sliced-attention lowering (see
    ``transformer_rows``; the learner unroll passes the config's
    ``kernels.attention``)."""
    f = fold_agent_params(variables, emb=emb, heads=heads, depth=depth,
                          standard_heads=standard_heads, dtype=dtype)
    b, a, _ = inputs.shape
    s = b * a

    with jax.named_scope("agent.embed"):
        x = inputs.reshape(s, n_entities, feat_dim).astype(dtype)
        h0 = hidden_state.reshape(s, emb).astype(dtype)

        fe = f["fe"]
        embs = (jnp.dot(x, fe["kernel"].astype(dtype),
                        preferred_element_type=jnp.float32)
                + fe["bias"].astype(jnp.float32)).astype(dtype)  # (S, N, E)
        # layer-0 key tokens: hidden token prepended at position 0
        k0 = jnp.concatenate([h0[:, None, :], embs], axis=1)     # (S, T, E)

    out = transformer_rows(f["tf"], k0, h0[:, None, :],
                           emb=emb, heads=heads, depth=depth,
                           dtype=dtype, attn_impl=attn_impl)    # (S, 1, E)

    h_new = out[:, 0, :]                                        # (S, E) f32
    q = _q_head(f["qb"], h_new, noise_key)
    return (q.reshape(b, a, n_actions),
            h_new.reshape(b, a, emb))


def make_mixer_qslice(mixer):
    """(fold_fn, apply_fn) pair closing over a ``TransformerMixer``'s
    attributes, so callers (the learner unroll) don't re-plumb the module
    config. ``apply_fn`` matches ``mixer.apply``'s positional signature.
    The mixer's ``attn_impl`` (= the config's ``kernels.attention``)
    threads through: this pair is consumed ONLY by the learner unroll,
    so the kernel switch lands exactly on the train path."""
    fold = lambda variables: fold_mixer_params(
        variables, emb=mixer.emb, heads=mixer.heads, depth=mixer.depth,
        standard_heads=mixer.standard_heads, dtype=mixer.dtype)
    apply = lambda mp, qvals, h, hyper, s, o: mixer_forward_qslice(
        mp, qvals, h, hyper, s, o,
        n_agents=mixer.n_agents, n_entities=mixer.n_entities,
        feat_dim=mixer.feat_dim, emb=mixer.emb, heads=mixer.heads,
        attn_impl=mixer.attn_impl,
        depth=mixer.depth, pos_func=mixer.qmix_pos_func,
        pos_func_beta=mixer.qmix_pos_func_beta,
        state_entity_mode=mixer.state_entity_mode,
        standard_heads=mixer.standard_heads, dtype=mixer.dtype)
    return fold, apply


#: lanes of a TPU vector register / MXU tile: the head-width entity forward
#: contracts its heads in groups of ``_LANES // head_dim`` (a group's
#: ``(h, a)`` rows against a group's lanes), the smallest unit that fills a
#: tile — every head beyond it only adds cross-head products that are
#: thrown away
_LANES = 128


def _entity_attention_heads(hp: dict, x0: jnp.ndarray, h_tok: jnp.ndarray,
                            feats: jnp.ndarray, inv_self: jnp.ndarray,
                            seen: jnp.ndarray, *, heads: int, dtype
                            ) -> jnp.ndarray:
    """One block's entity-table attention at HEAD width (``D < E``).

    ``hp`` one block of ``_fold_entity_heads``; ``x0 (B, A, E)`` the query
    rows; ``h_tok (B, A, E)`` the layer-0 hidden tokens (key 0); ``feats
    (B, 2A, 9)`` the normalised features of the visible then the masked
    entity rows; ``inv_self (B, A, 1)`` f32, the is-self feature's
    ``1 / std``; ``seen (B, 2A, A)`` bool, row ``j < A``: agent ``a`` sees
    entity ``j``, row ``A + j``: it does not. Returns ``attended
    (B·A, E)`` f32."""
    b, a, emb = x0.shape
    hd = hp["wq"].shape[1]
    d = hd // heads
    g = max(1, min(heads, _LANES // d))       # heads a group
    while heads % g:
        g -= 1
    ctx = []
    for lo in range(0, hd, g * d):
        cols = lambda name: hp[name][..., lo:lo + g * d]
        ctx.append(_entity_context_heads(
            {name: cols(name)
             for name in ("wq", "wk", "wv", "wek", "bek", "wev", "bev")},
            x0, h_tok, feats, inv_self, seen, heads=g, dtype=dtype))
    ctx = jnp.concatenate(ctx, axis=-1)                       # (B, A, H·D)
    return (jnp.dot(ctx.reshape(b * a, hd), hp["wu"],
                    preferred_element_type=jnp.float32)
            + hp["u_bias"].astype(jnp.float32))


def _entity_context_heads(hp: dict, x0: jnp.ndarray, h_tok: jnp.ndarray,
                          feats: jnp.ndarray, inv_self: jnp.ndarray,
                          seen: jnp.ndarray, *, heads: int, dtype
                          ) -> jnp.ndarray:
    """The attention context of ``heads`` heads whose kernels' columns
    ``hp`` holds (``H·D`` below is THEIR width) → ``(B, A, H·D)`` in
    ``dtype``.

    Every operand keeps ``H·D`` in the lanes and is never split into heads:
    a head is a block mask on the ``H·A`` expanded query rows (fused into
    the contraction's operand), the logits are laid out ``(B, 2A, H·A)`` so
    that the softmax reduces over rows, and of the context's
    ``(B, H·A, H·D)`` product each row keeps its own head's lanes."""
    b, a, emb = x0.shape
    hd = hp["wq"].shape[1]
    d = hd // heads
    f32 = jnp.float32
    # (H, H·D): the lanes of head h
    hmask = jnp.arange(hd)[None, :] // d == jnp.arange(heads)[:, None]

    def proj(x, w):                                   # (B, n, E) → (B, n, H·D)
        y = jnp.dot(x.reshape(-1, emb), w, preferred_element_type=f32)
        return y.astype(dtype).reshape(b, x.shape[1], hd)

    def table(w, bias):                               # (B, 2A, H·D)
        return (jnp.dot(feats, w, preferred_element_type=f32)
                + bias.astype(f32)).astype(dtype)

    def columns(x):                                   # (B·A, H) → (B, 1, H·A)
        return x.reshape(b, a, heads).transpose(0, 2, 1).reshape(
            b, 1, heads * a)

    q = proj(x0, hp["wq"])
    k_0, v_0 = proj(h_tok, hp["wk"]), proj(h_tok, hp["wv"])
    k_ent, v_ent = table(hp["wek"], hp["bek"]), table(hp["wev"], hp["bev"])

    # logits of key 0 (the own hidden token) and the is-self correction of
    # the diagonal: per-head sums over the lanes, as contractions with hmask
    l0 = columns(jnp.dot(
        (q.astype(f32) * k_0.astype(f32)).reshape(b * a, hd),
        hmask.T.astype(f32), precision=jax.lax.Precision.HIGHEST))
    ls = columns(jnp.dot(
        q.reshape(b * a, hd),
        jnp.where(hmask.T, hp["wek"][8][:, None], 0).astype(dtype),
        preferred_element_type=f32) * inv_self.reshape(b * a, 1))
    # logits against both entity tables, transposed: (B, 2A, H·A)
    qx = jnp.where(hmask[None, :, None, :], q[:, None], 0).reshape(
        b, heads * a, hd)
    lt = jnp.einsum("bje,bme->bjm", k_ent, qx,
                    preferred_element_type=f32).astype(dtype)
    # (·, 2A, H·A): every head's columns see the same rows
    seen = jnp.tile(seen, (1, 1, heads))
    own = jnp.tile(jnp.eye(2 * a, a, dtype=bool), (1, heads))[None]
    # parity mode keeps f32 softmax; bf16 perf mode stays in bf16 (mirrors
    # models/transformer.py); unseen rows drop out of max and sum
    sdt = f32 if dtype == jnp.float32 else dtype
    lg = jnp.where(seen, lt.astype(f32) + jnp.where(own, ls, 0.0),
                   jnp.finfo(sdt).min).astype(sdt)
    l0 = l0.astype(sdt)
    top = jax.lax.stop_gradient(
        jnp.maximum(l0, lg.max(axis=1, keepdims=True)))
    e0, ex = jnp.exp(l0 - top), jnp.exp(lg - top)
    den = e0 + ex.sum(axis=1, keepdims=True)
    a0 = (e0 / den).astype(dtype)                             # (B, 1, H·A)
    pt = (ex / den).astype(dtype)                             # (B, 2A, H·A)
    diag = jnp.where(own, pt, 0).sum(axis=1, keepdims=True)
    # context of every (h, a) row over ALL H·D lanes, of which it keeps its
    # own head's
    full = jnp.einsum("bjm,bje->bme", pt, v_ent,
                      preferred_element_type=dtype)           # (B, H·A, H·D)
    full = full.reshape(b, heads, a, hd)
    ctx = full[:, 0]
    for h in range(1, heads):
        ctx = jnp.where(hmask[h], full[:, h], ctx)            # (B, A, H·D)

    def lanes(x):             # (B, 1, H·A) → (B, A, H·D): a head's value on
        x = x.reshape(b, heads, a).transpose(0, 2, 1)         # its own lanes
        return jnp.dot(x.reshape(b * a, heads), hmask.astype(dtype),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=f32).reshape(b, a, hd)

    v_self = (hp["wev"][8].astype(f32) * inv_self).astype(dtype)  # (B, A, H·D)
    return (ctx.astype(f32) + lanes(a0) * v_0.astype(f32)
            + lanes(diag) * v_self.astype(f32)).astype(dtype)


def _entity_attention_folded(bp: dict, x0: jnp.ndarray, h_tok: jnp.ndarray,
                             e_vis: jnp.ndarray, e_hid: jnp.ndarray,
                             self_corr: jnp.ndarray, vis: jnp.ndarray,
                             eye: jnp.ndarray, idx_diag: jnp.ndarray, *,
                             heads: int, dtype) -> jnp.ndarray:
    """One block's entity-table attention through the FOLDED kernels
    (``head_dim == emb``): ``bp`` one block of ``fold_transformer``, the
    embedded ``(B, A, E)`` tables as keys and values, never projected.
    Returns ``attended (B·A, E)`` f32."""
    b, a, emb = x0.shape
    s = b * a
    qp = jnp.dot(x0.reshape(s, emb), bp["wqk"],
                 preferred_element_type=jnp.float32)
    qp = qp.astype(dtype).reshape(b, a, heads, emb)
    # logits against key 0 (own hidden token) and the entity tables
    l0 = jnp.einsum("bahe,bae->bah", qp, h_tok,
                    preferred_element_type=jnp.float32)
    lv = jnp.einsum("bahe,bje->bahj", qp, e_vis,
                    preferred_element_type=jnp.float32)
    lh = jnp.einsum("bahe,bje->bahj", qp, e_hid,
                    preferred_element_type=jnp.float32)
    ls = jnp.einsum("bahe,bae->bah", qp, self_corr,
                    preferred_element_type=jnp.float32)
    lent = jnp.where(vis, lv, lh) + eye.astype(jnp.float32) \
        * ls[..., None]
    logits = jnp.concatenate([l0[..., None], lent], axis=-1)
    if dtype == jnp.float32:
        attn = jax.nn.softmax(logits, axis=-1)
    else:
        attn = jax.nn.softmax(logits.astype(dtype), axis=-1)
    attn = attn.astype(dtype)
    a0, ae = attn[..., 0], attn[..., 1:]                  # (B,A,H[,A])
    av = ae * vis.astype(dtype)
    ah = ae - av  # masked branch
    diag = jnp.take_along_axis(ae, idx_diag, axis=-1)[..., 0]
    ctx = (a0[..., None] * h_tok[:, :, None, :]
           + jnp.einsum("bahj,bje->bahe", av, e_vis,
                        preferred_element_type=jnp.float32
                        ).astype(dtype)
           + jnp.einsum("bahj,bje->bahe", ah, e_hid,
                        preferred_element_type=jnp.float32
                        ).astype(dtype)
           + diag[..., None] * self_corr[:, :, None, :])
    ctx = ctx.astype(dtype).reshape(s, heads * emb)
    return (jnp.dot(ctx, bp["wvu"], preferred_element_type=jnp.float32)
            + bp["u_bias"].astype(jnp.float32))


def agent_forward_qslice_entity(variables: dict, rows: jnp.ndarray,
                                same_mec: jnp.ndarray, mean: jnp.ndarray,
                                std: jnp.ndarray, hidden_state: jnp.ndarray,
                                *, emb: int, heads: int, depth: int,
                                n_actions: int, standard_heads: bool = False,
                                dtype=jnp.float32,
                                noise_key: jnp.ndarray | None = None,
                                kernel: bool = False
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Entity-table forward (acting, and the learner's compact-storage
    unroll): ``agent_forward_qslice`` without ever materializing per-agent
    token embeddings. ``noise_key`` as in ``_q_head`` (noisy heads
    supported).

    Exploits the structure of the entity observation
    (``envs/mec_offload.py:_raw_obs`` + the shared ``fast_norm`` affine):
    agent ``i``'s token ``j`` is ``(same_mec[i,j] ? rows[j] : 0, is_self)``
    normalized by per-position statistics that are identical for every
    observer — so each env has only TWO distinct embedded values per entity
    (visible / masked) plus a diagonal is-self correction. Attention logits
    and context therefore contract against per-env ``(A, E)`` tables instead
    of per-agent ``(A, A+1, E)`` key tensors: at the north-star scale this
    removes the 576→emb embedding matmul (~1.2 TFLOP/slot) AND the
    ``(B·A, A+1, E)`` key materialization (~GBs/slot of HBM traffic) from
    the acting path. Exact to float reassociation vs the obs-path forward
    (pinned in tests/test_entity_tables.py).

    Where ``head_dim == emb`` the blocks run the folded kernels (``wqk`` /
    ``wvu``) against the embedded ``(B, A, E)`` tables, so keys and values
    are never projected. Where ``head_dim < emb`` (``standard_heads``) that
    fold would make every intermediate ``(B, A, H, E)``, ``heads`` times
    wider than a head needs: the blocks then project per-env key and value
    tables (``(B, 2A, H·D)``, from the nine features) and contract at head
    width (``_entity_attention_heads``). Which one runs follows from the
    shapes of the parameters (``fold_agent_params`` gives the ``"ent"``
    kernels exactly when ``head_dim < emb``) — no option selects it.

    ``kernel=True`` is acting's call (``BasicMAC.forward_entity``, forward
    only, one device): its head-width blocks run as ONE Pallas kernel each
    (``kernels/entity_attention.py``: projections, logits, softmax and
    context of an env stay in VMEM) where the shapes are the kernel's
    (``entity_attention.eligible``) and the program is lowered for a TPU.
    The learner's unrolls — the online one is differentiated, and the
    kernel has no backward pass — and every other platform and shape run
    the contractions below, unchanged. ``kernels.attention`` (the flash
    kernel of ``MultiHeadAttention``) has no say in it.

    Inputs per ``MultiAgvOffloadingEnv.compact_obs``: ``rows (B, A, 8)``,
    ``same_mec (B, A, A)`` bool, ``mean/std (B, A, 9)``; ``hidden_state
    (B, A, emb)``. Requires ``obs_entity_mode`` + ``fast_norm`` and no
    ``n_entities`` override (gated by ``entity_tables_eligible``)."""
    f = fold_agent_params(variables, emb=emb, heads=heads, depth=depth,
                          standard_heads=standard_heads, dtype=dtype)
    b, a, _ = rows.shape
    s = b * a

    # one association of the same sum per head geometry, known from the
    # shapes alone (fold_agent_params; docstring)
    head_width = "ent" in f
    use_kernel = False
    if kernel and head_width:
        from ..kernels import entity_attention as ek
        hd = f["ent"][0]["wq"].shape[1]
        use_kernel = ek.eligible(b, a, emb, hd, heads)
    with jax.named_scope("agent.embed"):
        # ---- per-env embedding tables (feat 8 = is_self; _raw_obs layout)
        denom = std.astype(jnp.float32) + 1e-8                    # (B, A, 9)
        rows9 = jnp.concatenate(
            [rows.astype(jnp.float32), jnp.zeros((b, a, 1))], axis=-1)
        nv = ((rows9 - mean) / denom).astype(dtype)               # visible row
        nh = ((-mean) / denom).astype(dtype)                      # masked row
        if head_width:
            h_tok = hidden_state.astype(dtype)                    # (B, A, E)
            feats = jnp.concatenate([nv, nh], axis=1)             # (B, 2A, 9)
            inv_self = 1.0 / denom[..., 8:9]                      # (B, A, 1)
            sees = jnp.swapaxes(same_mec, 1, 2)                   # (B, j, a)
            seen = jnp.concatenate([sees, ~sees], axis=1)         # (B, 2A, A)
            if use_kernel:
                # the same tables and visibility in the kernel's layout;
                # dead (and removed) on the platforms that run the XLA form
                k_tables = ek.tables(feats, inv_self, seen, dtype,
                                     ek.group(hd, heads))
        else:
            we = f["fe"]["kernel"].astype(dtype)                  # (9, E)
            be = f["fe"]["bias"].astype(jnp.float32)
            e_vis = (jnp.dot(nv, we, preferred_element_type=jnp.float32)
                     + be).astype(dtype)                          # (B, A, E)
            e_hid = (jnp.dot(nh, we, preferred_element_type=jnp.float32)
                     + be).astype(dtype)
            self_corr = (we[8][None, None, :].astype(jnp.float32)
                         / denom[..., 8:9]).astype(dtype)         # (B, A, E)
            h_tok = hidden_state.astype(dtype)
            vis = same_mec[:, :, None, :]  # (B, A, 1, A)
            eye = jnp.eye(a, dtype=dtype)[None, :, None, :]  # (1, A, 1, A)
            idx_diag = jnp.arange(a)[None, :, None, None]

    x0 = h_tok
    for i in range(depth):
        bp = f["tf"]["blocks"][i]
        with jax.named_scope("agent.attention"):
            if head_width:
                xla = lambda x, hp=f["ent"][i]: _entity_attention_heads(
                    hp, x, h_tok, feats, inv_self, seen, heads=heads,
                    dtype=dtype)
                if not use_kernel:
                    attended = xla(x0)
                else:
                    one = lambda x, hp=f["ent"][i]: ek.entity_attention(
                        hp, x, h_tok, *k_tables, heads=heads,
                        interpret=ek.INTERPRET)
                    attended = (one(x0) if ek.INTERPRET
                                else jax.lax.platform_dependent(
                                    x0, tpu=one, default=xla))
            else:
                attended = _entity_attention_folded(
                    bp, x0, h_tok, e_vis, e_hid, self_corr, vis, eye,
                    idx_diag, heads=heads, dtype=dtype)
        x0 = _block_tail(bp, attended, x0.reshape(s, emb), dtype) \
            .reshape(b, a, emb)

    h_new = x0.astype(jnp.float32).reshape(s, emb)
    q = _q_head(f["qb"], h_new, noise_key)
    return (q.reshape(b, a, n_actions),
            h_new.reshape(b, a, emb))


def fold_mixer_params(variables: dict, *, emb: int, heads: int, depth: int,
                      standard_heads: bool = False, dtype=jnp.float32
                      ) -> dict:
    """Pre-fold a mixer param tree for ``mixer_forward_qslice`` (see
    ``fold_agent_params``)."""
    if FOLDED in variables:
        return variables
    p = variables["params"]
    head_dim = emb // heads if standard_heads else emb
    tree = {FOLDED: True,
            "fe": p["feat_embedding"],
            "tf": fold_transformer(p["transformer"], emb=emb, heads=heads,
                                   head_dim=head_dim, depth=depth,
                                   dtype=dtype),
            "hb": p["hyper_b2"]}
    if "out_gate" in p:        # zero_init_gate configs (models/mixer.py)
        tree["og"] = p["out_gate"]
    return tree


def mixer_forward_qslice(variables: dict, qvals: jnp.ndarray,
                         hidden_states: jnp.ndarray,
                         hyper_weights: jnp.ndarray, states: jnp.ndarray,
                         obs: jnp.ndarray, *,
                         n_agents: int, n_entities: int, feat_dim: int,
                         emb: int, heads: int, depth: int,
                         pos_func: str, pos_func_beta: float,
                         state_entity_mode: bool = True,
                         standard_heads: bool = False,
                         dtype=jnp.float32, attn_impl: str = "xla"
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for ``TransformerMixer.apply`` (dropout=0): only the last
    ``n_agents+3`` output rows are consumed (w1 per agent, b1, w2, the b2
    source, and the 3 recurrent hyper tokens are WITHIN those rows —
    positions [-3:] — so one row-slice covers readout + recurrence); the
    ``n_entities`` state-embedding rows are dead computation in the dense
    module. Returns ``(q_tot (b,1,1), hyper (b,3,emb))``. Accepts either
    the raw flax variables or a ``fold_mixer_params`` tree."""
    from ..models.mixer import qmix_pos_func

    f = fold_mixer_params(variables, emb=emb, heads=heads, depth=depth,
                          standard_heads=standard_heads, dtype=dtype)
    b = qvals.shape[0]

    with jax.named_scope("agent.embed"):
        if state_entity_mode:
            inputs = states.reshape(b, n_entities, feat_dim).astype(dtype)
        else:  # Q12: all agents' obs entities
            inputs = obs.reshape(b, n_agents * n_entities,
                                 feat_dim).astype(dtype)

        fe = f["fe"]
        embs = (jnp.dot(inputs, fe["kernel"].astype(dtype),
                        preferred_element_type=jnp.float32)
                + fe["bias"].astype(jnp.float32)).astype(dtype)

        k0 = jnp.concatenate(
            [embs, hidden_states.astype(dtype), hyper_weights.astype(dtype)],
            axis=1)                                             # (b, T, E)

    r = n_agents + 3
    out = transformer_rows(f["tf"], k0, k0[:, -r:, :],
                           emb=emb, heads=heads, depth=depth,
                           dtype=dtype, attn_impl=attn_impl)    # (b, A+3, E)

    with jax.named_scope("agent.head"):
        w1 = out[:, :n_agents, :]  # (b, A, emb)
        b1 = out[:, -3, :].reshape(b, 1, emb)
        w2 = out[:, -2, :].reshape(b, emb, 1)
        hb = f["hb"]
        b2 = jax.nn.relu(
            jnp.dot(out[:, -1, :], hb["kernel"].astype(jnp.float32))
            + hb["bias"].astype(jnp.float32)).reshape(b, 1, 1)

        w1 = qmix_pos_func(w1, pos_func, pos_func_beta)
        w2 = qmix_pos_func(w2, pos_func, pos_func_beta)

        hidden = jax.nn.elu(jnp.matmul(qvals.astype(jnp.float32), w1) + b1)
        y = jnp.matmul(hidden, w2) + b2                             # (b, 1, 1)
        if "og" in f:              # zero_init_gate configs (models/mixer.py)
            y = y * f["og"].astype(jnp.float32)
        return y, out[:, -3:, :]
