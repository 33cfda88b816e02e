"""Operations and bytes the mathematics of ``agv16-kanana2-ep16``
requires, from shapes and from the program's own count of routed pairs —
the one source for ``experts_roofline_pct``, ``trunk_step_mfu_pct`` and
``attention_roofline_pct`` in this configuration's cells. A multiply-add
counts as two operations. Sizes are this file's own statement (the
configuration's reference states them a third time).

Counted, per token of an agent-step's ``A + 1``-token sequence and layer:
latent attention's four products over the heads held here — ``W_q``
(2048 x 16 x 192), the down-projection ``W_kva`` (2048 x 576, whole),
the up-projection ``W_kvb`` (512 x 16 x 256), ``W_o`` (16 x 128 x 2048)
— and logits (192 a head) and weighted sum (128 a head) over the causal
prefix (on average half the sequence); in an expert layer the router's
128 outputs and the shared experts' three products at their summed width
1536 (``6 d f``, every token), in the dense layer its three products of
width 6144; per token-expert pair held here the three expert products
(``6 d f`` at width 768); per agent-step the entity embedding and the Q
head; the mixer as ``benchmark/ops.py:mixer_step`` counts it. The
backward pass of the online networks at twice their forward.

Not counted: recomputation under ``model.remat``, the env step, the
normaliser, action selection, ring traffic, the optimizer's elementwise
update, softmax / sigmoid / RMSNorm / RoPE / top-k flops — and the
products of a held expert over a token that did not choose it (the
program runs them: they are the roofline share's shortfall).
"""

from __future__ import annotations

D, F, DENSE = 2048, 768, 6144        # hidden, expert, dense widths
SHARED = 2 * 768                     # the two shared experts, one product
HEADS = 16                           # held here, of 32
NOPE, ROPE, VALUE, LATENT = 128, 64, 128, 512
EXPERTS, HELD = 128, 8
EXPERT_LAYERS, DENSE_LAYERS = 4, 1
LAYERS = EXPERT_LAYERS + DENSE_LAYERS
AGENTS, ACTIONS, FEATS = 16, 5, 9
TOKENS = AGENTS + 1
BF16 = 2                             # bytes

#: (inputs, outputs) a token of attention's four products: W_q, W_kva,
#: W_kvb, W_o
ATTENTION_PRODUCTS = ((D, HEADS * (NOPE + ROPE)), (D, LATENT + ROPE),
                      (LATENT, HEADS * (NOPE + VALUE)), (HEADS * VALUE, D))


def pair_flops() -> int:
    """One token-expert pair, forward: gate, up, down."""
    return 6 * D * F


def attention_flops() -> float:
    """One token through one layer's attention sublayer, forward: the
    four products and the context over the causal prefix."""
    proj = sum(2 * i * o for i, o in ATTENTION_PRODUCTS)
    context = 2 * HEADS * (NOPE + ROPE + VALUE) * (TOKENS + 1) / 2
    return proj + context


def token_flops(dense: bool = False) -> float:
    """One token through one layer, forward, without its routed experts:
    attention; then the dense feed-forward, or the router and the shared
    experts."""
    ffn = 6 * D * DENSE if dense else 2 * D * EXPERTS + 6 * D * SHARED
    return attention_flops() + ffn


def agent_step_flops() -> float:
    """One agent-step, forward, without its routed experts."""
    layers = (EXPERT_LAYERS * token_flops()
              + DENSE_LAYERS * token_flops(dense=True))
    return TOKENS * layers + 2 * FEATS * D * AGENTS + 2 * D * ACTIONS


def _call(flops: float, weights: float, acts: float, peak: dict,
          backward: bool) -> float:
    """Seconds one call needs: the larger of operations over the bf16
    peak and bytes over the HBM peak. ``backward``: the two products per
    forward product of the backward pass (twice the operations; the
    weights read once more and their gradient written in float32, the
    activations' cotangents)."""
    if backward:
        flops, weights, acts = 2 * flops, 3 * weights, 2 * acts
    return max(flops / peak["bf16_flops_per_s"],
               (weights + acts) / peak["hbm_bytes_per_s"])


def experts_call(pairs: float, peak: dict, backward: bool = False) -> float:
    """Seconds one layer's routed expert products need for ``pairs``
    pairs: the held experts' weights once (bf16) and each pair's
    activations in and out of the three products."""
    return _call(pairs * pair_flops(), HELD * 3 * D * F * BF16,
                 pairs * (2 * D + 3 * F) * BF16, peak, backward)


def attention_call(rows: float, peak: dict, backward: bool = False) -> float:
    """Seconds one layer's attention sublayer needs over ``rows`` tokens:
    the four products' weights once (bf16) and their activations in and
    out, the context's operations."""
    return _call(rows * attention_flops(),
                 sum(i * o for i, o in ATTENTION_PRODUCTS) * BF16,
                 rows * sum(i + o for i, o in ATTENTION_PRODUCTS) * BF16,
                 peak, backward)


def _needed(act_rows: float, hidden_rows: float, entity_rows: float,
            layers: int, rollouts: float, updates: float, steps: int,
            call) -> float:
    """Seconds a window's calls of one sublayer need. What has to be a
    call of its own — one layer's weights read once — follows from the
    mathematics, not from the program: acting needs one an env-step and
    layer (``act_rows`` each); the learner needs one per step and layer
    for the hidden token alone (it is the recurrence; ``hidden_rows``
    each) and could take all entity tokens of an unroll in one call a
    layer (``entity_rows``: under the causal mask no entity token reads
    the hidden one). An update is the online and the target unroll
    forward and the online one backward."""
    act = steps * layers * call(act_rows, False)

    def unroll(backward):
        return ((steps + 1) * layers * call(hidden_rows, backward)
                + layers * call(entity_rows, backward))
    return rollouts * act + updates * (2 * unroll(False) + unroll(True))


def experts_needed_s(*, rollout_pairs: float, rollouts: float,
                     update_pairs: float, updates: float, steps: int,
                     peak: dict) -> float:
    """Seconds the routed expert products of a window need.
    ``rollout_pairs``: pairs held over one rollout (all expert layers, all
    steps); ``update_pairs``: pairs held over the online unroll of one
    update (``steps + 1`` steps); the target unroll is taken to route as
    many; the hidden token's share of the pairs is ``1 / TOKENS``."""
    per_layer = update_pairs / EXPERT_LAYERS
    return _needed(
        rollout_pairs / (steps * EXPERT_LAYERS),
        per_layer / TOKENS / (steps + 1), per_layer * AGENTS / TOKENS,
        EXPERT_LAYERS, rollouts, updates, steps,
        lambda rows, backward: experts_call(rows, peak, backward))


def attention_needed_s(*, lanes: int, batch: int, steps: int,
                       rollouts: float, updates: float,
                       peak: dict) -> float:
    """Seconds the attention sublayers of a window need, from shapes
    alone: ``lanes x AGENTS x TOKENS`` rows an acting call, ``batch x
    AGENTS`` hidden tokens a learner step and ``batch x (steps + 1) x
    AGENTS x AGENTS`` entity tokens an unroll, in every one of the
    ``LAYERS`` layers."""
    return _needed(
        lanes * AGENTS * TOKENS, batch * AGENTS,
        batch * (steps + 1) * AGENTS * AGENTS, LAYERS, rollouts, updates,
        steps, lambda rows, backward: attention_call(rows, peak, backward))


def period_flops(*, lanes: int, batch: int, steps: int,
                 period_iterations: int, rollout_pairs: float,
                 test_pairs: float, update_pairs: float,
                 mixer_step: float) -> float:
    """Operations of one period: ``period_iterations`` training
    iterations (a rollout and an update each) and the test rollout after
    them. ``mixer_step``: forward operations of one mixer step."""
    dense_roll = lanes * steps * AGENTS * agent_step_flops()
    roll = dense_roll + rollout_pairs * pair_flops()
    test = dense_roll + test_pairs * pair_flops()
    unroll = (batch * (steps + 1) * AGENTS * agent_step_flops()
              + update_pairs * pair_flops())
    online = unroll + batch * steps * mixer_step
    target = unroll + batch * (steps + 1) * mixer_step
    return period_iterations * (roll + 3 * online + target) + test
