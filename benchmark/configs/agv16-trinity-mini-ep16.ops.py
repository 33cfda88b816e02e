"""Operations and bytes the mathematics of ``agv16-trinity-mini-ep16``
requires, from shapes and from the program's own count of routed pairs —
the one source for ``experts_roofline_pct`` and ``trunk_step_mfu_pct`` in
this configuration's cells. A multiply-add counts as two operations.
Sizes are this file's own statement (the configuration's reference states
them a third time).

Counted, per token of an agent-step's ``A + 1``-token sequence and layer:
the q/k/v/o projections and the output gate's of the heads held here,
logits and weighted sum over the causal prefix (on average half the
sequence); in an expert layer the router's 128 outputs and the shared
expert's three products (``6 d f``, every token), in the dense layer its
three products of width 6144; per token-expert pair held here the three
expert products (``6 d f``); per agent-step the entity embedding and the
Q head; the mixer as ``benchmark/ops.py:mixer_step`` counts it. The
backward pass of the online networks at twice their forward.

Not counted: recomputation under ``model.remat``, the env step, the
normaliser, action selection, ring traffic, the optimizer's elementwise
update, softmax / sigmoid / RMSNorm / RoPE / top-k flops — and the
products of a held expert over a token that did not choose it (the
program runs them: they are the roofline share's shortfall).
"""

from __future__ import annotations

D, F, DENSE, HEAD = 2048, 1024, 6144, 128   # hidden, expert, dense, head_dim
SHARED = 1024                        # the shared expert's width
Q_HEADS, KV_HEADS = 8, 1             # held here
EXPERTS, HELD = 128, 8
EXPERT_LAYERS, DENSE_LAYERS = 4, 1
AGENTS, ACTIONS, FEATS = 16, 5, 9
TOKENS = AGENTS + 1
BF16 = 2                             # bytes


def pair_flops() -> int:
    """One token-expert pair, forward: gate, up, down."""
    return 6 * D * F


def token_flops(dense: bool = False) -> float:
    """One token through one layer, forward, without its routed experts:
    q, gate and o over the held query heads, k and v over the held
    key/value heads; then the dense feed-forward, or the router and the
    shared expert."""
    proj = 2 * D * HEAD * (3 * Q_HEADS + 2 * KV_HEADS)
    context = 4 * Q_HEADS * HEAD * (TOKENS + 1) / 2
    ffn = 6 * D * DENSE if dense else 2 * D * EXPERTS + 6 * D * SHARED
    return proj + context + ffn


def agent_step_flops() -> float:
    """One agent-step, forward, without its routed experts."""
    layers = (EXPERT_LAYERS * token_flops()
              + DENSE_LAYERS * token_flops(dense=True))
    return TOKENS * layers + 2 * FEATS * D * AGENTS + 2 * D * ACTIONS


def experts_call(pairs: float, peak: dict, backward: bool = False) -> float:
    """Seconds one layer's routed expert products need for ``pairs``
    pairs: the larger of operations over the bf16 peak and bytes over the
    HBM peak — the held experts' weights once (bf16) and each pair's
    activations in and out of the three products. ``backward``: the two
    products per forward product of the backward pass (twice the
    operations; the weights read once more and their gradient written in
    float32, the activations' cotangents)."""
    flops = pairs * pair_flops()
    weights = HELD * 3 * D * F * BF16
    acts = pairs * (2 * D + 3 * F) * BF16
    if backward:
        flops, weights, acts = 2 * flops, 3 * weights, 2 * acts
    return max(flops / peak["bf16_flops_per_s"],
               (weights + acts) / peak["hbm_bytes_per_s"])


def experts_needed_s(*, rollout_pairs: float, rollouts: float,
                     update_pairs: float, updates: float, steps: int,
                     peak: dict) -> float:
    """Seconds the routed expert products of a window need.
    ``rollout_pairs``: pairs held over one rollout (all expert layers, all
    steps); ``update_pairs``: pairs held over the online unroll of one
    update (``steps + 1`` steps); the target unroll is taken to route as
    many. What has to be a call of its own — one layer's weights read
    once — follows from the mathematics, not from the program: acting
    needs one an env-step and expert layer; the learner needs one per
    step and expert layer for the hidden token alone (it is the
    recurrence; a ``1 / TOKENS`` share of the pairs) and could take all
    entity tokens of an unroll in one call a layer."""
    act_calls = steps * EXPERT_LAYERS
    act = experts_call(rollout_pairs / act_calls, peak) * act_calls
    hidden_calls = (steps + 1) * EXPERT_LAYERS
    hidden = update_pairs / TOKENS / hidden_calls
    entities = update_pairs * AGENTS / TOKENS / EXPERT_LAYERS

    def unroll(backward):
        return (hidden_calls * experts_call(hidden, peak, backward)
                + EXPERT_LAYERS * experts_call(entities, peak, backward))
    learn = 2 * unroll(False) + unroll(True)
    return rollouts * act + updates * learn


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
