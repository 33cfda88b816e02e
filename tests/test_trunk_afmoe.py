"""An ``afmoe`` catalog trunk (``model.trunk`` with ``model_type: afmoe``,
``config.AfmoeTrunkConfig``; Trinity's family) through the one layer
function of ``models/trunk.py``, against its plain reference
(``benchmark/reference/afmoe.py``) on seeded weights in float32 at a tiny
size: forward, loss and every gradient leaf; the shares of a small
deployment add up at the two sublayer sums; the router (selection bias,
renormalisation, scale, ties, skew); q/k norm, gate, RoPE on the sliding
layers only, the window; the configuration's files and refusals; and
SmallThinker's programs, which must lower to what they lowered to before
this family was written.

Tolerances are ``tests/test_trunk.py``'s and for its reasons: float32
program against float32 reference, the two associating sums differently
(a wide product over all held experts against a loop over experts; heads
batched against one at a time) — forward ``rtol 2e-4 / atol 2e-5``,
gradients ``rtol 2e-3`` with ``atol`` 1e-5 of the largest gradient (a
gradient leaf sums thousands of such terms). bfloat16 reads 1e-2 and
fails them (``test_one_precision_step_down_fails``)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as ref
from benchmark.reference import model as ref_model
from t2omca_tpu.config import AfmoeTrunkConfig, from_dict, load_config
from t2omca_tpu.models import trunk as tr
from t2omca_tpu.run import Experiment

from test_trunk import episodes, noisy_scales, program_batch, strip

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A, MEC, CH, T = 3, 2, 2, 4
D, HD, F, DENSE = 32, 8, 16, 24          # hidden, head_dim, expert, dense

#: the tiny deployment: 8 experts 4 ways (3 a token), 4 query heads over 2
#: key/value heads 2 ways; published layers 1-3 of 4 held — layer 1 the
#: second dense layer (sliding), 2 an expert layer (full), 3 an expert
#: layer (sliding) — a window of 3 over A + 1 = 4 tokens, so it binds
TK = dict(model_type="afmoe", hidden_size=D, head_dim=HD,
          num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=3,
          num_dense_layers=2, intermediate_size=DENSE,
          moe_intermediate_size=F, num_experts=8, num_experts_per_tok=3,
          num_shared_experts=1, route_scale=2.5,
          layer_types=("sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"),
          sliding_window=3, rope_theta=100.0, experts_held=2, heads_held=2,
          share_index=0, first_layer=1)
KINDS = {0: "dense", 1: "full-expert", 2: "sliding-expert"}


def make_cfg(trunk=None, **over):
    data = {
        "batch_size_run": 2, "batch_size": 2, "target_update_interval": 2,
        "env_args": {"agv_num": A, "mec_num": MEC, "num_channels": CH,
                     "episode_limit": T},
        "model": {"emb": D, "depth": 3, "mixer_emb": D, "mixer_heads": 2,
                  "mixer_depth": 1, "standard_heads": True, "remat": True,
                  "trunk": dict(TK, **(trunk or {}))},
        "replay": {"buffer_size": 4}}
    for k, v in over.items():
        sec, _, sub = k.partition(".")
        if sub:
            data[sec][sub] = v
        else:
            data[k] = v
    return from_dict(data)


def ref_sizes(tk: AfmoeTrunkConfig) -> dict:
    """The reference's statement of an AfmoeTrunkConfig's share."""
    held = range(tk.first_layer, tk.first_layer + tk.num_hidden_layers)
    return dict(
        head_dim=tk.head_dim, q_heads=tk.heads_held,
        kv_heads=tk.kv_heads_held, experts=tk.num_experts,
        experts_held=tk.experts_held, expert_offset=tk.expert_offset,
        top_k=tk.num_experts_per_tok, route_scale=tk.route_scale,
        eps=tk.rms_norm_eps, theta=tk.rope_theta, window=tk.sliding_window,
        layers=tuple(("dense" if i < tk.num_dense_layers else "experts",
                      tk.layer_types[i].split("_")[0]) for i in held))


def sizes_of(cfg) -> dict:
    m = cfg.model
    return dict(n_agents=A, emb=m.emb, mixer_emb=m.mixer_emb,
                mixer_heads=m.mixer_heads, mixer_depth=m.mixer_depth,
                standard_heads=m.standard_heads)


@pytest.fixture(scope="module")
def exp():
    return Experiment.build(make_cfg())


@pytest.fixture(scope="module")
def params(exp):
    ls = exp.learner.init_state(jax.random.PRNGKey(3))
    return noisy_scales(ls.params, jax.random.PRNGKey(4))


# ------------------------------------------------- (a) against the reference

def test_the_tree_is_what_the_spec_says_each_layer_has(params):
    layers = params["agent"]["params"]["transformer"]
    every = {"input_norm", "post_norm", "attn_out_norm", "ff_out_norm",
             "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo"}
    assert set(layers["layer_0"]) == every | {"dense_gate", "dense_up",
                                              "dense_down"}
    routed = every | {"router", "expert_bias", "w_gate", "w_up", "w_down",
                      "shared_gate", "shared_up", "shared_down"}
    assert set(layers["layer_1"]) == set(layers["layer_2"]) == routed
    assert layers["layer_1"]["expert_bias"].shape == (8,)
    assert layers["layer_1"]["w_gate"].shape == (2, D, F)
    assert layers["layer_0"]["dense_up"].shape == (D, DENSE)
    assert layers["layer_1"]["q_norm"].shape == (HD,)
    # what stays float32 under a bfloat16 cast
    cast = tr.cast_weights(params["agent"], jnp.bfloat16)["transformer"]
    for name, x in cast["layer_1"].items():
        want = jnp.float32 if name in tr.KEEP_F32 else jnp.bfloat16
        assert x.dtype == want, name
    assert cast["layer_1"]["w_gate"].shape == (D, 2 * F)      # wide
    assert cast["layer_0"]["dense_gate"].dtype == jnp.bfloat16


@pytest.mark.parametrize("compact", [False, True],
                         ids=["dense-obs", "compact-rows"])
def test_unroll_matches_reference(exp, params, compact):
    """Q-values and carried hidden of a 3-step unroll."""
    batch = {k: v[:3] for k, v in episodes(jax.random.PRNGKey(0)).items()}
    tk = exp.cfg.model.trunk
    want_q, want_h = ref.unroll_agent(
        params["agent"]["params"], batch, sizes=sizes_of(exp.cfg),
        trunk=ref_sizes(tk))
    h = exp.mac.init_hidden(2)
    for t in range(3):
        if compact:
            mec = batch["mec"][t]
            q, h, aux = exp.mac.forward_trunk(
                params["agent"], None, h,
                compact=(batch["rows"][t], mec[:, :, None] == mec[:, None, :],
                         batch["mean"][t], batch["std"][t]))
        else:
            obs = ref_model.entity_obs(*(batch[k][t] for k in
                                         ("rows", "mec", "mean", "std")))
            q, h, aux = exp.mac.forward_trunk(
                params["agent"], obs.reshape(2, A, -1), h)
        np.testing.assert_allclose(q, want_q[t], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(h, want_h[t], rtol=2e-4, atol=2e-5)
        # the two routed layers' counts; the dense layer has none
        assert aux["load"].shape == (2, tk.experts_held)
        assert int(aux["held"].sum()) == int(aux["load"].sum()) > 0


@pytest.mark.parametrize("compact", [False, True],
                         ids=["dense-obs", "compact-rows"])
def test_loss_and_every_gradient_leaf_match_reference(exp, params, compact):
    batch = episodes(jax.random.PRNGKey(1))
    weights = jnp.asarray([1.0, 0.5])
    target = jax.tree.map(lambda x: x * 0.9, params)
    tk = exp.cfg.model.trunk

    def ref_loss(p):
        return ref.episode_loss(
            strip(p), strip(target), batch, weights, sizes=sizes_of(exp.cfg),
            trunk=ref_sizes(tk), gamma=exp.cfg.gamma)
    (want, want_aux), want_g = jax.value_and_grad(ref_loss, has_aux=True)(
        params)
    pb = program_batch(batch, compact)
    (loss, info), grads = jax.value_and_grad(
        lambda p: exp.learner._loss(p, target, pb, weights),
        has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-4)
    np.testing.assert_allclose(info["td_errors_abs"],
                               want_aux["td_errors_abs"], rtol=1e-4)
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree.leaves(grads)
    scale = max(float(jnp.abs(x).max()) for x in flat_g)
    assert len(flat_w) == len(flat_g) and scale > 0
    biases = 0
    for (path, w), g in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=1e-5 * scale,
                                   err_msg=name)
        if "expert_bias" in name:
            # the bias chooses and does not weigh: exactly zero, so Adam
            # leaves the leaf where it is
            assert not np.asarray(g).any() and not np.asarray(w).any()
            biases += 1
        elif "['agent']" in name:
            assert np.asarray(g).any(), name      # every other leaf is used
    assert biases == 2
    # the counters count the two routed layers only
    assert float(info["moe_dropped"]) == 0.0
    tokens = (T + 1) * 2 * A * (A + 1)
    assert float(info["moe_pairs_routed"]) == tokens * 3 * 2
    assert 0 < float(info["moe_load_max"]) <= float(info["moe_pairs_held"])


def test_one_precision_step_down_fails(exp, params):
    """The layer computed at bfloat16 is outside the tolerances above."""
    obs = jax.random.normal(jax.random.PRNGKey(20), (2, A, A, 9))
    h = 0.5 * jax.random.normal(jax.random.PRNGKey(21), (2, A, D))
    tk = exp.cfg.model.trunk
    want, _ = ref.agent_forward(params["agent"]["params"], obs, h,
                                trunk=ref_sizes(tk))
    q32, _, _ = tr.agent_forward_trunk(params["agent"], obs, h, tk=tk,
                                       dtype=jnp.float32)
    q16, _, _ = tr.agent_forward_trunk(params["agent"], obs, h, tk=tk,
                                       dtype=jnp.bfloat16)
    np.testing.assert_allclose(q32, want, rtol=2e-4, atol=2e-5)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(q16, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------- (b) the shares add up

def full_layer(key, dense: bool):
    """An uncut layer's weights (every head, every expert)."""
    ks = iter(jax.random.split(key, 24))
    g = lambda *s: 0.3 * jax.random.normal(next(ks), s)       # noqa: E731
    out = {"input_norm": 1 + g(D), "post_norm": 1 + g(D),
           "attn_out_norm": 1 + g(D), "ff_out_norm": 1 + g(D),
           "q_norm": 1 + g(HD), "k_norm": 1 + g(HD),
           "wq": g(D, 4 * HD), "wk": g(D, 2 * HD), "wv": g(D, 2 * HD),
           "wg": g(D, 4 * HD), "wo": g(4 * HD, D)}
    if dense:
        return dict(out, dense_gate=g(D, DENSE), dense_up=g(D, DENSE),
                    dense_down=g(DENSE, D))
    return dict(out, router=g(D, 8), expert_bias=0.5 * g(8),
                w_gate=g(8, D, F), w_up=g(8, D, F), w_down=g(8, F, D),
                shared_gate=g(D, F), shared_up=g(D, F), shared_down=g(F, D))


def share_of(full, tk: AfmoeTrunkConfig):
    """What share ``tk.share_index`` holds of ``full``."""
    att = tk.share_index % tk.attention_ways
    q0, q1 = att * tk.heads_held * HD, (att + 1) * tk.heads_held * HD
    k0, k1 = att * tk.kv_heads_held * HD, (att + 1) * tk.kv_heads_held * HD
    out = dict(full, wq=full["wq"][:, q0:q1], wk=full["wk"][:, k0:k1],
               wv=full["wv"][:, k0:k1], wg=full["wg"][:, q0:q1],
               wo=full["wo"][q0:q1])
    if "w_gate" in full:
        e0, e1 = tk.expert_offset, tk.expert_offset + tk.experts_held
        out.update(w_gate=full["w_gate"][e0:e1], w_up=full["w_up"][e0:e1],
                   w_down=full["w_down"][e0:e1])
    return out


def share(i, **kw):
    return AfmoeTrunkConfig(**dict(TK, share_index=i, **kw))


@pytest.mark.parametrize("layer", sorted(KINDS), ids=KINDS.get)
def test_shares_add_up_at_the_sublayer_sums(layer):
    """Experts 4 ways x heads 2 ways. The two output norms are not linear,
    so the shares add up BEFORE them: over the 2 attention shares the
    gated ``W_o`` outputs sum to the uncut attention output, and over the
    4 expert shares the routed sums plus the shared expert ONCE (a dense
    layer's feed-forward: once, every share computes it alike) sum to the
    uncut feed-forward."""
    full = full_layer(jax.random.PRNGKey(5), dense=layer == 0)
    h = jax.random.normal(jax.random.PRNGKey(6), (3, A + 1, D))
    uncut = ref_sizes(share(0, experts_held=8, heads_held=4))
    u = ref.rms(full["input_norm"], h, 1e-5)
    want_att = ref.attention(full, u, trunk=uncut, layer=layer, prec="f32")
    att = sum(tr.attention_part(share_of(full, share(i)), h, share(i), layer,
                                jnp.float32) for i in range(2))
    np.testing.assert_allclose(att, want_att, rtol=2e-4, atol=2e-5)
    # the feed-forward, from the uncut layer's own attention output
    a = h + ref.rms(full["attn_out_norm"], want_att, 1e-5)
    m = ref.rms(full["post_norm"], a, 1e-5).reshape(-1, D)
    want_f = ref.feed_forward(full, m, trunk=uncut, layer=layer, prec="f32")
    if layer == 0:
        parts = [tr.gated_ffn(share_of(full, share(i)), "dense", m,
                              jnp.float32, jax.nn.silu) for i in range(4)]
        for part in parts:                  # alike on every share: once
            np.testing.assert_allclose(part, want_f, rtol=2e-4, atol=2e-5)
        return
    routed, pairs = 0.0, 0
    for i in range(4):
        tk, p = share(i), share_of(full, share(i))
        weights, idx = tr.route(p["router"], m, tk, bias=p["expert_bias"])
        per = tr.held_weights(weights, idx, tk)
        routed = routed + tr.experts_part(p, m, per, jnp.float32, jax.nn.silu)
        pairs += int((per > 0).sum())
    assert pairs == m.shape[0] * 3             # every pair is held somewhere
    shared = tr.gated_ffn(full, "shared", m, jnp.float32, jax.nn.silu)
    np.testing.assert_allclose(routed + shared, want_f, rtol=2e-4, atol=2e-5)
    # and NOT after the output norm: the normed partial sums do not add up
    # to the normed sum (which is why each share normalises its own)
    normed = lambda f: ref.rms(full["ff_out_norm"], f, 1e-5)  # noqa: E731
    parts = sum(normed(tr.experts_part(
        share_of(full, share(i)), m, tr.held_weights(*tr.route(
            full["router"], m, share(i), bias=full["expert_bias"]), share(i)),
        jnp.float32, jax.nn.silu)) for i in range(4))
    assert np.abs(np.asarray(parts + normed(shared)
                             - normed(want_f))).max() > 1e-2


@pytest.mark.parametrize("index", range(4))
def test_every_share_matches_its_reference(index):
    """One share's whole layers (what the program runs: the output norms
    on the partial sums, which then go on) against the reference given
    the same share."""
    tk = share(index)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, A + 1, D))
    for layer in sorted(KINDS):
        p = share_of(full_layer(jax.random.PRNGKey(7 + layer),
                                dense=layer == 0), tk)
        got, aux = tr.trunk_layer(p, h, tk, layer, jnp.float32)
        want = ref.layer_forward(p, h, trunk=ref_sizes(tk), layer=layer,
                                 prec="f32")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert (aux is None) == (layer == 0)


# ------------------------------------------------------------ (c) the router

def _router_case(name):
    """(router kernel, bias, tokens) of a named case."""
    k = jax.random.split(jax.random.PRNGKey(30), 3)
    w = 0.3 * jax.random.normal(k[0], (D, 8))
    m = jax.random.normal(k[1], (12, D))
    bias = jnp.zeros(8)
    if name == "bias-flips-the-selection":
        # lifts expert 7 into every token's top-3 and sinks expert 0
        bias = bias.at[7].set(10.0).at[0].set(-10.0)
    if name == "ties":
        w = jnp.zeros((D, 8))          # every score 0.5: all eight tie
    if name == "all-on-one-expert":
        w = jnp.zeros((D, 8)).at[0, jnp.asarray([1, 5, 6])].set(
            50.0 - jnp.arange(3))
        m = jnp.abs(m) + 1
    return w, bias, m


@pytest.mark.parametrize("name", ["plain", "bias-flips-the-selection",
                                  "ties", "all-on-one-expert"])
def test_router(name):
    tk = share(0)                              # holds experts 0 and 1
    w, bias, m = _router_case(name)
    weights, idx = tr.route(w, m, tk, bias=bias)
    scores = np.asarray(jax.nn.sigmoid(m @ w))
    idx_np = np.asarray(idx)
    # the selection is the top-3 of score + bias …
    want_idx = np.argsort(-(scores + np.asarray(bias)), axis=-1,
                          kind="stable")[:, :3]
    assert (np.sort(idx_np, -1) == np.sort(want_idx, -1)).all()
    # … and the weights are those of the UNBIASED scores, renormalised
    # over the kept ones and scaled: they sum to route_scale
    kept = np.take_along_axis(scores, idx_np, -1)
    np.testing.assert_allclose(
        weights, 2.5 * kept / kept.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    # the reference agrees, expert by expert
    dense = ref.routing({"router": w, "expert_bias": bias}, m,
                        trunk=ref_sizes(tk))
    got = np.zeros((12, 8), np.float32)
    np.put_along_axis(got, idx_np, np.asarray(weights), -1)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-7)
    if name == "bias-flips-the-selection":
        assert (idx_np == 7).any(-1).all() and not (idx_np == 0).any()
        plain, _ = tr.route(w, m, tk, bias=jnp.zeros(8))
        assert np.abs(np.asarray(plain) - np.asarray(weights)).max() > 1e-3
    if name == "ties":
        assert (np.sort(idx_np, -1) == [0, 1, 2]).all()  # lowest ids first
        np.testing.assert_allclose(weights, 2.5 / 3, rtol=1e-6)


@pytest.mark.parametrize("to,held_share", [((0, 5, 6), 1 / 3), ((5, 6, 7), 0),
                                           ((0, 1, 7), 2 / 3)],
                         ids=["all-to-one-held", "to-none", "both-held"])
def test_routing_under_skew_loses_nothing(to, held_share):
    tk = share(0)                              # holds experts 0 and 1
    router = jnp.zeros((D, 8)).at[0, jnp.asarray(to)].set(
        50.0 - jnp.arange(3))
    p = dict(share_of(full_layer(jax.random.PRNGKey(13), False), tk),
             router=router, expert_bias=jnp.zeros(8),
             post_norm=jnp.ones(D), input_norm=jnp.ones(D))
    # tokens whose normed feed-forward input keeps a positive first entry
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(14), (4, A + 1, D))) + 1
    p = dict(p, wo=jnp.zeros_like(p["wo"]))    # a = h: m = N(h)
    got, aux = tr.trunk_layer(p, h, tk, 1, jnp.float32)
    want = ref.layer_forward(p, h, trunk=ref_sizes(tk), layer=1, prec="f32")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    n = 4 * (A + 1)
    assert int(aux["load"].sum()) == int(aux["held"]) == round(
        held_share * 3 * n)
    counters = tr.moe_counters(jax.tree.map(lambda x: x[None], aux), n, tk)
    assert float(counters["moe_dropped"]) == 0.0
    if held_share == 1 / 3:
        assert aux["load"].tolist() == [n, 0]  # one expert takes them all


# ------------------------------------------------- (d) attention's extras

def _attention(p, h, tk, layer):
    return np.asarray(tr.attention_part(p, h, tk, layer, jnp.float32))


@pytest.fixture(scope="module")
def attn_case():
    tk = share(1)
    p = share_of(full_layer(jax.random.PRNGKey(9), False), tk)
    h = jax.random.normal(jax.random.PRNGKey(10), (2, A + 1, D))
    return tk, p, h


def test_qk_norm_binds_and_is_applied_before_rope(attn_case):
    tk, p, h = attn_case
    for layer in (1, 2):
        want = ref.attention(p, ref.rms(p["input_norm"], h, 1e-5),
                             trunk=ref_sizes(tk), layer=layer, prec="f32")
        np.testing.assert_allclose(_attention(p, h, tk, layer), want,
                                   rtol=2e-4, atol=2e-5)
    # with the norms, a rescaled W_q changes nothing: q is normalised
    base = _attention(p, h, tk, 2)
    np.testing.assert_allclose(
        _attention(dict(p, wq=3.0 * p["wq"]), h, tk, 2), base, rtol=1e-3,
        atol=1e-5)
    other = _attention(dict(p, q_norm=2.0 * p["q_norm"]), h, tk, 2)
    assert np.abs(other - base).max() > 1e-3


def test_gate_scales_the_heads_output_before_w_o(attn_case):
    tk, p, h = attn_case
    base = _attention(p, h, tk, 1)
    # a gate kernel of zero is sigmoid(0) = 1/2 on every element
    shut = dict(p, wg=jnp.zeros_like(p["wg"]))
    half = _attention(shut, h, tk, 1)
    want = ref.attention(shut, ref.rms(p["input_norm"], h, 1e-5),
                         trunk=ref_sizes(tk), layer=1, prec="f32")
    np.testing.assert_allclose(half, want, rtol=2e-4, atol=2e-5)
    assert np.abs(half - base).max() > 1e-3


def test_rope_on_sliding_layers_only(attn_case):
    """A full layer has no positions: with one distinct token repeated,
    every position reads the same mixture; a sliding layer rotates."""
    tk, p, h = attn_case
    theta2 = dataclasses.replace(tk, rope_theta=7.0)
    assert np.abs(_attention(p, h, theta2, 2)
                  - _attention(p, h, tk, 2)).max() > 1e-4   # sliding: binds
    np.testing.assert_array_equal(_attention(p, h, theta2, 1),
                                  _attention(p, h, tk, 1))  # full: unread
    # and the layer kinds follow the PUBLISHED indices: moving the share
    # one layer down the model makes held layer 1 a sliding layer
    moved = dataclasses.replace(tk, first_layer=2, num_hidden_layers=2)
    assert [ls.rope for ls in moved.spec.layers] == [False, True]
    assert [ls.rope for ls in tk.spec.layers] == [True, False, True]
    assert [bool(ls.dense_width) for ls in tk.spec.layers] == [
        True, False, False]


def test_window_shorter_than_the_sequence_hides_old_keys(attn_case):
    tk, p, h = attn_case
    one = dataclasses.replace(tk, sliding_window=1)
    h2 = h.at[:, 0].add(1.0)
    d = np.abs(_attention(p, h, one, 2) - _attention(p, h2, one, 2))
    assert d[:, 1:].max() == 0.0 and d[:, 0].max() > 0
    # the full layer has no window: every later token moves
    d = np.abs(_attention(p, h, one, 1) - _attention(p, h2, one, 1))
    assert d[:, 1:].max(axis=(0, 2)).min() > 0


# ------------------------------------------- (e) the configuration's files

#: https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json as
#: the catalog gives it (``layer_types``: three sliding to one full, x 8)
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
HELD = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
        "num_attention_heads": 8, "num_key_value_heads": 1, "vocab_size": 0}


def test_shipped_config_is_the_catalog_row_key_by_key():
    """configs/config9_trunk_trinity.yaml: every key the dataclass carries
    equals the published one (the depth is the held layers'), and the
    benchmark's file states every catalog key, the held counts under the
    keys ``reduced`` lists."""
    tk = load_config(os.path.join(
        HERE, "configs", "config9_trunk_trinity.yaml")).model.trunk
    carried = {f.name for f in dataclasses.fields(tk)} & set(CATALOG)
    assert len(carried) == 22
    for key in carried - {"num_hidden_layers", "layer_types"}:
        assert getattr(tk, key) == CATALOG[key], key
    held = range(tk.first_layer, tk.first_layer + tk.num_hidden_layers)
    assert [tk.layer_types[i] for i in held] == [
        CATALOG["layer_types"][i] for i in held]
    assert (tk.num_hidden_layers, tk.first_layer, tk.experts_held,
            tk.heads_held, tk.kv_heads_held, tk.share_index) == (
        5, 1, 8, 8, 1, 0)
    with open(os.path.join(HERE, "benchmark", "configs",
                           "agv16-trinity-mini-ep16.json")) as f:
        top = json.load(f)
    for key, value in CATALOG.items():
        assert top[key] == HELD.get(key, value), key
    assert set(HELD) <= set(top["reduced"])
    assert from_dict(top["config"]).model.trunk.spec == tk.spec
    assert top["published"] == {k: CATALOG[k] for k in HELD}


def test_config_round_trips_and_takes_dotted_overrides(tmp_path):
    cfg = make_cfg()
    assert isinstance(cfg.model.trunk, AfmoeTrunkConfig)
    assert from_dict(json.loads(json.dumps(dataclasses.asdict(cfg)))) == cfg
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dataclasses.asdict(cfg)))
    moved = load_config(str(path), ("model.trunk.share_index=3",
                                    "model.trunk.route_scale=1.5"))
    assert moved.model.trunk.share_index == 3
    assert moved.model.trunk.expert_offset == 6
    assert moved.model.trunk.spec.route_scale == 1.5
    assert isinstance(moved.model.trunk.layer_types, tuple)


@pytest.mark.parametrize("trunk", [
    {"heads_held": 3}, {"experts_held": 3}, {"share_index": 4},
    {"num_dense_layers": 4}, {"num_dense_layers": 9},
    {"layer_types": ("sliding_attention",) * 3}, {"first_layer": 2},
    {"layer_types": ("sliding_attention", "linear_attention") * 2},
    {"n_group": 2}, {"topk_group": 2}, {"score_func": "softmax"},
    {"model_type": "qwen3_moe"}, {"no_such_key": 1}],
    ids=lambda t: "-".join(f"{k}={v}" for k, v in t.items())[:40])
def test_sanity_check_refuses(trunk):
    """A share that does not divide; ``num_dense_layers`` past the held
    layers (none would route); a ``layer_types`` shorter than the held
    layers (also by moving ``first_layer``) or with a kind not written;
    group-limited routing; another score function; an unknown family or
    key."""
    with pytest.raises((ValueError, KeyError)):
        make_cfg(trunk=trunk)


# ------------------------------------------------------- the normal path

def test_rollout_counts_its_pairs_and_drops_none(exp, params):
    rs = exp.runner.init_state(jax.random.PRNGKey(0))
    _, batch, stats = jax.jit(
        lambda p, r: exp.runner.run(p, r))(params["agent"], rs)
    tokens = T * 2 * A * (A + 1)
    assert float(stats.moe["moe_pairs_routed"]) == tokens * 3 * 2
    assert float(stats.moe["moe_dropped"]) == 0.0
    assert 0 < float(stats.moe["moe_pairs_held"]) < tokens * 3 * 2


def test_train_step_and_skip_branch_carry_the_same_info(exp, params):
    """``train_info_zeros`` mirrors ``train``'s info (the superstep's
    ``lax.cond``), counters included; and the step leaves every
    ``expert_bias`` where it was."""
    ls = exp.learner.init_state(jax.random.PRNGKey(1))
    pb = program_batch(episodes(jax.random.PRNGKey(2)), True)
    step = jax.jit(lambda l: exp.learner.train(
        l, pb, jnp.ones(2), jnp.int32(0), jnp.int32(0)))
    new, info = step(ls)
    zeros = exp.learner.train_info_zeros(2)
    assert set(zeros) == set(info)
    for k in tr.MOE_COUNTERS:
        assert info[k].shape == zeros[k].shape == ()
    before = ls.params["agent"]["params"]["transformer"]
    after = new.params["agent"]["params"]["transformer"]
    for name in ("layer_1", "layer_2"):
        np.testing.assert_array_equal(after[name]["expert_bias"],
                                      before[name]["expert_bias"])
        assert np.abs(np.asarray(before[name]["expert_bias"])).max() > 0
        assert np.abs(np.asarray(after[name]["router"]
                                 - before[name]["router"])).max() > 0


# ------------------ both families' programs lower as they did at the pin

#: sha256[:16] of the StableHLO (locations stripped) of the acting forward
#: and of the learner's loss gradient under each family's shipped file,
#: under this suite's conftest (``highest`` matmul precision): the one
#: layer function serves both, and a PR that means to change one
#: family's program must not change the other's unseen. SmallThinker's
#: stood at 26fd06c24a83505a / 3b34cde7cb7ad5f4 from the commit before the
#: second family (5c570de) through PR 31. Both families' digests below
#: were taken on the tree of PR 32 (parent 8d77ba7), whose ONE intended
#: difference is the router (``trunk.route``: the top-k by one-hot planes
#: in the place of ``lax.top_k`` + ``take_along_axis``; it changed all
#: four programs, SmallThinker's for the first time since PR 27)
SMALLTHINKER_LOWERING = {"forward": "cf288d23d13fce15",
                         "loss": "0c86cf19a893321f"}
TRINITY_LOWERING = {"forward": "297cc799394e8ff6",
                    "loss": "17f96004210a39ee"}


def _shipped(name):
    cfg = load_config(os.path.join(HERE, "configs", name))
    exp = Experiment.build(cfg)
    ts = jax.eval_shape(lambda: exp.init_train_state(0))
    return cfg, exp, ts


@pytest.fixture(scope="module")
def smallthinker():
    return _shipped("config8_trunk_smallthinker.yaml")


@pytest.fixture(scope="module")
def trinity():
    return _shipped("config9_trunk_trinity.yaml")


def _digest(lowered) -> str:
    import hashlib
    import re
    text = re.sub(r"loc\(.*?\)", "", lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _lowered(shipped, program):
    """Shapes only (``eval_shape``): nothing of the parameters is
    allocated."""
    cfg, exp, ts = shipped
    agent = ts.learner.params["agent"]
    if program == "forward":
        a = cfg.env_args.agv_num
        obs = jax.ShapeDtypeStruct((2, a, a, 9), jnp.float32)
        hid = jax.ShapeDtypeStruct((2, a, cfg.model.emb), jnp.float32)
        fwd = jax.jit(lambda p, o, h: tr.agent_forward_trunk(
            p, o, h, tk=cfg.model.trunk, dtype=jnp.bfloat16))
        return fwd.lower(agent, obs, hid)
    batch = jax.eval_shape(lambda p, r: exp.runner.run(p, r), agent,
                           ts.runner)[1]
    w = jax.ShapeDtypeStruct((batch.reward.shape[0],), jnp.float32)
    loss = jax.jit(jax.grad(
        lambda p, t, b, w: exp.learner._loss(p, t, b, w)[0]))
    return loss.lower(ts.learner.params, ts.learner.target_params, batch, w)


@pytest.mark.parametrize("program", ["forward", "loss"])
def test_smallthinker_lowering_is_unchanged(smallthinker, program):
    assert _digest(_lowered(smallthinker, program)) == \
        SMALLTHINKER_LOWERING[program]


@pytest.mark.parametrize("program", ["forward", "loss"])
def test_trinity_lowering_is_unchanged(trinity, program):
    assert _digest(_lowered(trinity, program)) == TRINITY_LOWERING[program]
