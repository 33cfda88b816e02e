"""A catalog decoder trunk as the agent's token stack (``model.trunk``,
``models/trunk.py``) against its plain reference
(``benchmark/reference/trunk.py``) on seeded weights in float32, at a tiny
size: forward, loss and every gradient leaf through the dense and the
compact-rows path; the shares of a small deployment add up to the uncut
layer; the window and the NoPE/RoPE layout where they bind; routing under
skew; the router's one-hot selection against its ``lax.top_k`` form under
every mechanism either family gives it; the parted storage /
sliced-forward predicates."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import model as ref_model
from benchmark.reference import trunk as ref_trunk
from t2omca_tpu.components.episode_buffer import CompactEntityObs, EpisodeBatch
from t2omca_tpu.config import TrunkConfig, from_dict
from t2omca_tpu.models import trunk as tr
from t2omca_tpu.ops import query_slice as qs
from t2omca_tpu.run import Experiment

A, MEC, CH, T = 3, 2, 2, 4
D, HD, F = 32, 8, 16                      # hidden, head_dim, expert width

#: the tiny deployment: 8 experts 4 ways, 4 query heads over 2 key/value
#: heads 2 ways; 2 layers (NoPE/global, RoPE/window); a window of 3 over
#: A + 1 = 4 tokens, so it binds
TK = dict(hidden_size=D, head_dim=HD, num_attention_heads=4,
          num_key_value_heads=2, num_hidden_layers=2, moe_ffn_hidden_size=F,
          moe_num_primary_experts=8, moe_num_active_primary_experts=3,
          rope_layout=(0, 1), sliding_window_layout=(0, 1),
          sliding_window_size=3, rope_theta=100.0, experts_held=2,
          heads_held=2, share_index=0)


def make_cfg(trunk=None, **over):
    data = {
        "batch_size_run": 2, "batch_size": 2, "target_update_interval": 2,
        "env_args": {"agv_num": A, "mec_num": MEC, "num_channels": CH,
                     "episode_limit": T},
        "model": {"emb": D, "depth": 2, "mixer_emb": D, "mixer_heads": 2,
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


def ref_sizes(tk: TrunkConfig) -> dict:
    """The reference's statement of a TrunkConfig's share."""
    return dict(
        head_dim=tk.head_dim, q_heads=tk.heads_held,
        kv_heads=tk.kv_heads_held, layers=tk.num_hidden_layers,
        experts=tk.moe_num_primary_experts, experts_held=tk.experts_held,
        expert_offset=tk.expert_offset,
        top_k=tk.moe_num_active_primary_experts, eps=tk.rms_norm_eps,
        rope=tk.rope_layout,
        window=tuple(tk.sliding_window_size * w
                     for w in tk.sliding_window_layout),
        theta=tk.rope_theta)


def sizes_of(cfg) -> dict:
    m = cfg.model
    return dict(n_agents=A, emb=m.emb, mixer_emb=m.mixer_emb,
                mixer_heads=m.mixer_heads, mixer_depth=m.mixer_depth,
                standard_heads=m.standard_heads)


def noisy_scales(params, key):
    """Norm scales off 1 and biases off 0, so that each is exercised."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def exp():
    return Experiment.build(make_cfg())


def held_experts(p, m, weights, idx, tk):
    """This share's expert sum and its pairs per held expert."""
    per = tr.held_weights(weights, idx, tk)
    return tr.experts_part(p, m, per, jnp.float32), (per > 0).sum(0)


@pytest.fixture(scope="module")
def params(exp):
    ls = exp.learner.init_state(jax.random.PRNGKey(3))
    return noisy_scales(ls.params, jax.random.PRNGKey(4))


def episodes(key, b=2, t=T):
    """A synthetic time-major batch in the reference's layout."""
    ks = jax.random.split(key, 8)
    n_act = CH + 1
    avail = jnp.concatenate(
        [jnp.ones((t + 1, b, A, 1), bool),
         jax.random.uniform(ks[5], (t + 1, b, A, n_act - 1)) < 0.7], -1)
    return {
        "rows": jax.random.normal(ks[0], (t + 1, b, A, 8)),
        "mec": jax.random.randint(ks[1], (t + 1, b, A), 0, MEC),
        "mean": 0.1 * jax.random.normal(ks[2], (t + 1, b, A, 9)),
        "std": 1 + jnp.abs(jax.random.normal(ks[3], (t + 1, b, A, 9))),
        "state": jax.random.normal(ks[4], (t + 1, b, A * 8)),
        "avail": avail,
        "actions": jnp.zeros((t, b, A), jnp.int32),
        "reward": jax.random.normal(ks[6], (t, b)),
        "terminated": jnp.zeros((t, b), bool),
        "filled": jnp.ones((t, b), bool).at[-1, 1].set(False)}


def program_batch(batch, compact: bool) -> EpisodeBatch:
    bm = lambda x: jnp.swapaxes(x, 0, 1)                    # noqa: E731
    if compact:
        obs = CompactEntityObs(rows=bm(batch["rows"]),
                               mec_index=bm(batch["mec"]).astype(jnp.int8),
                               mean=bm(batch["mean"]), std=bm(batch["std"]))
    else:
        full = ref_model.entity_obs(batch["rows"], batch["mec"],
                                    batch["mean"], batch["std"])
        obs = bm(full.reshape(full.shape[:3] + (-1,)))
    return EpisodeBatch(obs=obs, state=bm(batch["state"]),
                        avail_actions=bm(batch["avail"]),
                        actions=bm(batch["actions"]),
                        reward=bm(batch["reward"]),
                        terminated=bm(batch["terminated"]),
                        filled=bm(batch["filled"]))


def strip(p):
    return {"agent": p["agent"]["params"], "mixer": p["mixer"]["params"]}


# ------------------------------------------------- (a) against the reference

@pytest.mark.parametrize("compact", [False, True],
                         ids=["dense-obs", "compact-rows"])
def test_unroll_matches_reference(exp, params, compact):
    """Q-values and carried hidden of a 3-step unroll."""
    batch = {k: v[:3] for k, v in episodes(jax.random.PRNGKey(0)).items()}
    tk = exp.cfg.model.trunk
    want_q, want_h = ref_trunk.unroll_agent(
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
        assert int(aux["held"].sum()) == int(aux["load"].sum()) > 0


@pytest.mark.parametrize("compact", [False, True],
                         ids=["dense-obs", "compact-rows"])
def test_loss_and_every_gradient_leaf_match_reference(exp, params, compact):
    batch = episodes(jax.random.PRNGKey(1))
    weights = jnp.asarray([1.0, 0.5])
    target = jax.tree.map(lambda x: x * 0.9, params)
    tk = exp.cfg.model.trunk

    def ref_loss(p):
        return ref_trunk.episode_loss(
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
    for (path, w), g in zip(flat_w, flat_g):
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))
    # the counters of the online unroll: every pair held entered the
    # product
    assert float(info["moe_dropped"]) == 0.0
    tokens = (T + 1) * 2 * A * (A + 1)
    assert float(info["moe_pairs_routed"]) == tokens * 3 * 2
    assert 0 < float(info["moe_load_max"]) <= float(info["moe_pairs_held"])


# ------------------------------------------------- (b) the shares add up

def full_layer(key):
    """An uncut layer's weights (every head, every expert)."""
    ks = jax.random.split(key, 10)
    g = lambda k, *s: 0.3 * jax.random.normal(k, s)          # noqa: E731
    return {"input_norm": 1 + g(ks[0], D), "post_norm": 1 + g(ks[1], D),
            "wq": g(ks[2], D, 4 * HD), "wk": g(ks[3], D, 2 * HD),
            "wv": g(ks[4], D, 2 * HD), "wo": g(ks[5], 4 * HD, D),
            "router": g(ks[6], D, 8), "w_gate": g(ks[7], 8, D, F),
            "w_up": g(ks[8], 8, D, F), "w_down": g(ks[9], 8, F, D)}


def share_of(full, tk: TrunkConfig):
    """What share ``tk.share_index`` holds of ``full``."""
    att = tk.share_index % tk.attention_ways
    q0, q1 = att * tk.heads_held * HD, (att + 1) * tk.heads_held * HD
    k0, k1 = att * tk.kv_heads_held * HD, (att + 1) * tk.kv_heads_held * HD
    e0, e1 = tk.expert_offset, tk.expert_offset + tk.experts_held
    return dict(full, wq=full["wq"][:, q0:q1], wk=full["wk"][:, k0:k1],
                wv=full["wv"][:, k0:k1], wo=full["wo"][q0:q1],
                w_gate=full["w_gate"][e0:e1], w_up=full["w_up"][e0:e1],
                w_down=full["w_down"][e0:e1])


@pytest.mark.parametrize("layer", [0, 1], ids=["nope-global", "rope-window"])
def test_shares_add_up_to_the_uncut_layer(layer):
    """Experts 4 ways x heads 2 ways: over every share the program's
    partial ``W_o`` output (each attention share once: the two halves of
    the expert group hold copies) and partial expert output sum to the
    uncut reference layer; the router and the norms are counted once."""
    full = full_layer(jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (3, A + 1, D))
    uncut = TrunkConfig(**dict(TK, experts_held=8, heads_held=4))
    want = ref_trunk.layer_forward(full, h, trunk=ref_sizes(uncut),
                                   layer=layer, prec="f32")
    flat = h.reshape(-1, D)
    att = sum(tr.attention_part(share_of(full, tk), h, tk, layer,
                                jnp.float32)
              for tk in (TrunkConfig(**dict(TK, share_index=i))
                         for i in range(2)))
    a = h + att
    m = tr.rms_norm(a, full["post_norm"], 1e-6).reshape(-1, D)
    moe, pairs = 0.0, 0
    for i in range(4):
        tk = TrunkConfig(**dict(TK, share_index=i))
        weights, idx = tr.route(full["router"], flat, tk)
        part, sizes = held_experts(share_of(full, tk), m, weights, idx, tk)
        moe = moe + part
        pairs += int(sizes.sum())
    assert pairs == flat.shape[0] * 3          # every pair is held somewhere
    np.testing.assert_allclose(a + moe.reshape(h.shape), want, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("share", range(4))
def test_every_share_matches_its_reference(share):
    """One share's whole layer (what the program runs: the partial sums go
    on) against the reference given the same share."""
    tk = TrunkConfig(**dict(TK, share_index=share))
    p = share_of(full_layer(jax.random.PRNGKey(7)), tk)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, A + 1, D))
    for layer in range(2):
        got, _ = tr.trunk_layer(p, h, tk, layer, jnp.float32)
        want = ref_trunk.layer_forward(p, h, trunk=ref_sizes(tk),
                                       layer=layer, prec="f32")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ------------------------------------- (c) the window and the layout bind

@pytest.mark.parametrize("rope,window,size", [
    ((0, 0), (0, 0), 3), ((1, 1), (0, 0), 3), ((0, 0), (1, 1), 2),
    ((1, 0), (0, 1), 1), ((0, 1), (0, 1), 3)],
    ids=["nope-global", "rope-global", "nope-window2", "mixed-window1",
         "published-pattern"])
def test_window_and_layout_against_reference(rope, window, size):
    tk = TrunkConfig(**dict(TK, rope_layout=rope,
                            sliding_window_layout=window,
                            sliding_window_size=size))
    p = share_of(full_layer(jax.random.PRNGKey(9)), tk)
    h = jax.random.normal(jax.random.PRNGKey(10), (2, A + 1, D))
    outs = []
    for layer in range(2):
        got = tr.attention_part(p, h, tk, layer, jnp.float32)
        want = ref_trunk.attention(
            p, ref_trunk.rms_norm(p["input_norm"], h, 1e-6, "f32"),
            trunk=ref_sizes(tk), layer=layer, prec="f32")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        outs.append(np.asarray(got))
    if rope[0] != rope[1] or window[0] != window[1]:
        assert np.abs(outs[0] - outs[1]).max() > 1e-3   # the layout binds


def test_window_shorter_than_the_sequence_hides_old_keys():
    """With a window of 1 every token reads only itself: the output no
    longer depends on the other tokens."""
    tk = TrunkConfig(**dict(TK, sliding_window_layout=(1, 1),
                            sliding_window_size=1))
    p = share_of(full_layer(jax.random.PRNGKey(11)), tk)
    h = jax.random.normal(jax.random.PRNGKey(12), (1, A + 1, D))
    h2 = h.at[:, 0].add(1.0)
    d = np.abs(tr.attention_part(p, h, tk, 1, jnp.float32)
               - tr.attention_part(p, h2, tk, 1, jnp.float32))
    assert d[:, 1:].max() == 0.0 and d[:, 0].max() > 0


# ------------------------------------------------- (d) routing under skew

def skewed_router(to):
    """A router that sends every token to the experts ``to`` (its first
    ``len(to)`` choices)."""
    w = jnp.zeros((D, 8))
    return w.at[0, jnp.asarray(to)].set(50.0 - jnp.arange(len(to)))


@pytest.mark.parametrize("to,held_share", [((0, 5, 6), 1 / 3), ((5, 6, 7), 0),
                                           ((0, 1, 7), 2 / 3)],
                         ids=["all-to-one-held", "to-none", "both-held"])
def test_routing_under_skew_loses_nothing(to, held_share):
    tk = TrunkConfig(**TK)                     # holds experts 0 and 1
    p = dict(share_of(full_layer(jax.random.PRNGKey(13)), tk),
             router=skewed_router(to))
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(14), (4, A + 1, D))) + 1
    got, aux = tr.trunk_layer(p, h, tk, 0, jnp.float32)
    want = ref_trunk.layer_forward(p, h, trunk=ref_sizes(tk), layer=0,
                                   prec="f32")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    n = 4 * (A + 1)
    sizes = aux["load"]
    assert int(sizes.sum()) == int(aux["held"]) == round(held_share * 3 * n)
    if held_share == 1 / 3:
        assert sizes.tolist() == [n, 0]        # one expert takes them all


# ------------------------- (e) the router against its ``lax.top_k`` form

def route_by_top_k(w_router, h, tk, bias=None):
    """``trunk.route`` as it stood through PR 31: ``jax.lax.top_k`` (a
    sort of all experts on the chip), ``take_along_axis`` (a gather, and
    a scatter in its transpose). What the one-hot form must return."""
    sp = tk.spec
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    values = (logits if sp.router_scores == "softmax"
              else jax.nn.sigmoid(logits))
    _, idx = jax.lax.top_k(values if bias is None else values + bias,
                           sp.top_k)
    kept = jnp.take_along_axis(values, idx, axis=-1)
    if sp.router_scores == "softmax":
        return jax.nn.softmax(kept, axis=-1), idx
    if sp.route_norm:
        kept = kept / (kept.sum(axis=-1, keepdims=True) + 1e-20)
    return kept * sp.route_scale, idx


def tied_router_case(e: int, biased: bool):
    """(router kernel ``(D, e)``, tokens ``(96, D)``, bias or None) with
    ties planted: every token twice; experts 5 and 9 with one kernel
    column (equal logits in every row); experts 2, 3 and 11 so far up
    that their sigmoids read exactly 1.0 in float32 on the rows where
    the first feature is positive; and, with a bias, two experts of
    score 0.5 (zero columns) lifted by the same bias to the top."""
    k = jax.random.split(jax.random.PRNGKey(40 + e), 3)
    w = 0.3 * jax.random.normal(k[0], (D, e))
    w = w.at[:, 9].set(w[:, 5])
    w = w.at[:, jnp.asarray([2, 3, 11])].set(0.0).at[
        0, jnp.asarray([2, 3, 11])].set(40.0)
    m = jax.random.normal(k[1], (48, D))
    m = jnp.concatenate([m, m])
    bias = None
    if biased:
        w = w.at[:, jnp.asarray([20, 33])].set(0.0)
        bias = 0.02 * jax.random.normal(k[2], (e,))
        bias = bias.at[jnp.asarray([20, 33])].set(3.0)
    return w, m, bias


@pytest.mark.parametrize("share", [0, 3], ids=["offset-0", "offset-24"])
@pytest.mark.parametrize("k", [6, 8], ids=["top-6", "top-8"])
@pytest.mark.parametrize("e", [64, 128], ids=["of-64", "of-128"])
@pytest.mark.parametrize("biased", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("scores", ["softmax", "sigmoid"])
def test_route_is_lax_top_k_by_one_hot_planes(monkeypatch, scores, biased,
                                              e, k, share):
    """Over every mechanism ``TrunkSpec`` gives the router, at both
    cells' expert counts: the ids are ``lax.top_k``'s, in its order, ties
    and all; the weights, the held plane and the gradients agree to
    float32 rounding; a selection bias has a gradient of exactly zero;
    the layer's two counts are equal."""
    base = TrunkConfig(**dict(TK, moe_num_primary_experts=e,
                              moe_num_active_primary_experts=k,
                              experts_held=8, share_index=share))
    spec = dataclasses.replace(base.spec, router_scores=scores,
                               router_bias=biased, route_scale=2.5)
    tk = types.SimpleNamespace(spec=spec)     # all the router reads
    w, m, bias = tied_router_case(e, biased)
    want_w, want_idx = route_by_top_k(w, m, tk, bias=bias)
    got_w, got_idx = tr.route(w, m, tk, bias=bias)
    # the ties are there: equal ranked values inside the kept prefix
    ranked = np.take_along_axis(
        np.asarray((m @ w if scores == "softmax" else jax.nn.sigmoid(m @ w))
                   + (0 if bias is None else bias)), np.asarray(want_idx), -1)
    assert (np.diff(ranked, axis=-1) == 0).any(-1).mean() > 0.4
    assert got_idx.dtype == want_idx.dtype
    np.testing.assert_array_equal(got_idx, want_idx)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-6, atol=0)
    np.testing.assert_allclose(tr.held_weights(got_w, got_idx, tk),
                               tr.held_weights(want_w, want_idx, tk),
                               rtol=1e-6, atol=0)

    # gradients, through held_weights as the layer uses the router
    g = jax.random.normal(jax.random.PRNGKey(41), (m.shape[0], 8))

    def loss(route):
        def f(w, m, bias):
            weights, idx = route(w, m, tk, bias=bias)
            return (tr.held_weights(weights, idx, tk) * g).sum() + (
                weights ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2) if biased else (0, 1))(
            w, m, bias)
    got, want = loss(tr.route), loss(route_by_top_k)
    for a, b in zip(got[:2], want[:2]):
        assert float(jnp.abs(b).max()) > 1e-2
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(b).max()))
    if biased:
        assert not np.asarray(got[2]).any()    # selection only: exactly 0

    # the layer's aux: the product's mask and the range count the same
    lp = {"router": w, "expert_bias": bias}
    per, aux = tr._routing(lp, m, tk)
    monkeypatch.setattr(tr, "route", route_by_top_k)
    per_old, aux_old = tr._routing(lp, m, tk)
    np.testing.assert_allclose(per, per_old, rtol=1e-6, atol=0)
    assert aux["load"].tolist() == aux_old["load"].tolist()
    assert int(aux["held"]) == int(aux_old["held"]) == int(aux["load"].sum())


def test_experts_gradient_matches_the_reference():
    """Weights, inputs AND routing weights."""
    tk = TrunkConfig(**TK)
    p = share_of(full_layer(jax.random.PRNGKey(15)), tk)
    m = jax.random.normal(jax.random.PRNGKey(16), (10, D))
    weights, idx = tr.route(p["router"], m, tk)
    scatter = lambda w: jnp.zeros((10, 8)).at[                # noqa: E731
        jnp.arange(10)[:, None], idx].set(w)

    def program(p, m, w):
        return (held_experts(p, m, w, idx, tk)[0] ** 2).sum()

    def masked(p, m, w):
        return (ref_trunk.experts(p, m, scatter(w), trunk=ref_sizes(tk),
                                  prec="f32") ** 2).sum()
    keys = ("w_gate", "w_up", "w_down")
    sub = {k: p[k] for k in keys}
    g = jax.grad(lambda s, m, w: program(dict(p, **s), m, w), (0, 1, 2))(
        sub, m, weights)
    w = jax.grad(lambda s, m, w: masked(dict(p, **s), m, w), (0, 1, 2))(
        sub, m, weights)
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


# ------------------------------------------- (e) the parted predicates

def test_trunk_keeps_compact_storage_without_the_sliced_forward(exp):
    cfg = exp.cfg
    assert qs.entity_store_eligible(cfg) and qs.trunk_compact_eligible(cfg)
    assert not qs.agent_qslice_eligible(cfg)
    assert not qs.entity_tables_eligible(cfg)
    assert exp.runner.compact_store and exp.buffer.compact_obs
    assert not exp.mac.use_qslice and not exp.mac.use_entity_tables
    assert qs.mixer_qslice_eligible(cfg)        # the mixer's slice is its own
    dense = make_cfg(**{"replay.compact_entity_store": False})
    assert not qs.entity_store_eligible(dense)


@pytest.mark.parametrize("over,store,tables", [
    ({}, True, True),
    ({"model": {"use_qslice": False}}, False, False),
    ({"model": {"use_entity_tables": False}}, False, False),
    ({"env_args": {"fast_norm": False}}, False, False),
    ({"replay": {"compact_entity_store": False}}, False, True)],
    ids=["default", "no-qslice", "no-tables", "sequential-norm",
         "dense-store"])
def test_t2omca_eligibility_unchanged(over, store, tables):
    """Without a trunk, storage still follows the entity-table forward."""
    cfg = from_dict(dict({"model": {}, "env_args": {}, "replay": {}}, **over))
    assert cfg.model.trunk is None
    assert qs.entity_tables_eligible(cfg) is tables
    assert qs.entity_store_eligible(cfg) is store
    assert qs.agent_qslice_eligible(cfg) is cfg.model.use_qslice


@pytest.mark.parametrize("bad", [
    {"model.emb": 16}, {"model.depth": 3}, {"agent": "rnn"},
    {"model.dropout": 0.1}, {"action_selector": "noisy-new"}],
    ids=lambda b: next(iter(b)))
def test_sanity_check_refuses(bad):
    with pytest.raises(ValueError):
        make_cfg(**bad)


@pytest.mark.parametrize("trunk", [
    {"heads_held": 3}, {"experts_held": 3}, {"share_index": 4},
    {"rope_layout": (0,)}], ids=lambda t: next(iter(t)))
def test_sanity_check_refuses_a_share_that_does_not_divide(trunk):
    with pytest.raises(ValueError):
        make_cfg(trunk=trunk)


def test_config_round_trips_and_takes_dotted_overrides(tmp_path):
    import json
    from t2omca_tpu.config import load_config
    cfg = make_cfg()
    assert from_dict(json.loads(json.dumps(dataclasses.asdict(cfg)))) == cfg
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dataclasses.asdict(cfg)))
    moved = load_config(str(path), ("model.trunk.share_index=3",))
    assert moved.model.trunk.share_index == 3
    assert moved.model.trunk.expert_offset == 6


def test_shipped_config_is_the_catalog_row():
    """configs/config8_trunk_smallthinker.yaml: every width as published."""
    import os
    from t2omca_tpu.config import load_config
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tk = load_config(os.path.join(
        here, "configs", "config8_trunk_smallthinker.yaml")).model.trunk
    assert (tk.hidden_size, tk.head_dim, tk.moe_ffn_hidden_size,
            tk.moe_num_primary_experts, tk.moe_num_active_primary_experts,
            tk.sliding_window_size, tk.rope_theta, tk.rms_norm_eps) == (
        2560, 128, 768, 64, 6, 4096, 1.5e6, 1e-6)
    assert (tk.experts_held, tk.heads_held, tk.kv_heads_held,
            tk.num_hidden_layers, tk.rope_layout) == (8, 7, 1, 4,
                                                      (0, 1, 1, 1))


# ------------------------------------------------------- the normal path

def test_rollout_counts_its_pairs_and_drops_none(exp, params):
    rs = exp.runner.init_state(jax.random.PRNGKey(0))
    _, batch, stats = jax.jit(
        lambda p, r: exp.runner.run(p, r))(params["agent"], rs)
    assert isinstance(batch.obs, CompactEntityObs)
    tokens = T * 2 * A * (A + 1)
    assert float(stats.moe["moe_pairs_routed"]) == tokens * 3 * 2
    assert float(stats.moe["moe_dropped"]) == 0.0
    assert 0 < float(stats.moe["moe_pairs_held"]) < tokens * 3 * 2


def test_train_step_and_skip_branch_carry_the_same_info(exp, params):
    """``train_info_zeros`` mirrors ``train``'s info (the superstep's
    ``lax.cond``), counters included."""
    ls = exp.learner.init_state(jax.random.PRNGKey(1))
    pb = program_batch(episodes(jax.random.PRNGKey(2)), True)
    _, info = jax.eval_shape(
        lambda l: exp.learner.train(l, pb, jnp.ones(2), jnp.int32(0),
                                    jnp.int32(0)), ls)
    zeros = exp.learner.train_info_zeros(2)
    assert set(zeros) == set(info)
    for k in tr.MOE_COUNTERS:
        assert info[k].shape == zeros[k].shape == ()


def test_serving_refuses_a_trunk_by_name(tmp_path):
    from t2omca_tpu.serve.export import load_acting_params
    with pytest.raises(ValueError, match="model.trunk"):
        load_acting_params(make_cfg(), str(tmp_path))


def test_expert_axis_is_declared_with_the_others():
    from jax.sharding import PartitionSpec as P
    from t2omca_tpu.parallel.mesh import logical_to_mesh_axes
    assert logical_to_mesh_axes(("expert", "embed", "mlp")) == P(
        "model", None, "model")


def test_dropped_counts_a_held_pair_the_product_leaves_out(monkeypatch):
    """``moe_dropped`` compares two counts made from the expert ids — the
    range this share holds, and the mask that weights the product. A mask
    that leaves out this share's last expert reads as dropped pairs."""
    tk = TrunkConfig(**TK)
    p = dict(share_of(full_layer(jax.random.PRNGKey(17)), tk),
             router=skewed_router((1, 5, 6)))
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(18), (2, A + 1, D))) + 1
    n = 2 * (A + 1)

    def dropped():
        _, aux = tr.trunk_layer(p, h, tk, 0, jnp.float32)
        aux = jax.tree.map(lambda x: x[None], aux)
        return float(tr.moe_counters(aux, n, tk)["moe_dropped"])
    assert dropped() == 0.0
    real = tr.held_weights
    monkeypatch.setattr(tr, "held_weights", lambda w, idx, tk_: real(
        w, idx, tk_).at[:, -1].set(0.0))
    assert dropped() == n


def test_unroll_is_a_scan_of_the_acting_forward(exp, params):
    """The learner's unroll and acting share one entry: stepping
    ``agent_forward_trunk`` by hand gives the scan's Q-values, hiddens
    and counts."""
    tk = exp.cfg.model.trunk
    obs = jax.random.normal(jax.random.PRNGKey(19), (3, 2, A, A, 9))
    agent = params["agent"]
    qs, hs, aux = tr.unroll(agent, obs, exp.mac.init_hidden(2), tk=tk,
                            dtype=jnp.float32, wrap=jax.checkpoint)
    h = exp.mac.init_hidden(2)
    for t in range(3):
        q, h, a1 = tr.agent_forward_trunk(agent, obs[t], h, tk=tk,
                                          dtype=jnp.float32)
        np.testing.assert_allclose(qs[t], q, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(hs[t], h, rtol=1e-5, atol=1e-6)
        assert (aux["load"][t] == a1["load"]).all()
    assert aux["load"].shape == (3, 2, tk.experts_held)
