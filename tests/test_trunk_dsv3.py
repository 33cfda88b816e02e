"""A ``deepseek_v3`` catalog trunk (``model.trunk`` with ``model_type:
deepseek_v3``, ``config.DeepseekV3TrunkConfig``; kanana-2's family) through
the one layer function of ``models/trunk.py``, against its plain reference
(``benchmark/reference/dsv3.py``) on seeded weights in float32 at a tiny
size: forward, loss and every gradient leaf; the shares of a small
deployment add up to the uncut layer; latent attention alone (one rotary
key for all heads, the interleaved pairing, positions on the rotary part
only, the scale, value heads narrower than query/key heads, the latent's
norm); the router at the published top-6 / 2.448; the configuration's
files and refusals; and this family's shipped programs, pinned as the two
older families' are.

Tolerances are ``tests/test_trunk_afmoe.py``'s and for its reasons:
float32 program against float32 reference, the two associating sums
differently (a wide product over all held experts against a loop over
experts; heads batched and ``q_nope k_nope + q_rope k_rope`` as two
contractions against one head at a time over the concatenated 10
dimensions) — forward ``rtol 2e-4 / atol 2e-5``, gradients ``rtol 2e-3``
with ``atol`` 1e-5 of the largest gradient (a gradient leaf sums
thousands of such terms). The attention sublayer at bfloat16 reads 1e-2
and fails them (``test_attention_one_precision_step_down_fails``)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dsv3 as ref
from benchmark.reference import model as ref_model
from t2omca_tpu.config import DeepseekV3TrunkConfig, from_dict, load_config
from t2omca_tpu.models import trunk as tr

from test_trunk import episodes, noisy_scales, program_batch, strip
from test_trunk_afmoe import _digest, _lowered, _shipped, sizes_of

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A, MEC, CH, T = 3, 2, 2, 4
D, F, DENSE = 32, 8, 24                  # hidden, expert, dense
NOPE, ROPE, VALUE, RANK = 6, 4, 8, 12    # a head's widths; the latent's

#: the tiny deployment: 8 experts 4 ways (3 a token), 4 heads 2 ways;
#: published layers 0-2 of 3 held — layer 0 dense, 1 and 2 routed; value
#: heads (8) of another width than query/key heads (6 + 4)
TK = dict(model_type="deepseek_v3", hidden_size=D, head_dim=ROPE,
          num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
          first_k_dense_replace=1, intermediate_size=DENSE,
          moe_intermediate_size=F, n_routed_experts=8,
          num_experts_per_tok=3, n_shared_experts=2,
          routed_scaling_factor=2.5, kv_lora_rank=RANK,
          qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
          qk_head_dim=NOPE + ROPE, v_head_dim=VALUE, rope_theta=100.0,
          experts_held=2, heads_held=2, share_index=0, first_layer=0)


def make_cfg(trunk=None, **over):
    data = {
        "batch_size_run": 2, "batch_size": 2, "target_update_interval": 2,
        "env_args": {"agv_num": A, "mec_num": MEC, "num_channels": CH,
                     "episode_limit": T},
        "model": {"emb": D, "depth": 3, "mixer_emb": D, "mixer_heads": 2,
                  "mixer_depth": 1, "standard_heads": True, "remat": True,
                  "trunk": dict(TK, **(trunk or {}))},
        "replay": {"buffer_size": 4}}
    data.update(over)
    return from_dict(data)


def ref_sizes(tk: DeepseekV3TrunkConfig) -> dict:
    """The reference's statement of a DeepseekV3TrunkConfig's share."""
    held = range(tk.first_layer, tk.first_layer + tk.num_hidden_layers)
    return dict(
        q_heads=tk.heads_held, nope=tk.qk_nope_head_dim,
        rope=tk.qk_rope_head_dim, value=tk.v_head_dim,
        latent=tk.kv_lora_rank, experts=tk.n_routed_experts,
        experts_held=tk.experts_held, expert_offset=tk.expert_offset,
        top_k=tk.num_experts_per_tok, route_scale=tk.routed_scaling_factor,
        eps=tk.rms_norm_eps, theta=tk.rope_theta,
        layers=tuple("dense" if i < tk.first_k_dense_replace else "experts"
                     for i in held))


@pytest.fixture(scope="module")
def exp():
    from t2omca_tpu.run import Experiment
    return Experiment.build(make_cfg())


@pytest.fixture(scope="module")
def params(exp):
    ls = exp.learner.init_state(jax.random.PRNGKey(3))
    return noisy_scales(ls.params, jax.random.PRNGKey(4))


# ------------------------------------------------- (a) against the reference

def test_the_tree_is_what_the_spec_says_each_layer_has(params):
    layers = params["agent"]["params"]["transformer"]
    every = {"input_norm", "post_norm", "wq", "wkv_a", "kv_norm", "wkv_b",
             "wo"}
    assert set(layers["layer_0"]) == every | {"dense_gate", "dense_up",
                                              "dense_down"}
    routed = every | {"router", "expert_bias", "w_gate", "w_up", "w_down",
                      "shared_gate", "shared_up", "shared_down"}
    assert set(layers["layer_1"]) == set(layers["layer_2"]) == routed
    shapes = {k: v.shape for k, v in layers["layer_1"].items()}
    # the held heads' columns and rows; the down-projection and its norm
    # whole; the two shared experts as one feed-forward
    assert shapes["wq"] == (D, 2 * (NOPE + ROPE))
    assert shapes["wkv_a"] == (D, RANK + ROPE)
    assert shapes["kv_norm"] == (RANK,)
    assert shapes["wkv_b"] == (RANK, 2 * (NOPE + VALUE))
    assert shapes["wo"] == (2 * VALUE, D)
    assert shapes["shared_gate"] == (D, 2 * F)
    assert shapes["expert_bias"] == (8,) and shapes["router"] == (D, 8)
    cast = tr.cast_weights(params["agent"], jnp.bfloat16)["transformer"]
    for name, x in cast["layer_1"].items():
        want = jnp.float32 if name in tr.KEEP_F32 else jnp.bfloat16
        assert x.dtype == want, name
    assert cast["layer_1"]["kv_norm"].dtype == jnp.float32
    assert cast["layer_1"]["wkv_b"].dtype == jnp.bfloat16


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
            # the bias chooses and does not weigh: exactly zero
            assert not np.asarray(g).any() and not np.asarray(w).any()
            biases += 1
        elif "['agent']" in name:
            assert np.asarray(g).any(), name      # every other leaf is used
    assert biases == 2
    assert float(info["moe_dropped"]) == 0.0
    tokens = (T + 1) * 2 * A * (A + 1)
    assert float(info["moe_pairs_routed"]) == tokens * 3 * 2
    assert 0 < float(info["moe_load_max"]) <= float(info["moe_pairs_held"])


# ------------------------------------------------- (b) the shares add up

def full_layer(key, dense: bool, e: int = 8):
    """An uncut layer's weights (every head, every one of ``e`` experts)."""
    ks = iter(jax.random.split(key, 24))
    g = lambda *s: 0.3 * jax.random.normal(next(ks), s)       # noqa: E731
    out = {"input_norm": 1 + g(D), "post_norm": 1 + g(D),
           "kv_norm": 1 + g(RANK), "wq": g(D, 4 * (NOPE + ROPE)),
           "wkv_a": g(D, RANK + ROPE), "wkv_b": g(RANK, 4 * (NOPE + VALUE)),
           "wo": g(4 * VALUE, D)}
    if dense:
        return dict(out, dense_gate=g(D, DENSE), dense_up=g(D, DENSE),
                    dense_down=g(DENSE, D))
    return dict(out, router=g(D, e), expert_bias=0.5 * g(e),
                w_gate=g(e, D, F), w_up=g(e, D, F), w_down=g(e, F, D),
                shared_gate=g(D, 2 * F), shared_up=g(D, 2 * F),
                shared_down=g(2 * F, D))


def share_of(full, tk: DeepseekV3TrunkConfig):
    """What share ``tk.share_index`` holds of ``full``: its heads' columns
    of ``W_q`` and ``W_kvb`` and rows of ``W_o``, its experts; ``W_kva``,
    the norms, the shared experts and a dense feed-forward whole."""
    att, h = tk.share_index % tk.attention_ways, tk.heads_held
    cut = lambda w, axis, per: jax.lax.slice_in_dim(          # noqa: E731
        w, att * h * per, (att + 1) * h * per, axis=axis)
    out = dict(full, wq=cut(full["wq"], 1, NOPE + ROPE),
               wkv_b=cut(full["wkv_b"], 1, NOPE + VALUE),
               wo=cut(full["wo"], 0, VALUE))
    if "w_gate" in full:
        e0, e1 = tk.expert_offset, tk.expert_offset + tk.experts_held
        out.update(w_gate=full["w_gate"][e0:e1], w_up=full["w_up"][e0:e1],
                   w_down=full["w_down"][e0:e1])
    return out


def share(i, **kw):
    return DeepseekV3TrunkConfig(**dict(TK, share_index=i, **kw))


@pytest.mark.parametrize("layer", [0, 1], ids=["dense-layer", "routed-layer"])
def test_shares_add_up_to_the_uncut_layer(layer):
    """Experts 16 ways (one held a share) x heads 2 ways, against the
    UNCUT reference. Over the 2 attention shares ``o W_o`` sums to the
    uncut attention output — the latent and the rotary key are inside
    each share and counted nowhere twice; over the 16 expert shares the
    routed sums plus the shared experts ONCE (a dense layer's
    feed-forward: once, every share computes it alike) sum to the uncut
    feed-forward. Residuals are pre-norm, so with the residual counted
    once the shares' layer outputs are the uncut layer's."""
    one = dict(n_routed_experts=16, experts_held=1)
    full = full_layer(jax.random.PRNGKey(5), dense=layer == 0, e=16)
    h = jax.random.normal(jax.random.PRNGKey(6), (3, A + 1, D))
    uncut = ref_sizes(share(0, n_routed_experts=16, experts_held=16,
                            heads_held=4))
    u = ref.rms(full["input_norm"], h, 1e-6)
    want_att = ref.attention(full, u, trunk=uncut, prec="f32")
    att = sum(tr.attention_part(share_of(full, share(i, **one)), h,
                                share(i, **one), layer, jnp.float32)
              for i in range(2))
    np.testing.assert_allclose(att, want_att, rtol=2e-4, atol=2e-5)
    # what every attention share computes alike: the latent, once
    kv = [tr.latent_kv(share_of(full, share(i, **one)), u,
                       share(i, **one).spec, jnp.float32) for i in range(2)]
    np.testing.assert_array_equal(kv[0][1], kv[1][1])      # the rotary key
    a = h + want_att
    m = ref.rms(full["post_norm"], a, 1e-6).reshape(-1, D)
    want_f = ref.feed_forward(full, m, trunk=uncut, layer=layer, prec="f32")
    want_y = ref.layer_forward(full, h, trunk=uncut, layer=layer, prec="f32")
    if layer == 0:
        for i in (0, 7, 15):                # alike on every share: once
            part = tr.gated_ffn(share_of(full, share(i, **one)), "dense", m,
                                jnp.float32, jax.nn.silu)
            np.testing.assert_allclose(part, want_f, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(a + part.reshape(h.shape), want_y,
                                   rtol=2e-4, atol=2e-5)
        return
    routed, pairs = 0.0, 0
    for i in range(16):
        tk = share(i, **one)
        p = share_of(full, tk)
        weights, idx = tr.route(p["router"], m, tk, bias=p["expert_bias"])
        per = tr.held_weights(weights, idx, tk)
        routed = routed + tr.experts_part(p, m, per, jnp.float32, jax.nn.silu)
        pairs += int((per > 0).sum())
    assert pairs == m.shape[0] * 3             # every pair is held somewhere
    shared = tr.gated_ffn(full, "shared", m, jnp.float32, jax.nn.silu)
    np.testing.assert_allclose(routed + shared, want_f, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(a + (routed + shared).reshape(h.shape),
                               want_y, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("index", range(4))
def test_every_share_matches_its_reference(index):
    """One share's whole layers (what the program runs and hands on)
    against the reference given the same share."""
    tk = share(index)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, A + 1, D))
    for layer in (0, 1, 2):
        p = share_of(full_layer(jax.random.PRNGKey(7 + layer),
                                dense=layer == 0), tk)
        got, aux = tr.trunk_layer(p, h, tk, layer, jnp.float32)
        want = ref.layer_forward(p, h, trunk=ref_sizes(tk), layer=layer,
                                 prec="f32")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert (aux is None) == (layer == 0)


# ------------------------------------------------- (c) latent attention alone

@pytest.fixture(scope="module")
def attn_case():
    tk = share(1)
    p = share_of(full_layer(jax.random.PRNGKey(9), False), tk)
    h = jax.random.normal(jax.random.PRNGKey(10), (2, A + 1, D))
    return tk, p, h


def _attention(p, h, tk, dtype=jnp.float32):
    return np.asarray(tr.attention_part(p, h, tk, 1, dtype), np.float32)


def _by_hand(p, h, tk, *, scale=None, turn_nope=False):
    """Latent attention of ONE sequence in numpy float64, token by token
    and head by head: the rotation as 2 x 2 matrices on the pairs
    ``(2i, 2i + 1)``."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    n = h.shape[0]
    scale = scale or (NOPE + ROPE) ** -0.5

    def norm(x, w):
        return x / math.sqrt(float(np.mean(x * x)) + 1e-6) * w

    def turn(v, pos):
        out = v.copy()
        for i in range(len(v) // 2):
            ang = pos * tk.rope_theta ** (-2.0 * i / len(v))
            rot = np.array([[math.cos(ang), -math.sin(ang)],
                            [math.sin(ang), math.cos(ang)]])
            out[2 * i:2 * i + 2] = rot @ v[2 * i:2 * i + 2]
        return out
    u = np.stack([norm(h[i], p["input_norm"]) for i in range(n)])
    q = (u @ p["wq"]).reshape(n, 2, NOPE + ROPE)
    down = u @ p["wkv_a"]
    k_r = np.stack([turn(down[i, RANK:], i) for i in range(n)])  # ONE a token
    kv = np.stack([norm(down[i, :RANK], p["kv_norm"]) @ p["wkv_b"]
                   for i in range(n)]).reshape(n, 2, NOPE + VALUE)
    out = np.zeros((n, D))
    for i in range(n):
        heads = []
        for j in range(2):
            qn, qr = q[i, j, :NOPE], turn(q[i, j, NOPE:], i)
            if turn_nope:
                qn = turn(qn, i)
            s = np.array([qn @ (turn(kv[t, j, :NOPE], t) if turn_nope
                                else kv[t, j, :NOPE]) + qr @ k_r[t]
                          for t in range(i + 1)]) * scale
            w = np.exp(s - s.max())
            w = w / w.sum()
            heads.append(sum(w[t] * kv[t, j, NOPE:] for t in range(i + 1)))
        out[i] = np.concatenate(heads) @ p["wo"]
    return out


@pytest.mark.parametrize("what", [
    "one-rotary-key-for-all-heads", "interleaved-pairs-by-hand",
    "no-rotation-on-the-no-position-part", "scale-is-qk-head-dim",
    "value-width-is-not-the-query-width", "latent-norm-in-float32"])
def test_latent_attention(attn_case, what):
    tk, p, h = attn_case
    sp = tk.spec
    base = _attention(p, h, tk)
    hand = np.stack([_by_hand(p, np.asarray(s, np.float64), tk) for s in h])
    if what == "interleaved-pairs-by-hand":
        # the program's shifted-lane rotation against 2 x 2 rotations of
        # the pairs (2i, 2i + 1), and through the whole sublayer
        x = jax.random.normal(jax.random.PRNGKey(11), (2, 5, 3, 8))
        want = np.asarray(x, np.float64)
        for pos in range(5):
            for i in range(4):
                ang = pos * 50.0 ** (-2.0 * i / 8)
                rot = np.array([[math.cos(ang), -math.sin(ang)],
                                [math.sin(ang), math.cos(ang)]])
                pair = slice(2 * i, 2 * i + 2)
                want[:, pos, :, pair] = np.einsum(
                    "ab,shb->sha", rot, np.asarray(x)[:, pos, :, pair])
        np.testing.assert_allclose(tr.rope_pairs(x, 50.0), want, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(base, hand, rtol=2e-4, atol=2e-5)
        # the half-split pairing is another function of the same weights
        split = dataclasses.replace(tk, rope_interleave=False)
        assert np.abs(_attention(p, h, split) - base).max() > 1e-3
    elif what == "one-rotary-key-for-all-heads":
        # the rotary key has no head axis, and every head's logits move
        # when the ONE key's columns of W_kva move
        k_nope, k_rope, v = tr.latent_kv(
            p, ref.rms(p["input_norm"], h, 1e-6), sp, jnp.float32)
        assert k_rope.shape == (2, A + 1, ROPE)
        assert k_nope.shape == (2, A + 1, 2, NOPE)
        moved = dict(p, wkv_a=p["wkv_a"].at[:, RANK:].multiply(-1.0))
        x = ref.rms(p["input_norm"], h, 1e-6)
        ls = sp.layers[1]
        d = np.abs(np.asarray(
            tr.latent_scores(moved, x, sp, ls, jnp.float32)[0]
            - tr.latent_scores(p, x, sp, ls, jnp.float32)[0]))
        assert d.shape == (2, 2, 1, A + 1, A + 1)
        assert (d.max(axis=(0, 2, 3, 4)) > 1e-3).all()      # both heads
        np.testing.assert_allclose(base, hand, rtol=2e-4, atol=2e-5)
    elif what == "no-rotation-on-the-no-position-part":
        # with the rotary columns of W_q zeroed the logits carry no
        # position: theta is unread; with them, it binds
        flat = dict(p, wq=p["wq"].reshape(D, 2, NOPE + ROPE)
                    .at[:, :, NOPE:].set(0.0).reshape(D, -1))
        theta2 = dataclasses.replace(tk, rope_theta=7.0)
        np.testing.assert_array_equal(_attention(flat, h, theta2),
                                      _attention(flat, h, tk))
        assert np.abs(_attention(p, h, theta2) - base).max() > 1e-4
        wrong = np.stack([_by_hand(p, np.asarray(s, np.float64), tk,
                                   turn_nope=True) for s in h])
        assert np.abs(wrong - base).max() > 1e-3
    elif what == "scale-is-qk-head-dim":
        np.testing.assert_allclose(base, hand, rtol=2e-4, atol=2e-5)
        wrong = np.stack([_by_hand(p, np.asarray(s, np.float64), tk,
                                   scale=NOPE ** -0.5) for s in h])
        assert np.abs(wrong - base).max() > 1e-3
    elif what == "value-width-is-not-the-query-width":
        assert sp.value_dim == VALUE != sp.head_dim == NOPE + ROPE
        _, v = tr.latent_scores(p, ref.rms(p["input_norm"], h, 1e-6), sp,
                                sp.layers[1], jnp.float32)
        assert v.shape == (2, A + 1, 2, VALUE)
        assert p["wo"].shape == (2 * VALUE, D)
        np.testing.assert_allclose(base, hand, rtol=2e-4, atol=2e-5)
    else:
        # bf16 compute: the norm's statistics and scale stay float32. A
        # latent scaled by 2^12 would square past bfloat16's precision
        # (8 bits), not past float32's: the output is unmoved by the
        # scale, as RMSNorm says, to bf16 rounding of the products
        big = dict(p, wkv_a=p["wkv_a"].at[:, :RANK].multiply(4096.0))
        got = _attention(big, h, tk, jnp.bfloat16)
        np.testing.assert_allclose(got, _attention(p, h, tk, jnp.bfloat16),
                                   rtol=0.05, atol=0.02)
        cast = tr.cast_weights({"transformer": {"layer_1": p}},
                               jnp.bfloat16)["transformer"]["layer_1"]
        assert cast["kv_norm"].dtype == jnp.float32


def test_attention_one_precision_step_down_fails(exp, params, attn_case):
    """The attention sublayer computed at bfloat16 is outside the
    tolerances above — alone, and in the whole forward."""
    tk, p, h = attn_case
    want = ref.attention(p, ref.rms(p["input_norm"], h, 1e-6),
                         trunk=ref_sizes(tk), prec="f32")
    np.testing.assert_allclose(_attention(p, h, tk), want, rtol=2e-4,
                               atol=2e-5)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(_attention(p, h, tk, jnp.bfloat16), want,
                                   rtol=2e-4, atol=2e-5)
    obs = jax.random.normal(jax.random.PRNGKey(20), (2, A, A, 9))
    hid = 0.5 * jax.random.normal(jax.random.PRNGKey(21), (2, A, D))
    tk = exp.cfg.model.trunk
    want, _ = ref.agent_forward(params["agent"]["params"], obs, hid,
                                trunk=ref_sizes(tk))
    q32, _, _ = tr.agent_forward_trunk(params["agent"], obs, hid, tk=tk,
                                       dtype=jnp.float32)
    q16, _, _ = tr.agent_forward_trunk(params["agent"], obs, hid, tk=tk,
                                       dtype=jnp.bfloat16)
    np.testing.assert_allclose(q32, want, rtol=2e-4, atol=2e-5)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(q16, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------ (d) the router

@pytest.mark.parametrize("offset", [0, 5], ids=["share-0", "share-5"])
def test_router_at_the_published_top_6_of_128_scaled_2_448(offset):
    """``route`` unchanged serves this family: 128 sigmoid scores, the
    top-6 of score + bias, the unbiased kept scores over their sum times
    2.448 — against the reference's dense weights and ``lax.top_k``."""
    tk = DeepseekV3TrunkConfig(share_index=offset)
    assert (tk.spec.top_k, tk.spec.route_scale, tk.spec.experts) == (
        6, 2.448, 128)
    k = jax.random.split(jax.random.PRNGKey(30), 3)
    w = 0.3 * jax.random.normal(k[0], (D, 128))
    m = jax.random.normal(k[1], (24, D))
    bias = 0.5 * jax.random.normal(k[2], (128,))
    weights, idx = tr.route(w, m, tk, bias=bias)
    scores = jax.nn.sigmoid(m @ w)
    _, want_idx = jax.lax.top_k(scores + bias, 6)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.448, rtol=1e-5)
    assert not (np.asarray(idx) == np.argsort(-np.asarray(scores), -1)[:, :6]
                ).all()                        # the bias chose
    dense = ref.routing({"router": w, "expert_bias": bias}, m,
                        trunk=ref_sizes(tk))
    got = np.zeros((24, 128), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(weights), -1)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-7)
    per = tr.held_weights(weights, idx, tk)
    np.testing.assert_allclose(per, dense[:, 8 * offset:8 * offset + 8],
                               rtol=1e-5, atol=1e-7)
    g = jax.grad(lambda b: tr.route(w, m, tk, bias=b)[0].sum())(bias)
    assert not np.asarray(g).any()


# ------------------------------------------- (e) the configuration's files

#: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
#: as the catalog gives it
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}
HELD = {"num_hidden_layers": 5, "n_routed_experts": 8,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "vocab_size": 0}


def test_shipped_config_is_the_catalog_row_key_by_key():
    """configs/config10_trunk_kanana.yaml: every key the dataclass carries
    equals the published one (the depth is the held layers'), the
    dataclass's defaults are the shipped file, and the benchmark's file
    states every catalog key, the held counts under the keys ``reduced``
    lists."""
    tk = load_config(os.path.join(
        HERE, "configs", "config10_trunk_kanana.yaml")).model.trunk
    carried = {f.name for f in dataclasses.fields(tk)} & set(CATALOG)
    assert len(carried) == 31
    assert set(CATALOG) - carried == {"max_position_embeddings",
                                      "tie_word_embeddings", "vocab_size"}
    for key in carried - {"num_hidden_layers"}:
        assert getattr(tk, key) == CATALOG[key], key
    assert (tk.num_hidden_layers, tk.first_layer, tk.experts_held,
            tk.heads_held, tk.share_index, tk.attention_ways) == (
        5, 0, 8, 16, 0, 2)
    assert tk == DeepseekV3TrunkConfig()
    sp = tk.spec
    assert (sp.kv_latent, sp.qk_nope_dim, sp.qk_rope_dim, sp.value_dim,
            sp.head_dim, sp.rope_interleave) == (512, 128, 64, 128, 192, True)
    assert (sp.shared_width, sp.top_k, sp.route_scale, sp.router_scores,
            sp.router_bias, sp.route_norm, sp.expert_act) == (
        1536, 6, 2.448, "sigmoid", True, True, "silu")
    assert [ls.dense_width for ls in sp.layers] == [6144, 0, 0, 0, 0]
    assert all(ls.rope and not ls.window for ls in sp.layers)
    with open(os.path.join(HERE, "benchmark", "configs",
                           "agv16-kanana2-ep16.json")) as f:
        top = json.load(f)
    for key, value in CATALOG.items():
        assert top[key] == HELD.get(key, value), key
    assert set(HELD) <= set(top["reduced"])
    assert from_dict(top["config"]).model.trunk.spec == sp
    assert top["published"] == dict(
        {k: CATALOG[k] for k in HELD}, first_k_dense_replace=1)
    with open(os.path.join(HERE, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "agv16-kanana2-ep16")
    assert entry["reduced"] == top["reduced"]
    assert entry["source"].startswith(top["source"])


def test_config_round_trips_and_takes_dotted_overrides(tmp_path):
    cfg = make_cfg()
    assert isinstance(cfg.model.trunk, DeepseekV3TrunkConfig)
    assert from_dict(json.loads(json.dumps(dataclasses.asdict(cfg)))) == cfg
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dataclasses.asdict(cfg)))
    moved = load_config(str(path), ("model.trunk.share_index=3",
                                    "model.trunk.routed_scaling_factor=1.5",
                                    "model.trunk.rope_interleave=false"))
    assert moved.model.trunk.share_index == 3
    assert moved.model.trunk.expert_offset == 6
    assert moved.model.trunk.spec.route_scale == 1.5
    assert moved.model.trunk.spec.rope_interleave is False
    assert moved.model.trunk.q_lora_rank is None


@pytest.mark.parametrize("trunk", [
    {"heads_held": 3}, {"experts_held": 3}, {"share_index": 4},
    {"n_group": 2}, {"topk_group": 2}, {"q_lora_rank": 16},
    {"rope_scaling": {"type": "yarn", "factor": 4.0}},
    {"first_k_dense_replace": 3}, {"first_k_dense_replace": 9},
    {"scoring_func": "softmax"}, {"topk_method": "greedy"},
    {"attention_bias": True}, {"moe_layer_freq": 2},
    {"qk_head_dim": 12}, {"qk_rope_head_dim": 3, "qk_head_dim": 9},
    {"no_such_key": 1}],
    ids=lambda t: "-".join(f"{k}={v}" for k, v in t.items())[:40])
def test_sanity_check_refuses(trunk):
    """A share that does not divide the published counts; group-limited
    selection; a query latent; a RoPE scaling; ``first_k_dense_replace``
    past the held layers (none would route); another score function or
    selection; attention biases; expert layers that alternate; a
    ``qk_head_dim`` that is not the two parts' sum; an odd rotary width;
    an unknown key."""
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
    """``train_info_zeros`` mirrors ``train``'s info, counters included;
    the step leaves every ``expert_bias`` where it was and moves the
    latent's three leaves."""
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
        for leaf in ("router", "wkv_a", "kv_norm", "wkv_b"):
            assert np.abs(np.asarray(after[name][leaf]
                                     - before[name][leaf])).max() > 0, leaf


# ------------------ the shipped programs lower as they did at the pin

#: sha256[:16] of the StableHLO of the acting forward and of the
#: learner's loss gradient under configs/config10_trunk_kanana.yaml
#: (``tests/test_trunk_afmoe.py`` holds the two older families' pins,
#: which the PR that wrote this family left as they were), taken on the
#: tree of PR 33
KANANA_LOWERING = {"forward": "488b02111fcece49", "loss": "2b5fc1e4034aaffc"}


@pytest.fixture(scope="module")
def kanana():
    return _shipped("config10_trunk_kanana.yaml")


@pytest.mark.parametrize("program", ["forward", "loss"])
def test_kanana_lowering_is_pinned(kanana, program):
    lowered = _lowered(kanana, program)
    assert _digest(lowered) == KANANA_LOWERING[program]
    if program == "forward":
        # the scope this family adds, inside agent.attention
        debug = lowered.as_text(debug_info=True)
        assert "agent.attention/agent.latent" in debug
