"""Sequence-parallel attention (ring + Ulysses) vs dense reference on the
virtual 8-device mesh (SURVEY.md §4(5): distributed without a cluster)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from t2omca_tpu.parallel import make_mesh
from t2omca_tpu.parallel.ring_attention import (ring_attention,
                                                ulysses_attention)


def _dense(q, k, v):
    logits = jnp.einsum("...qd,...kd->...qk", q, k)
    return jnp.einsum("...qk,...kd->...qd",
                      jax.nn.softmax(logits, axis=-1), v)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8, axis_names=("sp",))


def test_ring_attention_matches_dense(mesh):
    b, t, d = 2, 32, 16                      # 32 tokens → 4 per device
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, d))
    k = jax.random.normal(ks[1], (b, t, d))
    v = jax.random.normal(ks[2], (b, t, d))

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None),) * 3,
        out_specs=P(None, "sp", None))
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(q, k, v)),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_with_head_batch(mesh):
    """Extra leading axes (batch, heads) broadcast through the ring."""
    b, h, t, d = 2, 3, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(q, k, v)),
                               atol=1e-5, rtol=1e-5)


def test_ulysses_attention_matches_dense(mesh):
    b, t, h, d = 2, 16, 8, 4                 # 8 heads / 8 devices
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    v = jax.random.normal(ks[2], (b, t, h, d))

    uly = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None))
    out = jax.jit(uly)(q, k, v)

    # dense reference over (b, h, t, d)
    qd, kd, vd = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    ref = _dense(qd, kd, vd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_grad_flows(mesh):
    """The online-softmax ring is differentiable (needed if SP ever spans
    the learner's entity axis)."""
    b, t, d = 1, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, t, d))
    k = jax.random.normal(ks[1], (b, t, d))
    v = jax.random.normal(ks[2], (b, t, d))
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None),) * 3,
        out_specs=P(None, "sp", None))

    g = jax.grad(lambda q: jax.jit(ring)(q, k, v).sum())(q)
    g_ref = jax.grad(lambda q: _dense(q, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_ring_attention_kv_mask_matches_dense(mesh):
    """Padded key positions (global token count not a multiple of the axis
    size) must be excluded from every softmax."""
    b, t_real, d = 2, 13, 8
    tp = 16                                   # padded to 8 devices x 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, tp, d))
    k = jax.random.normal(ks[1], (b, tp, d))
    v = jax.random.normal(ks[2], (b, tp, d))
    valid = jnp.arange(tp) < t_real
    kv_mask = jnp.broadcast_to(valid[None], (b, tp))

    ring = shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, "sp", m),
        mesh=mesh,
        in_specs=(P(None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, "sp", None))
    out = jax.jit(ring)(q, k, v, kv_mask)

    dense = _dense(q[:, :t_real], k[:, :t_real], v[:, :t_real])
    np.testing.assert_allclose(np.asarray(out[:, :t_real]),
                               np.asarray(dense), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("gate", [False, True])
def test_sp_mixer_matches_dense_mixer(mesh, gate):
    """mixer_apply_sp (token axis sharded over 8 devices, ring attention)
    must reproduce TransformerMixer.apply exactly — the config-5 consumer
    of the SP layer (SURVEY.md §2.2 extension point). Parametrized over
    zero_init_gate so the SP readout honors the gate param when present
    (gate value perturbed off its 0-init below to make the check real)."""
    from t2omca_tpu.models.mixer import TransformerMixer
    from t2omca_tpu.parallel.sp_mixer import mixer_apply_sp

    a, n_ent, feat, emb = 5, 5, 8, 16
    mixer = TransformerMixer(n_agents=a, n_entities=n_ent, feat_dim=feat,
                             emb=emb, heads=2, depth=2,
                             state_entity_mode=True, zero_init_gate=gate)
    b = 3
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    qvals = jax.random.normal(ks[0], (b, 1, a))
    hidden = jax.random.normal(ks[1], (b, a, emb))
    hyper = jax.random.normal(ks[2], (b, 3, emb))
    states = jax.random.normal(ks[3], (b, n_ent * feat))
    obs = jax.random.normal(ks[4], (b, a, 8))
    params = mixer.init(ks[5], qvals, hidden, hyper, states, obs)
    if gate:   # open the gate so equality is a non-trivial check
        params = jax.tree.map(lambda x: x, params)
        params["params"]["out_gate"] = jnp.full((1,), 0.7)

    y_dense, hyp_dense = mixer.apply(params, qvals, hidden, hyper, states,
                                     obs)
    y_sp, hyp_sp = jax.jit(
        lambda p, q_, h_, hy, s_, o_: mixer_apply_sp(
            mixer, p, q_, h_, hy, s_, o_, mesh))(
        params, qvals, hidden, hyper, states, obs)

    np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_dense),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hyp_sp), np.asarray(hyp_dense),
                               atol=1e-4, rtol=1e-4)


def test_sp_mixer_monotonic_and_q12(mesh):
    """Q12 fallback (obs entities) + monotonicity survive the SP path."""
    from t2omca_tpu.models.mixer import TransformerMixer
    from t2omca_tpu.parallel.sp_mixer import mixer_apply_sp

    a, feat, emb = 4, 6, 8
    mixer = TransformerMixer(n_agents=a, n_entities=1, feat_dim=feat,
                             emb=emb, heads=2, depth=1,
                             state_entity_mode=False)
    b = 2
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    qvals = jax.random.normal(ks[0], (b, 1, a))
    hidden = jax.random.normal(ks[1], (b, a, emb))
    hyper = jax.random.normal(ks[2], (b, 3, emb))
    states = jax.random.normal(ks[3], (b, 4))
    obs = jax.random.normal(ks[4], (b, a, feat))
    params = mixer.init(ks[5], qvals, hidden, hyper, states, obs)

    y_dense, _ = mixer.apply(params, qvals, hidden, hyper, states, obs)
    def sp(qv):
        y, _ = mixer_apply_sp(mixer, params, qv, hidden, hyper, states,
                              obs, mesh)
        return y
    np.testing.assert_allclose(np.asarray(sp(qvals)), np.asarray(y_dense),
                               atol=1e-4, rtol=1e-4)
    g = jax.grad(lambda qv: sp(qv).sum())(qvals)
    assert (np.asarray(g) >= 0).all()


@pytest.mark.slow   # SP backward compile (~18 s); SP forward equivalence stays in-gate
def test_sp_mixer_param_grads_finite_with_padding(mesh):
    """Gradients through the masked ring attention must stay finite even
    when a device's whole key block is padding (double-where NaN guard)."""
    from t2omca_tpu.models.mixer import TransformerMixer
    from t2omca_tpu.parallel.sp_mixer import mixer_apply_sp

    a, n_ent, feat, emb = 5, 5, 8, 16   # 13 tokens -> pad 16, last block all-pad
    mixer = TransformerMixer(n_agents=a, n_entities=n_ent, feat_dim=feat,
                             emb=emb, heads=2, depth=1,
                             state_entity_mode=True)
    b = 2
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    qvals = jax.random.normal(ks[0], (b, 1, a))
    hidden = jax.random.normal(ks[1], (b, a, emb))
    hyper = jax.random.normal(ks[2], (b, 3, emb))
    states = jax.random.normal(ks[3], (b, n_ent * feat))
    obs = jax.random.normal(ks[4], (b, a, 8))
    params = mixer.init(ks[5], qvals, hidden, hyper, states, obs)

    def loss(p):
        y, _ = mixer_apply_sp(mixer, p, qvals, hidden, hyper, states, obs,
                              mesh)
        return (y ** 2).sum()

    g = jax.grad(loss)(params)
    leaves = jax.tree.leaves(g)
    assert leaves and all(np.isfinite(np.asarray(x)).all() for x in leaves)
