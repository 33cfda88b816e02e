"""Coverage for the rollout hot-path kernel layer (t2omca_tpu/kernels/,
docs/PERF.md): the Pallas fused attention kernel vs the einsum path, the
single-scatter time-major ring insert, and the bf16 acting-dtype mode —
the PR-9 parity contracts the CPU tier-1 gate pins.

The pallas kernel runs in interpreter mode here (tests/conftest.py sets
it), so every assertion below holds for the exact kernel body that
lowers to Mosaic on a real chip (tests/test_mosaic_compile.py compiles
it for one)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.config import (EnvConfig, KernelsConfig, ModelConfig,
                               ReplayConfig, TrainConfig, from_dict,
                               sanity_check)
from t2omca_tpu.kernels.attention import (NEG_MASK_VALUE,
                                          _reference_attention,
                                          flash_attention)
from t2omca_tpu.models.transformer import MultiHeadAttention


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _mask_bias(mask):
    return None if mask is None else jnp.where(mask, 0.0, NEG_MASK_VALUE)


# ------------------------------------------------------- kernel vs einsum

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_matches_einsum_f32(causal, masked):
    """f32 parity: online softmax vs max-subtracted softmax is the same
    math under a different association — per-element error must sit at
    float-reassociation scale, orders below any training tolerance."""
    rng = np.random.default_rng(0)
    b, h, t, d = 2, 3, 9, 16
    q, k, v = (_rand(rng, (b, h, t, d)) for _ in range(3))
    mask = jnp.asarray(rng.random((b, 1, t, t)) > 0.3) if masked else None
    out = flash_attention(q, k, v, mask=mask, causal=causal)
    ref = _reference_attention(q, k, v, _mask_bias(mask), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=2e-6)


def test_flash_odd_shapes_padding():
    """Token/head dims that don't divide the tile sizes exercise the
    pad-and-mask tail path (t_q=5, t_k=7, d=12 — none tile-aligned)."""
    rng = np.random.default_rng(1)
    q = _rand(rng, (2, 2, 5, 12))
    k = _rand(rng, (2, 2, 7, 12))
    v = _rand(rng, (2, 2, 7, 12))
    mask = jnp.asarray(rng.random((2, 1, 5, 7)) > 0.4)
    out = flash_attention(q, k, v, mask=mask)
    ref = _reference_attention(q, k, v, _mask_bias(mask), False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=2e-6)


def test_flash_small_blocks_multi_tile():
    """Explicit tiny tiles force a real multi-block online-softmax pass
    (several k-block iterations carrying the running max/denominator)."""
    rng = np.random.default_rng(2)
    q, k, v = (_rand(rng, (1, 2, 40, 8)) for _ in range(3))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = _reference_attention(q, k, v, None, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=2e-6)


def test_flash_bf16_within_tolerance():
    """bf16 inputs, f32 accumulators: the kernel is *better*-conditioned
    than the einsum bf16 path (which softmaxes in bf16), so comparing
    against the f32 reference bounds both."""
    rng = np.random.default_rng(3)
    q, k, v = (_rand(rng, (2, 2, 17, 8), jnp.bfloat16) for _ in range(3))
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = _reference_attention(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), None, False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.02)


def test_flash_fully_masked_row_matches_einsum_degenerate():
    """All-masked rows degrade to the einsum path's uniform distribution
    (replacement semantics — an additive bias would silently cancel)."""
    rng = np.random.default_rng(4)
    q, k, v = (_rand(rng, (1, 1, 4, 8)) for _ in range(3))
    mask = jnp.ones((1, 1, 4, 4), bool).at[0, 0, 2].set(False)
    out = flash_attention(q, k, v, mask=mask)
    ref = _reference_attention(q, k, v, _mask_bias(mask), False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=2e-6)
    # the degenerate row really is the uniform mean of V
    np.testing.assert_allclose(np.asarray(out)[0, 0, 2],
                               np.asarray(v).mean(axis=2)[0, 0],
                               rtol=1e-5, atol=1e-5)


def _grad_pair(q, k, v, mask, causal, **kw):
    """(flash grads, einsum-reference grads) for a sum-of-squares loss —
    the flash side runs the PR 13 backward kernels (P recomputed in
    VMEM from the saved m/l residuals), the reference side is
    ``jax.grad`` through the einsum path."""
    bias = _mask_bias(mask)

    def loss_p(q, k, v):
        return (flash_attention(q, k, v, mask=mask, causal=causal,
                                **kw).astype(jnp.float32) ** 2).sum()

    def loss_r(q, k, v):
        return (_reference_attention(
            q, k, v, bias, causal).astype(jnp.float32) ** 2).sum()

    return (jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v),
            jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_backward_matches_einsum_f32(causal, masked):
    """The flash backward kernels must yield the einsum VJP's gradients
    at the same inputs to float-reassociation scale — the learner
    unrolls train straight through the kernel (mask-replacement and
    causal cotangent-zeroing semantics identical)."""
    rng = np.random.default_rng(5)
    q, k, v = (_rand(rng, (2, 2, 7, 8)) for _ in range(3))
    mask = jnp.asarray(rng.random((2, 1, 7, 7)) > 0.3) if masked else None
    gp, gr = _grad_pair(q, k, v, mask, causal)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_backward_pad_tails_and_per_head_mask():
    """Backward with explicit tiny blocks over non-dividing token counts
    (t_q=5, t_k=7 at 4-blocks): the recomputed P tiles carry real pad
    columns/rows whose cotangents must vanish exactly; the (B, H, ...)
    per-head mask exercises the backward's head-indexed bias specs."""
    rng = np.random.default_rng(6)
    q = _rand(rng, (2, 2, 5, 12))
    k = _rand(rng, (2, 2, 7, 12))
    v = _rand(rng, (2, 2, 7, 12))
    mask = jnp.asarray(rng.random((2, 2, 5, 7)) > 0.4)   # per-head
    gp, gr = _grad_pair(q, k, v, mask, False, block_q=4, block_k=4)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_backward_multi_k_block():
    """Several key blocks per query block: the backward's inner loop
    recomputes MULTIPLE P tiles against one residual pair — the case
    where a fused-lse residual (m + log l) or a per-block renormalize
    bug would surface."""
    rng = np.random.default_rng(7)
    q, k, v = (_rand(rng, (1, 2, 40, 8)) for _ in range(3))
    gp, gr = _grad_pair(q, k, v, None, False, block_q=16, block_k=16)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_backward_all_masked_row():
    """All-masked rows: the forward degrades to uniform-over-keys, and
    the einsum VJP still routes cotangent into V through those uniform
    weights while zeroing dQ/dK (every logit was replaced). The m/l
    residuals are kept SEPARATE precisely so the backward's recomputed
    P survives this case in f32 (m = −1e9 swallows log l)."""
    rng = np.random.default_rng(8)
    q, k, v = (_rand(rng, (1, 1, 4, 8)) for _ in range(3))
    mask = jnp.ones((1, 1, 4, 4), bool).at[0, 0, 2].set(False)
    gp, gr = _grad_pair(q, k, v, mask, False)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    # the masked row's uniform weights really do carry dV
    assert float(jnp.abs(gp[2]).max()) > 0.0
    # ... and its dq is exactly zero (all logits were replaced)
    assert float(jnp.abs(np.asarray(gp[0])[0, 0, 2]).max()) == 0.0


def test_flash_backward_bf16_within_tolerance():
    """bf16 inputs: backward recompute + accumulation stay f32 inside
    the kernels, so gradients sit within the established bf16 ULP
    tolerance of the f32 einsum reference."""
    rng = np.random.default_rng(9)
    q, k, v = (_rand(rng, (2, 2, 17, 8), jnp.bfloat16) for _ in range(3))
    gp, _ = _grad_pair(q, k, v, None, False)
    gr32 = jax.grad(
        lambda a, b, c: (_reference_attention(a, b, c, None, False)
                         ** 2).sum(), argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32))
    for a, b in zip(gp, gr32):
        assert a.dtype == jnp.bfloat16
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=0.05,
                                   atol=0.02 * max(scale, 1.0))


# ------------------------------------------------- module-level switch

@pytest.mark.parametrize("standard_heads", [False, True])
def test_mha_pallas_matches_xla(standard_heads):
    """MultiHeadAttention(attn_impl=pallas) == the einsum module over
    the SAME params — both the Q1 full-emb and standard head
    geometries."""
    rng = np.random.default_rng(6)
    x = _rand(rng, (3, 7, 16))
    kw = dict(emb=16, heads=2, standard_heads=standard_heads)
    mx = MultiHeadAttention(**kw)
    mp = MultiHeadAttention(**kw, attn_impl="pallas")
    params = mx.init(jax.random.PRNGKey(0), x, x)
    np.testing.assert_allclose(np.asarray(mx.apply(params, x, x)),
                               np.asarray(mp.apply(params, x, x)),
                               rtol=1e-5, atol=1e-5)


def test_mha_rejects_unknown_impl():
    x = jnp.zeros((1, 2, 8))
    m = MultiHeadAttention(emb=8, heads=2, attn_impl="cuda")
    with pytest.raises(AssertionError):
        m.init(jax.random.PRNGKey(0), x, x)


# ------------------------------------------------------- config plumbing

def test_kernels_config_sanity_and_merge():
    cfg = sanity_check(TrainConfig(kernels=KernelsConfig(
        attention="pallas")))
    assert cfg.kernels.attention == "pallas"
    with pytest.raises(ValueError, match="kernels.attention"):
        sanity_check(TrainConfig(kernels=KernelsConfig(attention="cuda")))
    # nested-dict + flat-key routing, and the meta.json roundtrip
    cfg = from_dict({"kernels": {"attention": "pallas"},
                     "model": {"act_dtype": "bfloat16"}})
    assert cfg.kernels.attention == "pallas"
    assert cfg.model.act_dtype == "bfloat16"
    rt = from_dict(dataclasses.asdict(cfg))
    assert rt.kernels.attention == "pallas"


def test_act_dtype_sanity():
    with pytest.raises(ValueError, match="act_dtype"):
        sanity_check(TrainConfig(model=ModelConfig(act_dtype="float16")))


# ----------------------------------------- integration (tiny Experiment)

def _tiny_cfg(**kw):
    model_kw = kw.pop("model", {})
    return sanity_check(TrainConfig(
        batch_size_run=2, batch_size=2,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=4),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1, **model_kw),
        replay=ReplayConfig(buffer_size=8), **kw))


@pytest.fixture(scope="module")
def tiny_exp():
    from t2omca_tpu.run import Experiment
    exp = Experiment.build(_tiny_cfg())
    ts = exp.init_train_state(0)
    rs, tm, _ = exp.runner.run_raw(ts.learner.params["agent"], ts.runner)
    return exp, ts, tm


def test_single_scatter_insert_bit_identical(tiny_exp):
    """insert_time_major (ONE combined-index scatter per leaf) must stay
    bit-identical to insert_episode_batch(to_batch()) — including across
    ring wraparound, where the slot set is non-contiguous."""
    exp, _, tm = tiny_exp
    buf = exp.buffer
    st = buf.init()
    for _ in range(5):                  # 10 episodes through capacity 8
        a = buf.insert_time_major(st, tm)
        b = buf.insert_episode_batch(st, tm.to_batch())
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert (np.asarray(la) == np.asarray(lb)).all()
        st = a
    assert int(st.episodes_in_buffer) == buf.capacity


def test_acting_default_bit_identical_to_train_forward(tiny_exp):
    """act_dtype unset: the acting fold + acting=True forward must be
    bit-identical to the training-path forward (the serving f32 parity
    contract rides on this)."""
    exp, ts, _ = tiny_exp
    mac = exp.mac
    p = ts.learner.params["agent"]
    rng = np.random.default_rng(7)
    obs = _rand(rng, (2, mac.n_agents, exp.env.obs_dim))
    hid = mac.init_hidden(2)
    fp = mac.prepare_acting_params(p)
    q_act, h_act = mac.forward_qslice(fp, obs, hid, acting=True)
    q_tr, h_tr = mac.forward_qslice(fp, obs, hid, acting=False)
    assert (np.asarray(q_act) == np.asarray(q_tr)).all()
    assert (np.asarray(h_act) == np.asarray(h_tr)).all()


def test_bf16_acting_within_tolerance(tiny_exp):
    """model.act_dtype=bfloat16 over an f32 train dtype: acting q-values
    stay within the established bf16 tolerance of the f32 path, greedy
    actions agree, and the TRAIN-path forward is untouched (bit-equal
    params/unroll dtype)."""
    from t2omca_tpu.run import Experiment
    exp32, ts, _ = tiny_exp
    expb = Experiment.build(_tiny_cfg(model={"act_dtype": "bfloat16"}))
    mac32, macb = exp32.mac, expb.mac
    assert macb.act_agent is None or macb.act_agent.dtype == jnp.bfloat16
    p = ts.learner.params["agent"]
    rng = np.random.default_rng(8)
    obs = _rand(rng, (2, mac32.n_agents, exp32.env.obs_dim))
    hid = mac32.init_hidden(2)
    avail = jnp.ones((2, mac32.n_agents, mac32.n_actions))

    fp32 = mac32.prepare_acting_params(p)
    fpb = macb.prepare_acting_params(p)
    # the acting fold really is bf16 (params halved per scan step)
    assert fpb["tf"]["blocks"][0]["wqk"].dtype == jnp.bfloat16
    q32, _ = mac32.forward_qslice(fp32, obs, hid, acting=True)
    qb, _ = macb.forward_qslice(fpb, obs, hid, acting=True)
    np.testing.assert_allclose(np.asarray(qb), np.asarray(q32),
                               rtol=0.05, atol=0.05)
    a32, _, _ = mac32.select_actions(fp32, obs, avail, hid,
                                     jax.random.PRNGKey(0), jnp.asarray(0),
                                     test_mode=True)
    ab, _, _ = macb.select_actions(fpb, obs, avail, hid,
                                   jax.random.PRNGKey(0), jnp.asarray(0),
                                   test_mode=True)
    assert (np.asarray(a32) == np.asarray(ab)).mean() > 0.9
    # train path untouched: learner-side forward ignores act_dtype
    qt32, _ = mac32.forward_qslice(p, obs, hid)
    qtb, _ = macb.forward_qslice(p, obs, hid)
    assert (np.asarray(qt32) == np.asarray(qtb)).all()


def test_bf16_acting_dense_path_uses_act_agent():
    """The DENSE acting path under act_dtype=bfloat16: BasicMAC.forward
    (acting=True) must route through the bf16 act_agent module clone,
    produce q within the bf16 tolerance of the f32 module, and leave
    the train-path forward (acting=False) bit-identical."""
    from t2omca_tpu.run import Experiment
    exp32 = Experiment.build(_tiny_cfg(model={"use_qslice": False}))
    expb = Experiment.build(_tiny_cfg(model={"use_qslice": False,
                                             "act_dtype": "bfloat16"}))
    mac32, macb = exp32.mac, expb.mac
    assert macb.act_agent is not None
    assert macb.act_agent.dtype == jnp.bfloat16
    assert macb.agent.dtype == jnp.float32      # train module untouched
    ts = exp32.init_train_state(0)
    p = ts.learner.params["agent"]
    rng = np.random.default_rng(9)
    obs = _rand(rng, (2, mac32.n_agents, exp32.env.obs_dim))
    hid = mac32.init_hidden(2)
    # dense path: prepare_acting_params pre-casts the raw tree
    pb = macb.prepare_acting_params(p)
    assert jax.tree.leaves(pb)[0].dtype == jnp.bfloat16
    q32, h32 = mac32.forward(p, obs, hid, acting=True)
    qb, hb = macb.forward(pb, obs, hid, acting=True)
    np.testing.assert_allclose(np.asarray(qb), np.asarray(q32),
                               rtol=0.05, atol=0.05)
    # train-path forward ignores act_dtype AND the acting clone
    qt32, _ = mac32.forward(p, obs, hid)
    qtb, _ = macb.forward(p, obs, hid)
    assert (np.asarray(qt32) == np.asarray(qtb)).all()
    # the full select_actions greedy path agrees across dtypes
    avail = jnp.ones((2, mac32.n_agents, mac32.n_actions))
    a32, _, _ = mac32.select_actions(
        mac32.prepare_acting_params(p), obs, avail, hid,
        jax.random.PRNGKey(0), jnp.asarray(0), test_mode=True)
    ab, _, _ = macb.select_actions(pb, obs, avail, hid,
                                   jax.random.PRNGKey(0), jnp.asarray(0),
                                   test_mode=True)
    assert (np.asarray(a32) == np.asarray(ab)).mean() > 0.9


def test_export_fold_stays_train_dtype_under_act_dtype():
    """The serving exporter folds at the TRAIN dtype even when the
    training config sets act_dtype=bfloat16 — the artifact's canonical
    f32 variant must never silently contain bf16 leaves
    (serve/export.py f32 bit-parity contract)."""
    from t2omca_tpu.run import Experiment
    expb = Experiment.build(_tiny_cfg(model={"act_dtype": "bfloat16"}))
    ts = expb.init_train_state(0)
    p = ts.learner.params["agent"]
    folded = expb.mac.prepare_acting_params(p, dtype=expb.mac.agent.dtype)
    for leaf in jax.tree.leaves(folded):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype


@pytest.mark.slow    # full rollout jit x2 (dense acting, ~40 s on 2 cores)
def test_dense_rollout_pallas_matches_xla():
    """End-to-end: the dense-acting rollout under kernels.attention=
    pallas selects bit-identical actions to the einsum path at f32 (the
    selector argmax absorbs reassociation-scale q differences), so the
    env stream — and therefore the whole episode batch — matches."""
    from t2omca_tpu.run import Experiment
    outs = {}
    for mode in ("xla", "pallas"):
        exp = Experiment.build(_tiny_cfg(
            model={"use_qslice": False},
            kernels=KernelsConfig(attention=mode)))
        ts = exp.init_train_state(0)
        _, batch, stats = exp.runner.run(ts.learner.params["agent"],
                                         ts.runner)
        outs[mode] = (batch, stats)
    bx, sx = outs["xla"]
    bp, sp = outs["pallas"]
    assert (np.asarray(bx.actions) == np.asarray(bp.actions)).all()
    np.testing.assert_allclose(np.asarray(sx.episode_return),
                               np.asarray(sp.episode_return),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------- learner-unroll threading (PR 13)

def test_transformer_rows_pallas_matches_xla_fwd_and_grad():
    """The qslice sliced attention under attn_impl=pallas (one flash
    call over the R·H query rows, k0 as keys AND values) must match the
    einsum branch — forward and gradients — at f32: this is the exact
    lowering the learner unrolls dispatch under kernels.attention:
    pallas."""
    from t2omca_tpu.models.transformer import Transformer
    from t2omca_tpu.ops.query_slice import (fold_transformer,
                                            transformer_rows)
    rng = np.random.default_rng(10)
    emb, heads, depth = 16, 2, 2
    tf = Transformer(emb=emb, heads=heads, depth=depth)
    k0 = _rand(rng, (3, 9, emb))
    params = tf.init(jax.random.PRNGKey(0), k0, k0)

    def rows(p, impl):
        folded = fold_transformer(p["params"], emb=emb, heads=heads,
                                  head_dim=emb, depth=depth,
                                  dtype=jnp.float32)
        out = transformer_rows(folded, k0, k0[:, -4:, :], emb=emb,
                               heads=heads, depth=depth,
                               attn_impl=impl)
        return out

    ox = rows(params, "xla")
    op = rows(params, "pallas")
    np.testing.assert_allclose(np.asarray(op), np.asarray(ox),
                               rtol=1e-5, atol=1e-5)

    gx = jax.grad(lambda p: (rows(p, "xla") ** 2).sum())(params)
    gp = jax.grad(lambda p: (rows(p, "pallas") ** 2).sum())(params)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gx)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_acting_and_serving_ignore_pallas_mode(tiny_exp):
    """The kernel switch must land ONLY on the learner unroll: the
    qslice acting forward (select_actions path) and the default
    forward_qslice (serving's serve_step calls it with no attn_impl)
    stay bit-identical between kernel modes — the serving artifact's
    lowering can never depend on a training-run perf knob."""
    from t2omca_tpu.run import Experiment
    exp32, ts, _ = tiny_exp
    expp = Experiment.build(_tiny_cfg(kernels=KernelsConfig(
        attention="pallas")))
    p = ts.learner.params["agent"]
    rng = np.random.default_rng(11)
    obs = _rand(rng, (2, exp32.mac.n_agents, exp32.env.obs_dim))
    hid = exp32.mac.init_hidden(2)
    for acting in (True, False):
        qx, _ = exp32.mac.forward_qslice(p, obs, hid, acting=acting)
        qp, _ = expp.mac.forward_qslice(p, obs, hid, acting=acting)
        assert (np.asarray(qx) == np.asarray(qp)).all()


@pytest.mark.slow   # two Experiment builds + a train step each (~40 s)
def test_qslice_train_step_pallas_matches_xla():
    """End-to-end learner parity on the qslice path (the audit config's
    shape): one train step under kernels.attention=pallas — agent AND
    mixer unrolls lowering through the flash forward + backward kernels
    — matches the einsum mode's loss exactly at f32 display precision
    and its gradients/updated params to reassociation scale."""
    from t2omca_tpu.run import Experiment
    outs = {}
    for mode in ("xla", "pallas"):
        exp = Experiment.build(_tiny_cfg(
            kernels=KernelsConfig(attention=mode)))
        assert exp.mac.use_qslice
        ts = exp.init_train_state(0)
        _, batch, _ = exp.runner.run(ts.learner.params["agent"],
                                     ts.runner)
        small = jax.tree.map(lambda x: x[:2], batch)
        ls, info = exp.learner.train(ts.learner, small, jnp.ones((2,)),
                                     jnp.asarray(0), jnp.asarray(0))
        outs[mode] = (ls, info)
    ix, ip = outs["xla"][1], outs["pallas"][1]
    np.testing.assert_allclose(float(ip["loss"]), float(ix["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(ip["grad_norm"]),
                               float(ix["grad_norm"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(outs["pallas"][0].params),
                    jax.tree.leaves(outs["xla"][0].params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.slow   # two dense Experiment builds + train compiles (~60 s)
def test_dense_train_step_grads_pallas_matches_xla():
    """E2E DENSE train-step grad parity (the ISSUE 13 pin): with the
    qslice fast path off, the learner unroll runs MultiHeadAttention —
    under pallas mode its custom VJP is now the flash backward, and one
    full QMIX update (agent + mixer, online + target unrolls) must
    reproduce the einsum mode's loss and gradient norm."""
    from t2omca_tpu.run import Experiment
    outs = {}
    for mode in ("xla", "pallas"):
        exp = Experiment.build(_tiny_cfg(
            model={"use_qslice": False},
            kernels=KernelsConfig(attention=mode)))
        ts = exp.init_train_state(0)
        _, batch, _ = exp.runner.run(ts.learner.params["agent"],
                                     ts.runner)
        small = jax.tree.map(lambda x: x[:2], batch)
        _, info = exp.learner.train(ts.learner, small, jnp.ones((2,)),
                                    jnp.asarray(0), jnp.asarray(0))
        outs[mode] = info
    np.testing.assert_allclose(float(outs["pallas"]["loss"]),
                               float(outs["xla"]["loss"]), rtol=1e-6)
    np.testing.assert_allclose(float(outs["pallas"]["grad_norm"]),
                               float(outs["xla"]["grad_norm"]),
                               rtol=1e-4)


@pytest.mark.slow   # full pallas-mode superstep compile (~60 s)
@pytest.mark.analysis
def test_pallas_superstep_compile_budget():
    """The pallas-mode fused superstep compiles exactly ONCE across
    repeated dispatches — the flash kernels (forward-with-residuals +
    the two backward programs, all behind lru-cached custom_vjp builds)
    must not defeat jit caching with fresh callable identities per
    trace."""
    from t2omca_tpu.analysis import compile_budget
    from t2omca_tpu.run import Experiment
    cfg = _tiny_cfg(kernels=KernelsConfig(attention="pallas"),
                    superstep=2)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    superstep = exp.superstep_program(2)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    with compile_budget(1, match="_superstep") as log:
        for i in range(3):
            ts, stats, infos = superstep(ts, keys,
                                         jnp.asarray(i * 16, jnp.int32))
    assert log.count == 1
    assert np.isfinite(
        np.asarray(jax.device_get(stats.episode_return))).all()


# ------------------------------------------ acting's entity-table attention

def _entity_block_inputs(b, a, emb, heads, dtype, visibility, seed=0):
    """One head-width block's inputs as ``agent_forward_qslice_entity``
    hands them to ``_entity_attention_heads``: the folded kernels of a
    random block, query rows, hidden tokens, feature tables, the is-self
    ``1 / std`` and the same-MEC visibility."""
    from t2omca_tpu.ops.query_slice import _fold_entity_heads
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    n = lambda shape, scale: jax.random.normal(next(ks), shape) * scale
    at = {"toqueries": {"kernel": n((emb, emb), emb ** -0.5)},
          "tokeys": {"kernel": n((emb, emb), emb ** -0.5)},
          "tovalues": {"kernel": n((emb, emb), emb ** -0.5)},
          "unifyheads": {"kernel": n((emb, emb), emb ** -0.5),
                         "bias": n((emb,), 0.1)}}
    p = {"feat_embedding": {"kernel": n((9, emb), 0.3),
                            "bias": n((emb,), 0.1)},
         "transformer": {"block_0": {"attention": at}}}
    hp = _fold_entity_heads(p, head_dim=emb // heads, depth=1,
                            dtype=dtype)[0]
    x0 = n((b, a, emb), 1.0).astype(dtype)
    h_tok = n((b, a, emb), 1.0).astype(dtype)
    feats = n((b, 2 * a, 9), 1.0).astype(dtype)
    inv_self = 1.0 / (jax.random.uniform(next(ks), (b, a, 1)) * 0.3 + 0.05)
    mec = {"one-mec": jnp.zeros((b, a), jnp.int32),
           "alone": jnp.broadcast_to(jnp.arange(a), (b, a)),
           "random": jax.random.randint(next(ks), (b, a), 0, 4)}.get(
               visibility)
    if visibility == "padded":
        # the env's padded agents: each a negative mec_index of its own
        mec = jnp.where(jnp.arange(a) >= a - 3, -1 - jnp.arange(a),
                        jax.random.randint(next(ks), (b, a), 0, 3))
    same_mec = mec[:, :, None] == mec[:, None, :]
    return hp, x0, h_tok, feats, inv_self, same_mec


def _entity_block_xla(hp, x0, h_tok, feats, inv_self, same_mec, heads,
                      dtype):
    from t2omca_tpu.ops.query_slice import _entity_attention_heads
    sees = jnp.swapaxes(same_mec, 1, 2)
    seen = jnp.concatenate([sees, ~sees], axis=1)
    cast = lambda t: jax.tree.map(
        lambda x: x.astype(dtype) if x.dtype == jnp.bfloat16 else x, t)
    return _entity_attention_heads(cast(hp), cast(x0), cast(h_tok),
                                   cast(feats), inv_self, seen,
                                   heads=heads, dtype=dtype)


def _entity_block_kernel(hp, x0, h_tok, feats, inv_self, same_mec, heads):
    from t2omca_tpu.kernels import entity_attention as ek
    b, a, emb = x0.shape
    assert ek.eligible(b, a, emb, hp["wq"].shape[1], heads)
    sees = jnp.swapaxes(same_mec, 1, 2)
    tables = ek.tables(feats, inv_self,
                       jnp.concatenate([sees, ~sees], axis=1), x0.dtype,
                       ek.group(hp["wq"].shape[1], heads))
    return ek.entity_attention(hp, x0, h_tok, *tables, heads=heads,
                               interpret=True)


_rms = lambda x: float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


@pytest.mark.parametrize("visibility", ["one-mec", "alone", "random",
                                        "padded"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("emb,heads", [(256, 4), (256, 2), (128, 4)],
                         ids=["4x64", "2x128", "4x32"])
@pytest.mark.parametrize("a", [16, 64])
def test_entity_attention_kernel_matches_xla_form(a, emb, heads, dtype,
                                                  visibility):
    """The kernel (interpreted) against ``_entity_attention_heads`` on the
    same inputs. float32: the same sum to reassociation. bfloat16: both
    round q, k, v, the tables, the probabilities and the context to
    bf16; the kernel keeps logits and softmax in float32 where the XLA
    form rounds them to bf16 — so it must lie at least as near the
    float32 form (run on the same bf16-rounded inputs) as the XLA form
    does, and near the XLA form by that same measure."""
    dtype = jnp.dtype(dtype)
    args = _entity_block_inputs(2, a, emb, heads, dtype, visibility)
    got = _entity_block_kernel(*args, heads)
    want = _entity_block_xla(*args, heads, dtype)
    assert got.shape == want.shape and got.dtype == jnp.float32
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        return
    exact = _entity_block_xla(*args, heads, jnp.float32)
    err_xla, err_kernel = _rms(want - exact), _rms(got - exact)
    assert err_kernel <= 1.1 * err_xla, (err_kernel, err_xla)
    assert _rms(got - want) <= 1.5 * err_xla, (_rms(got - want), err_xla)
    assert err_xla < 0.02 * _rms(exact)


def test_entity_attention_kernel_grid_of_env_tiles():
    """Sixteen 64-agent envs are two grid steps of eight envs: the
    per-tile blocks and the per-env slices of the scratch line up."""
    from t2omca_tpu.kernels import entity_attention as ek
    assert ek._env_tile(16, 64) == 8
    args = _entity_block_inputs(16, 64, 256, 4, jnp.float32, "random", 3)
    np.testing.assert_allclose(_entity_block_kernel(*args, 4),
                               _entity_block_xla(*args, 4, jnp.float32),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("b,a,emb,hd,heads,ok", [
    (1024, 64, 256, 256, 4, True),     # the north-star cell
    (256, 16, 128, 128, 4, True),      # 16 AGVs, four heads of 32
    (4, 64, 256, 256, 2, True),        # one head a group
    (4, 5, 16, 16, 2, False),          # A off the sublane quantum
    (4, 16, 96, 96, 2, False),         # 48-wide heads do not tile 128 lanes
    (4, 16, 64, 64, 2, False),         # H·D under one lane tile
    (8, 128, 256, 256, 4, True),       # a full lane tile of agents
    (8, 144, 256, 256, 4, False),      # more: the logits tiles outgrow VMEM
])
def test_entity_attention_kernel_eligibility(b, a, emb, hd, heads, ok):
    from t2omca_tpu.kernels import entity_attention as ek
    assert ek.eligible(b, a, emb, hd, heads) is ok
