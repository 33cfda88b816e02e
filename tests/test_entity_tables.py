"""Exactness of the entity-table acting path (ops/query_slice,
``agent_forward_qslice_entity``) against the obs-based query-slice forward.

The factored form must reproduce the full normalized entity observation's
embeddings (visible/masked tables + is-self diagonal) and hence identical
Q-values — on REAL env states (including the post-reset first-sample
statistics and mid-episode Welford states), not just synthetic inputs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.config import EnvConfig, ModelConfig, TrainConfig, sanity_check
from t2omca_tpu.controllers.basic_mac import BasicMAC
from t2omca_tpu.envs.mec_offload import MultiAgvOffloadingEnv
from t2omca_tpu.run import Experiment


def _cfg(**model_kw):
    return sanity_check(TrainConfig(
        batch_size_run=4,
        env_args=EnvConfig(agv_num=5, mec_num=2, num_channels=3,
                           episode_limit=6, fast_norm=True),
        model=ModelConfig(**{**dict(emb=16, heads=2, depth=2, mixer_emb=16,
                                    mixer_heads=2, mixer_depth=2),
                             **model_kw}),
    ))


def _rolled_states(env, b, steps, key):
    """Env states after ``steps`` random steps (real queues + norm stats)."""
    states, obs, *_ = jax.vmap(env.reset)(jax.random.split(key, b))
    for t in range(steps):
        k = jax.random.fold_in(key, 100 + t)
        actions = jax.random.randint(k, (b, env.n_agents), 0, env.n_actions)
        actions = actions * states.job_valid[:, :, 0]
        states, _, _, _, obs, *_ = jax.vmap(env.step)(
            states, actions, jax.random.split(k, b))
    return states, obs


@pytest.mark.parametrize("steps", [0, 4])
@pytest.mark.parametrize("standard_heads", [False, True])
def test_entity_forward_matches_obs_forward(steps, standard_heads):
    cfg = _cfg(standard_heads=standard_heads)
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    assert mac.use_entity_tables

    b = cfg.batch_size_run
    key = jax.random.PRNGKey(steps)
    states, obs = _rolled_states(env, b, steps, key)
    compact = jax.vmap(env.compact_obs)(states)

    params = mac.init_params(key, env.obs_dim)
    hidden = jax.random.normal(jax.random.fold_in(key, 1),
                               (b, env.n_agents, cfg.model.emb))

    q_obs, h_obs = mac.forward_qslice(params, obs, hidden)
    q_ent, h_ent = mac.forward_entity(params, compact, hidden)
    np.testing.assert_allclose(q_ent, q_obs, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_ent, h_obs, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("standard_heads", [False, True])
def test_entity_forward_matches_dense_flax(standard_heads):
    """Transitively exact vs the dense module too — in the folded form
    (``head_dim == emb``) and in the head-width form (``head_dim < emb``)."""
    cfg = _cfg(standard_heads=standard_heads)
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    b = cfg.batch_size_run
    key = jax.random.PRNGKey(7)
    states, obs = _rolled_states(env, b, 3, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.init_params(key, env.obs_dim)
    hidden = jnp.zeros((b, env.n_agents, cfg.model.emb))

    q_dense, h_dense = mac.forward(params, obs, hidden)
    q_ent, h_ent = mac.forward_entity(params, compact, hidden)
    np.testing.assert_allclose(q_ent, q_dense, rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(h_ent, h_dense, rtol=5e-4, atol=5e-5)


def _assert_close_bf16_ulp(actual, desired, max_ulp=32):
    """Compare two bf16-computed tensors IN THE STORAGE DTYPE with a
    per-element tolerance of ``max_ulp`` bf16 ULPs. Both paths round
    intermediates at different points (docs/SPEC.md §7 header note), so
    the error scales with element MAGNITUDE — a flat f32 atol is too
    tight for large elements (the seed-known 1/320-element failure at
    atol=0.05) while saying nothing near zero. The per-element ULP is
    floored at the tensor's RMS scale: attention/LayerNorm reductions
    cancel, so absolute error lives at the scale of the SUMMANDS, and a
    near-zero result legitimately carries rounding noise from O(rms)
    terms. bf16 ULP at magnitude m = 2^(floor(log2 m) − 7) (8-bit
    mantissa). Observed worst over this path: ~22 ULPs (depth-2
    transformer, ≈20 differently-placed rounding steps)."""
    import ml_dtypes  # ships with jax

    a = np.asarray(np.asarray(actual, ml_dtypes.bfloat16), np.float32)
    d = np.asarray(np.asarray(desired, ml_dtypes.bfloat16), np.float32)
    scale = np.maximum(np.maximum(np.abs(a), np.abs(d)),
                       np.sqrt(np.mean(d ** 2)))
    ulp = 2.0 ** (np.floor(np.log2(scale)) - 7.0)
    err = np.abs(a - d) / ulp
    assert err.max() <= max_ulp, (
        f"{int((err > max_ulp).sum())}/{err.size} elements beyond "
        f"{max_ulp} bf16 ULPs (worst {err.max():.1f})")


@pytest.mark.parametrize("standard_heads", [True, False])
def test_entity_forward_bf16_matches_obs_forward(standard_heads):
    """The production bench config (bfloat16 + standard heads + fast_norm)
    runs exactly this path — pin its numerics too. Both forwards compute
    in bf16, so equivalence is asserted in the storage dtype with a
    per-element ULP bound (32 bf16 ULPs at the tensor's scale), not a flat
    f32 atol (see ``_assert_close_bf16_ulp``). At ``standard_heads`` the
    entity path contracts at head width and rounds where the dense module
    does (q, k, v), the obs path where the fold does (wqk, qp, wvu)."""
    cfg = _cfg(standard_heads=standard_heads, dtype="bfloat16")
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    assert mac.use_entity_tables
    b = cfg.batch_size_run
    key = jax.random.PRNGKey(3)
    states, obs = _rolled_states(env, b, 3, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.init_params(key, env.obs_dim)
    hidden = jnp.zeros((b, env.n_agents, cfg.model.emb))
    q_obs, h_obs = mac.forward_qslice(params, obs, hidden)
    q_ent, h_ent = mac.forward_entity(params, compact, hidden)
    _assert_close_bf16_ulp(q_ent, q_obs)
    _assert_close_bf16_ulp(h_ent, h_obs)


@pytest.mark.parametrize("standard_heads", [False, True])
def test_entity_unroll_gradients_match_obs_unroll(standard_heads):
    """The learner differentiates the entity forward: a short unroll of
    ``QMixLearner._unroll_agent`` over compact storage (``forward_entity``)
    gives the gradient of the obs-path unroll (``forward_qslice``), for
    every agent parameter, at both head geometries."""
    cfg = _cfg(standard_heads=standard_heads)
    exp = Experiment.build(cfg)
    env, mac, learner = exp.env, exp.mac, exp.learner
    b, t = cfg.batch_size_run, 3
    key = jax.random.PRNGKey(5)
    obs_t, compact_t = [], []
    for step in range(t):
        states, obs = _rolled_states(env, b, step, key)
        obs_t.append(obs)
        compact_t.append(jax.vmap(env.compact_obs)(states))
    obs_tm = jnp.stack(obs_t)
    compact_tm = tuple(jnp.stack(x) for x in zip(*compact_t))
    params = mac.init_params(key, env.obs_dim)

    def loss(p, **kw):
        qs, hs = learner._unroll_agent(p, **kw)
        return (qs ** 2).mean() + (hs ** 2).mean()

    g_obs = jax.grad(lambda p: loss(p, obs_tm=obs_tm))(params)
    g_ent = jax.grad(lambda p: loss(p, obs_tm=None,
                                    compact_tm=compact_tm))(params)
    flat_obs = jax.tree_util.tree_leaves_with_path(g_obs)
    flat_ent = jax.tree.leaves(g_ent)
    assert len(flat_obs) == len(flat_ent)
    for (path, a), e in zip(flat_obs, flat_ent):
        scale = float(jnp.abs(a).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(e, a, rtol=2e-3, atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("standard_heads", [False, True])
def test_noisy_entity_head_with_key(standard_heads):
    """NoisyNet head through the entity forward with a key, at both head
    geometries: the draw perturbs q off the mu path and leaves the hidden
    stream alone, the same key gives the same draw, and — the q-head being
    shared code — the obs-path forward with that key gives the same q."""
    cfg = sanity_check(_cfg(standard_heads=standard_heads).replace(
        action_selector="noisy-new"))
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    assert mac.use_entity_tables and mac.agent.noisy
    b = cfg.batch_size_run
    key = jax.random.PRNGKey(2)
    states, obs = _rolled_states(env, b, 2, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.init_params(key, env.obs_dim)
    hidden = jax.random.normal(jax.random.fold_in(key, 1),
                               (b, env.n_agents, cfg.model.emb))
    k = jax.random.PRNGKey(9)
    q_mu, h_mu = mac.forward_entity(params, compact, hidden)
    q_n, h_n = mac.forward_entity(params, compact, hidden, key=k,
                                  deterministic=False)
    q_n2, _ = mac.forward_entity(params, compact, hidden, key=k,
                                 deterministic=False)
    q_obs, _ = mac.forward_qslice(params, obs, hidden, key=k,
                                  deterministic=False)
    np.testing.assert_array_equal(np.asarray(h_n), np.asarray(h_mu))
    np.testing.assert_array_equal(np.asarray(q_n), np.asarray(q_n2))
    assert not np.allclose(np.asarray(q_n), np.asarray(q_mu))
    np.testing.assert_allclose(q_n, q_obs, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("lanes", [8, 16])
def test_head_groups_agree(monkeypatch, lanes):
    """The head-width form contracts its heads in groups that fill a lane
    tile (``_LANES // head_dim`` heads each). At emb 32 x 4 heads
    (``head_dim`` 8): one head a group, two a group, and all four in one
    (the default tile) give the same Q-values, and the obs path's."""
    from t2omca_tpu.ops import query_slice
    cfg = _cfg(standard_heads=True, emb=32, heads=4, mixer_emb=32)
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    b = cfg.batch_size_run
    key = jax.random.PRNGKey(4)
    states, obs = _rolled_states(env, b, 2, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.init_params(key, env.obs_dim)
    hidden = jax.random.normal(jax.random.fold_in(key, 1),
                               (b, env.n_agents, cfg.model.emb))
    q_one, h_one = mac.forward_entity(params, compact, hidden)
    monkeypatch.setattr(query_slice, "_LANES", lanes)
    q_grp, h_grp = mac.forward_entity(params, compact, hidden)
    q_obs, h_obs = mac.forward_qslice(params, obs, hidden)
    np.testing.assert_allclose(q_grp, q_one, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_grp, h_one, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(q_grp, q_obs, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_grp, h_obs, rtol=2e-4, atol=2e-5)


def _acting_step_jaxpr(standard_heads, dtype="bfloat16"):
    # four heads: the (B, 2A, H·D) tables stay under H·E an agent-step
    cfg = _cfg(standard_heads=standard_heads, dtype=dtype, heads=4)
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    b = cfg.batch_size_run
    key = jax.random.PRNGKey(0)
    states, _ = _rolled_states(env, b, 0, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.prepare_acting_params(mac.init_params(key, env.obs_dim))
    hidden = mac.init_hidden(b)
    step = lambda p, c, h: mac.forward_entity(p, c, h, acting=True)
    closed = jax.make_jaxpr(step)(params, compact, hidden)
    # the folded kernels among the step's inputs, and whether any op reads them
    folded = [v for (path, _), v in zip(
        jax.tree_util.tree_leaves_with_path(params), closed.jaxpr.invars)
        if any(k in jax.tree_util.keystr(path) for k in ("wqk", "wvu"))]
    assert len(folded) == 2 * cfg.model.depth
    read = {id(v) for eqn in closed.jaxpr.eqns for v in eqn.invars}
    return cfg, closed, [id(v) in read for v in folded]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_head_width_acting_step_has_no_folded_intermediates():
    """Structure of one acting step at ``head_dim < emb`` (config 3's
    geometry): no op reads the fold's ``wqk (E, H·E)`` / ``wvu (H·E, E)``;
    inside ``agent.attention`` nothing float32 is as large as ``H·E``
    elements an agent-step; and of the tensors of that size that every op
    of the folded form made (a dozen a block, float32 among them) only the
    two the head-width form is built on remain, in the compute dtype: the
    head-masked query rows (a broadcast and a select) and the context
    product (one contraction a block). Fails if the folded association, a
    float32 copy of those two, or a second wide contraction comes back."""
    cfg, closed, folded_read = _acting_step_jaxpr(standard_heads=True)
    assert not any(folded_read)
    m = cfg.model
    wide = cfg.batch_size_run * cfg.env_args.agv_num * m.heads * m.emb
    big = [(eqn.primitive.name, v.aval)
           for eqn in _eqns(closed.jaxpr)
           if "agent.attention" in str(eqn.source_info.name_stack)
           for v in eqn.outvars
           if v.aval.size >= wide and jnp.issubdtype(v.aval.dtype,
                                                     jnp.floating)]
    assert big and all(av.dtype == jnp.bfloat16 for _, av in big), big
    assert sum(name == "dot_general" for name, _ in big) == m.depth, big


#: sha256[:16] of the ``head_dim == emb`` lowering under this suite's
#: conftest, recorded from the parent commit of PR 26 by this same recipe
#: (JAX 0.9.0; another JAX, or a deliberate change of the folded body,
#: re-records it)
FOLDED_LOWERING = "0f57f99f6a96078a"


def test_full_width_heads_keep_the_folded_lowering():
    """At ``head_dim == emb`` (the reference's geometry, configs 1 and 2)
    the function lowers to exactly what it lowered to before the
    head-width form existed: the folded kernels feed its contractions and
    the fingerprint of the lowered module is the recorded one (taken from
    the parent commit by this same recipe; a deliberate change of the
    folded body re-records it)."""
    import hashlib
    import re
    _, _, folded_read = _acting_step_jaxpr(standard_heads=False)
    assert all(folded_read)
    from t2omca_tpu.ops.query_slice import agent_forward_qslice_entity
    b, a, e = 3, 5, 16
    agent = Experiment.build(_cfg()).mac.agent.clone(
        n_agents=a, n_entities=a, emb=e, heads=2, depth=2, n_actions=4)
    params = jax.eval_shape(
        lambda k: agent.init(k, jnp.zeros((1, a, a * 9)),
                             agent.initial_hidden(1)), jax.random.PRNGKey(0))
    args = (jnp.zeros((b, a, 8)), jnp.zeros((b, a, a), bool),
            jnp.zeros((b, a, 9)), jnp.ones((b, a, 9)), jnp.zeros((b, a, e)))
    fn = lambda p, *xs: agent_forward_qslice_entity(
        p, *xs, emb=e, heads=2, depth=2, n_actions=4, standard_heads=False,
        dtype=jnp.float32)
    text = re.sub(r"loc\(.*?\)", "", jax.jit(fn).lower(params, *args).as_text())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == FOLDED_LOWERING


#: as ``FOLDED_LOWERING``: the gradient of the HEAD-WIDTH forward (the
#: learner's differentiated unroll runs it, ``kernel`` left False), from
#: the parent commit of PR 28
HEAD_WIDTH_GRAD_LOWERING = "407055a471c3cbc8"


def _kernel_shapes_agent(b=3, a=16, e=128, heads=4):
    """An agent at shapes acting's entity kernel engages for (16 agents on
    the sublane quantum, four heads of 32 = one 128-lane group), its
    abstract parameters and the entity forward's zero inputs."""
    agent = Experiment.build(_cfg()).mac.agent.clone(
        n_agents=a, n_entities=a, emb=e, heads=heads, depth=2, n_actions=4,
        standard_heads=True)
    params = jax.eval_shape(
        lambda k: agent.init(k, jnp.zeros((1, a, a * 9)),
                             agent.initial_hidden(1)), jax.random.PRNGKey(0))
    args = (jnp.zeros((b, a, 8)), jnp.zeros((b, a, a), bool),
            jnp.zeros((b, a, 9)), jnp.ones((b, a, 9)), jnp.zeros((b, a, e)))
    return agent, params, args


def test_learner_unroll_keeps_the_head_width_lowering():
    """The learner differentiates the head-width forward and acting's
    kernel has no backward pass, so the two have their own paths: with
    ``kernel`` left False the gradient of ``agent_forward_qslice_entity``
    lowers to exactly what it lowered to on the parent commit of PR 28 —
    at shapes where acting's call takes the kernel."""
    import hashlib
    import re
    from t2omca_tpu.kernels import entity_attention as ek
    from t2omca_tpu.ops.query_slice import agent_forward_qslice_entity
    agent, params, args = _kernel_shapes_agent()
    b, a, e = args[-1].shape
    assert ek.eligible(b, a, e, e, 4)

    def loss(p, *xs):
        q, h = agent_forward_qslice_entity(
            p, *xs, emb=e, heads=4, depth=2, n_actions=4,
            standard_heads=True, dtype=jnp.bfloat16)
        return (q ** 2).sum() + (h ** 2).sum()
    text = re.sub(r"loc\(.*?\)", "",
                  jax.jit(jax.grad(loss)).lower(params, *args).as_text())
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == HEAD_WIDTH_GRAD_LOWERING)


def _float_tensors(stablehlo: str):
    """Shapes of every float32 / bfloat16 tensor type in a lowered
    module's text."""
    import re
    return {tuple(int(n) for n in dims[:-1].split("x"))
            for dims in re.findall(r"tensor<((?:\d+x)+)(?:f32|bf16)>",
                                   stablehlo)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_acting_tpu_lowering_keeps_the_logits_inside_the_kernel(dtype):
    """Acting's step lowered FOR A TPU (from here, no chip): each block's
    attention is one Mosaic call, and outside it no floating tensor holds
    an env's logits — nothing batched over the envs has ``H·A x 2A``
    elements an env, bar the activations ``(B, A, ·)``. The same step
    lowered with ``kernel`` off (what the learner runs, and acting
    anywhere but on a TPU) has such tensors: the logits ``(B, 2A, H·A)``
    among them."""
    from t2omca_tpu.ops.query_slice import agent_forward_qslice_entity
    agent, params, args = _kernel_shapes_agent()
    b, a, e = args[-1].shape
    heads = 4

    def lowered(kernel, platform):
        fn = lambda p, *xs: agent_forward_qslice_entity(
            p, *xs, emb=e, heads=heads, depth=2, n_actions=4,
            standard_heads=True, dtype=jnp.dtype(dtype), kernel=kernel)
        return jax.jit(fn).trace(params, *args).lower(
            lowering_platforms=(platform,)).as_text()

    logits = b * heads * a * 2 * a
    wide = lambda text: {s for s in _float_tensors(text)
                         if s[0] == b and int(np.prod(s)) >= logits
                         and s[:2] != (b, a)}
    tpu = lowered(True, "tpu")
    assert tpu.count("tpu_custom_call") == 2          # one a block
    assert not wide(tpu), wide(tpu)
    assert (b, 2 * a, heads * a) in wide(lowered(False, "tpu"))
    # anywhere but on a TPU acting runs the XLA form, and no kernel
    cpu = lowered(True, "cpu")
    assert "tpu_custom_call" not in cpu
    assert (b, 2 * a, heads * a) in wide(cpu)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_acting_through_the_kernel_matches_the_learner_path(monkeypatch,
                                                            dtype):
    """``forward_entity(acting=True)`` run THROUGH the kernel (interpreted
    here; on a TPU it is compiled) against ``acting=False`` (the XLA
    association the learner unrolls), on real env states, both blocks of a
    two-block forward: Q-values and next hidden within the tolerance the
    two associations of this file are held to."""
    from t2omca_tpu.kernels import entity_attention as ek
    cfg = _cfg(standard_heads=True, emb=128, heads=4, mixer_emb=128,
               mixer_heads=4, dtype=dtype)
    cfg = cfg.replace(env_args=dataclasses.replace(
        cfg.env_args, agv_num=16, mec_num=3))
    exp = Experiment.build(sanity_check(cfg))
    env, mac = exp.env, exp.mac
    assert mac.use_entity_tables and mac.entity_kernel
    b = cfg.batch_size_run
    key = jax.random.PRNGKey(6)
    states, _ = _rolled_states(env, b, 3, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.prepare_acting_params(mac.init_params(key, env.obs_dim))
    hidden = jax.random.normal(jax.random.fold_in(key, 1),
                               (b, env.n_agents, cfg.model.emb))
    q_xla, h_xla = mac.forward_entity(params, compact, hidden)
    monkeypatch.setattr(ek, "INTERPRET", True)
    step = lambda p, c, h: mac.forward_entity(p, c, h, acting=True)
    jaxpr = str(jax.make_jaxpr(step)(params, compact, hidden))
    assert jaxpr.count("pallas_call") == cfg.model.depth
    q_ker, h_ker = step(params, compact, hidden)
    if dtype == "float32":
        np.testing.assert_allclose(q_ker, q_xla, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(h_ker, h_xla, rtol=2e-4, atol=2e-5)
    else:
        _assert_close_bf16_ulp(q_ker, q_xla)
        _assert_close_bf16_ulp(h_ker, h_xla)


@pytest.mark.parametrize("change,platform,line", [
    ({}, "tpu", "entity tables, attention: kernel"),
    ({}, "cpu", "entity tables, attention: xla"),
    ({"entity_kernel": False}, "tpu", "entity tables, attention: xla"),
    ({"n_agents": 5}, "tpu", "entity tables, attention: xla"),
    ({"use_entity_tables": False}, "tpu", "qslice, attention: xla"),
    ({"use_entity_tables": False, "use_qslice": False}, "tpu",
     "obs, attention: xla"),
    ({"trunk": object()}, "tpu", "trunk, attention: xla"),
], ids=["kernel", "cpu", "lanes-span-devices", "five-agents", "qslice",
        "obs", "trunk"])
def test_describe_acting(change, platform, line):
    """The start-up log's line follows ``act``'s own order of forwards,
    and says ``kernel`` exactly where ``agent_forward_qslice_entity``
    engages it: head-width kernels, the kernel's shapes, a TPU, one
    device."""
    info = {"n_agents": 16, "n_entities": 16, "obs_entity_feats": 9,
            "n_actions": 5, "obs_shape": 144}
    mac = BasicMAC.build(_cfg(standard_heads=True, emb=128, heads=4,
                              mixer_emb=128, mixer_heads=4), info)
    mac = dataclasses.replace(mac, **change)
    assert mac.describe_acting(4, platform) == "acting forward: " + line
    full = BasicMAC.build(_cfg(emb=128, heads=4, mixer_emb=128,
                               mixer_heads=4), info)
    assert full.describe_acting(4, "tpu").endswith("attention: xla")


def test_entity_kernel_is_for_one_device_and_one_member():
    """Env lanes that span devices (``dp_devices``, sebulba's actor mesh:
    GSPMD has no rule for the kernel's custom call) or members (a
    population's vmap) keep the XLA association in acting."""
    info = {"n_agents": 16, "n_entities": 16, "obs_entity_feats": 9,
            "n_actions": 5, "obs_shape": 144}
    base = _cfg(standard_heads=True, emb=128, heads=4, mixer_emb=128,
                mixer_heads=4)
    assert BasicMAC.build(base, info).entity_kernel
    for off in (dict(dp_devices=2),
                dict(sebulba=dataclasses.replace(
                    base.sebulba, actor_devices=2, learner_devices=1)),
                dict(population=dataclasses.replace(
                    base.population, size=2))):
        assert not BasicMAC.build(base.replace(**off), info).entity_kernel


@pytest.mark.slow   # two rollout compiles (~16 s); numeric equivalence of the paths pinned above
def test_rollout_actions_match_obs_path():
    """Greedy episode through the runner: entity-table acting and obs-path
    acting pick identical actions and returns."""
    cfg = _cfg()
    exp_ent = Experiment.build(cfg)
    cfg_obs = cfg.replace(
        model=dataclasses.replace(cfg.model, use_entity_tables=False))
    exp_obs = Experiment.build(cfg_obs)
    assert exp_ent.mac.use_entity_tables
    assert not exp_obs.mac.use_entity_tables

    ts = exp_ent.init_train_state(0)
    run_ent = jax.jit(exp_ent.runner.run, static_argnames="test_mode")
    run_obs = jax.jit(exp_obs.runner.run, static_argnames="test_mode")
    p = ts.learner.params["agent"]
    _, b_ent, s_ent = run_ent(p, ts.runner, test_mode=True)
    _, b_obs, s_obs = run_obs(p, ts.runner, test_mode=True)
    np.testing.assert_array_equal(b_ent.actions, b_obs.actions)
    np.testing.assert_allclose(s_ent.episode_return, s_obs.episode_return,
                               rtol=1e-5)


def test_eligibility_gating():
    # sequential normalizer → tables ineligible (per-observer prefix stats)
    cfg = sanity_check(TrainConfig(
        env_args=EnvConfig(agv_num=4, mec_num=2, episode_limit=5,
                           fast_norm=False),
        model=ModelConfig(emb=16, heads=2, depth=1, mixer_emb=16,
                          mixer_heads=2)))
    assert not Experiment.build(cfg).mac.use_entity_tables

    # flat obs mode → ineligible
    cfg2 = sanity_check(TrainConfig(
        env_args=EnvConfig(agv_num=4, mec_num=2, episode_limit=5,
                           obs_entity_mode=False, fast_norm=True),
        model=ModelConfig(emb=16, heads=2, depth=1, mixer_emb=16,
                          mixer_heads=2)))
    assert not Experiment.build(cfg2).mac.use_entity_tables

    # eligible default
    cfg3 = _cfg()
    mac3 = Experiment.build(cfg3).mac
    assert mac3.use_entity_tables and mac3.use_qslice


@pytest.mark.slow   # two full train-step compiles (~40 s)
def test_compact_store_train_matches_full_store():
    """Rollout → insert → PER sample → train with compact entity storage
    produces the same loss/priorities as full-obs storage (the stored
    representation is exact, so the whole training step must agree)."""
    import jax.numpy as jnp

    def build(compact):
        cfg = _cfg()
        cfg = cfg.replace(batch_size=4, replay=dataclasses.replace(
            cfg.replay, buffer_size=8, prioritized=True,
            compact_entity_store=compact))
        return Experiment.build(cfg)

    exp_c, exp_f = build(True), build(False)
    assert exp_c.buffer.compact_obs and not exp_f.buffer.compact_obs

    losses = {}
    for name, exp in (("compact", exp_c), ("full", exp_f)):
        ts = exp.init_train_state(0)
        rollout, insert, train_iter = exp.jitted_programs()
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=jnp.asarray(4, jnp.int32))
        _, info = train_iter(ts, jax.random.PRNGKey(5), jnp.asarray(100))
        losses[name] = (float(info["loss"]),
                        jax.device_get(info["td_errors_abs"]))
    np.testing.assert_allclose(losses["compact"][0], losses["full"][0],
                               rtol=1e-4)
    np.testing.assert_allclose(losses["compact"][1], losses["full"][1],
                               rtol=1e-3, atol=1e-4)


@pytest.mark.slow   # noisy unroll compiles (~20 s); sigma-grad flow also pinned in test_learner_runner
def test_noisy_entity_path_noise_and_sigma_gradients():
    """The 16-agent campaign's arm-B training branch: a noisy config with
    the default fast stack routes acting AND the compact-storage learner
    unroll through ``forward_entity`` with noise keys. Pin (a) the key
    actually reaches the q-head (q perturbs off the mu path; same key →
    same draw; hidden stream untouched) and (b) sigma params receive
    gradient through the full compact-storage loss."""
    cfg = _cfg()
    cfg = cfg.replace(
        action_selector="noisy-new", batch_size=4,
        replay=dataclasses.replace(cfg.replay, buffer_size=8,
                                   prioritized=True))
    cfg = sanity_check(cfg)
    exp = Experiment.build(cfg)
    env, mac = exp.env, exp.mac
    assert mac.use_entity_tables and mac.agent.noisy

    b = cfg.batch_size_run
    key = jax.random.PRNGKey(0)
    states, _obs = _rolled_states(env, b, 3, key)
    compact = jax.vmap(env.compact_obs)(states)
    params = mac.init_params(key, env.obs_dim)
    hidden = jnp.zeros((b, env.n_agents, cfg.model.emb))

    q_mu, h_mu = mac.forward_entity(params, compact, hidden)
    q_n, h_n = mac.forward_entity(params, compact, hidden,
                                  key=jax.random.PRNGKey(5),
                                  deterministic=False)
    q_n2, _ = mac.forward_entity(params, compact, hidden,
                                 key=jax.random.PRNGKey(5),
                                 deterministic=False)
    np.testing.assert_array_equal(np.asarray(h_n), np.asarray(h_mu))
    np.testing.assert_array_equal(np.asarray(q_n), np.asarray(q_n2))
    assert not np.allclose(np.asarray(q_n), np.asarray(q_mu))

    # (b) full loss through the CompactEntityObs unroll
    from t2omca_tpu.components.episode_buffer import CompactEntityObs
    ts = exp.init_train_state(0)
    rollout, insert, _ = exp.jitted_programs()
    rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                           test_mode=False)
    bstate = insert(ts.buffer, batch)
    sample, idx, w = exp.buffer.sample(bstate, jax.random.PRNGKey(2),
                                       cfg.batch_size, 0)
    assert isinstance(sample.obs, CompactEntityObs)
    grads, _ = jax.grad(exp.learner._loss, has_aux=True)(
        ts.learner.params, ts.learner.target_params, sample, w,
        jax.random.PRNGKey(7))
    qg = grads["agent"]["params"]["q_basic"]
    for name in ("w_sigma", "b_sigma"):
        assert np.abs(np.asarray(qg[name])).max() > 0, name


@pytest.mark.slow   # full run() + resume (~30 s)
def test_compact_store_driver_e2e(tmp_path):
    """Full run() through compact storage: trains, checkpoints (the buffer
    pytree now nests CompactEntityObs), resumes."""
    from t2omca_tpu.run import run as run_driver

    cfg = _cfg()
    cfg = cfg.replace(
        t_max=40, batch_size=2, test_interval=1000, log_interval=1000,
        save_model=True, save_model_interval=10,
        local_results_path=str(tmp_path),
        replay=dataclasses.replace(cfg.replay, buffer_size=8))
    from t2omca_tpu.ops.query_slice import entity_store_eligible
    assert entity_store_eligible(cfg)
    ts = run_driver(cfg)
    assert float(jax.tree.leaves(ts.learner.params)[0].sum()) == \
        float(jax.tree.leaves(ts.learner.params)[0].sum())  # finite/no nan

    import glob as g
    ckpts = g.glob(str(tmp_path) + "/models/*/*")
    assert ckpts, "driver saved no checkpoint under compact storage"
    cfg2 = cfg.replace(checkpoint_path=str(
        sorted(ckpts)[0].rsplit("/", 1)[0]))
    ts2 = run_driver(cfg2)   # resumes from the saved step and finishes
    assert int(ts2.runner.t_env) >= 40


def test_compact_obs_reconstructs_full_obs():
    """(rows, mask, stats) → the exact normalized obs the env returned."""
    cfg = _cfg()
    env = Experiment.build(cfg).env
    b = 3
    key = jax.random.PRNGKey(11)
    states, obs = _rolled_states(env, b, 5, key)
    rows, same_mec, mean, std = jax.vmap(env.compact_obs)(states)

    a, f = env.n_agents, env.obs_entity_feats
    rows9 = jnp.concatenate([rows, jnp.zeros((b, a, 1))], axis=-1)
    raw = jnp.where(same_mec[:, :, :, None], rows9[:, None, :, :], 0.0)
    raw = raw.at[:, jnp.arange(a), jnp.arange(a), f - 1].set(1.0)
    denom = std + 1e-8
    norm = (raw - mean[:, None]) / denom[:, None]
    np.testing.assert_allclose(norm.reshape(b, a, a * f), obs,
                               rtol=1e-5, atol=1e-6)


def test_default_config_resolves_to_full_fast_stack():
    """TrainConfig() defaults land on the documented production path:
    entity-table acting + compact entity storage, with fast_norm gating
    satisfied (config, docs, and this pin must agree)."""
    from t2omca_tpu.config import TrainConfig, sanity_check
    from t2omca_tpu.ops.query_slice import (agent_qslice_eligible,
                                            entity_store_eligible,
                                            entity_tables_eligible)
    cfg = sanity_check(TrainConfig())
    assert cfg.env_args.fast_norm
    assert agent_qslice_eligible(cfg)
    assert entity_tables_eligible(cfg)
    assert entity_store_eligible(cfg)
    # and the built experiment actually wires those paths
    exp = Experiment.build(cfg.replace(
        env_args=dataclasses.replace(cfg.env_args, episode_limit=4),
        replay=dataclasses.replace(cfg.replay, buffer_size=8)))
    assert exp.mac.use_entity_tables
    assert exp.buffer.compact_obs


def test_compact_store_ineligible_past_int8_mec_range():
    """mec_index narrows to int8 in compact storage; ids are 0..mec_num-1,
    so mec_num=128 (max id 127) still fits and mec_num=129 would alias —
    the eligibility predicate must fall back to dense storage there."""
    from t2omca_tpu.ops.query_slice import entity_store_eligible
    base = sanity_check(TrainConfig())
    assert entity_store_eligible(base)
    at_edge = base.replace(env_args=dataclasses.replace(
        base.env_args, mec_num=128, agv_num=256))
    assert entity_store_eligible(at_edge)
    big = base.replace(env_args=dataclasses.replace(
        base.env_args, mec_num=129, agv_num=256))
    assert not entity_store_eligible(big)
