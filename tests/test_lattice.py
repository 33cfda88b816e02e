"""graftlattice: the rank-polymorphic superstep compositions
(docs/POPULATION.md §composition, docs/PERF.md §lattice) — the
population axis composed with the other graft axes through the one
shared superstep core (``run._superstep_fn``):

* **vmap-over-pallas** — the member axis vmapped over the fused
  flash-attention kernels: P=1 pallas is BIT-identical to the classic
  pallas superstep loop (the neutral-spec squeeze path), and at P=2 the
  ACTING path stays bit-identical between kernel modes while the train
  step matches at measured vmapped-kernel tolerances (looser than the
  solo tests/test_kernels.py pins — the batched grid reassociates);
* **population-over-dp** — whole members sharded over a device mesh
  (``parallel.population_shardings``) reproduce the replicated
  single-device run on the conftest-forced multi-device CPU host:
  control/integer state bit-equal, floats at ULP scale (SPMD retiling);
* **population × Sebulba** — the vmapped learner in lockstep behind the
  device-resident queue ends on the classic population driver's train
  state (the solo lockstep anchor lifted to rank P: control state
  bit-equal, floats at ULP scale — bitwise holds at the P=1 squeeze).

The combo-rejection pins (which illegal lattice points raise, naming
the blocking mechanism and the nearest legal alternative) live in
tests/test_population.py::test_sanity_lattice_legal_and_gated_combos.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu import population as graftpop
from t2omca_tpu.config import (EnvConfig, KernelsConfig, ModelConfig,
                               PopulationConfig, ReplayConfig,
                               SebulbaConfig, TrainConfig, sanity_check)
from t2omca_tpu.run import Experiment, run_sequential
from t2omca_tpu.utils.logging import Logger

pytestmark = pytest.mark.lattice


def tiny_cfg(tmp_path=None, **kw):
    """The test_superstep parity point (dense storage, sequential
    normalizer — the bit-comparable path) at test scale."""
    env_kw = kw.pop("env_kw", {})
    replay_kw = kw.pop("replay_kw", {})
    defaults = dict(
        t_max=60, batch_size_run=2, batch_size=4, test_interval=1_000_000,
        test_nepisode=2, log_interval=12, runner_log_interval=12,
        save_model=False, save_model_interval=24, epsilon_anneal_time=50,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6, fast_norm=False, **env_kw),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8, **replay_kw),
    )
    if tmp_path is not None:
        defaults["local_results_path"] = str(tmp_path)
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


def pop_cfg(p, tmp_path=None, **kw):
    return tiny_cfg(tmp_path, population=PopulationConfig(size=p), **kw)


def _assert_trees_equal(a, b, strip_member=False, msg=""):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (kp, x), (_, y) in zip(la, lb):
        x, y = np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y))
        if strip_member:
            y = y[0]
        np.testing.assert_array_equal(
            x, y, err_msg=f"{msg}{jax.tree_util.keystr(kp)}")


def _assert_trees_ulp_close(a, b, msg=""):
    """Integer/bool/control leaves bit-equal; float leaves at f32 ULP
    scale (rtol 1e-4, atol 1e-6). The cross-LAYOUT contract for rank-P
    programs: two batched lowerings of the same math (vmapped-fused vs
    vmapped-split, single-device vs member-sharded) tile their f32
    reduces differently, so bitwise equality holds only within one
    layout (docs/POPULATION.md §parity); control flow must still agree
    exactly. Measured drift shapes on this CPU: params ~5e-7 rel, but
    small-magnitude adam moments show the same ~1e-7 ABSOLUTE drift at
    up to 2.4e-5 relative — hence the atol floor and the 1e-4 rtol
    headroom (a real composition bug — wrong member's data, dropped
    train — lands at rel ~1, orders away)."""
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (kp, x), (_, y) in zip(la, lb):
        x = np.asarray(jax.device_get(x))
        y = np.asarray(jax.device_get(y))
        name = f"{msg}{jax.tree_util.keystr(kp)}"
        if np.issubdtype(x.dtype, np.inexact):
            np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-6,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(x, y, err_msg=name)


def _pop_dispatches(exp, cfg, n_dispatches, keyseed=0, shardings=None):
    """k=1 population dispatches with the driver's gate discipline:
    zero keys while the ring is below the train batch, per-member split
    streams once it can sample (tests/test_population.py::_pop_loop)."""
    p = cfg.population.size
    ts, spec = graftpop.init_population(exp, cfg)
    prog = exp.population_superstep_program(1)
    keys = [jax.random.PRNGKey(cfg.seed + keyseed + m) for m in range(p)]
    if shardings is not None:
        ts = jax.device_put(ts, shardings(ts))
        spec = jax.device_put(spec, shardings(spec))
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env, filled = 0, 0
    all_infos = []
    for _ in range(n_dispatches):
        filled = min(filled + cfg.batch_size_run, exp.buffer.capacity)
        if filled >= cfg.batch_size:
            row = []
            for m in range(p):
                keys[m], ks = jax.random.split(keys[m])
                row.append(ks)
            kstack = jnp.stack(row)[:, None, :]
        else:
            kstack = jnp.zeros((p, 1) + keys[0].shape, keys[0].dtype)
        if shardings is not None:
            kstack = jax.device_put(kstack, shardings(kstack))
        ts, stats, infos = prog(ts, kstack, jnp.asarray(t_env), spec)
        t_env += spr
        all_infos.append(infos)
    return ts, all_infos


# ------------------------------------------------------- vmap-over-pallas

@pytest.mark.slow   # two pallas-mode superstep compiles (~90 s)
def test_p1_pallas_population_bit_identical_to_classic_pallas():
    """The P=1 double-bypass contract survives UNDER the pallas kernel
    mode: a neutral single-member population lowers the classic pallas
    superstep's exact arithmetic — params, opt_state, replay ring and
    runner state all bit-equal after gated train dispatches."""
    kernels = KernelsConfig(attention="pallas")
    cfg = tiny_cfg(kernels=kernels)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(cfg.seed)
    prog = exp.superstep_program(1)
    key = jax.random.PRNGKey(cfg.seed + 1)
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env, filled = 0, 0
    for _ in range(3):
        filled = min(filled + cfg.batch_size_run, exp.buffer.capacity)
        if filled >= cfg.batch_size:
            key, ks = jax.random.split(key)
            kstack = ks[None]
        else:
            kstack = jnp.zeros((1,) + key.shape, key.dtype)
        ts, _, _ = prog(ts, kstack, jnp.asarray(t_env))
        t_env += spr

    cfgp = pop_cfg(1, kernels=kernels)
    expp = Experiment.build(cfgp)
    ts_p, _ = _pop_dispatches(expp, cfgp, 3, keyseed=1)
    _assert_trees_equal(ts, ts_p, strip_member=True, msg="state ")


@pytest.mark.slow   # two P=2 population superstep compiles (~90 s)
def test_p2_pallas_superstep_matches_xla_at_kernel_tolerances():
    """vmap-over-pallas vs vmap-over-xla: identical seeds/keys through
    the P=2 population superstep in both kernel modes.

    Two-layer contract, each layer at its honest tolerance:

    * the ACTING path is bit-identical between modes even under vmap —
      every ring storage leaf (obs, state, actions, rewards, masks) and
      the full runner state are asserted bit-equal, so the first gated
      train consumes EXACTLY the same inputs in both modes (the solo
      qslice bit-parity of tests/test_kernels.py survives batching);
    * the TRAIN step matches at vmapped-kernel tolerances, measured on
      this CPU: the batched flash grid reassociates the f32
      forward/backward reduces more aggressively than the solo kernel
      (the solo pins — loss 1e-6, grad_norm 1e-4 — do NOT transfer),
      observed loss 8.2e-5 rel / grad_norm 1.2e-2 rel (on an ~3e5
      audit-scale norm) / params 7.3e-5 abs after the first gated
      train, pinned here with ~3x headroom."""
    outs = {}
    for mode in ("xla", "pallas"):
        cfgp = pop_cfg(2, kernels=KernelsConfig(attention=mode))
        expp = Experiment.build(cfgp)
        outs[mode] = _pop_dispatches(expp, cfgp, 3)
    ts_x, infos_x = outs["xla"]
    ts_p, infos_p = outs["pallas"]
    # acting layer: ring storage + runner state bit-equal across modes
    _assert_trees_equal(jax.device_get(ts_x.buffer.storage),
                        jax.device_get(ts_p.buffer.storage),
                        msg="ring ")
    _assert_trees_equal(jax.device_get(ts_x.runner),
                        jax.device_get(ts_p.runner), msg="runner ")
    # train layer: the gated third dispatch trained on identical inputs
    np.testing.assert_allclose(
        np.asarray(jax.device_get(infos_p[-1]["loss"]), np.float64),
        np.asarray(jax.device_get(infos_x[-1]["loss"]), np.float64),
        rtol=3e-4)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(infos_p[-1]["grad_norm"]), np.float64),
        np.asarray(jax.device_get(infos_x[-1]["grad_norm"]), np.float64),
        rtol=5e-2)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(
                jax.device_get(ts_p.learner.params)),
            jax.tree_util.tree_leaves_with_path(
                jax.device_get(ts_x.learner.params))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-3, atol=1e-3, err_msg=jax.tree_util.keystr(kp))


# ------------------------------------------------------ population-over-dp

@pytest.mark.slow   # sharded + replicated population compiles (~60 s)
def test_population_over_dp_sharded_matches_replicated():
    """Whole members sharded over the mesh (one batched program, the
    member axis split 4-ways — ``parallel.population_shardings``)
    reproduce the replicated single-device population run with zero
    cross-member communication: every integer leaf (ring write indices,
    episode counters, stored actions — the CONTROL state) is bit-equal,
    and float leaves agree at ULP scale. Measured CPU fact the
    tolerance stands on: there is no psum to reassociate, but SPMD
    partitioning retiles each member's reduces (batch-P arrays on one
    device vs batch-P/D shards per device), which drifts f32 sums by
    ~1 ULP exactly like the documented P=1 vmap story
    (docs/POPULATION.md §parity) — observed max 5.5e-7 relative on
    params and ~1e-7 absolute (2.4e-5 relative) on small-magnitude
    adam moments after the first gated train."""
    from t2omca_tpu.parallel import make_mesh, population_shardings
    cfgp = pop_cfg(4)
    expp = Experiment.build(cfgp)
    ts_rep, _ = _pop_dispatches(expp, cfgp, 3)

    mesh = make_mesh(4)
    exps = Experiment.build(cfgp)
    ts_sh, _ = _pop_dispatches(
        exps, cfgp, 3,
        shardings=lambda tree: population_shardings(mesh, tree))
    _assert_trees_ulp_close(ts_rep, jax.device_get(ts_sh), msg="state ")


# ---------------------------------------------------- population x sebulba

@pytest.mark.slow   # two full tiny driver runs (~150 s)
def test_population_sebulba_lockstep_matches_population_classic(tmp_path):
    """The rank-P lift of the solo lockstep anchor
    (tests/test_sebulba.py): a P=2 population behind the 1+1 device
    split at queue_slots=1/staleness=0 ends on the classic population
    driver's train state — every control/integer leaf (stored actions,
    ring write indices, episode counters, t_env) bit-equal, float
    leaves at f32 ULP scale. Measured CPU fact the tolerance stands on:
    the per-member losses/returns are IDENTICAL at every log cadence
    (same trajectories, same train sequence), but the vmapped SPLIT
    learner program and the vmapped FUSED superstep tile their batched
    f32 reduces differently — observed max 1 ULP (1.1e-7 rel) on final
    params. The bitwise version of this anchor lives at P=1, where both
    paths squeeze to the verbatim solo programs (tests/test_sebulba.py
    pins solo lockstep ≡ solo classic bit-exactly)."""
    cfg_classic = pop_cfg(2, tmp_path, test_interval=24)
    cfg_seb = pop_cfg(2, tmp_path, test_interval=24,
                      sebulba=SebulbaConfig(actor_devices=1,
                                            learner_devices=1,
                                            queue_slots=1, staleness=0))
    ts1 = run_sequential(Experiment.build(cfg_classic), Logger(),
                         str(tmp_path / "classic"))
    ts2 = run_sequential(Experiment.build(cfg_seb), Logger(),
                         str(tmp_path / "sebulba"))
    h1, h2 = jax.device_get(ts1), jax.device_get(ts2)
    _assert_trees_ulp_close(h1.learner, h2.learner, msg="learner ")
    _assert_trees_ulp_close(h1.buffer, h2.buffer, msg="buffer ")
    _assert_trees_ulp_close(h1.runner, h2.runner, msg="runner ")
    _assert_trees_ulp_close(h1.episode, h2.episode, msg="episode ")
