"""graftpop: the vmapped population axis (``t2omca_tpu/population.py``,
``run.Experiment.population_superstep_program``, docs/POPULATION.md).

Pins the contracts the ISSUE-15 acceptance criteria stand on:

* P=1 training is BIT-identical to the classic superstep loop — params,
  opt_state, replay ring, PER priorities and stats all equal (the
  neutral-spec squeeze path lowers the classic program's exact
  arithmetic; even a value-neutral traced seam would perturb XLA fusion
  enough to drift a ULP, measured);
* P=2 members with different seeds diverge, while ``seed_stride=0``
  members are bit-identical to EACH OTHER (vmap applies one batched
  kernel per member — identical inputs give identical outputs) and
  member 0 tracks its solo run to float tolerance (cross-rank
  bit-parity is a CPU-XLA impossibility under vmap: batched reduces
  reassociate f32 sums — docs/POPULATION.md §parity);
* ONE donated dispatch advances all P members, compiled exactly once
  (compile_budget(1) across repeated dispatches);
* per-member knob plumbing (lr/eps/alpha spec leaves), host-side PBT
  select-and-perturb, the population stats/sight surfaces, and the
  v4→v5 single-member → PopState checkpoint lift.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu import population as graftpop
from t2omca_tpu.analysis import compile_budget
import dataclasses

from t2omca_tpu.config import (EnvConfig, ModelConfig, PBTConfig,
                               PopulationConfig, ReplayConfig, TrainConfig,
                               from_dict, sanity_check)
from t2omca_tpu.run import Experiment, run
from t2omca_tpu.utils.checkpoint import load_checkpoint, save_checkpoint
from t2omca_tpu.utils.logging import Logger
from t2omca_tpu.utils.stats import StatsAccumulator

pytestmark = pytest.mark.population


def tiny_cfg(tmp_path=None, **kw):
    """The test_superstep parity point (dense storage, sequential
    normalizer — the bit-comparable path) at test scale."""
    env_kw = kw.pop("env_kw", {})
    replay_kw = kw.pop("replay_kw", {})
    env_defaults = dict(agv_num=3, mec_num=2, num_channels=2,
                        episode_limit=6, fast_norm=False)
    env_defaults.update(env_kw)
    defaults = dict(
        t_max=60, batch_size_run=2, batch_size=4, test_interval=1_000_000,
        test_nepisode=2, log_interval=12, runner_log_interval=12,
        save_model=False, save_model_interval=24, epsilon_anneal_time=50,
        env_args=EnvConfig(**env_defaults),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8, **replay_kw),
    )
    if tmp_path is not None:
        defaults["local_results_path"] = str(tmp_path)
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


def pop_cfg(p, tmp_path=None, **kw):
    pop_kw = kw.pop("pop_kw", {})
    return tiny_cfg(tmp_path, population=PopulationConfig(size=p, **pop_kw),
                    **kw)


def _pop_loop(exp, cfg, k, n_dispatches):
    """The population driver's fused path, verbatim (run.run_sequential):
    one shared gate mirror, per-member key streams, (P, K, 2) stacks."""
    p = cfg.population.size
    ts, spec = graftpop.init_population(exp, cfg)
    prog = exp.population_superstep_program(k, donate=True)
    keys = graftpop.member_keys(cfg)
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env, episode, filled = 0, 0, 0
    all_stats = []
    for _ in range(n_dispatches):
        rows = []
        for _ in range(k):
            episode += cfg.batch_size_run
            filled = min(filled + cfg.batch_size_run, exp.buffer.capacity)
            if filled >= cfg.batch_size:
                row = []
                for m in range(p):
                    keys[m], ks = jax.random.split(keys[m])
                    row.append(ks)
                rows.append(jnp.stack(row))
            else:
                rows.append(jnp.zeros((p,) + keys[0].shape,
                                      keys[0].dtype))
        ts, stats, infos = prog(ts, jnp.stack(rows, axis=1),
                                jnp.asarray(t_env), spec)
        t_env += k * spr
        all_stats.append(stats)
    return ts, spec, all_stats


def _classic_superstep_loop(exp, k, n_dispatches):
    cfg = exp.cfg
    ts = exp.init_train_state(cfg.seed)
    prog = exp.superstep_program(k, donate=True)
    key = jax.random.PRNGKey(cfg.seed + 1)
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env, episode, filled = 0, 0, 0
    all_stats = []
    for _ in range(n_dispatches):
        rows = []
        for _ in range(k):
            episode += cfg.batch_size_run
            filled = min(filled + cfg.batch_size_run, exp.buffer.capacity)
            if filled >= cfg.batch_size:
                key, ks = jax.random.split(key)
                rows.append(ks)
            else:
                rows.append(jnp.zeros_like(key))
        ts, stats, infos = prog(ts, jnp.stack(rows), jnp.asarray(t_env))
        t_env += k * spr
        all_stats.append(stats)
    return ts, all_stats


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


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_population_bare_int_shorthand_and_grids():
    cfg = tiny_cfg()
    base = dataclasses.asdict(cfg)
    c2 = from_dict({**base, "population": 4})
    assert c2.population.size == 4
    c3 = from_dict({**base, "save_model": True,
                    "population": {"size": 2, "lr": [5e-4, 1e-3],
                                   "pbt.enabled": True,
                                   "pbt.perturb": 1.5}})
    assert c3.population.lr == (5e-4, 1e-3)
    assert isinstance(c3.population.lr, tuple)
    assert c3.population.pbt.enabled and c3.population.pbt.perturb == 1.5
    # roundtrip (serve meta.json path)
    c4 = from_dict(dataclasses.asdict(c3))
    assert c4.population == c3.population


def test_sanity_rejects_incompatible_combos():
    # every REMAINING rejection names the blocking mechanism AND the
    # nearest legal alternative (graftlattice satellite contract)
    with pytest.raises(ValueError, match="vmaps the device-resident"):
        pop_cfg(2, replay_kw={"buffer_cpu_only": True})
    with pytest.raises(ValueError, match="separate solo runs"):
        pop_cfg(2, replay_kw={"buffer_cpu_only": True})
    with pytest.raises(ValueError, match="evaluate"):
        pop_cfg(2, evaluate=True)
    with pytest.raises(ValueError, match="exactly P entries"):
        pop_cfg(2, pop_kw={"lr": (1e-3,)})
    with pytest.raises(ValueError, match="must be > 0"):
        pop_cfg(2, pop_kw={"eps_scale": (1.0, -0.5)})
    with pytest.raises(ValueError, match=r"in \(0, 1\]"):
        pop_cfg(2, pop_kw={"per_alpha": (0.5, 1.5)})
    with pytest.raises(ValueError, match="prioritized"):
        pop_cfg(2, pop_kw={"per_alpha": (0.5, 0.6)},
                replay_kw={"prioritized": False})
    with pytest.raises(ValueError, match="seed_stride"):
        pop_cfg(2, pop_kw={"seed_stride": -1})
    with pytest.raises(ValueError, match="pbt.frac"):
        pop_cfg(2, pop_kw={"pbt": PBTConfig(frac=0.9)})
    with pytest.raises(ValueError, match="save_model"):
        pop_cfg(2, pop_kw={"pbt": PBTConfig(enabled=True)},
                save_model=False)
    # P=0 composes with everything (the off state)
    assert tiny_cfg(dp_devices=0).population.size == 0


def test_sanity_lattice_legal_and_gated_combos():
    """graftlattice composition surface: population x pallas and
    population x dp are LEGAL now; what remains rejected is the
    divisibility/lockstep/pbt boundary, each naming the mechanism and
    the nearest legal alternative."""
    from t2omca_tpu.config import KernelsConfig, SebulbaConfig
    # population x pallas: vmap-over-pallas — plain legal
    cfg = pop_cfg(2, kernels=KernelsConfig(attention="pallas"))
    assert cfg.population.size == 2 and cfg.kernels.attention == "pallas"
    # population x dp: member axis shards over the mesh when divisible
    cfg = pop_cfg(2, dp_devices=2)
    assert cfg.population.size == 2 and cfg.dp_devices == 2
    with pytest.raises(ValueError, match="not divisible by dp_devices"):
        pop_cfg(3, dp_devices=2)
    with pytest.raises(ValueError, match="divisible P or drop dp_devices"):
        pop_cfg(3, dp_devices=2)
    # population x sebulba: lockstep only (queue_slots=1, staleness=0)
    sb = dict(actor_devices=1, learner_devices=1)
    cfg = pop_cfg(2, sebulba=SebulbaConfig(queue_slots=1, staleness=0,
                                           **sb))
    assert cfg.population.size == 2
    with pytest.raises(ValueError, match="LOCKSTEP"):
        pop_cfg(2, sebulba=SebulbaConfig(queue_slots=2, staleness=0,
                                         **sb))
    with pytest.raises(ValueError, match="staleness=0"):
        pop_cfg(2, sebulba=SebulbaConfig(queue_slots=1, staleness=1,
                                         **sb))
    # pbt x sebulba: save-boundary exploit/explore can't reach the
    # decoupled actor thread mid-epoch
    with pytest.raises(ValueError, match="checkpoint-save boundary"):
        pop_cfg(2, pop_kw={"pbt": PBTConfig(enabled=True)},
                save_model=True,
                sebulba=SebulbaConfig(queue_slots=1, staleness=0, **sb))
    # member axis must tile each sebulba device set
    with pytest.raises(ValueError, match="divisible by sebulba"):
        pop_cfg(3, sebulba=SebulbaConfig(queue_slots=1, staleness=0,
                                         actor_devices=2,
                                         learner_devices=1))


def test_build_spec_neutral_and_gridded():
    cfg = pop_cfg(3)
    spec = graftpop.build_spec(cfg)
    np.testing.assert_array_equal(np.asarray(spec.lr_scale), [1, 1, 1])
    np.testing.assert_array_equal(np.asarray(spec.eps_scale), [1, 1, 1])
    np.testing.assert_allclose(np.asarray(spec.per_alpha),
                               cfg.replay.per_alpha)
    np.testing.assert_array_equal(np.asarray(spec.member), [0, 1, 2])
    g = pop_cfg(2, pop_kw={"lr": (cfg.lr, 2 * cfg.lr),
                           "eps_scale": (1.0, 0.5),
                           "per_alpha": (0.6, 0.8)})
    sg = graftpop.build_spec(g)
    np.testing.assert_allclose(np.asarray(sg.lr_scale), [1.0, 2.0])
    np.testing.assert_allclose(np.asarray(sg.eps_scale), [1.0, 0.5])
    np.testing.assert_allclose(np.asarray(sg.per_alpha), [0.6, 0.8])


def test_cli_bare_int_composes_with_dotted_overrides():
    """The README-documented command line: `population=4
    population.seed_stride=1` must compose in either order (the bare
    int lifts to {size: ...}; the reversed order merges instead of
    silently replacing the dict)."""
    from t2omca_tpu.config import load_config
    c = load_config(overrides=("population=4", "population.seed_stride=0"))
    assert c.population.size == 4 and c.population.seed_stride == 0
    c2 = load_config(overrides=("population.seed_stride=0",
                                "population=4"))
    assert c2.population.size == 4 and c2.population.seed_stride == 0


def test_member_seeds_stride():
    assert graftpop.member_seeds(pop_cfg(3)) == [0, 1, 2]
    assert graftpop.member_seeds(
        pop_cfg(3, pop_kw={"seed_stride": 0})) == [0, 0, 0]
    assert graftpop.member_seeds(
        pop_cfg(3, seed=7, pop_kw={"seed_stride": 10})) == [7, 17, 27]


# ---------------------------------------------------------------------------
# PBT (host-side select-and-perturb)
# ---------------------------------------------------------------------------


def _fake_pop_state(p, val=0.0):
    return {"w": jnp.arange(p, dtype=jnp.float32) + val}


def test_pbt_step_noop_without_full_perf():
    cfg = pop_cfg(4, pop_kw={"pbt": PBTConfig(enabled=True)},
                  save_model=True)
    ts = _fake_pop_state(4)
    spec = graftpop.build_spec(cfg)
    for perf in (None, [1.0, 2.0], [1.0, None, 2.0, 3.0]):
        ts2, spec2, info = graftpop.pbt_step(cfg, ts, spec, perf, 100)
        assert info is None
        assert ts2 is ts and spec2 is spec


def test_pbt_step_copies_losers_from_winners_and_perturbs():
    cfg = pop_cfg(4, pop_kw={"pbt": PBTConfig(enabled=True, frac=0.25,
                                              perturb=1.2)},
                  save_model=True)
    ts = _fake_pop_state(4)
    spec = graftpop.build_spec(cfg)
    perf = [3.0, 1.0, 2.0, 4.0]          # loser: member 1; winner: 3
    ts2, spec2, info = graftpop.pbt_step(cfg, ts, spec, perf, 100)
    assert info == {"copied": {1: 3}, "perf": perf}
    w = np.asarray(ts2["w"])
    np.testing.assert_array_equal(w, [0.0, 3.0, 2.0, 3.0])
    l1 = float(np.asarray(spec2.lr_scale)[1])
    assert any(l1 == pytest.approx(v, rel=1e-6) for v in (1.2, 1 / 1.2))
    # untouched members keep their leaves, member ids never move
    np.testing.assert_array_equal(np.asarray(spec2.member), [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(spec2.lr_scale)[[0, 2, 3]],
                                  [1.0, 1.0, 1.0])
    # deterministic in (seed, t_env): same inputs → same decisions
    ts3, spec3, info3 = graftpop.pbt_step(cfg, ts, spec, perf, 100)
    assert info3 == info
    np.testing.assert_array_equal(np.asarray(spec3.lr_scale),
                                  np.asarray(spec2.lr_scale))


def test_pbt_step_resalts_exploited_rollout_keys():
    """The exploit gather copies the donor's ``runner.key`` verbatim —
    without a re-salt the loser would replay its donor's exact
    trajectories (scenario draws + exploration). Pin: losers' rollout
    keys differ from the donor's after the step; untouched members keep
    theirs; the salt is deterministic."""
    from flax import struct

    @struct.dataclass
    class _Runner:
        key: jnp.ndarray

    @struct.dataclass
    class _State:
        w: jnp.ndarray
        runner: _Runner

    cfg = pop_cfg(4, pop_kw={"pbt": PBTConfig(enabled=True, frac=0.25)},
                  save_model=True)
    keys = jnp.stack([jax.random.PRNGKey(100 + m) for m in range(4)])
    ts = _State(w=jnp.arange(4, dtype=jnp.float32), runner=_Runner(keys))
    spec = graftpop.build_spec(cfg)
    perf = [3.0, 1.0, 2.0, 4.0]                    # loser 1 copies 3
    ts2, _spec2, info = graftpop.pbt_step(cfg, ts, spec, perf, 100)
    assert info["copied"] == {1: 3}
    k2 = np.asarray(ts2.runner.key)
    k0 = np.asarray(keys)
    # loser 1: copied from member 3 then salted — neither its old key
    # nor the donor's
    assert not np.array_equal(k2[1], k0[3])
    assert not np.array_equal(k2[1], k0[1])
    # everyone else untouched
    for m in (0, 2, 3):
        np.testing.assert_array_equal(k2[m], k0[m])
    # deterministic in (t_env, member)
    ts3, _, _ = graftpop.pbt_step(cfg, ts, spec, perf, 100)
    np.testing.assert_array_equal(np.asarray(ts3.runner.key), k2)


def test_pbt_step_rescales_copied_ring_priorities():
    """An exploited member's gathered ring stores the DONOR's
    pre-exponentiated priorities (p^alpha_donor); with a per_alpha grid
    the loser's perturbed exponent would otherwise mix bases in one
    ring — pin the rescale to p^alpha_new and the winner's ring staying
    untouched (zero tail stays zero)."""
    from flax import struct

    @struct.dataclass
    class _Buf:
        priorities: jnp.ndarray

    @struct.dataclass
    class _State:
        w: jnp.ndarray
        buffer: _Buf

    cfg = pop_cfg(2, save_model=True,
                  pop_kw={"per_alpha": (0.6, 0.8),
                          "pbt": PBTConfig(enabled=True, frac=0.5)})
    raw = np.asarray([[2.0, 3.0, 0.0], [4.0, 5.0, 0.0]], np.float32)
    ts = _State(w=jnp.arange(2, dtype=jnp.float32),
                buffer=_Buf(jnp.asarray(raw)))
    spec = graftpop.build_spec(cfg)
    ts2, spec2, info = graftpop.pbt_step(cfg, ts, spec, [1.0, 2.0], 50)
    assert info["copied"] == {0: 1}
    a_new = float(np.asarray(spec2.per_alpha)[0])
    assert a_new != pytest.approx(0.8)
    got = np.asarray(ts2.buffer.priorities)
    np.testing.assert_allclose(got[0], raw[1] ** (a_new / 0.8),
                               rtol=1e-6)
    np.testing.assert_array_equal(got[1], raw[1])
    assert got[0][2] == 0.0                    # unfilled tail inert


def test_pbt_step_p2_frac_clamps_to_disjoint_sets():
    cfg = pop_cfg(2, pop_kw={"pbt": PBTConfig(enabled=True, frac=0.5)},
                  save_model=True)
    ts = _fake_pop_state(2)
    spec = graftpop.build_spec(cfg)
    ts2, spec2, info = graftpop.pbt_step(cfg, ts, spec, [1.0, 2.0], 50)
    assert info["copied"] == {0: 1}
    np.testing.assert_array_equal(np.asarray(ts2["w"]), [1.0, 1.0])


# ---------------------------------------------------------------------------
# stats + sight population surfaces
# ---------------------------------------------------------------------------


class _FakeStats:
    """Minimal RolloutStats stand-in with a leading (P,) member axis."""

    def __init__(self, p, b, seed=0):
        r = np.random.default_rng(seed)
        self.episode_return = jnp.asarray(
            r.normal(size=(p, b)).astype(np.float32))
        self.epsilon = jnp.full((p, b), 0.25, jnp.float32)
        self.task_completion_rate = jnp.asarray(
            r.random((p, b)).astype(np.float32))


def test_stats_accumulator_population_rows_and_ema():
    acc = StatsAccumulator(population=2)
    logger = Logger()
    s = _FakeStats(2, 3)
    acc.push(s)
    assert acc.n_episodes == 6            # total across members
    acc.flush(logger, 10)
    assert "pop0_return_mean" in logger.stats
    assert "pop1_return_mean" in logger.stats
    assert "pop0_task_completion_rate_mean" in logger.stats
    r0 = float(np.asarray(s.episode_return)[0].mean())
    assert logger.stats["pop0_return_mean"][-1][1] == pytest.approx(r0)
    # aggregate row is the across-member mean
    ra = float(np.asarray(s.episode_return).mean())
    assert logger.stats["return_mean"][-1][1] == pytest.approx(ra)
    # EMA survives the flush (the PBT ranking signal)
    assert acc.member_return_ema[0] == pytest.approx(r0)
    acc.push(_FakeStats(2, 3, seed=1))
    acc.flush(logger, 20)
    assert acc.member_return_ema[0] != pytest.approx(r0)


def test_stats_accumulator_p1_keeps_solo_stream():
    acc = StatsAccumulator(population=1)
    logger = Logger()
    acc.push(_FakeStats(1, 3))
    acc.flush(logger, 10)
    assert not any(k.startswith("pop0_") for k in logger.stats)
    assert "return_mean" in logger.stats
    # but the EMA still tracks (PBT needs it even at... P=1 no-op)
    assert acc.member_return_ema[0] is not None


def test_population_sight_monitor_slices_and_names():
    from t2omca_tpu.config import SightConfig
    from t2omca_tpu.obs.sight import PopulationSightMonitor
    logger = Logger()
    mon = PopulationSightMonitor(SightConfig(enabled=True, q_div=10.0),
                                 2, logger=logger)
    info = {"loss": np.asarray([1.0, 2.0]),
            "q_taken_mean": np.asarray([0.5, 99.0]),   # member 1 diverges
            "target_mean": np.asarray([0.5, 99.0]),
            "sight_per_ess": np.asarray([0.9, 0.9])}
    newly = mon.observe(info, 10)
    assert newly == ["pop1:q_divergence"]
    assert mon.members[0].status["q_divergence"]["ok"]
    assert not mon.members[1].status["q_divergence"]["ok"]
    # per-member stat keys rode the same observation
    assert "pop0_sight_per_ess" in logger.stats
    assert "pop1_sight_per_ess" in logger.stats
    # /healthz names carry the member tag
    names = []

    class _Hub:
        def health(self, name, fn):
            names.append(name)
    mon.wire_pulse(_Hub())
    assert "sight-pop0-q_divergence" in names
    assert "sight-pop1-q_divergence" in names
    rep = mon.report()
    assert rep["population"] == 2 and len(rep["members"]) == 2


def test_learning_cli_renders_member_table():
    from t2omca_tpu.obs.sight import render_learning
    series = {
        "return_mean": [(10, 1.0), (20, 2.0)],
        "pop0_return_mean": [(10, 1.5), (20, 2.5)],
        "pop1_return_mean": [(10, 0.5), (20, 1.5)],
        "pop0_loss": [(20, 0.25)],
        "pop1_sight_alert_q_divergence": [(20, 1.0)],
    }
    out = "\n".join(render_learning("/tmp/x", series))
    assert "population members (2" in out
    assert "pop0" in out and "pop1" in out
    assert "q_divergence" in out          # member 1's standing alert


# ---------------------------------------------------------------------------
# checkpoint lift (v4 single-member → v5 PopState)
# ---------------------------------------------------------------------------


def test_lift_population_replicates_single_member_raw():
    from flax import serialization

    from t2omca_tpu.utils.checkpoint import _migrate_raw
    solo = {"w": np.arange(3, dtype=np.float32), "b": np.float32(2.0)}
    cfg = pop_cfg(2)
    spec = graftpop.build_spec(cfg)
    target = graftpop.PopState(
        ts={"w": jnp.zeros((2, 3), jnp.float32),
            "b": jnp.zeros((2,), jnp.float32)},
        spec=spec)
    raw = _migrate_raw({"format": 4},
                       serialization.to_state_dict(
                           {"w": solo["w"], "b": solo["b"]}), target)
    assert set(raw) == {"ts", "spec"}
    np.testing.assert_array_equal(raw["ts"]["w"],
                                  np.stack([solo["w"]] * 2))
    np.testing.assert_array_equal(raw["spec"]["lr_scale"], [1.0, 1.0])


@pytest.mark.slow
def test_v4_single_member_checkpoint_lifts_into_population(tmp_path):
    """A pre-population (single-member) checkpoint restores into a P=2
    population template with every member replicated from it — and the
    meta doctored to format 4 takes the same path (the lift keys on
    STRUCTURE, so v4 and v5 single-member trees both lift)."""
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(cfg.seed)
    d = save_checkpoint(str(tmp_path), 24, ts)
    # doctor the sidecar to the v4 format a real pre-population run wrote
    meta_path = os.path.join(d, "meta.json")
    meta = json.load(open(meta_path))
    meta["format"] = 4
    json.dump(meta, open(meta_path, "w"))

    pcfg = pop_cfg(2)
    pexp = Experiment.build(pcfg)
    pts, spec = graftpop.init_population(pexp, pcfg)
    restored = load_checkpoint(
        d, graftpop.PopState(ts=pts, spec=spec), verify=False)
    for (kp, x), (_, y) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts)),
            jax.tree_util.tree_leaves_with_path(
                jax.device_get(restored.ts))):
        x, y = np.asarray(x), np.asarray(y)
        path = jax.tree_util.keystr(kp)
        assert y.shape == (2,) + x.shape, path
        np.testing.assert_array_equal(y[0], x, err_msg=path)
        if ".runner" in path and "key" in path.rsplit(".", 1)[-1]:
            # members 1..P-1 get a re-salted rollout key — a verbatim
            # replica would make every member draw the SAME
            # trajectories for the rest of the run
            assert not np.array_equal(y[1], x), path
        else:
            np.testing.assert_array_equal(y[1], x, err_msg=path)
    # the template's spec came through
    np.testing.assert_array_equal(np.asarray(restored.spec.member),
                                  [0, 1])


@pytest.mark.slow
def test_population_checkpoint_roundtrips_popstate(tmp_path):
    cfg = pop_cfg(2, pop_kw={"lr": (5e-4, 1e-3)})
    exp = Experiment.build(cfg)
    ts, spec = graftpop.init_population(exp, cfg)
    ps = graftpop.PopState(ts=ts, spec=spec)
    d = save_checkpoint(str(tmp_path), 24, ps)
    ts2, spec2 = graftpop.init_population(exp, cfg)
    restored = load_checkpoint(
        d, graftpop.PopState(ts=ts2, spec=spec2), verify=True)
    _assert_trees_equal(ps, restored)


# ---------------------------------------------------------------------------
# the parity / divergence / one-dispatch contracts (compile-heavy)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_p1_population_bit_identical_to_classic_superstep_loop():
    """THE acceptance pin: a P=1 population with a neutral spec is
    bit-identical to the classic fused loop — params, opt_state, replay
    ring, PER priorities, runner state AND the emitted stats stream."""
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts_c, stats_c = _classic_superstep_loop(exp, 2, 3)

    cfgp = pop_cfg(1)
    expp = Experiment.build(cfgp)
    ts_p, _spec, stats_p = _pop_loop(expp, cfgp, 2, 3)

    _assert_trees_equal(ts_c, ts_p, strip_member=True, msg="state ")
    for sc, sp in zip(stats_c, stats_p):
        _assert_trees_equal(sc, sp, strip_member=True, msg="stats ")


@pytest.mark.slow
def test_p2_seeds_diverge_and_member0_tracks_solo():
    """Default stride: the two members (seeds 0, 1) must DIVERGE —
    different rollouts, different params. Member 0 tracks its solo run
    to float tolerance over the first dispatches (cross-rank bit-parity
    under vmap is impossible: batched f32 reduces reassociate — the
    squeeze-path docstring; the exact contract lives at P=1)."""
    cfgp = pop_cfg(2)
    expp = Experiment.build(cfgp)
    ts_p, _spec, _stats = _pop_loop(expp, cfgp, 2, 2)
    params = jax.device_get(ts_p.learner.params)
    # members diverged (different seeds → different episodes → params)
    diffs = [not np.array_equal(np.asarray(x)[0], np.asarray(x)[1])
             for x in jax.tree.leaves(params)]
    assert any(diffs), "different seeds must diverge"

    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts_c, _ = _classic_superstep_loop(exp, 2, 2)
    for (kp, x), (_, y) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts_c)),
            jax.tree_util.tree_leaves_with_path(ts_p)):
        x, y = np.asarray(x), np.asarray(y)[0]
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(
                y, x, rtol=2e-3, atol=2e-3,
                err_msg=jax.tree_util.keystr(kp))
        else:
            np.testing.assert_array_equal(
                y, x, err_msg=jax.tree_util.keystr(kp))


@pytest.mark.slow
def test_p2_stride0_members_bit_identical():
    """seed_stride=0 (identical seeds, neutral grids, no salt): the two
    members are bit-identical to EACH OTHER forever — vmap applies the
    same batched kernel to identical per-member inputs. The invariant
    that makes grid comparisons controlled."""
    cfgp = pop_cfg(2, pop_kw={"seed_stride": 0})
    expp = Experiment.build(cfgp)
    ts_p, _spec, stats = _pop_loop(expp, cfgp, 2, 3)
    for kp, x in jax.tree_util.tree_leaves_with_path(
            jax.device_get(ts_p)):
        x = np.asarray(x)
        np.testing.assert_array_equal(x[0], x[1],
                                      err_msg=jax.tree_util.keystr(kp))


@pytest.mark.slow
@pytest.mark.analysis
def test_population_superstep_compiles_once():
    """compile_budget(1): 3 donated population dispatches, ONE compile
    (the t_env weak-type discipline holds on the population rank too)."""
    cfgp = pop_cfg(2)
    expp = Experiment.build(cfgp)
    ts, spec = graftpop.init_population(expp, cfgp)
    prog = expp.population_superstep_program(2, donate=True)
    keys = jnp.stack([jax.random.split(jax.random.PRNGKey(m), 2)
                      for m in range(2)])
    spr = cfgp.batch_size_run * cfgp.env_args.episode_limit
    with compile_budget(1, match="_superstep_pop"):
        for i in range(3):
            ts, stats, infos = prog(
                ts, keys, jnp.asarray(i * 2 * spr), spec)
    assert prog._cache_size() == 1
    # the donated dispatch advanced every member
    assert np.asarray(jax.device_get(ts.episode)).tolist() == [12, 12]


@pytest.mark.slow
def test_run_sequential_population_end_to_end(tmp_path):
    """The real driver at population=2: completes, logs per-member
    pop<i>_* rows, saves a PopState checkpoint that a fresh run
    resumes."""
    logger = Logger()
    cfg = pop_cfg(2, tmp_path, t_max=70, superstep=2, save_model=True,
                  test_interval=36, log_interval=24,
                  runner_log_interval=24)
    ts = run(cfg, logger)
    assert np.asarray(jax.device_get(ts.episode)).shape == (2,)
    for key in ("pop0_loss", "pop1_loss", "pop0_return_mean",
                "pop1_return_mean", "loss", "return_mean"):
        assert key in logger.stats, key
    # the checkpoint holds a PopState a fresh population run can resume
    from t2omca_tpu.utils.checkpoint import find_checkpoint
    model_dir = os.path.join(
        str(tmp_path), "models",
        os.listdir(os.path.join(str(tmp_path), "models"))[0])
    found = find_checkpoint(model_dir)
    assert found is not None
    cfg2 = pop_cfg(2, tmp_path, t_max=70, superstep=2, save_model=True,
                   checkpoint_path=model_dir, test_interval=36,
                   log_interval=24, runner_log_interval=24)
    ts2 = run(cfg2, Logger())
    assert np.asarray(jax.device_get(ts2.episode)).shape == (2,)


@pytest.mark.slow
def test_run_sequential_population_pbt_fires(tmp_path):
    """PBT at the save boundary: with runner-log flushes feeding the
    member EMA before the save cadence, the exploit/explore pass runs
    and logs pbt_copies (exactly one loser at P=2 frac=0.5)."""
    logger = Logger()
    cfg = pop_cfg(
        2, tmp_path, t_max=94, superstep=2, save_model=True,
        save_model_interval=24, test_interval=1_000_000,
        log_interval=12, runner_log_interval=12,
        pop_kw={"pbt": PBTConfig(enabled=True, frac=0.5, perturb=1.3)})
    run(cfg, logger)
    assert "pbt_copies" in logger.stats
    assert logger.stats["pbt_copies"][-1][1] == 1.0


# ---------------------------------------------------------------------------
# mixer-side padding mask (ROADMAP item 3's open remainder)
# ---------------------------------------------------------------------------


def _pad_cfg(pad: bool):
    from t2omca_tpu.config import ScenarioConfig
    env_kw = ({"scenario": ScenarioConfig(kind="uniform", min_agents=2)}
              if pad else {})
    return tiny_cfg(batch_size_run=4,
                    env_kw={"agv_num": 4, **env_kw})


def test_mask_padded_gate_is_config_static():
    from t2omca_tpu.envs.graftworld import distribution_can_pad
    from t2omca_tpu.envs.registry import make_scenario_distribution
    assert Experiment.build(_pad_cfg(True)).learner._mask_padded
    assert not Experiment.build(_pad_cfg(False)).learner._mask_padded
    # the predicate itself: fixed full-fleet never pads; uniform with
    # min_agents below the fleet does
    cfg = _pad_cfg(False)
    assert not distribution_can_pad(
        make_scenario_distribution(cfg.env_args), 4)
    cfgp = _pad_cfg(True)
    assert distribution_can_pad(
        make_scenario_distribution(cfgp.env_args), 4)


@pytest.mark.slow
def test_padding_mask_invariance_and_full_fleet_parity():
    """The ISSUE-15 satellite pins: (a) padded agents enter the mixer
    NEUTRALLY — garbage written into their stored obs changes neither
    the loss nor the updated params, bit-for-bit; (b) at full fleet the
    masked loss program is bit-identical to the unmasked one (active
    agents multiply by 1.0 — bitwise identity)."""
    cfg_pad, cfg_plain = _pad_cfg(True), _pad_cfg(False)
    exp_pad, exp_plain = Experiment.build(cfg_pad), Experiment.build(
        cfg_plain)

    ts = exp_pad.init_train_state(0)
    rollout = exp_pad.jitted_programs()[0]
    _rs, batch, _stats = rollout(ts.learner.params["agent"], ts.runner,
                                 False)
    avail = np.asarray(jax.device_get(batch.avail_actions))
    act_m = (avail[..., 1:] > 0).any(axis=(1, 3))      # (B, A)
    assert (~act_m).any(), "the uniform min_agents=2 draw must pad"
    assert act_m.any(axis=1).all(), "every lane keeps active agents"

    key = jax.random.PRNGKey(5)
    w = jnp.ones((cfg_pad.batch_size,), jnp.float32)
    ls1, info1 = exp_pad.learner.train(ts.learner, batch, w,
                                       jnp.asarray(24), jnp.asarray(4),
                                       key)
    obs = np.asarray(jax.device_get(batch.obs)).copy()
    b_idx, a_idx = np.where(~act_m)
    obs[b_idx, :, a_idx] = 777.0                       # garbage rows
    ls2, info2 = exp_pad.learner.train(
        ts.learner, batch.replace(obs=jnp.asarray(obs)), w,
        jnp.asarray(24), jnp.asarray(4), key)
    assert float(info1["loss"]) == float(info2["loss"])
    _assert_trees_equal(ls1.params, ls2.params, msg="tampered-pad ")

    # (b) full fleet: the masked program (pad-capable config) on an
    # all-active batch bit-matches the unmasked program
    ts_plain = exp_plain.init_train_state(0)
    _rs, batch_full, _ = exp_plain.jitted_programs()[0](
        ts_plain.learner.params["agent"], ts_plain.runner, False)
    lsA, infoA = exp_pad.learner.train(ts_plain.learner, batch_full, w,
                                       jnp.asarray(24), jnp.asarray(4),
                                       key)
    lsB, infoB = exp_plain.learner.train(ts_plain.learner, batch_full, w,
                                         jnp.asarray(24), jnp.asarray(4),
                                         key)
    assert float(infoA["loss"]) == float(infoB["loss"])
    _assert_trees_equal(lsA.params, lsB.params, msg="full-fleet ")


@pytest.mark.slow
def test_padding_mask_suffix_rule_spares_interior_jobless_agent():
    """An ACTIVE agent that never saw a job is avail-indistinguishable
    from a padded one — but padding is always a trailing block, so the
    suffix rule masks an idle-only-forever agent ONLY when every agent
    after it is idle-only too. Pin: an interior idle-only agent
    (followed by a job-seeing agent) still contributes to the loss —
    garbage in its obs CHANGES the result."""
    cfg = _pad_cfg(True)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    rollout = exp.jitted_programs()[0]
    _rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner, False)
    avail = np.asarray(jax.device_get(batch.avail_actions)).copy()
    act_m = (avail[..., 1:] > 0).any(axis=(1, 3))      # (B, A)
    lane = int(np.argmax(act_m.all(axis=1)))           # a full-fleet lane
    assert act_m[lane].all()
    # simulate a jobless INTERIOR agent: idle-only at every step, but
    # agents after it keep their jobs
    idle_only = np.zeros_like(avail[:, lane, 1])
    idle_only[..., 0] = 1
    avail[:, lane, 1] = idle_only
    batch_a = batch.replace(avail_actions=jnp.asarray(avail))
    key = jax.random.PRNGKey(5)
    w = jnp.ones((cfg.batch_size,), jnp.float32)
    _ls1, info1 = exp.learner.train(ts.learner, batch_a, w,
                                    jnp.asarray(24), jnp.asarray(4), key)
    obs = np.asarray(jax.device_get(batch.obs)).copy()
    obs[lane, :, 1] = 333.0
    batch_b = batch_a.replace(obs=jnp.asarray(obs))
    _ls2, info2 = exp.learner.train(ts.learner, batch_b, w,
                                    jnp.asarray(24), jnp.asarray(4), key)
    assert float(info1["loss"]) != float(info2["loss"]), \
        "interior jobless agent must NOT be masked out of the loss"


# ---------------------------------------------------------------------------
# per-member scenario decorrelation
# ---------------------------------------------------------------------------


def test_member_scenario_key_decorrelates_and_salt_gates():
    from t2omca_tpu.envs.graftworld import member_scenario_key
    k = jax.random.PRNGKey(3)
    k0 = member_scenario_key(k, jnp.asarray(0))
    k1 = member_scenario_key(k, jnp.asarray(1))
    assert not np.array_equal(np.asarray(k0), np.asarray(k1))
    # fold_in(k, 0) is NOT the identity — which is exactly why
    # scenario_salt defaults off (member 0 must match the solo stream)
    assert not np.array_equal(np.asarray(k0), np.asarray(k))


@pytest.mark.slow
def test_sample_scenarios_member_salt():
    """The runner's per-member scenario seam: different members draw
    different EnvParams from the same key chain; member=None keeps the
    pre-population draw bit-identical."""
    cfg = _pad_cfg(True)
    exp = Experiment.build(cfg)
    key = jax.random.PRNGKey(9)
    base = exp.runner._sample_scenarios(key)
    same = exp.runner._sample_scenarios(key, member=None)
    _assert_trees_equal(base, same, msg="member=None ")
    m0 = exp.runner._sample_scenarios(key, member=jnp.asarray(0))
    m1 = exp.runner._sample_scenarios(key, member=jnp.asarray(1))
    diff = any(
        not np.array_equal(np.asarray(jax.device_get(a)),
                           np.asarray(jax.device_get(b)))
        for a, b in zip(jax.tree.leaves(m0), jax.tree.leaves(m1)))
    assert diff, "member salts must decorrelate the draws"


@pytest.mark.slow
@pytest.mark.sight
def test_population_sight_keys_per_member(tmp_path):
    """graftsight over the population axis (ISSUE-15 satellite): the
    in-graph diagnostics vmap with the train step (PR 14's reduces are
    rank-polymorphic) and each member's sight_* keys land as
    pop<i>_sight_* on the same log-cadence fetch."""
    from t2omca_tpu.config import ObsConfig, SightConfig
    logger = Logger()
    cfg = pop_cfg(2, tmp_path, t_max=40, superstep=2,
                  log_interval=12, runner_log_interval=12,
                  obs=ObsConfig(sight=SightConfig(enabled=True, bins=8)))
    run(cfg, logger)
    for member in (0, 1):
        keys = [k for k in logger.stats
                if k.startswith(f"pop{member}_sight_")]
        assert any("grad_norm" in k for k in keys), keys
        assert any("per_ess" in k for k in keys), keys
        assert any("attn_entropy" in k for k in keys), keys
