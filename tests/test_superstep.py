"""The fused training superstep (``run.Experiment.superstep_program``,
``config.superstep``): one donated XLA program scanning K rollout → ring
insert → gated sample+train iterations per dispatch (Anakin/Podracer,
PAPERS.md). Pins the contract the driver relies on: bit-identical
training vs the classic three-program loop (RNG key threading preserved),
gate correctness across the ``can_sample``/``accumulated_episodes``
boundary, one-dispatch-per-K in the real driver, donation safety, and
the resilience interplay (ShutdownGuard at a dispatch boundary,
non-finite guard inside the scan)."""

import glob
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.analysis import (CompileBudgetExceeded, compile_budget,
                                 no_transfer)
from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               ResilienceConfig, TrainConfig, sanity_check)
from t2omca_tpu.run import Experiment, run, superstep_eligible
from t2omca_tpu.utils import resilience
from t2omca_tpu.utils.checkpoint import find_checkpoint
from t2omca_tpu.utils.logging import Logger


@pytest.fixture(autouse=True)
def _no_fault_leaks():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


def tiny_cfg(tmp_path=None, **kw):
    """Shrunk config-1 parity point (configs/config1_cpu_parity.yaml knobs:
    fast_norm off → sequential normalizer, dense obs storage — the
    bit-comparable path) at test scale."""
    env_kw = kw.pop("env_kw", {})
    replay_kw = kw.pop("replay_kw", {})
    res_kw = kw.pop("res_kw", {})
    defaults = dict(
        t_max=60, batch_size_run=2, batch_size=4, test_interval=1_000_000,
        test_nepisode=2, log_interval=12, runner_log_interval=12,
        save_model=False, save_model_interval=24,
        epsilon_anneal_time=50,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6, fast_norm=False, **env_kw),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8, **replay_kw),
        resilience=ResilienceConfig(**res_kw),
    )
    if tmp_path is not None:
        defaults["local_results_path"] = str(tmp_path)
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


def _three_program_loop(exp, n_iters, accumulated=0):
    """The classic driver train path, verbatim (run.run_sequential K=1):
    host-gated train, conditional key split."""
    cfg = exp.cfg
    ts = exp.init_train_state(cfg.seed)
    rollout, insert, train_iter = exp.jitted_programs()
    key = jax.random.PRNGKey(cfg.seed + 1)
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env, episode, filled = 0, 0, 0
    infos = []
    for _ in range(n_iters):
        rs, batch, stats = rollout(ts.learner.params["agent"], ts.runner,
                                   test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=ts.episode + cfg.batch_size_run)
        t_env += spr
        episode += cfg.batch_size_run
        filled = min(filled + cfg.batch_size_run, exp.buffer.capacity)
        if filled >= cfg.batch_size and episode >= accumulated:
            key, k_sample = jax.random.split(key)
            ts, info = train_iter(ts, k_sample, jnp.asarray(t_env))
            infos.append(info)
    return ts, infos


def _superstep_loop(exp, k, n_dispatches, accumulated=0, donate=False):
    """The driver's K>1 path, verbatim: host mirror of the gate drives
    the conditional key splits; zeros for skipped rows."""
    cfg = exp.cfg
    ts = exp.init_train_state(cfg.seed)
    superstep = exp.superstep_program(k, donate=donate)
    key = jax.random.PRNGKey(cfg.seed + 1)
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env, episode, filled = 0, 0, 0
    all_stats, kept = [], []
    for _ in range(n_dispatches):
        rows, gated = [], []
        for _ in range(k):
            episode += cfg.batch_size_run
            filled = min(filled + cfg.batch_size_run, exp.buffer.capacity)
            g = filled >= cfg.batch_size and episode >= accumulated
            gated.append(g)
            if g:
                key, k_sample = jax.random.split(key)
                rows.append(k_sample)
            else:
                rows.append(jnp.zeros_like(key))
        ts, stats, infos = superstep(ts, jnp.stack(rows),
                                     jnp.asarray(t_env))
        t_env += k * spr
        all_stats.append(stats)
        kept.extend(jax.tree.map(lambda x, i=i: x[i], infos)
                    for i, g in enumerate(gated) if g)
    return ts, all_stats, kept


def test_superstep_bit_identical_to_three_program_loop():
    """8 iterations at the parity config: K=4 (2 dispatches) must end on
    EXACTLY the params/opt-state/priorities of the K=1 three-program loop
    — same values, same RNG streams, gate opening mid-dispatch (buffer
    fills at iteration 2, accumulated_episodes passes at iteration 3)."""
    cfg = tiny_cfg(accumulated_episodes=6)
    exp = Experiment.build(cfg)
    ts1, infos1 = _three_program_loop(exp, 8, accumulated=6)
    ts4, _, infos4 = _superstep_loop(exp, 4, 2, accumulated=6)

    assert int(jax.device_get(ts1.learner.train_steps)) == 6   # iters 3..8
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts1)),
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts4))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(kp))
    # per-step train infos line up too (losses bit-equal)
    assert len(infos1) == len(infos4)
    for a, b in zip(infos1, infos4):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a["loss"])),
                                      np.asarray(jax.device_get(b["loss"])))


@pytest.mark.slow   # extra K=3 compile (~17 s); gate boundaries also pinned by the parity + dispatch tests
def test_superstep_gate_counts_train_steps():
    """Gate arithmetic on the carried counters: with buffer capacity 8 and
    batch 4, training starts at iteration 2; accumulated_episodes=10
    delays it to iteration 5 (episode 10) — wherever that lands inside a
    dispatch."""
    cfg = tiny_cfg(accumulated_episodes=10)
    exp = Experiment.build(cfg)
    ts, _, kept = _superstep_loop(exp, 3, 2, accumulated=10)
    # iterations 5 and 6 of 6 train
    assert int(jax.device_get(ts.learner.train_steps)) == 2
    assert len(kept) == 2
    assert all(bool(jax.device_get(i["all_finite"])) for i in kept)


@pytest.mark.slow   # extra donated compile (~19 s); the in-gate run() test executes the donated program
def test_superstep_donation_updates_in_place():
    """donate=True must consume the input TrainState (ring updated in
    place — the HBM contract the production driver relies on) and keep a
    single compiled executable across chained dispatches."""
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    superstep = exp.superstep_program(2, donate=True)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    pre_leaves = [x for x in jax.tree.leaves(ts) if isinstance(x, jax.Array)]
    ts, stats, infos = superstep(ts, keys, jnp.zeros((), jnp.int32))
    ts, stats, infos = superstep(ts, keys, jnp.asarray(24, jnp.int32))
    assert all(x.is_deleted() for x in pre_leaves), \
        "superstep must consume (donate) the train state"
    assert superstep._cache_size() == 1
    ret = np.asarray(jax.device_get(stats.episode_return))
    assert ret.shape[0] == 2 and np.isfinite(ret).all()
    assert int(jax.device_get(ts.episode)) == 8


def test_run_sequential_issues_one_dispatch_per_k(tmp_path, monkeypatch):
    """The real driver at superstep=3: exactly ONE fused dispatch per 3
    iterations — counted by wrapping the program the driver builds."""
    calls = []
    orig = Experiment.superstep_program

    def counting(self, k, **kw):
        prog = orig(self, k, **kw)

        def wrapped(*a, **k2):
            calls.append(1)
            return prog(*a, **k2)
        return wrapped

    monkeypatch.setattr(Experiment, "superstep_program", counting)
    # spr = 12; t_max=70 → dispatches at t_env 0 and 36 (72 > 70 ends)
    cfg = tiny_cfg(tmp_path, t_max=70, superstep=3, save_model=True,
                   log_interval=36, runner_log_interval=36)
    ts = run(cfg, Logger())
    assert len(calls) == 2
    t_end = int(jax.device_get(ts.runner.t_env))
    assert t_end == 2 * 3 * 12                     # K-aligned boundary
    assert int(jax.device_get(ts.learner.train_steps)) == 5  # iters 2..6


def test_superstep_ineligible_on_host_buffer(tmp_path):
    """buffer_cpu_only keeps the three-program path (eligibility
    predicate; the host-buffer driver e2e itself is
    test_driver::test_host_buffer_branch_end_to_end) and
    superstep_program must refuse the host buffer outright."""
    cfg = tiny_cfg(tmp_path, superstep=2,
                   replay_kw=dict(buffer_cpu_only=True))
    assert not superstep_eligible(cfg)
    assert superstep_eligible(tiny_cfg(superstep=2))
    assert not superstep_eligible(tiny_cfg())          # K=1: classic loop
    exp = Experiment.build(cfg)
    with pytest.raises(ValueError, match="buffer_cpu_only"):
        exp.superstep_program(2)


@pytest.mark.faultinject
def test_shutdown_guard_exits_at_dispatch_boundary(tmp_path):
    """SIGTERM mid-run under superstep=2: the orderly exit lands at a
    DISPATCH boundary (t_env a multiple of K·B·T) with the emergency
    checkpoint covering it — preemption loses at most K iterations."""
    cfg = tiny_cfg(tmp_path, t_max=100_000, superstep=2, save_model=True,
                   save_model_interval=10_000)

    def _preempt(t_env, guard, **kw):
        if t_env >= 48:
            signal.raise_signal(signal.SIGTERM)

    resilience.register_fault("driver.iteration", _preempt)
    ts = run(cfg, Logger())
    stopped_at = int(jax.device_get(ts.runner.t_env))
    assert stopped_at < cfg.t_max
    assert stopped_at % (2 * 12) == 0              # dispatch-aligned
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    found = find_checkpoint(model_dir)
    assert found is not None and found[1] >= 48
    assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL


@pytest.mark.faultinject
def test_nonfinite_guard_trips_inside_scan(tmp_path):
    """resilience.inject_nan_at_step inside the fused scan: the tripped
    sub-iteration must be a no-op on params (guard inside jit) and the
    driver must see its all_finite flag through the stacked infos at the
    log cadence."""
    # one injected step → streak 1 < the default tolerance 3: the guard
    # skips the update but no restore escalation fires
    cfg = tiny_cfg(tmp_path, t_max=60, superstep=2, save_model=False,
                   log_interval=12, res_kw=dict(inject_nan_at_step=1))
    ts = run(cfg, Logger())
    leaves = jax.tree.leaves(jax.device_get(ts.learner.params))
    assert all(np.isfinite(np.asarray(x)).all() for x in leaves)
    # the injected step was counted (nonfinite_steps metric logged)
    import json
    rows = []
    for p in glob.glob(os.path.join(tmp_path, "*", "metrics.jsonl")):
        with open(p) as f:
            rows.extend(json.loads(l)["key"] for l in f)
    assert "nonfinite_steps" in rows


# --------------------------------------------------------------------------
# tracing-hygiene enforcement at the program level (t2omca_tpu/analysis,
# docs/ANALYSIS.md): the fused superstep's whole value is ONE compile and
# ZERO host round-trips per K iterations — pinned here with the runtime
# guards. Cheap toy-program guard tests (always in gate): tests/test_analysis.py.


@pytest.mark.slow   # full superstep compile (~17 s) x2
@pytest.mark.analysis
def test_superstep_program_compile_budget():
    """`Experiment.superstep_program` compiles exactly ONCE across K
    dispatches — a silent retrace would erase the dispatch-amortization
    win (the bug class run._strong exists to stop). And the budget must
    FAIL when the program is made to retrace: passing a raw Python
    scalar for t_env0 (instead of the committed int32 array the driver
    passes) flips the aval to weak-typed and recompiles."""
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    superstep = exp.superstep_program(2)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    with compile_budget(1, match="_superstep") as log:
        for i in range(3):
            ts, stats, infos = superstep(ts, keys,
                                         jnp.asarray(i * 24, jnp.int32))
    assert log.count == 1
    assert np.isfinite(
        np.asarray(jax.device_get(stats.episode_return))).all()

    # retrace demonstration (ISSUE 3 acceptance): same computation, but
    # one dispatch passes a Python scalar -> weak_type aval -> recompile
    prog2 = exp.superstep_program(2)
    ts2 = exp.init_train_state(0)
    with pytest.raises(CompileBudgetExceeded, match="_superstep"):
        with compile_budget(1, match="_superstep"):
            ts2, _, _ = prog2(ts2, keys, jnp.asarray(0, jnp.int32))
            prog2(ts2, keys, 24)


@pytest.mark.slow   # mesh-sharded superstep compile on the 8-device CPU mesh
@pytest.mark.analysis
def test_dataparallel_superstep_compile_budget():
    """`DataParallel.superstep_program` too: the constraint hooks pin
    output shardings to the canonical input placement, so dispatch 2+
    reuses the executable — GSPMD choosing a different output sharding
    would silently compile a second program every iteration."""
    from t2omca_tpu.parallel import DataParallel, make_mesh
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    dp = DataParallel(exp, make_mesh(2))
    ts = dp.init_sharded(cfg.seed)           # born sharded, outside budget
    superstep = dp.superstep_program(2)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    with compile_budget(1, match="_superstep") as log:
        for i in range(3):
            ts, stats, infos = superstep(ts, keys,
                                         jnp.asarray(i * 24, jnp.int32))
    assert log.count == 1
    assert int(jax.device_get(ts.episode)) == 12


@pytest.mark.slow   # rollout+insert+train compiles (~15 s)
@pytest.mark.analysis
def test_train_iter_compile_budget():
    """The classic-loop learner step (`_train_iter`) holds one compile
    across iterations at fixed shapes — the driver feeds back
    weak-type-stripped state (run._strong) precisely so iteration 2
    doesn't silently recompile."""
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(cfg.seed)
    rollout, insert, train_iter = exp.jitted_programs()
    key = jax.random.PRNGKey(cfg.seed + 1)
    spr = cfg.batch_size_run * cfg.env_args.episode_limit
    t_env = 0
    for _ in range(2):                       # fill to batch_size episodes
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=ts.episode + cfg.batch_size_run)
        t_env += spr
    with compile_budget(1, match="_train_iter") as log:
        for _ in range(3):
            key, k = jax.random.split(key)
            t_env += spr
            ts, info = train_iter(ts, k, jnp.asarray(t_env))
    assert log.count == 1
    assert int(jax.device_get(ts.learner.train_steps)) == 3


@pytest.mark.slow   # superstep compile (~17 s)
@pytest.mark.analysis
def test_superstep_no_implicit_transfer_between_dispatches():
    """One fused dispatch on the K>1 path runs with ZERO implicit host
    transfers: every per-dispatch input is a committed device array
    (keys stack, int32 t_env), every output stays on device. A Python
    scalar sneaking into the dispatch args — simultaneously a retrace
    hazard, see above — is exactly what the guard rejects. (On this CPU
    backend only the host→device direction has teeth; on a real
    accelerator no_transfer() also rejects implicit device→host
    fetches between boundaries.)"""
    cfg = tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    superstep = exp.superstep_program(2)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    ts, stats, infos = superstep(ts, keys, jnp.asarray(0, jnp.int32))
    # compile + constant upload happened above; dispatch 2 must be clean
    t1 = jnp.asarray(24, jnp.int32)
    with no_transfer():
        ts, stats, infos = superstep(ts, keys, t1)
        jax.block_until_ready(stats.epsilon)   # barrier, not a transfer
    # seeded hazard: a per-dispatch Python scalar is an implicit upload
    with pytest.raises(Exception, match="[Tt]ransfer"):
        with no_transfer():
            superstep(ts, keys, 48)
    # explicit cadence-boundary fetches stay allowed under the guard
    with no_transfer():
        assert int(jax.device_get(ts.episode)) == 8
