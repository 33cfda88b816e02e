"""graftmorph — topology-elastic checkpoint restore (docs/RESILIENCE.md
§6, ``utils/elastic.py`` + the elastic half of ``utils/checkpoint.py``).

Pins the elastic matrix: the meta.json topology stamp round-trips and
routes resumes (same shape → the rigid bit-exact paths, population
mismatch → ``restore_elastic``), per-host shard saves assemble back
into one complete state and are valid ONLY when every shard landed
(``find_checkpoint`` skips an incomplete set — the all-shards-or-skip
gate), dp N↔M restores are bit-identical through the leaf-streamed
path, population P grows (fold_in-salted runner keys, so no two members
share a trajectory stream) and shrinks (best-ranked members kept when
an EMA ranking exists, prefix otherwise), the checked-in v3 fixture
drives the full v3→v4→v5 migration chain from real frozen bytes, and
the classic↔sebulba loop flip resumes across shapes. The coordinated-
preemption negotiation's single-host and injected-failure legs are here
too; the multi-host SIGKILL leg lives in tests/test_multihost.py and
the driver-level chaos scenarios in tests/test_chaos.py."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization, struct
from jax.sharding import NamedSharding, PartitionSpec as P

from t2omca_tpu import population as graftpop
from t2omca_tpu.config import (EnvConfig, ModelConfig, PopulationConfig,
                               ReplayConfig, ResilienceConfig,
                               SebulbaConfig, TrainConfig, sanity_check)
from t2omca_tpu.parallel import distributed as dist
from t2omca_tpu.parallel import make_mesh
from t2omca_tpu.run import Experiment, run_sequential
from t2omca_tpu.utils import elastic, resilience
from t2omca_tpu.utils.checkpoint import (CheckpointIntegrityError,
                                         find_checkpoint, load_checkpoint,
                                         load_checkpoint_sharded,
                                         restore_elastic,
                                         restore_host_state,
                                         save_checkpoint,
                                         save_checkpoint_shards,
                                         verify_checkpoint, write_shard)
from t2omca_tpu.utils.logging import Logger

from tests.fixture_ckpt_v3 import FIXTURE_DIR, FIXTURE_STEP, fixture_cfg

pytestmark = pytest.mark.elastic


@pytest.fixture(autouse=True)
def _no_fault_leaks():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


# ------------------------------------------------------- tiny structures

@struct.dataclass
class _Runner:
    key: jnp.ndarray
    t_env: jnp.ndarray


@struct.dataclass
class _TS:
    runner: _Runner
    w: jnp.ndarray


def _bare(seed=0, n=8):
    """A minimal checkpointable state with the two leaves the elastic
    machinery treats specially (``runner.key`` for re-salting, a bulk
    ``w`` for data movement)."""
    return _TS(runner=_Runner(key=jax.random.PRNGKey(seed),
                              t_env=jnp.asarray(seed, jnp.int32)),
               w=jnp.arange(seed, seed + 2 * n, dtype=jnp.float32
                            ).reshape(n, 2))


def _pop(p, n=8):
    """A P-member PopState over ``_TS`` (leading (P,) axis on every
    leaf), members distinguishable by content."""
    ts = jax.tree.map(lambda *xs: jnp.stack(xs),
                      *[_bare(seed=m, n=n) for m in range(p)])
    spec = graftpop.PopulationSpec(
        lr_scale=jnp.arange(p, dtype=jnp.float32) + 1.0,
        eps_scale=jnp.ones((p,), jnp.float32),
        per_alpha=jnp.full((p,), 0.6, jnp.float32),
        member=jnp.arange(p, dtype=jnp.int32))
    return graftpop.PopState(ts=ts, spec=spec)


def _eq(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for la, lb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------------- topology stamp

def test_topology_stamp_written_and_compared(tmp_path):
    root = str(tmp_path / "ck")
    save_checkpoint(root, 10, _bare(), topology={"loop": "classic"})
    with open(os.path.join(root, "10", "meta.json")) as f:
        meta = json.load(f)
    stamp = meta["topology"]
    assert stamp["device_count"] == jax.device_count()
    assert stamp["process_count"] == jax.process_count()
    assert stamp["population"] is None
    assert stamp["loop"] == "classic"
    # same shape → no mismatch, no elastic routing
    cur = elastic.current_topology(_bare(), loop="classic")
    assert elastic.topology_mismatch(stamp, cur) == []
    assert not elastic._needs_elastic(stamp, cur)
    # a population resize IS a mismatch and needs the elastic path
    cur_p = elastic.current_topology(_pop(2), loop="classic")
    diffs = elastic.topology_mismatch(stamp, cur_p)
    assert any("population" in d for d in diffs)
    assert elastic._needs_elastic(stamp, cur_p)
    # a stampless (pre-graftmorph) checkpoint is unknown, NOT mismatched
    assert elastic.topology_mismatch(None, cur_p) == []
    assert not elastic._needs_elastic(None, cur_p)
    # population size is read from the spec leaves
    assert elastic.current_topology(_pop(3))["population"] == 3


# ----------------------------------------------------------- shard saves

def test_shard_save_roundtrip_and_assembly(tmp_path):
    root = str(tmp_path / "ck")
    state = _pop(2)
    d = save_checkpoint_shards(root, 16, state,
                               topology={"loop": "classic"})
    assert os.path.basename(d) == "16"
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    assert meta["partial"] is True and meta["shards"] == 1
    # a 1-host shard set is already complete: verify passes and the
    # assembled state round-trips bit-exactly
    assert verify_checkpoint(d)
    _, raw = restore_host_state(d)
    restored = restore_elastic(d, state)
    _eq(restored, state)
    assert isinstance(raw, dict)


def test_find_checkpoint_all_shards_or_skip(tmp_path):
    """Satellite regression: an INCOMPLETE shard set (host died before
    every peer flushed) must fail verification and be skipped in favor
    of the newest complete checkpoint — never half-restored."""
    root = str(tmp_path / "ck")
    state = _bare()
    save_checkpoint(root, 10, state)           # complete, older
    # hand-write shard 0 of a claimed 2-shard set at a NEWER step
    host = jax.device_get(state)
    write_shard(root, 20, 0, 2, host)
    incomplete = os.path.join(root, "20")
    assert os.path.isdir(incomplete)
    assert not verify_checkpoint(incomplete)
    found = find_checkpoint(root)
    assert found is not None and found[1] == 10
    with pytest.raises(CheckpointIntegrityError):
        restore_host_state(incomplete)
    # the moment the second shard lands the set is complete: newest wins
    write_shard(root, 20, 1, 2, host, sharded_paths=["['w']"])
    assert verify_checkpoint(incomplete)
    assert find_checkpoint(root)[1] == 20
    # assembly: sharded leaves concatenate on axis 0, others take shard 0
    _, raw = restore_host_state(incomplete)
    np.testing.assert_array_equal(
        raw["w"], np.concatenate([host.w, host.w], axis=0))
    np.testing.assert_array_equal(raw["runner"]["key"],
                                  np.asarray(host.runner.key))


# --------------------------------------------------- population reshapes

def test_population_shrink_prefix_and_ranked(tmp_path):
    root = str(tmp_path / "ck")
    state = _pop(4)
    save_checkpoint(root, 8, state)
    d = os.path.join(root, "8")
    # prefix shrink: members 0..1 survive verbatim
    out = restore_elastic(d, _pop(2))
    _eq(out.ts, jax.tree.map(lambda a: a[:2], state.ts))
    _eq(out.spec, jax.tree.map(lambda a: a[:2], state.spec))
    # ranked shrink: the ranking's best two members land in slots 0, 1
    out = restore_elastic(d, _pop(2), member_ranking=[3, 1, 0, 2])
    _eq(out.ts, jax.tree.map(lambda a: a[np.array([3, 1])], state.ts))
    # a ranking that is not a permutation is rejected loudly
    with pytest.raises(ValueError):
        restore_elastic(d, _pop(2), member_ranking=[3, 3, 0, 2])


def test_population_grow_salts_new_member_keys(tmp_path):
    root = str(tmp_path / "ck")
    state = _pop(2)
    save_checkpoint(root, 8, state)
    out = restore_elastic(os.path.join(root, "8"), _pop(4))
    # members 0..1 are the restored run, verbatim
    _eq(jax.tree.map(lambda a: a[:2], out.ts), state.ts)
    # members 2..3 replicate 0..1 EXCEPT the runner key, which is
    # fold_in-salted — four distinct trajectory streams
    np.testing.assert_array_equal(np.asarray(out.ts.w[2]),
                                  np.asarray(state.ts.w[0]))
    keys = np.asarray(out.ts.runner.key)
    assert len({k.tobytes() for k in keys}) == 4, \
        "grown members must not share a rollout key stream"


def test_population_to_bare_extraction(tmp_path):
    root = str(tmp_path / "ck")
    state = _pop(3)
    save_checkpoint(root, 8, state)
    d = os.path.join(root, "8")
    # default: member 0 is the run that continues
    out = restore_elastic(d, _bare())
    _eq(out, jax.tree.map(lambda a: a[0], state.ts))
    # with a ranking: the BEST member is the one extracted
    out = restore_elastic(d, _bare(), member_ranking=[2, 0, 1])
    _eq(out, jax.tree.map(lambda a: a[2], state.ts))


def test_member_ranking_defaults_from_saved_stamp(tmp_path):
    """A shrink with no explicit ranking uses the one the SAVE stamped
    (the driver's EMA ordering at save time)."""
    root = str(tmp_path / "ck")
    state = _pop(4)
    save_checkpoint(root, 8, state,
                    topology={"member_ranking": [2, 3, 1, 0]})
    out = restore_elastic(os.path.join(root, "8"), _pop(2))
    _eq(out.ts, jax.tree.map(lambda a: a[np.array([2, 3])], state.ts))


# -------------------------------------------------------- resume routing

def test_resume_state_rigid_same_shape(tmp_path):
    root = str(tmp_path / "ck")
    state = _bare()
    save_checkpoint(root, 10, state, topology={"loop": "classic"})
    out, used = elastic.resume_state(os.path.join(root, "10"), _bare(),
                                     topology={"loop": "classic"})
    assert used is False
    _eq(out, state)


def test_resume_state_routes_population_mismatch(tmp_path):
    root = str(tmp_path / "ck")
    save_checkpoint(root, 10, _pop(4), topology={"loop": "classic"})
    fired = []
    resilience.register_fault("checkpoint.elastic",
                              lambda **kw: fired.append(kw))
    out, used = elastic.resume_state(os.path.join(root, "10"), _pop(2),
                                     topology={"loop": "classic"})
    assert used is True and fired
    assert jax.tree_util.tree_leaves(out.spec)[0].shape[0] == 2


def test_resume_state_stampless_falls_back_once(tmp_path):
    """A pre-graftmorph checkpoint (no stamp) that fails the rigid path
    STRUCTURALLY gets one elastic retry — old population saves restore
    into a resized run without anyone re-stamping them."""
    root = str(tmp_path / "ck")
    state = _pop(4)
    save_checkpoint(root, 10, state)
    meta_path = os.path.join(root, "10", "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    del meta["topology"]
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    out, used = elastic.resume_state(os.path.join(root, "10"), _pop(2))
    assert used is True
    _eq(out.ts, jax.tree.map(lambda a: a[:2], state.ts))


# ------------------------------------------------- dp N <-> M placement

def test_dp2_to_1_restore_bit_identity(tmp_path):
    """A dp=2 checkpoint restores on ONE device bit-exactly: the save
    gathered global content, the restore is placement-only."""
    root = str(tmp_path / "ck")
    mesh = make_mesh(2)
    state = _bare(n=8)
    sharded = _TS(
        runner=jax.device_put(state.runner,
                              NamedSharding(mesh, P())),
        w=jax.device_put(state.w, NamedSharding(mesh, P("data"))))
    save_checkpoint(root, 12, sharded, topology={"mesh_shape": [2]})
    template = jax.eval_shape(lambda: state)
    out, used = elastic.resume_state(os.path.join(root, "12"), template)
    assert used is False       # placement-only: rigid path, logged
    _eq(out, state)


def test_dp1_to_2_restore_streams_onto_mesh(tmp_path):
    """The reverse flip: a single-device save restores straight onto a
    dp=2 mesh (leaf-streamed, born-sharded placement) bit-exactly."""
    root = str(tmp_path / "ck")
    state = _bare(n=8)
    save_checkpoint(root, 12, state)
    mesh = make_mesh(2)
    template = jax.eval_shape(lambda: state)
    shardings = _TS(
        runner=jax.tree.map(lambda _: NamedSharding(mesh, P()),
                            template.runner),
        w=NamedSharding(mesh, P("data")))
    out, used = elastic.resume_state(os.path.join(root, "12"), template,
                                     shardings)
    assert used is False
    assert out.w.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data")), out.w.ndim)
    _eq(jax.device_get(out), state)


# ------------------------------------------------- preemption negotiation

def test_negotiate_stop_step_single_host():
    target, ok = dist.negotiate_stop_step(42)
    assert (target, ok) == (42, True)


def test_negotiate_stop_step_degrades_on_barrier_fault():
    def boom(**kw):
        raise RuntimeError("peer died mid-negotiation")
    resilience.register_fault("preempt.barrier", boom)
    target, ok = dist.negotiate_stop_step(42)
    assert (target, ok) == (42, False)


def test_announce_and_peer_poll_are_noops_single_host():
    dist.announce_shutdown(7)                   # must not raise
    assert dist.peer_shutdown_requested() is False


# --------------------------------------------------- v3 fixture, e2e

def test_v3_fixture_full_migration_chain(tmp_path):
    """The checked-in v3-era bytes restore through the WHOLE chain:
    v3→v4 injects ``runner.env_params`` from the template, v4→v5 lifts
    the single member into a population with re-salted rollout keys —
    against real frozen bytes, not a synthesized old tree."""
    d = os.path.join(FIXTURE_DIR, str(FIXTURE_STEP))
    with open(os.path.join(d, "meta.json")) as f:
        assert json.load(f)["format"] == 3
    assert verify_checkpoint(d)                # sha256 gate still holds
    cfg = fixture_cfg(tmp_path)
    exp = Experiment.build(cfg)
    ts_template = exp.init_train_state(cfg.seed)

    # v3 → v4: bare restore, env_params injected from the template
    ts = load_checkpoint(d, ts_template)
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(ts.runner.env_params)[0]),
        np.asarray(jax.tree_util.tree_leaves(
            ts_template.runner.env_params)[0]))
    # everything the v3 writer DID store restores verbatim: every leaf
    # against the fixture's own bytes, decoded without the chain (the
    # template's values are this installation's PRNG output, not the
    # fixture's)
    with open(os.path.join(d, "state.msgpack"), "rb") as f:
        stored = serialization.msgpack_restore(f.read())
    restored = serialization.to_state_dict(ts)
    injected = restored["runner"].pop("env_params")
    assert "env_params" not in stored["runner"] and injected
    assert (jax.tree_util.tree_structure(restored)
            == jax.tree_util.tree_structure(stored))
    for path, leaf in jax.tree_util.tree_leaves_with_path(stored):
        got = restored
        for k in path:
            got = got[k.key]
        np.testing.assert_array_equal(np.asarray(got), leaf,
                                      err_msg=jax.tree_util.keystr(path))

    # v3 → v4 → v5: population restore lifts the single member to P=2
    cfg_p = sanity_check(cfg.replace(
        population=PopulationConfig(size=2)))
    exp_p = Experiment.build(cfg_p)
    shapes = jax.eval_shape(
        lambda: graftpop.init_population(exp_p, cfg_p))[0]
    template = graftpop.PopState(ts=shapes,
                                 spec=graftpop.build_spec(cfg_p))
    ps = restore_elastic(d, template)
    assert jax.tree_util.tree_leaves(ps.ts)[0].shape[0] == 2
    # member 0 IS the restored run; member 1's rollout key is re-salted
    np.testing.assert_array_equal(np.asarray(ps.ts.runner.key[0]),
                                  np.asarray(ts.runner.key))
    assert not np.array_equal(np.asarray(ps.ts.runner.key[1]),
                              np.asarray(ps.ts.runner.key[0]))


# ------------------------------------------------ driver-level (slow)

def _pop_cfg(p, tmp_path, **kw):
    defaults = dict(
        t_max=24, batch_size_run=2, batch_size=4,
        test_interval=1_000_000, test_nepisode=2, log_interval=12,
        runner_log_interval=12, save_model=True, save_model_interval=12,
        epsilon_anneal_time=50, local_results_path=str(tmp_path),
        use_tensorboard=False,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6, fast_norm=False),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8),
        resilience=ResilienceConfig(),
    )
    if p:
        defaults["population"] = PopulationConfig(size=p)
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


def _model_dir(tmp_path):
    dirs = glob.glob(os.path.join(str(tmp_path), "models", "*"))
    assert dirs
    return dirs[0]


@pytest.mark.slow
@pytest.mark.parametrize("p_from,p_to", [(4, 2), (2, 4)])
def test_population_resize_resumes_to_tmax(tmp_path, p_from, p_to):
    """The acceptance matrix's P legs: a P=p_from run's checkpoint
    resumes as P=p_to and trains to t_max with DISTINCT per-member
    rollout streams (prefix/replicate + fold_in re-salt)."""
    cfg = _pop_cfg(p_from, tmp_path / "a")
    run_sequential(Experiment.build(cfg), Logger(), str(tmp_path / "ra"))
    ckpt = _model_dir(tmp_path / "a")
    cfg2 = _pop_cfg(p_to, tmp_path / "b", t_max=48,
                    checkpoint_path=ckpt)
    ts = run_sequential(Experiment.build(cfg2), Logger(),
                        str(tmp_path / "rb"))
    t_final = np.asarray(jax.device_get(ts.runner.t_env))
    assert t_final.shape == (p_to,)
    assert int(t_final[0]) >= cfg2.t_max
    keys = np.asarray(jax.device_get(ts.runner.key))
    assert len({k.tobytes() for k in keys}) == p_to, \
        "every member must roll out from its own key stream"


@pytest.mark.slow
def test_classic_to_sebulba_resume_parity(tmp_path):
    """The loop-shape leg: one classic checkpoint, resumed by the
    classic loop AND by lockstep sebulba (queue_slots=1, staleness=0 —
    the bit-parity mode test_sebulba pins), reaches t_max with
    BIT-identical learner params: the flip is pure routing."""
    cfg = _pop_cfg(0, tmp_path / "a")
    run_sequential(Experiment.build(cfg), Logger(), str(tmp_path / "ra"))
    ckpt = _model_dir(tmp_path / "a")

    cfg_c = _pop_cfg(0, tmp_path / "b", t_max=48, checkpoint_path=ckpt,
                     save_model=False)
    ts_c = run_sequential(Experiment.build(cfg_c), Logger(),
                          str(tmp_path / "rb"))
    cfg_s = _pop_cfg(0, tmp_path / "c", t_max=48, checkpoint_path=ckpt,
                     save_model=False,
                     sebulba=SebulbaConfig(actor_devices=1,
                                           learner_devices=1,
                                           queue_slots=1, staleness=0))
    ts_s = run_sequential(Experiment.build(cfg_s), Logger(),
                          str(tmp_path / "rc"))
    assert int(jax.device_get(ts_s.runner.t_env)) >= cfg_s.t_max
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        ts_c.learner.params, ts_s.learner.params)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.faultinject
def test_degraded_shard_save_resumes_elastic_single_host(tmp_path):
    """The chaos acceptance's single-host leg: a preemption whose peer
    barrier FAILS degrades to the per-host shard save; on one host that
    shard set is already complete, and ``resume_state`` resumes it to
    t_max — a degraded exit costs nothing when no peer actually died."""
    def barrier_dies(**kw):
        raise RuntimeError("injected: peer died mid-negotiation")

    def trip(t_env=0, guard=None, **kw):
        if guard is not None and t_env >= 12:
            guard.request("preempt-test")

    resilience.register_fault("preempt.barrier", barrier_dies)
    resilience.register_fault("driver.iteration", trip)
    cfg = _pop_cfg(0, tmp_path / "a", t_max=60,
                   resilience=ResilienceConfig(emergency_checkpoint=True))
    run_sequential(Experiment.build(cfg), Logger(), str(tmp_path / "ra"))
    resilience.clear_faults()

    ckpt = _model_dir(tmp_path / "a")
    found = find_checkpoint(ckpt)
    assert found is not None and found[1] >= 12
    # the emergency save took the DEGRADED path: shard files, partial
    # meta — and it still verifies because the 1-host set is complete
    assert glob.glob(os.path.join(found[0], "shard.*.msgpack")), \
        "the failed barrier must route the exit through the shard save"
    with open(os.path.join(found[0], "meta.json")) as f:
        assert json.load(f)["partial"] is True
    assert verify_checkpoint(found[0])

    cfg2 = _pop_cfg(0, tmp_path / "b", t_max=60, checkpoint_path=ckpt,
                    save_model=False)
    ts = run_sequential(Experiment.build(cfg2), Logger(),
                        str(tmp_path / "rb"))
    assert int(jax.device_get(ts.runner.t_env)) >= cfg2.t_max
