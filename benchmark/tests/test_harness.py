"""CPU rehearsal of the harness: ``run.py`` up to the device check's
refusal, and past it through the phase functions on a tiny cell that is
added as new files (``tiny.py``)."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness
from benchmark.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device", "numbers",
        "td_errors_abs", "compared"}


def device_copy(ts, key, t_env):
    """What the snapshot held before it went to the host: device copies."""
    cp = lambda t: jax.tree.map(jnp.copy, t)            # noqa: E731
    return {"learner": cp(ts.learner),
            "priorities": jnp.copy(ts.buffer.priorities),
            "max_priority": jnp.copy(ts.buffer.max_priority),
            "insert_pos": jnp.copy(ts.buffer.insert_pos),
            "episodes_in_buffer": jnp.copy(ts.buffer.episodes_in_buffer),
            "episode": jnp.copy(ts.episode), "key": jnp.copy(key),
            "runner_key": jnp.copy(ts.runner.key), "t_env": int(t_env)}


class Twin(harness.Window):
    """The harness's window, keeping besides what its hooks were handed, a
    device copy of the state taken at the boundary of each snapshot, the
    followed dispatch's info rows, and the state the loop ends with."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.handed = {}
        self.device_snap = self.kept_infos = self.final_ts = None

    def on_boundary(self, **kw):
        self.handed.setdefault("driver.iteration", {
            n: type(v).__name__ for n, v in kw.items()})
        snap = self.snap
        super().on_boundary(**kw)
        if self.snap is not snap:
            self.device_snap = device_copy(kw["ts"], kw["key"], kw["t_env"])
        if self.phase == "done":
            self.final_ts = kw["ts"]

    def on_infos(self, **kw):
        self.handed.setdefault("fetch.train_infos", {
            n: type(v).__name__ for n, v in kw.items()})
        super().on_infos(**kw)
        self.kept_infos = self.infos or self.kept_infos


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


@pytest.fixture(scope="module")
def fused():
    root = tiny.make(k=2)
    yield (root,) + tiny.run(root, window_cls=Twin)
    shutil.rmtree(root, ignore_errors=True)


@pytest.fixture(scope="module")
def classic():
    """The classic loop, with a shape that changes inside the window: a
    second hook compiles a new program once the window is open."""
    from t2omca_tpu.utils import resilience
    root = tiny.make(k=1)
    made, fired = [], []
    orig_init = harness.Window.__init__

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        made.append(self)

    def compile_once(**_):
        if made and made[-1].phase == "window" and not fired:
            fired.append(1)
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 5))).block_until_ready()
    harness.Window.__init__ = init
    resilience.register_fault("dispatch.rollout", compile_once)
    try:
        out = tiny.run(root, window_cls=Twin)
    finally:
        harness.Window.__init__ = orig_init
        resilience.clear_faults("dispatch.rollout")
    assert fired
    yield (root,) + out
    shutil.rmtree(root, ignore_errors=True)


def test_result_has_the_contracts_keys(fused):
    _, result, _ = fused
    assert set(result) == KEYS
    assert list(result)[-1] == "compared"        # the compared numbers last
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    json.dumps(result)


@pytest.mark.parametrize("which", ["fused", "classic"])
def test_sound_run_is_correct_with_every_number_under_its_limit(
        which, request):
    _, result, _ = request.getfixturevalue(which)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    assert set(result["compared"]) == set(tiny.LIMITS) | (
        set(tiny.LIMITS_K1) if which == "classic" else set())
    for c in result["compared"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("which,k,warm", [("fused", 2, 8), ("classic", 1, 5)])
def test_window_opens_and_closes_on_period_boundaries(which, k, warm,
                                                      request):
    _, result, kept = request.getfixturevalue(which)
    w = kept["window"]
    assert w.k == k and w.it_open == warm
    assert (w.it_close - w.it_open) % 4 == 0 and w.it_close > w.it_open
    assert [i for i, _ in w.boundaries] == list(
        range(w.it_open, w.it_close + 1, 4))
    assert result["attempted"] == w.it_close - w.it_open
    steps = result["attempted"] * 8 * 6
    assert result["metrics"]["env_steps_per_s"]["value"] == pytest.approx(
        steps / w.window_s)


def test_no_compile_in_a_sound_window_and_a_changed_shape_is_counted(
        fused, classic):
    assert fused[2]["window"].compiles_in_window == 0
    assert classic[2]["window"].compiles_in_window >= 1


def test_cell_config_and_metric_added_as_files_only(fused):
    root, _, kept = fused
    bd = os.path.join(root, "benchmark")
    # nothing that was there differs from the repository's copy
    cmp = filecmp.dircmp(tiny.BENCH, bd, ignore=["__pycache__"])

    def walk(c):
        assert not c.diff_files, c.diff_files
        assert not c.left_only, c.left_only
        for sub in c.subdirs.values():
            walk(sub)
    walk(cmp)
    added = {os.path.relpath(os.path.join(d, f), bd)
             for d, _, fs in os.walk(bd) for f in fs
             if "__pycache__" not in d
             and not os.path.exists(os.path.join(
                 tiny.BENCH, os.path.relpath(os.path.join(d, f), bd)))}
    assert added == {"configs/tiny.json", "configs/tiny.limits.json",
                     "configs/tiny.reference.py",
                     "workloads/tiny.train.json",
                     "metrics/tiny_iterations.py"}
    # and the added per-layer metric is read for the added cell
    assert "tiny_iterations" in kept["cell"].per_layer
    mod = harness.load_reader("tiny_iterations", bd)
    assert mod.UNIT == "count"


def test_a_registered_hook_is_handed_the_loops_state(fused):
    """The window reads the train state, the driver's key stream and the
    pending info rows from the hooks' keyword arguments, not from the
    driver's frame: a refactor of the loop that stops handing one over
    must fail here, not turn ``correct`` into an error on the chip."""
    handed = fused[2]["window"].handed
    it = handed["driver.iteration"]
    assert {"t_env", "guard", "ts", "key", "train_infos"} <= set(it)
    assert it["ts"] == "TrainState" and it["train_infos"] == "list"
    assert "Array" in it["key"]
    assert handed["fetch.train_infos"]["train_infos"] == "list"


def test_a_hook_fired_without_the_state_says_so():
    """Sebulba's loop hands its hooks ``t_env`` and the guard only."""
    window = harness.Window.__new__(harness.Window)
    with pytest.raises(RuntimeError, match="without ts="):
        window.on_boundary(t_env=0, guard=None)


@pytest.mark.parametrize("which", ["fused", "classic"])
def test_the_snapshot_lives_on_the_host(which, request):
    snap = request.getfixturevalue(which)[2]["comparison"].snap
    leaves = jax.tree.leaves(snap)
    assert len(leaves) > 10
    for x in leaves:
        assert not isinstance(x, jax.Array), type(x)
        assert isinstance(x, (np.ndarray, int)), type(x)


@pytest.mark.parametrize("which", ["fused", "classic"])
def test_the_snapshot_is_a_device_copy_at_the_same_boundary_bit_for_bit(
        which, request):
    """Compared once the run has ended: the followed dispatch has since
    replaced the live state, and the host copy is as it was."""
    kept = request.getfixturevalue(which)[2]
    snap, w = kept["comparison"].snap, kept["window"]
    dev = w.device_snap
    assert jax.tree.structure(snap) == jax.tree.structure(dev)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(dev)):
        assert same_bits(a, b)
    moved = [not same_bits(a, b) for a, b in zip(
        jax.tree.leaves(snap["learner"].params),
        jax.tree.leaves(w.final_ts.learner.params))]
    assert any(moved)


def test_donating_the_state_after_the_snapshot_leaves_it_as_it_was():
    from types import SimpleNamespace as NS
    p = jnp.arange(12.0).reshape(3, 4)
    ts = NS(learner={"w": p}, episode=jnp.int32(7),
            runner=NS(key=jax.random.PRNGKey(3)),
            buffer=NS(priorities=jnp.ones(5), max_priority=jnp.float32(2),
                      insert_pos=jnp.int32(1),
                      episodes_in_buffer=jnp.int32(4)))
    snap = harness.snapshot(ts, jax.random.PRNGKey(4), 96)
    for x in jax.tree.leaves(snap):
        assert isinstance(x, int) or x.flags.owndata
    new = jax.jit(lambda x: x * 0 - 1, donate_argnums=0)(p)
    assert p.is_deleted() and float(new[0, 0]) == -1
    assert same_bits(snap["learner"]["w"], np.arange(12.0, dtype=np.float32
                                                     ).reshape(3, 4))
    assert snap["t_env"] == 96 and snap["key"].dtype == np.uint32


@pytest.mark.parametrize("which", ["fused", "classic"])
def test_the_comparison_reads_as_it_did_from_device_copies(which, request):
    """Every number ``finish`` gives from the host snapshot, it gives to
    the last bit from the device copy the harness held before."""
    kept = request.getfixturevalue(which)[2]
    c, w = kept["comparison"], kept["window"]
    dev = check.Comparison(c.cfg, w.k, w.device_snap, w.kept_infos,
                           w.final_ts, c.ref)
    assert dev.finish() == c.numbers


def test_the_optimizer_by_leaf_reads_as_one_program_over_the_tree(classic):
    """``optimizer_numbers`` a leaf at a time against the one program over
    all leaves that it replaces, to the last bit."""
    from benchmark.reference import qmix
    c = classic[2]["comparison"]
    cfg, snap, prog = c.cfg, c.snap, c.prog
    adam0 = check._find_adam(snap["learner"].opt_state)
    count = jnp.asarray(adam0.count)

    def f(before, after, mu0, nu0, mu1):
        g = jax.tree.map(lambda m1, m0: (m1 - check.B1 * m0)
                         / (1 - check.B1), mu1, mu0)
        new, _, _ = qmix.adam_step(before, g, mu0, nu0, count, lr=cfg.lr,
                                   b1=check.B1, b2=check.B2,
                                   eps=cfg.optim_eps)
        norms = lambda t: jnp.stack([jnp.sqrt(jnp.sum(x ** 2))  # noqa: E731
                                     for x in jax.tree.leaves(t)])
        ref = norms(jax.tree.map(lambda n, b: n - b, new, before))
        off = norms(jax.tree.map(lambda a, n: a - n, after, new))
        return (qmix.global_norm(g),
                (off / jnp.maximum(ref, jnp.median(ref))).max())
    g_norm, adam_gap = jax.jit(f)(*[check._strip(t) for t in (
        snap["learner"].params, prog["params_after"], adam0.mu, adam0.nu,
        prog["mu_after"])])
    want = min(float(cfg.grad_norm_clip),
               float(prog["infos"][-1]["grad_norm"]))
    got = check.optimizer_numbers(cfg, snap, prog)
    assert got == {"clip_gap": abs(float(g_norm) - want) / want,
                   "adam_gap": float(adam_gap)}
    assert got["adam_gap"] > 0


def test_a_number_that_is_missing_or_not_finite_fails():
    from benchmark import check
    ok, compared = check.verdict({"a": 0.0, "c": float("nan")},
                                 {"a": 0, "b": 1, "c": 1})
    assert not ok
    assert compared["b"]["value"] == "None" and compared["c"]["value"] == "nan"
    assert check.verdict({"a": 0.0}, {"a": 0})[0]
    json.dumps(compared, allow_nan=False)


def test_without_a_tpu_the_command_refuses_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "agv64-d256.train", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tiny.REPO, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith('{"correct"') for ln in
                   p.stdout.splitlines())


def test_alone_with_the_manifest_the_command_refuses(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "agv64-d256.train", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("which", ["fused", "classic"])
def test_beside_the_live_state_the_comparison_takes_no_tree_to_the_chip(
        which, request):
    """Built while the program's state still lives, the comparison adds
    on the device only the batch and the acting sample it gathers from the
    ring: the parameters taken back (K > 1) wait for ``finish``."""
    kept = request.getfixturevalue(which)[2]
    c, w = kept["comparison"], kept["window"]
    params = sum(x.nbytes for x in jax.tree.leaves(
        w.final_ts.learner.params))
    held = {id(x) for x in jax.live_arrays()}
    dev = check.Comparison(c.cfg, w.k, c.snap, w.kept_infos, w.final_ts,
                           c.ref)
    added = sum(x.nbytes for x in jax.live_arrays() if id(x) not in held)
    gathered = sum(x.nbytes for x in jax.tree.leaves((dev.batch,
                                                      dev.acting)))
    assert added - gathered < params / 4
