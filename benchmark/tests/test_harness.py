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
import pytest

from benchmark import harness
from benchmark.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device", "numbers",
        "td_errors_abs", "compared"}


@pytest.fixture(scope="module")
def fused():
    root = tiny.make(k=2)
    yield (root,) + tiny.run(root)
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
        out = tiny.run(root)
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


def test_the_driver_loop_still_has_the_locals_the_hooks_read():
    """``harness._driver_locals`` reads these names off ``run_sequential``'s
    frame: a refactor of the loop that renames one must fail here, not
    turn ``correct`` into an error on the chip."""
    from t2omca_tpu.run import run_sequential
    names = set(run_sequential.__code__.co_varnames) | set(
        run_sequential.__code__.co_cellvars)
    assert {"ts", "key", "t_env", "train_infos"} <= names


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
