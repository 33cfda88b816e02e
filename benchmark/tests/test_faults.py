"""``correct`` has to come out false when the timed path is broken
underneath the harness. Each test skips the look for a chip, drives the
rest of a run on the tiny cell with one fault planted in the PROGRAM,
and names the number that caught it:

* a step that returns its state unchanged;
* a step that moves the parameters double;
* half of the batch left out, the mean taken over the rest;
* an answer altered where it is produced: the env's reward; the action
  (every AGV acts on its neighbour's Q-values, and records what it did);
  the explored action (not the selector's draw).

(The exchange between chips does not exist in a one-chip cell.) And the
control — the reference put in the program's place one precision step
down — fails the comparison too: the networks at float8 for bfloat16 by
the greedy actions' regret, the env at bfloat16 for float32 by the reward.
"""

import shutil

import jax.numpy as jnp
import pytest

from benchmark import check
from benchmark.tests import tiny


def _run(k=2, **kw):
    root = tiny.make(k=k, **kw)
    try:
        return tiny.run(root)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _failed(result):
    return {n for n, c in result["compared"].items()
            if isinstance(c["value"], str) or not c["value"] <= c["limit"]}


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from t2omca_tpu.learners.qmix_learner import QMixLearner
    train = QMixLearner.train

    def unchanged(self, ls, *a, **kw):
        _, info = train(self, ls, *a, **kw)
        return ls, info
    monkeypatch.setattr(QMixLearner, "train", unchanged)
    result, _ = _run()
    assert result["correct"] is False
    assert "counters_off" in _failed(result)


@pytest.mark.parametrize("k", [2, 1])
def test_half_of_the_batch_left_out(monkeypatch, k):
    from t2omca_tpu.learners.qmix_learner import QMixLearner
    loss = QMixLearner._loss

    def half(self, params, target_params, batch, weights, key=None):
        b = batch.filled.shape[0]
        keep = (jnp.arange(b) < b // 2)[:, None]
        return loss(self, params, target_params,
                    batch.replace(filled=batch.filled & keep), weights, key)
    monkeypatch.setattr(QMixLearner, "_loss", half)
    result, _ = _run(k)
    assert result["correct"] is False
    assert "td_rms_gap" in _failed(result)


def test_answer_altered_where_it_is_produced(monkeypatch):
    from t2omca_tpu.envs.mec_offload import MultiAgvOffloadingEnv
    reward = MultiAgvOffloadingEnv._reward

    def altered(self, state, ack, params):
        r, d, o, s = reward(self, state, ack, params)
        return r + 7.0, d, o, s
    monkeypatch.setattr(MultiAgvOffloadingEnv, "_reward", altered)
    result, _ = _run()
    assert result["correct"] is False
    assert "reward_gap" in _failed(result)


def test_a_step_that_moves_the_parameters_double(monkeypatch):
    import jax
    from t2omca_tpu.learners.qmix_learner import QMixLearner
    train = QMixLearner.train

    def double(self, ls, *a, **kw):
        new, info = train(self, ls, *a, **kw)
        return new.replace(params=jax.tree.map(
            lambda n, o: o + 2 * (n - o), new.params, ls.params)), info
    monkeypatch.setattr(QMixLearner, "train", double)
    result, _ = _run(1)
    assert result["correct"] is False
    assert "adam_gap" in _failed(result)


@pytest.mark.parametrize("k", [2, 1])
def test_every_agv_acts_on_its_neighbours_q_values(monkeypatch, k):
    """Planted in the program's selector: the actions are recorded as
    taken, so the env's numbers stay sound; the regret catches it."""
    from t2omca_tpu.components.action_selectors import EpsilonGreedySelector
    select = EpsilonGreedySelector.select

    def neighbours(self, key, q, avail, *a, **kw):
        return select(self, key, jnp.roll(q, 1, axis=-2), avail, *a, **kw)
    monkeypatch.setattr(EpsilonGreedySelector, "select", neighbours)
    result, _ = _run(k, lanes=64, agents=6)
    assert result["correct"] is False
    assert _failed(result) == {"greedy_regret"}


def test_an_explored_action_that_is_not_the_selectors_draw(monkeypatch):
    from t2omca_tpu.components import action_selectors

    def first_available(key, avail):
        return jnp.argmax(avail > 0, axis=-1)
    monkeypatch.setattr(action_selectors, "random_avail", first_available)
    result, _ = _run()
    assert result["correct"] is False
    assert "selector_off" in _failed(result)


def test_control_one_precision_down_is_not_correct():
    """On a sound run: the reference put in the program's place one step
    of precision down. The networks at float8 for bfloat16: at each
    agent-step of the acting sample (2,300 here), the action float8 puts
    first lies further below the reference's best than the cells' limit
    allows, and far further than bfloat16's does (PERF.md gives the
    chip's readings at the cells' sizes). The env at bfloat16 for
    float32: the reward."""
    result, kept = _run(1, lanes=64, agents=6)
    assert result["correct"] is True
    c = kept["comparison"]
    avail = c.acting["avail"]
    control = check.policy_regret(c.q_ref, c.agent_qs("fp8"), avail)
    sound = check.policy_regret(c.q_ref, c.agent_qs("bf16"), avail)
    assert control["greedy_regret"] > 10 * sound["greedy_regret"]
    control.update(check.env_numbers(c.cfg, c.batch, dtype=jnp.bfloat16))
    for cell in ("agv64-d256",):
        limits = check.load_limits(cell, tiny.BENCH)
        ok, compared = check.verdict(dict(result["numbers"], **control),
                                     limits)
        assert not ok
        for n in ("greedy_regret", "reward_gap"):
            assert compared[n]["value"] > compared[n]["limit"]
        assert sound["greedy_regret"] < limits["greedy_regret"]


def test_calibration_reads_seeds_in_one_process(monkeypatch, tmp_path):
    """``calibrate.py`` past the look for a chip: two seeds in one
    process, the control's and the planted fault's readings on the first."""
    import json
    import os

    import jax

    from benchmark import calibrate, harness
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    root = tiny.make(k=2, lanes=64, agents=6)
    out = tmp_path / "calib.jsonl"
    try:
        rc = calibrate.main(["--workload", "tiny.train", "--seeds", "5,6",
                             "--control", "1", "--out", str(out)],
                            bench_dir=os.path.join(root, "benchmark"))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    rows = [json.loads(ln) for ln in open(out)]
    assert rc == 0 and [r["correct"] for r in rows] == [True, True]
    first = rows[0]
    assert first["acting_neighbour"]["greedy_regret"] > 5 * tiny.LIMITS[
        "greedy_regret"]
    assert first["env_bf16"]["reward_gap"] > 1e-3
    print(json.dumps(first))
