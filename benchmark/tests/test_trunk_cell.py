"""The CPU rehearsal of a cell with a catalog trunk (``tiny_trunk``): a
sound run is correct and its counters reach the readers; each fault
planted in the PROGRAM's trunk comes out ``correct: false`` by a number
named here."""

import shutil

import jax
import jax.numpy as jnp
import pytest

from benchmark.tests import tiny_trunk
from benchmark.tests.test_faults import _failed


def _run(k=2, extra=None, **kw):
    root = tiny_trunk.make(k=k, **kw)
    try:
        return tiny_trunk.run(root, extra=extra)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_sound_run_is_correct_and_its_counters_reach_the_readers():
    from benchmark import harness, moe
    read = {}

    def extra(ctx):
        read["counters"] = moe.counters(ctx)
        for name in ("expert_load_max_share", "trunk_step_mfu_pct",
                     "moe_dev_ms", "experts_roofline_pct"):
            read[name] = harness.load_reader(name, ctx.bench_dir).read(ctx)
    result, kept = _run(extra=extra)
    assert result["correct"] is True, result["compared"]
    c = read["counters"]
    assert c["moe_dropped"] == c["moe_dropped_mean"] == 0.0
    assert 0 < c["moe_pairs_held"] < c["moe_pairs_routed"]
    # 4 of 8 experts held, 3 of 8 kept a token: between even and all-on-one
    assert 0.25 <= read["expert_load_max_share"] <= 1.0
    # the throw-away configuration brings no .ops.py, and nothing was
    # traced: those readers find nothing and say so
    assert read["trunk_step_mfu_pct"] is None
    assert read["moe_dev_ms"] is None and read["experts_roofline_pct"] is None
    assert set(tiny_trunk.METRICS) <= set(kept["cell"].per_layer)
    assert "step_mfu_pct" not in kept["cell"].per_layer


def _plant(monkeypatch, name, wrapper):
    from t2omca_tpu.models import trunk
    monkeypatch.setattr(trunk, name, wrapper(getattr(trunk, name)))


def _top5_for_top6(route):
    """One expert too few: the last kept expert's weight goes to the
    others."""
    def f(w_router, h, tk):
        weights, idx = route(w_router, h, tk)
        w = weights.at[:, -1].set(0.0)
        return w / w.sum(-1, keepdims=True), idx
    return f


def _neighbour_experts(held_weights):
    """The experts after the held block computed in its place: the pairs
    routed to experts 4 ... 7 go through the weights of 0 ... 3."""
    def f(weights, idx, tk):
        return held_weights(weights, idx - tk.experts_held, tk)
    return f


def _rope_on_the_nope_layer(attention_part):
    import dataclasses

    def f(lp, h, tk, layer, dtype):
        return attention_part(
            lp, h, dataclasses.replace(tk, rope_layout=(1,) * len(
                tk.rope_layout)), layer, dtype)
    return f


def _router_reads_the_normed_input(trunk_layer):
    """The router after the input norm, where the model has it before."""
    def f(lp, h, tk, layer, dtype):
        from t2omca_tpu.models import trunk
        route = trunk.route

        def normed(w_router, x, tk_):
            return route(w_router, trunk.rms_norm(
                x, lp["input_norm"], tk_.rms_norm_eps), tk_)
        trunk.route = normed
        try:
            return trunk_layer(lp, h, tk, layer, dtype)
        finally:
            trunk.route = route
    return f


@pytest.mark.parametrize("name,wrapper,number", [
    ("route", _top5_for_top6, "td_rms_gap"),
    ("held_weights", _neighbour_experts, "td_rms_gap"),
    ("attention_part", _rope_on_the_nope_layer, "td_rms_gap"),
    ("trunk_layer", _router_reads_the_normed_input, "td_rms_gap")],
    ids=["top-k-one-short", "experts-of-the-next-share",
         "rope-on-the-nope-layer", "router-reads-the-normed-input"])
def test_fault_in_the_trunk_is_not_correct(monkeypatch, name, wrapper,
                                           number):
    _plant(monkeypatch, name, wrapper)
    result, _ = _run()
    assert result["correct"] is False
    assert number in _failed(result), result["compared"]


def test_acting_on_other_q_values_is_not_correct(monkeypatch):
    """What ``greedy_regret`` does hold in a trunk cell: an acting forward
    whose Q-values are not the model's (here each action's value handed to
    the next action) takes actions the reference ranks lower. It cannot
    tell a precision or an AGV's neighbour apart (the test below)."""
    from t2omca_tpu.controllers.basic_mac import BasicMAC
    forward = BasicMAC.forward_trunk

    def rolled(self, *a, **kw):
        q, h, aux = forward(self, *a, **kw)
        return jnp.roll(q, 1, axis=-1), h, aux
    monkeypatch.setattr(BasicMAC, "forward_trunk", rolled)
    result, _ = _run()
    assert result["correct"] is False
    assert "greedy_regret" in _failed(result), result["compared"]


def test_control_one_precision_down_reads_above_bf16():
    """The reference put in the program's place one precision step down
    (float8 networks) moves the learner's numbers several times as far as
    the precision the configurations state (bfloat16)."""
    from benchmark import check
    result, kept = _run(1, lanes=16, agents=4)
    assert result["correct"] is True, result["compared"]
    c = kept["comparison"]
    fp8 = check.learner_numbers(c.reference(prec="fp8"), c.ref_out)
    bf16 = check.learner_numbers(c.reference(prec="bf16"), c.ref_out)
    # (bfloat16 already flips a top-k choice between near-tied experts,
    # so its per-episode readings are not small: PERF.md par.6)
    assert fp8["td_rms_gap"] > 1.5 * bf16["td_rms_gap"] > 0
    assert fp8["loss_gap"] > 3 * bf16["loss_gap"]
    # the acting sample separates nothing in a trunk cell: at an episode's
    # first step the hidden token is zero and every agent ranks the actions
    # alike, so float8 flips no arg-max (PERF.md par.6, par.7) — the control
    # is told apart by the learner's numbers above, not by `greedy_regret`
    q8 = check.policy_regret(c.q_ref, c.agent_qs("fp8"), c.acting["avail"])
    assert q8["greedy_steps"] > 0 and q8["greedy_regret"] >= 0.0
