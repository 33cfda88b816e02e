"""The CPU rehearsal of a cell with an ``afmoe`` catalog trunk on the
classic loop (``tiny_afmoe``, ``superstep: 1``): a sound run is correct
with the optimizer's ``clip_gap`` / ``adam_gap`` among the compared, and
its counters reach the readers; each fault planted in the PROGRAM's layer
comes out ``correct: false`` by a number named here."""

import dataclasses
import shutil

import pytest

from benchmark.tests import tiny_afmoe
from benchmark.tests.test_faults import _failed


def _run(k=1, extra=None, **kw):
    root = tiny_afmoe.make(k=k, **kw)
    try:
        return tiny_afmoe.run(root, extra=extra)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_sound_run_is_correct_with_the_optimizer_among_the_compared():
    from benchmark import harness, moe
    read = {}

    def extra(ctx):
        read["counters"] = moe.counters(ctx)
        for name in tiny_afmoe.METRICS:
            read[name] = harness.load_reader(name, ctx.bench_dir).read(ctx)
    result, kept = _run(extra=extra)
    assert result["correct"] is True, result["compared"]
    assert kept["window"].k == 1
    compared = result["compared"]
    assert {"clip_gap", "adam_gap", "td_rms_gap"} <= set(compared)
    assert compared["clip_gap"]["value"] <= 1e-5
    assert 0 <= compared["adam_gap"]["value"] <= 1e-3
    c = read["counters"]
    assert c["moe_dropped"] == c["moe_dropped_mean"] == 0.0
    assert 0 < c["moe_pairs_held"] < c["moe_pairs_routed"]
    # the two routed layers only: 4 x 151-step... here 4 episodes x 7
    # steps x 3 agents x 4 tokens x top-3 x 2 layers
    assert c["moe_pairs_routed"] == 4 * 7 * 3 * 4 * 3 * 2
    # 4 of 8 experts held, 3 of 8 kept a token: between even and all-on-one
    assert 0.25 <= read["expert_load_max_share"] <= 1.0
    # no .ops.py in the throw-away configuration and nothing traced: the
    # other readers, the two new ones among them, find nothing and say so
    for name in set(tiny_afmoe.METRICS) - {"expert_load_max_share"}:
        assert read[name] is None, name
    assert set(tiny_afmoe.METRICS) <= set(kept["cell"].per_layer)


class _Spec:
    """The layer's configuration with some of its mechanisms altered."""

    def __init__(self, tk, **kw):
        self._tk = tk
        self.spec = dataclasses.replace(tk.spec, **kw)

    def __getattr__(self, name):
        return getattr(self._tk, name)


def _altered(**kw):
    def wrapper(trunk_layer):
        def f(lp, h, tk, layer, dtype):
            return trunk_layer(lp, h, _Spec(tk, **kw), layer, dtype)
        return f
    return wrapper


def _rope_on_the_full_layer(trunk_layer):
    def f(lp, h, tk, layer, dtype):
        layers = tuple(dataclasses.replace(ls, rope=True)
                       for ls in tk.spec.layers)
        return trunk_layer(lp, h, _Spec(tk, layers=layers), layer, dtype)
    return f


def _bias_added_to_the_weights(route):
    """The selection bias weighs, where the model lets it choose only."""
    def f(w_router, h, tk, bias=None):
        import jax.numpy as jnp
        weights, idx = route(w_router, h, tk, bias=bias)
        return weights + jnp.take(bias, idx), idx
    return f


@pytest.mark.parametrize("name,wrapper,number", [
    ("route", _bias_added_to_the_weights, "td_rms_gap"),
    ("trunk_layer", _altered(route_scale=1.0), "td_rms_gap"),
    ("trunk_layer", _altered(attn_gate=False), "td_rms_gap"),
    ("trunk_layer", _altered(sandwich_norm=False), "td_rms_gap"),
    ("trunk_layer", _altered(shared_width=0), "td_rms_gap"),
    ("trunk_layer", _rope_on_the_full_layer, "td_rms_gap")],
    ids=["bias-added-to-the-weights", "route-scale-left-out",
         "gate-left-out", "post-norms-left-out", "shared-expert-left-out",
         "rope-on-the-full-layer"])
def test_fault_in_the_layer_is_not_correct(monkeypatch, name, wrapper,
                                           number):
    from t2omca_tpu.models import trunk
    monkeypatch.setattr(trunk, name, wrapper(getattr(trunk, name)))
    result, _ = _run()
    assert result["correct"] is False
    assert number in _failed(result), result["compared"]


def test_step_that_moves_the_parameters_double_is_not_correct(monkeypatch):
    """What only K = 1 can hold: the optimizer's step itself. A learning
    rate doubled inside the program leaves loss and |TD| of the followed
    update as they are and fails ``adam_gap``."""
    import optax
    from t2omca_tpu.learners import qmix_learner
    adam = optax.adam

    def doubled(learning_rate, *a, **kw):
        return adam(2.0 * learning_rate, *a, **kw)
    monkeypatch.setattr(qmix_learner.optax, "adam", doubled)
    result, _ = _run()
    assert result["correct"] is False
    assert "adam_gap" in _failed(result), result["compared"]
