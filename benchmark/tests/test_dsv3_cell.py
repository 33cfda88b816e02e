"""The CPU rehearsal of a cell with a ``deepseek_v3`` catalog trunk —
latent attention — on the classic loop (``tiny_dsv3``, ``superstep: 1``):
a sound run is correct with the optimizer's ``clip_gap`` / ``adam_gap``
among the compared, and its counters reach the readers; each fault
planted in the PROGRAM's layer comes out ``correct: false`` by a number
named here."""

import shutil

import jax
import jax.numpy as jnp
import pytest

from benchmark.tests import tiny_dsv3
from benchmark.tests.test_afmoe_cell import _altered
from benchmark.tests.test_faults import _failed

NOPE = tiny_dsv3.NOPE


def _run(extra=None, **kw):
    root = tiny_dsv3.make(**kw)
    try:
        return tiny_dsv3.run(root, extra=extra)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_sound_run_is_correct_with_the_optimizer_among_the_compared():
    from benchmark import harness, moe
    read = {}

    def extra(ctx):
        read["counters"] = moe.counters(ctx)
        for name in tiny_dsv3.METRICS:
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
    # the two routed layers only: 4 episodes x 7 steps x 3 agents x 4
    # tokens x top-3 x 2 layers
    assert c["moe_pairs_routed"] == 4 * 7 * 3 * 4 * 3 * 2
    assert 0.25 <= read["expert_load_max_share"] <= 1.0
    # no .ops.py in the throw-away configuration and nothing traced: the
    # other readers, the three new ones among them, find nothing and say so
    for name in set(tiny_dsv3.METRICS) - {"expert_load_max_share"}:
        assert read[name] is None, name
    assert set(tiny_dsv3.METRICS) <= set(kept["cell"].per_layer)


# ------------------------------------------- faults in latent attention

def _faulty_scores(fault):
    """``trunk.latent_scores`` written out once more with ONE fault in it
    (``None``: none — held equal to the program's below)."""
    from t2omca_tpu.models import trunk as tr

    def scores(lp, x, sp, ls, dtype):
        s, n, _ = x.shape
        hq, nope = sp.heads_held, sp.qk_nope_dim
        q = jnp.dot(x, lp["wq"].astype(dtype),
                    preferred_element_type=jnp.float32
                    ).astype(dtype).reshape(s, n, hq, sp.head_dim)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        k_nope, k_rope, v = tr.latent_kv(lp, x, sp, dtype)
        if fault == "value-from-the-key-half":
            v = k_nope[..., :sp.value_dim]
        q_rope = tr.rope_pairs(q_rope, sp.rope_theta)
        k_rope = tr.rope_pairs(k_rope[:, :, None, :], sp.rope_theta)
        if fault == "rotary-key-per-head":
            # head j reads a key of its own: the shared one, its lanes
            # shifted by 2 j
            k_rope = jnp.concatenate(
                [jnp.roll(k_rope, 2 * j, axis=-1) for j in range(hq)], axis=2)
        else:
            k_rope = jnp.broadcast_to(k_rope, (s, n, hq, k_rope.shape[-1]))
        if fault == "rope-on-the-no-position-part":
            q_nope = tr.rope_pairs(q_nope, sp.rope_theta)
            k_nope = tr.rope_pairs(k_nope, sp.rope_theta)
        logits = (jnp.einsum("sqhd,skhd->shqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("sqhd,skhd->shqk", q_rope, k_rope,
                               preferred_element_type=jnp.float32))
        width = nope if fault == "scale-of-the-no-position-width" \
            else sp.head_dim
        return (logits * width ** -0.5)[:, :, None], v
    return lambda _: scores


def _latent_norm_left_out(rms_norm):
    def f(x, scale, eps):
        if scale.shape == (tiny_dsv3.RANK,):
            return x.astype(jnp.float32)
        return rms_norm(x, scale, eps)
    return f


def _one_shared_expert_of_two(gated_ffn):
    def f(lp, prefix, m, dtype, act):
        if prefix == "shared":
            half = lp["shared_gate"].shape[1] // 2
            lp = dict(lp, shared_gate=lp["shared_gate"][:, :half],
                      shared_up=lp["shared_up"][:, :half],
                      shared_down=lp["shared_down"][:half])
        return gated_ffn(lp, prefix, m, dtype, act)
    return f


def test_the_faulty_copy_without_a_fault_is_the_program():
    from t2omca_tpu.config import DeepseekV3TrunkConfig
    from t2omca_tpu.models import trunk as tr
    tk = DeepseekV3TrunkConfig(**tiny_dsv3.TRUNK)
    sp = tk.spec
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    lp = {"wq": jax.random.normal(k[0], (16, 2 * 12)),
          "wkv_a": jax.random.normal(k[1], (16, 16)),
          "kv_norm": 1 + 0.1 * jax.random.normal(k[2], (12,)),
          "wkv_b": jax.random.normal(k[3], (12, 2 * 14))}
    x = jax.random.normal(k[4], (3, 4, 16))
    got = _faulty_scores(None)(None)(lp, x, sp, sp.layers[1], jnp.float32)
    want = tr.latent_scores(lp, x, sp, sp.layers[1], jnp.float32)
    for g, w in zip(got, want):
        assert jnp.allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,wrapper,number", [
    ("latent_scores", _faulty_scores("rotary-key-per-head"), "td_rms_gap"),
    ("rms_norm", _latent_norm_left_out, "td_rms_gap"),
    ("trunk_layer", _altered(rope_interleave=False), "td_rms_gap"),
    ("latent_scores", _faulty_scores("rope-on-the-no-position-part"),
     "td_rms_gap"),
    ("latent_scores", _faulty_scores("scale-of-the-no-position-width"),
     "td_rms_gap"),
    ("latent_scores", _faulty_scores("value-from-the-key-half"),
     "td_rms_gap"),
    ("gated_ffn", _one_shared_expert_of_two, "td_rms_gap"),
    ("trunk_layer", _altered(route_scale=1.0), "td_rms_gap"),
    ("trunk_layer", _altered(top_k=5), "td_rms_gap")],
    ids=["rotary-key-per-head", "latent-norm-left-out",
         "half-split-pairing", "rope-on-the-no-position-part",
         "scale-of-the-no-position-width", "value-from-the-key-half",
         "one-shared-expert-of-two", "routed-scaling-factor-left-out",
         "two-experts-more-a-token"])
def test_fault_in_the_layer_is_not_correct(monkeypatch, name, wrapper,
                                           number):
    """Top-5 where 3 is published stands for the issue's top-8 where 6
    is: two experts a token more. The value read from the key half reads
    the first 6 of the no-position key's 8 columns (at the published
    widths the two halves are 128 each)."""
    from t2omca_tpu.models import trunk
    monkeypatch.setattr(trunk, name, wrapper(getattr(trunk, name)))
    result, _ = _run()
    assert result["correct"] is False
    assert number in _failed(result), result["compared"]
