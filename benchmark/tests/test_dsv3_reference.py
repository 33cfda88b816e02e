"""``benchmark/reference/dsv3.py`` against an independent restatement:
numpy, float64, one token at a time (no batched contraction, no mask
tensor, rotations as 2 x 2 matrices on the pairs ``(2i, 2i + 1)``, the
top-k by sorting) at a tiny size; the configuration's second statement of
its sizes against its file; its operation counts by hand; and the three
new per-layer readers on synthetic readings."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dsv3
from benchmark.tests import tiny
from benchmark.tests.test_afmoe_reference import (_ctx, _load, _read,
                                                  _sigmoid, _swiglu)

D, NOPE, ROPE, VALUE, RANK, F, DENSE, N = 12, 4, 2, 3, 5, 6, 10, 5
SIZES = dict(q_heads=3, nope=NOPE, rope=ROPE, value=VALUE, latent=RANK,
             experts=6, experts_held=3, expert_offset=2, top_k=2,
             route_scale=1.7, eps=1e-6, theta=50.0,
             layers=("dense", "experts", "experts"))


def _weights(rng):
    g = lambda *s: rng.standard_normal(s) * 0.4               # noqa: E731

    def layer(dense):
        p = {"input_norm": 1 + g(D), "post_norm": 1 + g(D),
             "kv_norm": 1 + g(RANK), "wq": g(D, 3 * (NOPE + ROPE)),
             "wkv_a": g(D, RANK + ROPE), "wkv_b": g(RANK, 3 * (NOPE + VALUE)),
             "wo": g(3 * VALUE, D)}
        if dense:
            return dict(p, dense_gate=g(D, DENSE), dense_up=g(D, DENSE),
                        dense_down=g(DENSE, D))
        return dict(p, router=g(D, 6), expert_bias=g(6),
                    w_gate=g(3, D, F), w_up=g(3, D, F), w_down=g(3, F, D),
                    shared_gate=g(D, 2 * F), shared_up=g(D, 2 * F),
                    shared_down=g(2 * F, D))
    return {"layer_0": layer(True), "layer_1": layer(False),
            "layer_2": layer(False)}


def _norm(x, scale):
    return x / math.sqrt(float(np.mean(x * x)) + 1e-6) * scale


def _turn(v, pos, theta):
    """Rotate the pairs (2i, 2i + 1) of one vector by pos * theta^(-2i/D)."""
    out = v.copy()
    for i in range(len(v) // 2):
        ang = pos * theta ** (-2.0 * i / len(v))
        rot = np.array([[math.cos(ang), -math.sin(ang)],
                        [math.sin(ang), math.cos(ang)]])
        out[2 * i:2 * i + 2] = rot @ v[2 * i:2 * i + 2]
    return out


def _layer_one_token_at_a_time(p, h, layer):
    """``h (N, D)`` one sequence → the layer's output, token by token."""
    n = h.shape[0]
    u = np.stack([_norm(h[i], p["input_norm"]) for i in range(n)])
    q = (u @ p["wq"]).reshape(n, 3, NOPE + ROPE)
    down = u @ p["wkv_a"]
    # ONE rotary key a token, split off before the latent's norm
    k_r = np.stack([_turn(down[i, RANK:], i, SIZES["theta"])
                    for i in range(n)])
    kv = np.stack([_norm(down[i, :RANK], p["kv_norm"]) @ p["wkv_b"]
                   for i in range(n)]).reshape(n, 3, NOPE + VALUE)
    out = np.zeros_like(h)
    for i in range(n):
        heads = []
        for j in range(3):
            qj = np.concatenate([q[i, j, :NOPE],
                                 _turn(q[i, j, NOPE:], i, SIZES["theta"])])
            s = np.array([qj @ np.concatenate([kv[t, j, :NOPE], k_r[t]])
                          for t in range(i + 1)])
            w = np.exp((s - s.max()) / math.sqrt(NOPE + ROPE))
            w = w / w.sum()
            heads.append(sum(w[t] * kv[t, j, NOPE:] for t in range(i + 1)))
        a = h[i] + np.concatenate(heads) @ p["wo"]
        m = _norm(a, p["post_norm"])
        if SIZES["layers"][layer] == "dense":
            f = _swiglu(m, p["dense_gate"], p["dense_up"], p["dense_down"])
        else:
            score = _sigmoid(m @ p["router"])
            kept = sorted(range(6), key=lambda e: -(
                score[e] + p["expert_bias"][e]))[:SIZES["top_k"]]
            total = sum(score[e] for e in kept) + 1e-20
            f = _swiglu(m, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
            for e in kept:
                local = e - SIZES["expert_offset"]
                if 0 <= local < SIZES["experts_held"]:  # held on this chip
                    f = f + SIZES["route_scale"] * score[e] / total * _swiglu(
                        m, p["w_gate"][local], p["w_up"][local],
                        p["w_down"][local])
        out[i] = a + f
    return out


def test_reference_matches_the_one_token_at_a_time_restatement():
    rng = np.random.default_rng(0)
    p = _weights(rng)
    h = rng.standard_normal((3, N, D))
    want = h
    for i in range(3):
        want = np.stack([_layer_one_token_at_a_time(p[f"layer_{i}"], s, i)
                         for s in want])
    f32 = lambda t: {k: jnp.asarray(v, jnp.float32)           # noqa: E731
                     for k, v in t.items()}
    got = jnp.asarray(h, jnp.float32)
    for i in range(3):
        got = dsv3.layer_forward(f32(p[f"layer_{i}"]), got, trunk=SIZES,
                                 layer=i, prec="f32")
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_rotation_is_of_the_interleaved_pairs_and_leaves_norms():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 4, 6))
    got = np.asarray(dsv3.rotate_pairs(jnp.asarray(x, jnp.float32), 50.0))
    want = np.stack([[_turn(x[s, i], i, 50.0) for i in range(4)]
                     for s in range(2)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)  # position 0
    np.testing.assert_allclose((got ** 2).sum(-1), (x ** 2).sum(-1),
                               rtol=1e-5)


# ------------------------------------------------ the configuration's files

CONFIG = "agv16-kanana2-ep16"


def test_reference_file_states_the_configurations_sizes_a_second_time():
    from benchmark import check, harness
    cell = harness.load_cell(CONFIG + ".train")
    cfg = harness.build_cfg(cell, 0, "/tmp")
    assert cfg.superstep == 1 and cfg.batch_size_run == 8
    check.check_supported(cfg, 1)
    ref = check.load_reference(CONFIG, tiny.BENCH, cfg)      # SIZES, GAMMA
    tk, t = cfg.model.trunk, ref.TRUNK
    sp = tk.spec
    assert (t["q_heads"], t["nope"], t["rope"], t["value"], t["latent"]) == (
        sp.heads_held, sp.qk_nope_dim, sp.qk_rope_dim, sp.value_dim,
        sp.kv_latent) == (16, 128, 64, 128, 512)
    assert t["nope"] + t["rope"] == sp.head_dim == tk.qk_head_dim
    assert (t["experts"], t["experts_held"], t["expert_offset"], t["top_k"],
            t["route_scale"]) == (sp.experts, sp.experts_held,
                                  sp.expert_offset, sp.top_k, sp.route_scale)
    assert (t["eps"], t["theta"]) == (sp.rms_norm_eps, sp.rope_theta)
    assert sp.rope_interleave and all(ls.rope and not ls.window
                                      for ls in sp.layers)
    # the leading dense layer and four of the routed layers after it
    assert list(t["layers"]) == ["dense"] + ["experts"] * 4 == [
        "dense" if ls.dense_width else "experts" for ls in sp.layers]
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["source"].startswith(cell.config["source"])
    for key in ("changed", "assumed", "published", "deployment"):
        assert cell.config[key], key


def test_ops_file_counts_what_its_docstring_says():
    ops = _load(os.path.join(tiny.BENCH, "configs", CONFIG + ".ops.py"),
                "dsv3_ops_under_test")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ops.pair_flops() == 6 * 2048 * 768
    # ISSUE 33's token: q 12.6, latent down 2.4, up 4.2, o 8.4, context
    # 0.1 = 27.6 MFLOP; router 0.5, shared 18.9; the dense layer 75.5
    q, down = 2 * 2048 * 16 * 192, 2 * 2048 * 576
    up, o = 2 * 512 * 16 * 256, 2 * 16 * 128 * 2048
    context = 2 * 16 * (192 + 128) * 9
    assert [round(x / 1e6, 1) for x in (q, down, up, o, context)] == [
        12.6, 2.4, 4.2, 8.4, 0.1]
    assert ops.attention_flops() == pytest.approx(q + down + up + o + context)
    assert ops.token_flops() == pytest.approx(
        ops.attention_flops() + 2 * 2048 * 128 + 6 * 2048 * 1536)
    assert ops.token_flops(dense=True) == pytest.approx(
        ops.attention_flops() + 6 * 2048 * 6144)
    assert ops.token_flops(dense=True) / 1e6 == pytest.approx(103.1, abs=0.1)
    # + the 8 held experts over every token (what the PROGRAM multiplies):
    # ISSUE 33's 122.5 MFLOP an expert layer, 593 over the five layers
    program = ops.token_flops() + 8 * ops.pair_flops()
    assert program / 1e6 == pytest.approx(122.5, abs=0.1)
    assert (4 * program + ops.token_flops(dense=True)) / 1e6 == pytest.approx(
        593, abs=1)
    # an even router holds 6 x 8 / 128 pairs a token a layer: 2,176 tokens
    # an acting step -> 816 pairs a call; the weights' bytes bind
    act = ops.experts_call(816, peak)
    assert act == pytest.approx(
        (8 * 3 * 2048 * 768 * 2 + 816 * (2 * 2048 + 3 * 768) * 2) / 819e9)
    assert act > 816 * ops.pair_flops() / 197e12
    # attention at the acting call's 2,176 rows: the operations bind; at
    # the learner's 64 hidden tokens a step: the weights' bytes (13.77 M
    # parameters held a layer)
    weights = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256
               + 16 * 128 * 2048)
    assert weights == 13_762_560                 # 13.77 M with the norms
    assert ops.attention_call(2176, peak) == pytest.approx(
        2176 * ops.attention_flops() / 197e12)
    acts = 64 * (2048 + 3072 + 2048 + 576 + 512 + 4096 + 2048 + 2048)
    assert ops.attention_call(64, peak) == pytest.approx(
        (weights + acts) * 2 / 819e9)
    assert ops.attention_call(64, peak, backward=True) == pytest.approx(
        (3 * weights + 2 * acts) * 2 / 819e9)
    # a window of one period, by hand: 5 rollouts of 150 steps x 5 layers,
    # 4 updates of (2 forward + 1 backward) x (151 hidden calls + 1 entity
    # call) x 5 layers
    hidden = lambda b: ops.attention_call(4 * 16, peak, b)    # noqa: E731
    entity = lambda b: ops.attention_call(4 * 151 * 256, peak, b)  # noqa
    want = (5 * 150 * 5 * ops.attention_call(2176, peak)
            + 4 * 5 * (2 * (151 * hidden(False) + entity(False))
                       + 151 * hidden(True) + entity(True)))
    assert ops.attention_needed_s(lanes=8, batch=4, steps=150, rollouts=5,
                                  updates=4, peak=peak) == pytest.approx(want)
    # experts_needed_s is the accepted cells' reckoning at this width
    tri = _load(os.path.join(tiny.BENCH, "configs",
                             "agv16-trinity-mini-ep16.ops.py"), "tri_ops")
    tri.F = 768
    kw = dict(rollout_pairs=1.3e6, rollouts=5, update_pairs=7.1e5, updates=4,
              steps=150, peak=peak)
    assert ops.experts_needed_s(**kw) == pytest.approx(
        tri.experts_needed_s(**kw))
    # a period of the cell with nothing routed here, by hand
    step = ops.agent_step_flops()
    assert step == pytest.approx(17 * (4 * ops.token_flops()
                                       + ops.token_flops(dense=True))
                                 + 2 * 9 * 2048 * 16 + 2 * 2048 * 5)
    total = ops.period_flops(lanes=8, batch=4, steps=150, period_iterations=4,
                             rollout_pairs=0.0, test_pairs=0.0,
                             update_pairs=0.0, mixer_step=0.0)
    assert total == pytest.approx(
        (5 * 8 * 150 + 4 * 4 * 4 * 151) * 16 * step)


# ------------------------------------------------------------- the readers

def _ctx33(tmp_path, inner):
    ctx = _ctx(tmp_path, inner)
    ctx.cell.config_name = CONFIG
    ctx.cfg.batch_size_run, ctx.cfg.batch_size = 8, 4
    ctx.cfg.env_args = type("E", (), {"episode_limit": 150})
    return ctx


def test_new_readers_on_synthetic_scope_seconds(tmp_path):
    ctx = _ctx33(tmp_path, {"agent.attention": 6.0, "agent.latent": 2.0,
                            "agent.experts": 7.6})
    assert _read("attention_dev_ms", ctx) == pytest.approx(2000.0)
    assert _read("latent_dev_ms", ctx) == pytest.approx(500.0)
    ops = _load(os.path.join(tiny.BENCH, "configs", CONFIG + ".ops.py"),
                "dsv3_ops_for_reader")
    needed = ops.attention_needed_s(
        lanes=8, batch=4, steps=150, rollouts=5, updates=4,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    share = _read("attention_roofline_pct", ctx)
    assert share == pytest.approx(100.0 * needed / 8.0)
    assert 0 < share < 100


def test_new_readers_find_nothing_on_a_program_without_the_scope(tmp_path):
    """The parent of the PR that brought ``agent.latent`` (or a
    grouped-query trunk): ``None``, and nothing raised."""
    ctx = _ctx33(tmp_path, {"agent.attention": 2.0, "agent.experts": 3.0})
    for name in ("attention_dev_ms", "latent_dev_ms",
                 "attention_roofline_pct"):
        assert _read(name, ctx) is None, name
    ctx = _ctx33(tmp_path, {"agent.attention": 2.0, "agent.latent": 1.0})
    ctx.cell.config_name = "agv16-trinity-mini-ep16"   # no attention count
    assert _read("attention_roofline_pct", ctx) is None
    ctx.trace = None                                   # an untraced run
    for name in ("attention_dev_ms", "latent_dev_ms",
                 "attention_roofline_pct"):
        assert _read(name, ctx) is None, name
