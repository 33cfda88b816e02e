"""``benchmark/reference/afmoe.py`` against an independent restatement:
numpy, float64, one token at a time (no batched contraction, no mask
tensor, rotations as 2 x 2 matrices, the top-k by sorting) at a tiny
size; the configuration's second statement of its sizes against its
file; its operation counts by hand; and the two new per-layer readers on
synthetic readings."""

import importlib.util
import json
import math
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe
from benchmark.tests import tiny

D, HD, F, DENSE, N = 12, 4, 6, 10, 5
SIZES = dict(head_dim=HD, q_heads=4, kv_heads=2, experts=6, experts_held=3,
             expert_offset=2, top_k=2, route_scale=1.7, eps=1e-5, theta=50.0,
             window=3, layers=(("dense", "sliding"), ("experts", "full"),
                               ("experts", "sliding")))


def _weights(rng):
    g = lambda *s: rng.standard_normal(s) * 0.4               # noqa: E731

    def layer(dense):
        p = {"input_norm": 1 + g(D), "post_norm": 1 + g(D),
             "attn_out_norm": 1 + g(D), "ff_out_norm": 1 + g(D),
             "q_norm": 1 + g(HD), "k_norm": 1 + g(HD),
             "wq": g(D, 4 * HD), "wk": g(D, 2 * HD), "wv": g(D, 2 * HD),
             "wg": g(D, 4 * HD), "wo": g(4 * HD, D)}
        if dense:
            return dict(p, dense_gate=g(D, DENSE), dense_up=g(D, DENSE),
                        dense_down=g(DENSE, D))
        return dict(p, router=g(D, 6), expert_bias=g(6),
                    w_gate=g(3, D, F), w_up=g(3, D, F), w_down=g(3, F, D),
                    shared_gate=g(D, F), shared_up=g(D, F),
                    shared_down=g(F, D))
    return {"layer_0": layer(True), "layer_1": layer(False),
            "layer_2": layer(False)}


def _norm(x, scale):
    return x / math.sqrt(float(np.mean(x * x)) + 1e-5) * scale


def _turn(v, pos, theta):
    """Rotate the pairs (j, j + D/2) of one head vector by pos * f_j."""
    out = v.copy()
    half = len(v) // 2
    for j in range(half):
        ang = pos * theta ** (-2.0 * j / len(v))
        rot = np.array([[math.cos(ang), -math.sin(ang)],
                        [math.sin(ang), math.cos(ang)]])
        out[j], out[j + half] = rot @ np.array([v[j], v[j + half]])
    return out


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _swiglu(x, gate, up, down):
    a = x @ gate
    return ((a * _sigmoid(a)) * (x @ up)) @ down


def _layer_one_token_at_a_time(p, h, layer):
    """``h (N, D)`` one sequence → the layer's output, token by token."""
    kind, span = SIZES["layers"][layer]
    n = h.shape[0]
    u = np.stack([_norm(h[i], p["input_norm"]) for i in range(n)])
    q = (u @ p["wq"]).reshape(n, 4, HD)
    k = (u @ p["wk"]).reshape(n, 2, HD)
    v = (u @ p["wv"]).reshape(n, 2, HD)
    q = np.stack([[_norm(q[i, j], p["q_norm"]) for j in range(4)]
                  for i in range(n)])
    k = np.stack([[_norm(k[i, j], p["k_norm"]) for j in range(2)]
                  for i in range(n)])
    if span == "sliding":                      # positions on these only
        q = np.stack([[_turn(q[i, j], i, SIZES["theta"]) for j in range(4)]
                      for i in range(n)])
        k = np.stack([[_turn(k[i, j], i, SIZES["theta"]) for j in range(2)]
                      for i in range(n)])
    out = np.zeros_like(h)
    for i in range(n):
        first = (max(0, i - SIZES["window"] + 1) if span == "sliding" else 0)
        heads = []
        for j in range(4):
            g = j // 2
            s = np.array([q[i, j] @ k[t, g] for t in range(first, i + 1)])
            w = np.exp((s - s.max()) / math.sqrt(HD))
            w = w / w.sum()
            heads.append(sum(w[t - first] * v[t, g]
                             for t in range(first, i + 1)))
        gated = np.concatenate(heads) * _sigmoid(u[i] @ p["wg"])
        a = h[i] + _norm(gated @ p["wo"], p["attn_out_norm"])
        m = _norm(a, p["post_norm"])
        if kind == "dense":
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
        out[i] = a + _norm(f, p["ff_out_norm"])
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
        got = afmoe.layer_forward(f32(p[f"layer_{i}"]), got, trunk=SIZES,
                                  layer=i, prec="f32")
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_routing_keeps_top_k_of_the_biased_scores_and_weighs_unbiased():
    rng = np.random.default_rng(1)
    p = {"router": jnp.asarray(rng.standard_normal((D, 6)), jnp.float32),
         "expert_bias": jnp.asarray([0, 0, 0, 0, 9.0, -9.0], jnp.float32)}
    m = jnp.asarray(rng.standard_normal((7, D)), jnp.float32)
    r = np.asarray(afmoe.routing(p, m, trunk=SIZES))
    assert ((r > 0).sum(-1) == 2).all()
    np.testing.assert_allclose(r.sum(-1), 1.7, rtol=1e-6)
    assert (r[:, 4] > 0).all() and (r[:, 5] == 0).all()   # the bias chose
    score = 1 / (1 + np.exp(-np.asarray(m @ p["router"])))
    other = np.argmax(np.where(np.arange(6) < 4, score, -1), -1)
    rows = np.arange(7)
    np.testing.assert_allclose(                      # … and did not weigh
        r[:, 4] / r[rows, other], score[:, 4] / score[rows, other],
        rtol=1e-5)


# ------------------------------------------------ the configuration's files

def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = "agv16-trinity-mini-ep16"


def test_reference_file_states_the_configurations_sizes_a_second_time():
    from benchmark import check, harness
    cell = harness.load_cell(CONFIG + ".train")
    cfg = harness.build_cfg(cell, 0, "/tmp")
    assert cfg.superstep == 1 and cfg.batch_size_run == 8
    check.check_supported(cfg, 1)
    ref = check.load_reference(CONFIG, tiny.BENCH, cfg)      # SIZES, GAMMA
    tk, t = cfg.model.trunk, ref.TRUNK
    sp = tk.spec
    assert (t["head_dim"], t["q_heads"], t["kv_heads"], len(t["layers"])) == (
        sp.head_dim, sp.heads_held, sp.kv_heads_held, tk.num_hidden_layers)
    assert (t["experts"], t["experts_held"], t["expert_offset"], t["top_k"],
            t["route_scale"]) == (sp.experts, sp.experts_held,
                                  sp.expert_offset, sp.top_k, sp.route_scale)
    assert (t["eps"], t["theta"], t["window"]) == (
        sp.rms_norm_eps, sp.rope_theta, tk.sliding_window)
    for (kind, span), ls in zip(t["layers"], sp.layers):
        assert (kind == "dense") == bool(ls.dense_width)
        assert (span == "sliding") == ls.rope == bool(ls.window)
    # one dense layer and one whole period of expert layers, 3 : 1
    assert [k for k, _ in t["layers"]] == ["dense"] + ["experts"] * 4
    assert [s for _, s in t["layers"]][1:] == ["sliding", "full", "sliding",
                                               "sliding"]
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["source"].startswith(cell.config["source"])


def test_ops_file_counts_what_its_docstring_says():
    ops = _load(os.path.join(tiny.BENCH, "configs", CONFIG + ".ops.py"),
                "afmoe_ops_under_test")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ops.pair_flops() == 6 * 2048 * 1024
    # per token: q, gate, o over 8 heads and k, v over 1 (26 head-width
    # products), the causal prefix, then router + shared expert or the
    # dense feed-forward — ISSUE 31's 13.6 + 0.5 + 12.6 and 89.1 MFLOP
    proj = 2 * 2048 * 128 * 26
    context = 4 * 8 * 128 * 9
    assert ops.token_flops() == pytest.approx(
        proj + context + 2 * 2048 * 128 + 6 * 2048 * 1024)
    assert ops.token_flops(dense=True) == pytest.approx(
        proj + context + 6 * 2048 * 6144)
    assert ops.token_flops() / 1e6 == pytest.approx(13.6 + 0.5 + 12.6,
                                                    abs=0.1)
    assert ops.token_flops(dense=True) / 1e6 == pytest.approx(89.1, abs=0.1)
    # an even router holds 0.5 pairs a token a layer here: 2,176 tokens an
    # acting step -> 1,088 pairs a call; the weights' bytes bind
    act = ops.experts_call(1088, peak)
    assert act > 1088 * ops.pair_flops() / 197e12
    assert act == pytest.approx(
        (8 * 3 * 2048 * 1024 * 2 + 1088 * (2 * 2048 + 3 * 1024) * 2)
        / 819e9)
    # all-on-this-chip (8 pairs a token): the operations bind
    full = ops.experts_call(2176 * 8, peak)
    assert full == pytest.approx(2176 * 8 * ops.pair_flops() / 197e12)
    # a period of the cell with an even router, by hand
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

def _ctx(tmp_path, inner):
    from benchmark import moe
    window = types.SimpleNamespace(
        it_open=8, it_close=12, spi=1200, iterations=4, window_s=24.0,
        trace_dir=str(tmp_path / "trace"))
    moe._CACHE[window.trace_dir] = inner
    cfg = types.SimpleNamespace(local_results_path=str(tmp_path))
    cell = types.SimpleNamespace(config_name=CONFIG, period_iterations=4)
    return types.SimpleNamespace(
        window=window, cfg=cfg, cell=cell, trace={"busy_s": 23.9},
        device_kind="TPU v5 lite", chips=1, bench_dir=tiny.BENCH)


def _read(name, ctx):
    from benchmark import harness
    return harness.load_reader(name, tiny.BENCH).read(ctx)


def test_new_readers_on_synthetic_scope_seconds(tmp_path):
    ctx = _ctx(tmp_path, {"agent.router": 0.4, "agent.experts": 7.6,
                          "agent.shared": 1.2, "agent.dense": 0.8})
    assert _read("router_dev_ms", ctx) == pytest.approx(100.0)
    assert _read("shared_ffn_dev_ms", ctx) == pytest.approx(500.0)
    assert _read("moe_dev_ms", ctx) == pytest.approx(2000.0)


def test_new_readers_find_nothing_on_a_program_without_the_scopes(tmp_path):
    """The parent of the PR that brought ``agent.shared`` / ``agent.dense``
    (or a trunk without a shared expert and a dense layer): ``None``, and
    nothing raised; ``router_dev_ms`` likewise without ``agent.router``."""
    ctx = _ctx(tmp_path, {"agent.attention": 2.0, "agent.experts": 3.0})
    assert _read("shared_ffn_dev_ms", ctx) is None
    assert _read("router_dev_ms", ctx) is None
    ctx.trace = None                                  # an untraced run
    assert _read("router_dev_ms", ctx) is None
    assert _read("shared_ffn_dev_ms", ctx) is None
