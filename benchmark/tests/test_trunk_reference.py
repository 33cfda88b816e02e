"""``benchmark/reference/trunk.py`` against an independent restatement:
numpy, float64, one token at a time (no batched contraction, no mask
tensor, rotations as 2 x 2 matrices) at a tiny size; the configuration's
second statement of its sizes against its file; and the new per-layer
readers on synthetic readings."""

import importlib.util
import json
import math
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import trunk
from benchmark.tests import tiny

D, HD, F, N = 12, 4, 6, 5
SIZES = dict(head_dim=HD, q_heads=4, kv_heads=2, layers=2, experts=6,
             experts_held=3, expert_offset=2, top_k=2, eps=1e-6,
             rope=(0, 1), window=(0, 3), theta=50.0)


def _weights(rng):
    g = lambda *s: rng.standard_normal(s) * 0.4               # noqa: E731
    layer = lambda: {                                         # noqa: E731
        "input_norm": 1 + g(D), "post_norm": 1 + g(D),
        "wq": g(D, 4 * HD), "wk": g(D, 2 * HD), "wv": g(D, 2 * HD),
        "wo": g(4 * HD, D), "router": g(D, 6), "w_gate": g(3, D, F),
        "w_up": g(3, D, F), "w_down": g(3, F, D)}
    return {"layer_0": layer(), "layer_1": layer(), "norm": 1 + g(D)}


def _norm(x, scale):
    return x / math.sqrt(float(np.mean(x * x)) + 1e-6) * scale


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


def _layer_one_token_at_a_time(p, h, layer):
    """``h (N, D)`` one sequence → the layer's output, token by token."""
    n = h.shape[0]
    x = np.stack([_norm(h[i], p["input_norm"]) for i in range(n)])
    q = (x @ p["wq"]).reshape(n, 4, HD)
    k = (x @ p["wk"]).reshape(n, 2, HD)
    v = (x @ p["wv"]).reshape(n, 2, HD)
    if SIZES["rope"][layer]:
        q = np.stack([[_turn(q[i, j], i, SIZES["theta"]) for j in range(4)]
                      for i in range(n)])
        k = np.stack([[_turn(k[i, j], i, SIZES["theta"]) for j in range(2)]
                      for i in range(n)])
    out = np.zeros_like(h)
    for i in range(n):
        window = SIZES["window"][layer]
        first = max(0, i - window + 1) if window else 0
        heads = []
        for j in range(4):
            g = j // 2
            s = np.array([q[i, j] @ k[t, g] for t in range(first, i + 1)])
            w = np.exp((s - s.max()) / math.sqrt(HD))
            w = w / w.sum()
            heads.append(sum(w[t - first] * v[t, g]
                             for t in range(first, i + 1)))
        a = h[i] + np.concatenate(heads) @ p["wo"]
        # the router reads the layer's INPUT, un-normed
        logits = h[i] @ p["router"]
        probs = np.exp(logits - logits.max())
        probs = probs / probs.sum()
        kept = sorted(range(6), key=lambda e: -probs[e])[:SIZES["top_k"]]
        total = sum(probs[e] for e in kept)
        m = _norm(a, p["post_norm"])
        y = a.copy()
        for e in kept:
            local = e - SIZES["expert_offset"]
            if 0 <= local < SIZES["experts_held"]:      # held on this chip
                act = np.maximum(m @ p["w_gate"][local], 0) * (
                    m @ p["w_up"][local])
                y = y + probs[e] / total * (act @ p["w_down"][local])
        out[i] = y
    return out


def test_reference_matches_the_one_token_at_a_time_restatement():
    rng = np.random.default_rng(0)
    p = _weights(rng)
    h = rng.standard_normal((3, N, D))
    want = np.stack([_layer_one_token_at_a_time(
        p["layer_1"], _layer_one_token_at_a_time(p["layer_0"], s, 0), 1)
        for s in h])
    f32 = lambda t: {k: jnp.asarray(v, jnp.float32)           # noqa: E731
                     for k, v in t.items()}
    got = jnp.asarray(h, jnp.float32)
    for i in range(2):
        got = trunk.layer_forward(f32(p[f"layer_{i}"]), got, trunk=SIZES,
                                  layer=i, prec="f32")
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_routing_keeps_top_k_and_renormalises():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((D, 6)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((7, D)), jnp.float32)
    r = np.asarray(trunk.routing(w, h, 2))
    assert ((r > 0).sum(-1) == 2).all()
    np.testing.assert_allclose(r.sum(-1), 1.0, rtol=1e-6)
    logits = np.asarray(h @ w)
    assert (np.argsort(-logits, -1)[:, :2] == np.argsort(-r, -1)[:, :2]).all()


# ------------------------------------------------ the configuration's files

def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = "agv16-smallthinker-ep8"


def test_reference_file_states_the_configurations_sizes_a_second_time():
    from benchmark import check, harness
    cell = harness.load_cell(CONFIG + ".train")
    cfg = harness.build_cfg(cell, 0, "/tmp")
    ref = check.load_reference(CONFIG, tiny.BENCH, cfg)      # SIZES, GAMMA
    tk, t = cfg.model.trunk, ref.TRUNK
    assert (t["head_dim"], t["q_heads"], t["kv_heads"], t["layers"]) == (
        tk.head_dim, tk.heads_held, tk.kv_heads_held, tk.num_hidden_layers)
    assert (t["experts"], t["experts_held"], t["expert_offset"],
            t["top_k"]) == (tk.moe_num_primary_experts, tk.experts_held,
                            tk.expert_offset,
                            tk.moe_num_active_primary_experts)
    assert t["rope"] == tk.rope_layout[:4] and t["theta"] == tk.rope_theta
    assert t["window"] == tuple(tk.sliding_window_size * w
                                for w in tk.sliding_window_layout[:4])
    assert t["eps"] == tk.rms_norm_eps
    # the catalog's numbers at the top level of the file, the held counts
    # under the keys `reduced` lists
    top = cell.config
    assert (top["hidden_size"], top["head_dim"], top["moe_ffn_hidden_size"],
            top["moe_num_active_primary_experts"], top["sliding_window_size"],
            top["rope_theta"]) == (2560, 128, 768, 6, 4096, 1500000)
    assert (top["num_hidden_layers"], top["moe_num_primary_experts"],
            top["num_attention_heads"], top["num_key_value_heads"]) == (
        tk.num_hidden_layers, tk.experts_held, tk.heads_held,
        tk.kv_heads_held)
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == top["reduced"]


def test_ops_file_counts_what_its_docstring_says():
    ops = _load(os.path.join(tiny.BENCH, "configs", CONFIG + ".ops.py"),
                "ops_under_test")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ops.pair_flops() == 6 * 2560 * 768
    # acting: 816 pairs a held expert a call — the operations bind
    act = ops.experts_call(816 * 8, peak)
    assert act == pytest.approx(816 * 8 * 6 * 2560 * 768 / 197e12)
    # the learner's 204 pairs a held expert — the weights' bytes bind
    learn = ops.experts_call(204 * 8, peak)
    assert learn > 204 * 8 * 6 * 2560 * 768 / 197e12
    assert learn == pytest.approx(
        (8 * 3 * 2560 * 768 * 2 + 204 * 8 * (2 * 2560 + 3 * 768) * 2)
        / 819e9)
    # per token and layer: 16 head-width projections, the causal prefix,
    # the router
    assert ops.token_flops() == pytest.approx(
        2 * 2560 * 128 * 16 + 4 * 7 * 128 * 9 + 2 * 2560 * 64)


# ------------------------------------------------------------- the readers

def _ctx(tmp_path, rows, inner=None):
    """A MetricContext-like object over synthetic logged rows (and
    synthetic scope seconds put where ``moe.inner_seconds`` caches)."""
    from benchmark import moe
    run = tmp_path / "run"
    run.mkdir(exist_ok=True)
    with open(run / "metrics.jsonl", "w") as f:
        for key, value, t in rows:
            f.write(json.dumps({"key": key, "value": value, "t": t}) + "\n")
    window = types.SimpleNamespace(
        it_open=8, it_close=12, spi=4800, iterations=4, window_s=10.0,
        trace_dir=str(tmp_path / "trace") if inner else None)
    if inner:
        moe._CACHE[window.trace_dir] = inner
    cfg = types.SimpleNamespace(
        local_results_path=str(tmp_path), batch_size_run=32, batch_size=8,
        env_args=types.SimpleNamespace(episode_limit=150, agv_num=16),
        model=types.SimpleNamespace(mixer_emb=2560, mixer_depth=1))
    cell = types.SimpleNamespace(config_name=CONFIG, period_iterations=4)
    return types.SimpleNamespace(
        window=window, cfg=cfg, cell=cell, trace={"busy_s": 9.9} if inner
        else None, device_kind="TPU v5 lite", chips=1, bench_dir=tiny.BENCH)


ROWS = [
    # inside the window (38,400 < t <= 57,600): one logged row of each
    ("moe_pairs_held", 2.0e6, 57600), ("moe_pairs_held_mean", 1.2e5, 57600),
    ("moe_load_max_mean", 2.4e4, 57600),
    ("test_moe_pairs_held_mean", 1.2e5, 57600),
    # outside it
    ("moe_pairs_held", 9.9e9, 38400), ("moe_load_max_mean", 9.9e9, 76800)]


def _read(name, ctx):
    from benchmark import harness
    return harness.load_reader(name, tiny.BENCH).read(ctx)


def test_readers_on_synthetic_readings(tmp_path):
    inner = {"agent.router": 0.4, "agent.experts": 7.6, "agent.attention": 2}
    ctx = _ctx(tmp_path, ROWS, inner)
    assert _read("expert_load_max_share", ctx) == pytest.approx(0.2)
    assert _read("moe_dev_ms", ctx) == pytest.approx(8.0e3 / 4)
    roof = _read("experts_roofline_pct", ctx)
    mfu = _read("trunk_step_mfu_pct", ctx)
    assert 0 < roof < 100 and 0 < mfu < 100
    # by hand: the needed time of 5 rollouts and 4 updates over 7.6 s
    ops = _load(os.path.join(tiny.BENCH, "configs", CONFIG + ".ops.py"),
                "ops_by_hand")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    need = ops.experts_needed_s(rollout_pairs=1.2e5 * 32, rollouts=5,
                                update_pairs=2.0e6, updates=4, steps=150,
                                peak=peak)
    assert roof == pytest.approx(100 * need / 7.6)


def test_readers_find_nothing_on_a_program_that_counts_nothing(tmp_path):
    """The parent of the PR that brought the counters and the scopes: no
    ``moe_*`` row, no such scope — every reader returns ``None``."""
    ctx = _ctx(tmp_path, [("loss", 1.0, 57600)],
               {"agent.attention": 2.0})
    for name in ("moe_dev_ms", "experts_roofline_pct",
                 "expert_load_max_share", "trunk_step_mfu_pct"):
        assert _read(name, ctx) is None
