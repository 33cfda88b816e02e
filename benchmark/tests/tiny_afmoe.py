"""``tiny.make`` for a configuration with an ``afmoe`` catalog trunk
(``model.trunk`` with ``model_type: afmoe``): a throw-away cell
``tinyafmoe.train`` on the classic three-program loop (``superstep: 1``,
so the optimizer's ``clip_gap`` / ``adam_gap`` are among the compared) —
a tiny share of published layers 1-3 of a tiny model (a dense layer, a
full-attention and a sliding-attention expert layer) as the agent's
stack, its reference through ``benchmark/reference/afmoe.py`` — ADDED as
new files and entries to a temporary copy of ``benchmark/`` and
``BENCHMARK.json``."""

import json
import os
import shutil
import tempfile
import time

import jax

from benchmark.tests import tiny

D = 16
TRUNK = {"model_type": "afmoe", "hidden_size": D, "head_dim": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 3, "num_dense_layers": 2,
         "intermediate_size": 24, "moe_intermediate_size": 8,
         "num_experts": 8, "num_experts_per_tok": 3,
         "num_shared_experts": 1, "route_scale": 2.5,
         "layer_types": ["sliding_attention", "sliding_attention",
                         "full_attention", "sliding_attention"],
         "sliding_window": 3, "rope_theta": 100.0, "experts_held": 4,
         "heads_held": 2, "share_index": 0, "first_layer": 1}

REFERENCE = '''"""Plain reference of the throw-away afmoe configuration."""
from benchmark.reference import afmoe

SIZES = dict(n_agents=%(agents)d, emb=16, heads=3, depth=3, mixer_emb=16,
             mixer_heads=2, mixer_depth=1, standard_heads=True, n_actions=3,
             n_mec=2)
GAMMA = 0.99
TRUNK = dict(head_dim=4, q_heads=2, kv_heads=1, experts=8, experts_held=4,
             expert_offset=0, top_k=3, route_scale=2.5, eps=1e-5,
             theta=100.0, window=3,
             layers=(("dense", "sliding"), ("experts", "full"),
                     ("experts", "sliding")))


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return afmoe.episode_loss(params, target_params, batch, weights,
                              sizes=SIZES, trunk=TRUNK, gamma=GAMMA,
                              prec=prec, half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return afmoe.unroll_agent(agent_params, batch, sizes=SIZES, trunk=TRUNK,
                              prec=prec)[0]
'''

#: the per-layer metrics the cell of this family reports, as
#: BENCHMARK.json has them
METRICS = ("moe_dev_ms", "experts_roofline_pct", "expert_load_max_share",
           "trunk_step_mfu_pct", "router_dev_ms", "shared_ffn_dev_ms")
NAME = "tinyafmoe"


def make(k: int = 1, dtype: str = "float32", lanes: int = 8,
         agents: int = 3) -> str:
    """→ root of a temporary checkout with the cell ``tinyafmoe.train``."""
    root = tempfile.mkdtemp(prefix="tinyafmoe_")
    bd = os.path.join(root, "benchmark")
    shutil.copytree(tiny.BENCH, bd,
                    ignore=shutil.ignore_patterns("__pycache__"))
    t_len = 6
    cfg = {"name": NAME, "source": "throw-away", "reduced": [],
           "config": {
        "batch_size_run": lanes, "batch_size": 4, "superstep": k,
        "t_max": 2_000_000_000, "test_interval": lanes * t_len * 4,
        "save_model": False, "target_update_interval": lanes,
        "log_interval": 1, "runner_log_interval": 1,
        "epsilon_anneal_time": 100,
        "env_args": {"agv_num": agents, "mec_num": 2, "num_channels": 2,
                     "episode_limit": t_len},
        "model": {"emb": D, "depth": 3, "mixer_emb": D, "mixer_heads": 2,
                  "mixer_depth": 1, "standard_heads": True, "dtype": dtype,
                  "remat": True, "trunk": TRUNK},
        "replay": {"buffer_size": 2 * lanes, "store_dtype": "bfloat16"},
        "obs": {"enabled": True, "pulse_port": 0,
                "sight": {"enabled": True}}}}
    conf = os.path.join(bd, "configs", NAME)
    with open(conf + ".json", "w") as f:
        json.dump(cfg, f)
    with open(conf + ".reference.py", "w") as f:
        f.write(REFERENCE % {"agents": agents})
    with open(conf + ".limits.json", "w") as f:
        json.dump({"limits": dict(tiny.LIMITS, **(
            tiny.LIMITS_K1 if k == 1 else {}))}, f)
    with open(os.path.join(bd, "workloads", NAME + ".train.json"), "w") as f:
        json.dump({"period_iterations": 4,
                   "warmup_iterations": 8 if k > 1 else 5}, f)
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": NAME, "source": "throw-away",
                          "file": f"benchmark/configs/{NAME}.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": NAME + ".train", "config": NAME,
                            "traffic": "train", "chips": 1, "why": "x"})
    for m in bm["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"].append(NAME + ".train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def run(root: str, seconds: float = 0.5, seed: int = 2 ** 31 + 5,
        extra=None):
    """``tiny.run`` for the cell ``tinyafmoe.train``; ``extra(ctx)`` is
    called with a ``MetricContext`` while the run's files still exist."""
    from benchmark import harness
    from benchmark import run as brun
    bd = os.path.join(root, "benchmark")
    cell = harness.load_cell(NAME + ".train", bench_dir=bd)
    ledger = harness.CompileLedger().install()
    kept = {"cell": cell}
    work = tempfile.mkdtemp(prefix="tinyafmoe_work_")

    def keep(c, w):
        kept.update(comparison=c, window=w)
        if extra is not None:
            extra(harness.MetricContext(
                cell=cell, cfg=c.cfg, window=w, trace=None,
                device_kind="TPU v5 lite", chips=1, bench_dir=bd))
    try:
        result = brun.run_cell(
            cell, seed, seconds, False, work, ledger, jax.devices()[:1],
            bench_dir=bd, t_process=time.perf_counter(), extra=keep)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return result, kept
