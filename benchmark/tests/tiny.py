"""A throw-away cell for the CPU rehearsals: a temporary copy of
``benchmark/`` and of ``BENCHMARK.json`` to which a tiny configuration,
its limits, its traffic mix and one per-layer metric are ADDED as new
files and entries — no file that is there is edited, which is what a
later PR is held to."""

import json
import os
import shutil
import tempfile
import time

import jax

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
REPO = os.path.dirname(BENCH)

LIMITS = {"counters_off": 0, "last_update_skipped": 0, "priority_gap": 1e-5,
          "td_rms_gap": 1e-3, "reward_gap": 1e-4, "ack_off": 0,
          "avail_off": 0, "selector_off": 0, "greedy_regret": 1e-3}
# held where the state before the update is known (superstep: 1)
LIMITS_K1 = {"clip_gap": 1e-3, "adam_gap": 1e-3}

REFERENCE = '''"""Plain reference of the throw-away configuration."""
from benchmark.reference import qmix

SIZES = dict(n_agents=%d, emb=8, heads=2, depth=2, mixer_emb=8, mixer_heads=2,
             mixer_depth=2, standard_heads=True, n_actions=3, n_mec=2)
GAMMA = 0.99


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return qmix.episode_loss(params, target_params, batch, weights,
                             sizes=SIZES, gamma=GAMMA, prec=prec,
                             half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return qmix.unroll_agent(agent_params, batch, sizes=SIZES, prec=prec)[0]
'''

METRIC = '''"""A throw-away per-layer metric: iterations in the window."""
UNIT = "count"


def read(ctx):
    return ctx.window.iterations
'''


def make(k: int = 2, dtype: str = "float32", lanes: int = 8,
         agents: int = 3) -> str:
    """→ root of a temporary checkout holding BENCHMARK.json + benchmark/
    with the cell ``tiny.train`` added."""
    root = tempfile.mkdtemp(prefix="tinybench_")
    bd = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bd, ignore=shutil.ignore_patterns("__pycache__"))
    t_len = 6
    cfg = {"name": "tiny", "source": "throw-away", "reduced": [], "config": {
        "batch_size_run": lanes, "batch_size": 4, "superstep": k,
        "t_max": 2_000_000_000, "test_interval": lanes * t_len * 4,
        "save_model": False, "target_update_interval": lanes,
        "log_interval": 1, "runner_log_interval": 1,
        "epsilon_anneal_time": 100,
        "env_args": {"agv_num": agents, "mec_num": 2, "num_channels": 2,
                     "episode_limit": t_len},
        "model": {"emb": 8, "heads": 2, "depth": 2, "mixer_emb": 8,
                  "mixer_heads": 2, "mixer_depth": 2, "standard_heads": True,
                  "dtype": dtype, "remat": True},
        "replay": {"buffer_size": 2 * lanes, "store_dtype": "bfloat16"},
        "obs": {"enabled": True, "pulse_port": 0,
                "sight": {"enabled": True}}}}
    with open(os.path.join(bd, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bd, "configs", "tiny.reference.py"), "w") as f:
        f.write(REFERENCE % agents)
    with open(os.path.join(bd, "configs", "tiny.limits.json"), "w") as f:
        json.dump({"limits": dict(LIMITS, **(LIMITS_K1 if k == 1 else {}))},
                  f)
    with open(os.path.join(bd, "workloads", "tiny.train.json"), "w") as f:
        json.dump({"period_iterations": 4,
                   "warmup_iterations": 8 if k > 1 else 5}, f)
    with open(os.path.join(bd, "metrics", "tiny_iterations.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny", "source": "throw-away",
                          "file": "benchmark/configs/tiny.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "tiny.train", "config": "tiny",
                            "traffic": "train", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "tiny_iterations", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "driver loop",
                            "moves": "env_steps_per_s",
                            "workloads": ["tiny.train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def run(root: str, seconds: float = 0.5, seed: int = 2 ** 31 + 5):
    """Everything of a run after the look for a chip, on the CPU →
    (result, window facts kept by the comparison hook)."""
    from benchmark import harness
    from benchmark import run as brun
    bd = os.path.join(root, "benchmark")
    cell = harness.load_cell("tiny.train", bench_dir=bd)
    ledger = harness.CompileLedger().install()
    kept = {}
    work = tempfile.mkdtemp(prefix="tinybench_work_")
    try:
        result = brun.run_cell(
            cell, seed, seconds, False, work, ledger, jax.devices()[:1],
            bench_dir=bd, t_process=time.perf_counter(),
            extra=lambda c, w: kept.update(comparison=c, window=w))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    kept["cell"] = cell
    return result, kept
