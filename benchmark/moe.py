"""What the readers of a sparse-expert trunk's per-layer metrics share
(``metrics/moe_dev_ms.py``, ``experts_roofline_pct.py``,
``expert_load_max_share.py``, ``trunk_step_mfu_pct.py``): the program's
``moe_*`` counters as it logged them inside the window, and the device
self time under the model's innermost scopes whatever the outer scope.

The counters ride the program's own fetches (``models/trunk.moe_counters``
in the training info rows and the rollout stats) and reach a file only
through its logger: ``metrics.jsonl`` under the run's results path. With a
program that counts nothing (one without ``model.trunk``, or the parent of
the PR that brought it) everything here reads as nothing and the metrics
return ``None``.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os

_CACHE: dict = {}


def counters(ctx) -> dict:
    """{logged key: mean over the rows logged inside the window} for the
    keys that start with ``moe_`` or ``test_moe_``; ``{}`` where none is."""
    w = ctx.window
    lo, hi = w.it_open * w.spi, w.it_close * w.spi
    rows: dict = {}
    for path in glob.glob(os.path.join(ctx.cfg.local_results_path, "**",
                                       "metrics.jsonl"), recursive=True):
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                key = str(ev.get("key", ""))
                if (key.startswith(("moe_", "test_moe_"))
                        and lo < ev.get("t", -1) <= hi):
                    rows.setdefault(key, []).append(float(ev["value"]))
    return {k: sum(v) / len(v) for k, v in rows.items()}


def inner_seconds(ctx) -> dict:
    """{innermost scope: device self seconds in the traced window, summed
    over the outer scopes it was opened under} (``scopes.reduce``'s
    ``inner_s``, which ``scopes.reduction`` does not hand on); ``{}``
    without a trace."""
    trace_dir = getattr(ctx.window, "trace_dir", None)
    if not trace_dir or not ctx.trace:
        return {}
    if trace_dir not in _CACHE:
        from benchmark import scopes
        names, _ = scopes.vocabulary()
        red = scopes.reduce(scopes.load(trace_dir), names,
                            busy_s=ctx.trace["busy_s"])
        out: dict = {}
        for inner in red["inner_s"].values():
            for name, s in inner.items():
                out[name] = out.get(name, 0.0) + s
        _CACHE[trace_dir] = out
    return _CACHE[trace_dir]


def config_ops(ctx):
    """``configs/<config>.ops.py`` — the configuration's operation and
    byte counts — or ``None`` where the configuration brings none."""
    path = os.path.join(ctx.bench_dir, "configs",
                        ctx.cell.config_name + ".ops.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark_ops_" + ctx.cell.config_name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(ctx) -> dict:
    with open(os.path.join(ctx.bench_dir, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if ctx.device_kind not in table:
        raise KeyError(f"no peak for device kind {ctx.device_kind!r} in "
                       f"peaks.json")
    return table[ctx.device_kind]


def rollouts_in_window(ctx) -> tuple:
    """(training rollouts, test rollouts) the window's periods hold."""
    it = ctx.window.iterations
    return it, it / ctx.cell.period_iterations
