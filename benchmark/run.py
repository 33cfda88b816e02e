"""``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` — one run of one cell of ``BENCHMARK.json`` on the chips
this process finds. Last line of stdout: one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``; with ``--trace 1``
also ``breakdown``), with the numbers compared beside their limits last.
Without a TPU, or with fewer chips than the cell asks for, the exit code
is non-zero and no result is printed."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse                          # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
import tempfile                          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)


def say(obj) -> None:
    print(json.dumps(obj), flush=True)


def plain(x):
    """A reading that is not finite as a word: strict JSON has no NaN."""
    if isinstance(x, dict):
        return {n: plain(v) for n, v in x.items()}
    if isinstance(x, list):
        return [plain(v) for v in x]
    return repr(x) if isinstance(x, float) and x - x != 0 else x


def run_cell(cell, seed: int, seconds: float, trace: bool, workdir: str,
             ledger, devices, bench_dir: str = BENCH_DIR,
             t_process: float = T_PROCESS, extra=None,
             window_cls=None) -> dict:
    """Everything after the device check → the result object. ``extra``
    and ``window_cls`` (``calibrate.py`` and the tests only): ``extra`` is
    called with the finished comparison and the window."""
    from benchmark import check, harness
    cfg = harness.build_cfg(cell, seed, workdir)
    trace_dir = os.path.join(workdir, "trace") if trace else None
    window = (window_cls or harness.Window)(cfg, cell, seconds, ledger,
                                            t_process, trace_dir)
    ts = harness.drive(cfg, window)
    if window.phase != "done":
        raise RuntimeError(f"the run ended in phase {window.phase!r}")
    say({"phase": "window", "seconds": window.window_s,
         "iterations": window.iterations, "env_steps": window.env_steps,
         "setup_s": window.setup_s, "superstep": window.k,
         "compiles_in_window": window.compiles_in_window,
         "compile_seconds": {n: s for n, s in ledger.seconds.items()
                             if sum(s) >= 0.5},
         "compile_events": ledger.events, "cache_hits": ledger.cache_hits,
         "boundaries": [[i, t - window.t_open]
                        for i, t in window.boundaries]})
    peak = int(window.memory.get("peak_bytes_in_use", 0))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak,
              "memory_limit_bytes": int(window.memory.get("bytes_limit", 0))}

    comparison = check.Comparison(
        cfg, window.k, window.snap, window.infos, ts,
        check.load_reference(cell.config_name, bench_dir, cfg))
    del ts
    window.snap = window.infos = None
    t0 = time.perf_counter()
    numbers = comparison.finish()
    ok, compared = check.verdict(
        numbers, check.load_limits(cell.config_name, bench_dir))
    numbers = plain(numbers)
    say({"phase": "compare", "seconds": time.perf_counter() - t0,
         "numbers": numbers})
    if extra is not None:
        extra(comparison, window)

    result = {"correct": ok, "attempted": window.iterations,
              "failed": 0 if ok else window.iterations}
    if trace:
        from benchmark import trace as trace_mod
        t0 = time.perf_counter()
        reduced = trace_mod.reduce(trace_mod.load(trace_dir))
        ctx = harness.MetricContext(
            cell=cell, cfg=cfg, window=window, trace=reduced,
            device_kind=devices[0].device_kind, chips=cell.chips,
            bench_dir=bench_dir)
        result["metrics"] = harness.per_layer_metrics(ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_mod.breakdown(
            reduced, trace_mod.host_spans(workdir), window.trace_t0_ns)
        say({"phase": "trace", "seconds": time.perf_counter() - t0,
             "events": reduced["n_events"],
             "programs": dict(sorted(reduced["programs"].items(),
                                     key=lambda kv: -kv[1]["seconds"])[:8])})
    else:
        result["metrics"] = harness.end_to_end_metrics(window)
    result["device"] = device
    result["numbers"] = numbers          # every reading, compared or not
    result["td_errors_abs"] = plain(comparison.td)
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name} = {c['value']} (limit {c['limit']:g})",
              file=sys.stderr)
    print(f"correct = {ok}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    from benchmark import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)[:cell.chips]

    from t2omca_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    ledger = harness.CompileLedger().install()
    say({"phase": "device", "kind": devices[0].device_kind,
         "count": len(devices), "compile_cache": cache_dir,
         "workload": cell.name, "seed": args.seed})
    workdir = tempfile.mkdtemp(prefix="benchmark_")
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          workdir, ledger, devices)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
