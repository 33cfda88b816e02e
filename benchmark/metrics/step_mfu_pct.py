"""The whole step's share of the chip's bf16 peak: the operations the
mathematics of the window's periods requires (``benchmark/ops.py``, the
sliced association; from shapes) over the window's time, the chips and
the peak of ``benchmark/peaks.json``. An unknown device is an error."""
import importlib.util
import json
import os

UNIT = "%"


def read(ctx):
    with open(os.path.join(ctx.bench_dir, "peaks.json")) as f:
        peaks = json.load(f)["peaks"]
    if ctx.device_kind not in peaks:
        raise KeyError(f"no peak for device kind {ctx.device_kind!r} in "
                       f"peaks.json")
    spec = importlib.util.spec_from_file_location(
        "benchmark_ops", os.path.join(ctx.bench_dir, "ops.py"))
    ops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ops)
    w = ctx.window
    periods = w.iterations / ctx.cell.period_iterations
    total = ops.period(ops.sizes_of(ctx.cfg),
                       ctx.cell.period_iterations) * periods
    peak = peaks[ctx.device_kind]["bf16_flops_per_s"]
    return 100.0 * total / w.window_s / ctx.chips / peak
