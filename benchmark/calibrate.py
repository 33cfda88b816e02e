"""Readings that the limits of ``configs/<config>.limits.json`` are set
from (PERF.md gives them), in one process for all seeds (set-up is most
of a run; a training cell's readings need no measured window): per seed
the program against the reference after a warm-up that fills the ring,
and on the first ``--control`` seeds also

* the *control*: the reference put in the program's place one precision
  step down — the networks at float8 for bfloat16: the regret of the
  actions float8 puts first, and the learner's forward pass; the env at
  bfloat16 for float32;
* the planted fault "every AGV acts on its neighbour's Q-values", read
  through the reference put in the program's place.

The benchmark's own runs never call this.

    python benchmark/calibrate.py --workload agv64-d256.train \
        --seeds 101,102,103 --control 3 --out chiprun_out/calib.jsonl
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)


def main(argv=None, bench_dir: str = BENCH_DIR) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0,
                    help="the first N seeds also read the control and faults")
    ap.add_argument("--out", default="chiprun_out/calibrate.jsonl")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from benchmark import check, harness
    from benchmark import run as brun
    cell = harness.load_cell(args.workload, bench_dir)
    devices = harness.require_chips(cell.chips)[:cell.chips]
    from t2omca_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ledger = harness.CompileLedger().install()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    class NoWindow(harness.Window):
        """Closes the window at the boundary that opens it."""

        def on_boundary(self, **kw):
            was = self.phase
            super().on_boundary(**kw)
            if was == "warmup" and self.phase == "window":
                super().on_boundary(**kw)

    # the shortest warm-up of whole dispatches that fills the ring
    c = cell.config["config"]
    k = int(c.get("superstep", 1))
    fill = -(-c["replay"]["buffer_size"] // c["batch_size_run"])
    cell = dataclasses.replace(cell, warmup_iterations=-(-fill // k) * k)

    ok = True
    for i, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        readings = {"seed": seed, "workload": cell.name,
                    "bytes_in_use_before": (devices[0].memory_stats() or {}
                                            ).get("bytes_in_use")}

        def extra(c, window, control=i < args.control):
            readings["refollowed"] = window.refollowed
            if not (control and c.prog_out["all_finite"]):
                return
            avail = c.acting["avail"]
            for prec in ("bf16", "fp8"):
                readings["acting_" + prec] = check.policy_regret(
                    c.q_ref, c.agent_qs(prec), avail)
            readings["acting_neighbour"] = check.policy_regret(
                c.q_ref, jnp.roll(c.q_ref, 1, axis=-2), avail)
            readings["learner_fp8"] = check.learner_numbers(
                c.reference(prec="fp8"), c.ref_out)
            readings["env_bf16"] = check.env_numbers(c.cfg, c.batch,
                                                     dtype=jnp.bfloat16)

        workdir = tempfile.mkdtemp(prefix="benchmark_calib_")
        t0 = time.perf_counter()
        try:
            result = brun.run_cell(cell, seed, 0.0, False, workdir, ledger,
                                   devices, bench_dir=bench_dir,
                                   t_process=t0, extra=extra,
                                   window_cls=NoWindow)
            readings.update(program=result["numbers"],
                            correct=result["correct"],
                            compared=result["compared"],
                            memory_peak_bytes=result["device"][
                                "memory_peak_bytes"])
            ok = ok and result["correct"]
        except Exception:                           # noqa: BLE001
            import traceback
            readings["error"] = traceback.format_exc()[-3000:]
            ok = False
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        readings["wall_s"] = time.perf_counter() - t0
        with open(args.out, "a") as f:
            f.write(json.dumps(readings) + "\n")
        print("READINGS " + json.dumps(readings), flush=True)
        if "error" in readings:
            break               # a later seed would meet the same
        del extra
        gc.collect()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
