"""The builder's measuring protocol, for this and for later PRs: runs of
one cell, each a new process of ``run.py`` (the parent never touches JAX),
their result lines appended to a ``.jsonl`` file, and the spread of every
end-to-end metric as the contract takes it (the distance between the
first and third quartile of ``statistics.quantiles(values, n=4)`` over the
median). Sets are run one after the other with the same seeds.

    python benchmark/measure.py --workload agv64-d256.train \
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 30 \
        --out chiprun_out/full64.jsonl [--trace-seeds 21,22,23]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def run_once(workload, seed, seconds, trace):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(int(trace))]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if p.returncode == 0 else None
    except (ValueError, IndexError):
        result = None
    phases = {}
    for ln in lines[:-1]:
        if ln.startswith('{"phase": '):
            row = json.loads(ln)
            phases[row["phase"]] = row
    return {"seed": seed, "trace": int(trace), "rc": p.returncode,
            "wall_s": time.perf_counter() - t0, "result": result,
            # the window's period boundaries, the compile ledger, the
            # comparison's and the trace's seconds
            "phases": phases,
            "stderr_tail": p.stderr[-(600 if result else 4000):]}


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    plan = [(s, False, i) for i in range(args.sets) for s in seeds]
    plan += [(int(s), True, -1) for s in args.trace_seeds.split(",") if s]
    rows = []
    for seed, trace, which in plan:
        row = dict(run_once(args.workload, seed, args.seconds, trace),
                   set=which, workload=args.workload)
        rows.append(row)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        r = row["result"]
        print(f"set {which} seed {seed} trace {int(trace)} rc {row['rc']} "
              f"{row['wall_s']:.0f}s correct "
              f"{r['correct'] if r else None} "
              + (" ".join(f"{n}={m['value']:.6g}"
                          for n, m in r["metrics"].items()) if r
                 else row["stderr_tail"][-1500:]), flush=True)
    for which in range(args.sets):
        done = [r["result"] for r in rows
                if r["set"] == which and r["result"]]
        if len(done) >= 2:
            for name in done[0]["metrics"]:
                vals = [d["metrics"][name]["value"] for d in done]
                print(f"set {which} {name}: median "
                      f"{statistics.median(vals):.6g} spread "
                      f"{spread(vals):.4%} (n={len(vals)})", flush=True)
    return 0 if all(r["result"] and r["result"]["correct"]
                    for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
