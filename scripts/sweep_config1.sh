#!/bin/bash
# Reproduces the config-1 learning-stability sweep
# (runs/config1_stable/SUMMARY.md): 5 seeds, full horizon, full fast
# stack, current default hypers. ~6 min/seed on one CPU core.
set -e
OUT=${1:-/tmp/config1_sweep}
for s in 0 1 2 3 4; do
  JAX_PLATFORMS=cpu python -m t2omca_tpu.run train \
    --config configs/config1_cpu_parity.yaml \
    env_args.fast_norm=true seed=$s save_model=false \
    local_results_path=$OUT/seed$s
  echo "seed $s done"
done
python - <<'PY'
import glob, json, os, sys
import numpy as np
out = os.environ.get("OUT", "/tmp/config1_sweep")
for s in range(5):
    for p in glob.glob(f"{out}/seed{s}/qmix*/metrics.jsonl"):
        rows = [json.loads(l) for l in open(p)]
        tr = [r["value"] for r in rows if r["key"] == "test_return_mean"]
        print(f"seed {s}: mean(last3 test_return) = {np.mean(tr[-3:]):.0f}")
PY
