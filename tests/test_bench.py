"""The bench harness's output contract, pinned on its CPU rehearsal
path (``--smoke``: tiny config, CPU pin, kernels interpreted) — where a
harness regression would otherwise only be discovered on the chip — and
its refusal to measure anything without one."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def run_bench(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE JSON line, got: {proc.stdout!r}"
    return json.loads(lines[0])


@pytest.mark.slow   # subprocess + fresh jit (~30 s); the round driver
                    # runs `bench.py --smoke` directly anyway
def test_default_line_schema():
    rec = run_bench()
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in rec, rec
    assert rec["metric"] == "env_steps_per_sec"
    assert rec["unit"] == "env-steps/s/chip"
    assert isinstance(rec["value"], (int, float)) and rec["value"] > 0
    # smoke runs must not claim a BASELINE config id
    assert rec["config"] is None


@pytest.mark.slow   # subprocess + fresh jit; rides the same smoke run shape
def test_span_summary_embedded_in_record():
    """graftscope satellite (docs/OBSERVABILITY.md): every BENCH record
    embeds the per-phase span summary — build, compile (the first
    dispatch), warm, and the steady-state measure phase — so a record
    says where its wall-clock went."""
    rec = run_bench()
    spans = rec["spans"]
    for phase in ("bench.build", "bench.compile", "bench.warm",
                  "bench.measure"):
        assert phase in spans, (phase, sorted(spans))
        assert spans[phase]["n"] >= 1
        assert spans[phase]["total_ms"] > 0
    # the measure phase ran the timed iterations: first_ms isolates the
    # first timed run, steady_ms the warm median's neighborhood
    assert spans["bench.measure"]["n"] >= 3
    assert spans["bench.measure"]["steady_ms"] > 0
    # compile dominates warm on a fresh subprocess
    assert spans["bench.compile"]["first_ms"] > spans["bench.warm"]["first_ms"]


@pytest.mark.slow   # two subprocess benches; the acting flag plumbing is pure argparse
@pytest.mark.parametrize("acting", ["qslice", "dense"])
def test_acting_selector_reported(acting):
    rec = run_bench("--acting", acting)
    assert rec["acting"] == acting
    assert rec["value"] > 0


@pytest.mark.slow   # subprocess + two fresh dense-rollout jits (xla + pallas
                    # interpret) — the --kernels A/B contract (docs/PERF.md)
def test_kernels_ab_leg_records_per_mode():
    """``--kernels ab``: TWO records per kernel mode since PR 13 — the
    dense rollout (env_steps_per_sec) and the train-step leg
    (train_iters_per_sec, the flash-backward half of the A/B) — each
    carrying the mode and its own per-mode span legs, schema'd via
    ``_finalize``; the attributable A/B the roofline report joins
    against."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--kernels", "ab",
         "--envs", "4", "--steps", "4"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    assert [(r["kernels"], r["metric"]) for r in recs] == [
        ("xla", "env_steps_per_sec"), ("xla", "train_iters_per_sec"),
        ("pallas", "env_steps_per_sec"), ("pallas", "train_iters_per_sec")]
    for rec in recs:
        assert isinstance(rec["value"], (int, float)) and rec["value"] > 0
        assert rec["schema"] == 1
        assert "bench.measure" in rec["spans"]
        if rec["metric"] == "env_steps_per_sec":
            assert rec["acting"] == "dense"
        else:
            assert rec["unit"] == "train-iters/s/chip"
            assert rec["train_batch_episodes"] > 0
            assert rec["leg"] == f"kernels-{rec['kernels']}-train"


@pytest.mark.slow   # subprocess + fresh jit; rbg impl pinned cheaply in test_driver
def test_prng_rbg_end_to_end():
    """--prng rbg routes every key through the XLA RngBitGenerator (the
    TPU-hardware path; subprocess keeps the process-global impl switch
    out of this pytest process). The record must carry the non-default
    impl so a chip measurement can't be misattributed to threefry."""
    rec = run_bench("--prng", "rbg")
    assert rec["value"] > 0
    assert rec["prng"] == "rbg"


@pytest.mark.slow   # subprocess + fresh jit; --pipeline plumbing only
def test_pipeline_flag_adds_steady_state_rate():
    rec = run_bench("--pipeline", "2")
    assert rec["pipelined_env_steps_per_sec"] > 0
    # the blocking median stays the headline value
    assert rec["metric"] == "env_steps_per_sec" and rec["value"] > 0


@pytest.mark.slow   # subprocess + train compile; pipeline flag covered by the rollout variant
def test_pipeline_train_steady_state():
    rec = run_bench("--train", "--pipeline", "2")
    assert rec["pipelined_train_steps_per_sec"] > 0
    assert rec["pipelined_interleaved_env_steps_per_sec"] > 0


def test_committed_config_presets_load():
    """The configs/ presets (BASELINE measurement points as config files —
    the reference's sacred-config workflow, M14) must stay loadable and
    sane as flags evolve."""
    from t2omca_tpu.config import load_config
    expect = {
        "config1_cpu_parity.yaml": dict(agv=4, envs=8, dp=0),
        "config3_tpu_northstar.yaml": dict(agv=64, envs=1024, dp=0),
        "config5_dp8.yaml": dict(agv=256, envs=8192, dp=8),
    }
    for name, e in expect.items():
        cfg = load_config(os.path.join(REPO, "configs", name))
        assert cfg.env_args.agv_num == e["agv"]
        assert cfg.batch_size_run == e["envs"]
        assert cfg.dp_devices == e["dp"]


#: measuring modes, one flag set per argument-validation branch that
#: precedes the (single) device check in main()
MEASURING_MODES = [
    pytest.param([], id="default"),
    pytest.param(["--envs", "8", "--steps", "2"], id="resized"),
    pytest.param(["--train"], id="train"),
    pytest.param(["--all"], id="all"),
    pytest.param(["--superstep", "4"], id="superstep"),
    pytest.param(["--kernels", "ab"], id="kernels"),
    pytest.param(["--sebulba"], id="sebulba"),
    pytest.param(["--config", "5"], id="dp"),
    pytest.param(["--serve", "--artifact", "nowhere"], id="serve"),
]


@pytest.mark.parametrize("flags", MEASURING_MODES)
def test_measuring_mode_without_a_tpu_exits_nonzero_with_no_record(flags):
    """The measurement path fails when it finds no chip — it does not
    fall back, and it writes NOTHING under a device metric's name (not
    even a null-valued partial record). In-process check, no child."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "bench.py", *flags], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no TPU" in proc.stderr and "found 'cpu'" in proc.stderr


@pytest.mark.slow   # subprocess + fused-program jit (~33 s, the heaviest
                    # remaining in-gate bench test); the round driver runs
                    # `bench.py --smoke --superstep 1` directly anyway
def test_superstep_bench_reports_amortized_rate():
    """--superstep K: the fused-dispatch measurement. K=4 exercises the
    scan and the warm dispatch must have opened the train gate; the K=1
    leg (same code path, k-independent) rides the round driver's
    acceptance run of `bench.py --smoke --superstep 1`."""
    rec = run_bench("--superstep", "4")
    assert rec["metric"] == "env_steps_per_sec"
    assert isinstance(rec["value"], (int, float)) and rec["value"] > 0
    assert rec["superstep"] == 4
    assert rec["train_gate_open"] is True
    assert rec["config"] is None


def test_hbm_estimator_sizes_the_file_it_names():
    """--hbm CONFIG.yaml is pure shape arithmetic on the committed
    file — the real ring (2048 episodes x 151 slots: 2.47 GiB), not a
    32-slot stand-in — and says what it does not model."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--hbm",
         "configs/config3_tpu_northstar.yaml"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "hbm_estimate_gib"
    assert rec["config"] == "config3_tpu_northstar.yaml"
    assert rec["platform"] == "cpu"
    assert rec["superstep_temporaries_modelled"] is False
    assert set(rec["breakdown_gib"]) == {
        "replay_ring", "rollout_episode_batch", "train_episode_batch",
        "learner_scan_residuals"}
    assert 2.4 < rec["breakdown_gib"]["replay_ring"] < 2.5
    assert rec["value"] == pytest.approx(sum(rec["breakdown_gib"].values()),
                                         abs=0.01)


@pytest.mark.slow   # DP=8 allocation + train compile (~2 min on the 2-core box)
def test_prod_hbm_allocates_ring_and_cross_checks_analytic():
    """--prod-hbm: PRODUCTION-shaped ring (agv 256 / emb 256 / bf16
    compact storage) actually allocated on the 8-device virtual mesh
    (--smoke: the harness's CPU rehearsal), insert + train iteration
    run with it co-resident, and the --hbm analytic cross-checked
    against real allocated bytes. Reduced --ring/--envs/--steps keep
    the CI cost bounded; shapes per episode stay production."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="8")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--prod-hbm", "--ring", "64",
         "--envs", "32", "--steps", "2"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "prod_ring_resident_gib"
    assert rec["value"] > 0
    assert rec["ring_episodes"] == 64
    # the analytic model must track the real allocation closely — this
    # is the bound that makes the --hbm budget trustworthy at config 5
    assert abs(rec["analytic_delta_pct"]) < 10, rec
    assert rec["train_loss"] is not None
    import math
    assert math.isfinite(rec["train_loss"])


@pytest.mark.slow   # 8-virtual-device mesh compile (~3 min on the 2-core box)
def test_dp_bench_path_on_virtual_mesh():
    """The --config 5 (DP=8) bench: rehearse it (--smoke) at reduced
    shapes on the 8-device virtual CPU mesh and check both metric
    halves appear (rollout env-steps/s headline + train-steps/s
    field)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="8")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--config", "5", "--envs", "8",
         "--steps", "2", "--iters", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "env_steps_per_sec"
    assert rec["dp"] == 8
    assert rec["value"] > 0
    assert rec["train_steps_per_sec"] > 0
    # reduced shapes must not claim the BASELINE scale point
    assert rec["config"] is None
