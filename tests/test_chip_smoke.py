"""CPU rehearsal of ``chip_smoke.py`` (guide ``on-chip-measurement``
§2.1): the phase functions the chip run calls, handed a tiny
configuration, with the Pallas kernels interpreted (tests/conftest.py)
— the steering lives here, the program has no switch for it. Plus the
contract's edges: no TPU -> non-zero exit and no result line, a raising
phase -> non-zero exit, and where the compile cache goes."""

import json
import os
import subprocess
import sys

import jax
import pytest

from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               TrainConfig, load_config, sanity_check)
from t2omca_tpu.obs.compiles import CompileListener

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

sys.path.pop(0)


def tiny_cfg(**kw):
    return sanity_check(TrainConfig(
        batch_size_run=4, batch_size=4, superstep=2,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8), **kw))


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """train -> (facts, state, checkpoint dir), shared by the phases
    that follow it in the chip run."""
    work = str(tmp_path_factory.mktemp("chip_smoke"))
    ledger = CompileListener().install()
    try:
        yield (work, ledger) + chip_smoke.phase_train(tiny_cfg(), work,
                                                     ledger)
    finally:
        ledger.uninstall()


def test_train_phase_trains_checkpoints_and_compiles_once(trained):
    work, ledger, facts, ts, model_dir = trained
    assert facts["dispatches"] == 2 and facts["superstep"] == 2
    assert facts["train_iterations"] >= 3
    assert facts["t_env"] == 2 * 2 * 4 * 6
    assert facts["compile_seconds"].keys() == {"_superstep", "_rollout"}
    assert all(len(s) == 1 for s in facts["compile_seconds"].values())
    assert os.path.isdir(facts["checkpoint"])
    assert facts["checkpoint"].startswith(model_dir)
    json.dumps(facts)                       # the phase line serializes


def test_kernels_phase_interpreted(trained):
    facts = chip_smoke.phase_kernels(tiny_cfg())
    assert facts["mosaic"] is False         # conftest set INTERPRET
    assert facts["cases"].keys() == {"agent-qslice", "mixer-qslice",
                                     "agent-dense", "mixer-dense"}


def test_attention_cases_are_what_config3_traces():
    """The shapes the chip phase runs are the ones the Mosaic compile
    tests pin (tests/test_mosaic_compile.py) — both come from tracing
    the learner of the committed file."""
    from test_mosaic_compile import (AGENT_DENSE, AGENT_QSLICE,
                                     MIXER_DENSE, MIXER_QSLICE)
    from t2omca_tpu.envs.registry import make_env
    cfg = load_config(chip_smoke.CONFIG3)
    info = make_env(cfg.env_args).get_env_info()
    assert chip_smoke.attention_cases(cfg, info) == {
        "agent-qslice": AGENT_QSLICE, "mixer-qslice": MIXER_QSLICE,
        "agent-dense": AGENT_DENSE, "mixer-dense": MIXER_DENSE}


def test_serve_phase_round_trip(trained):
    work, ledger, facts, ts, model_dir = trained
    out = chip_smoke.phase_serve(tiny_cfg(), ts, model_dir, work,
                                 buckets=(2, 4))
    assert out["checkpoint_t_env"] == facts["t_env"]
    assert out["agree"] == out["decisions"] > 0     # f32: bit parity
    assert out["hidden_max_err"] == 0.0


def test_dp_phase_on_four_virtual_devices(tmp_path):
    """guide §2.2: the four-chip path on four of the CPU's virtual
    devices — shards everywhere, params identical, loss parity."""
    assert len(jax.devices()) >= 4
    ledger = CompileListener().install()
    try:
        out = chip_smoke.phase_dp(tiny_cfg(), str(tmp_path), ledger, n=4)
    finally:
        ledger.uninstall()
    assert out["dp_devices"] == 4
    assert out["env_lanes_per_chip"] == 4 and out["ring_episodes_per_chip"] == 8
    assert out["loss_rel_diff"] <= chip_smoke.DP_LOSS_RTOL
    # dp_config runs the three-program loop, unsaved: the no-recompile
    # check follows the driver there too
    assert {"_insert", "_train_iter"} <= out["compile_seconds"].keys()
    assert out["train_iterations"] == 3 and out["dispatches"] == 3
    assert out["superstep"] == 1 and out["checkpoint"] is None


def _python(code, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_no_tpu_is_a_nonzero_exit_and_no_result(argv):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py", *argv],
                          cwd=REPO, env=full, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_raising_phase_is_a_nonzero_exit_and_no_result():
    """main() has no net under its phases: past the device check (set
    aside here, in the test) a raising phase ends the process."""
    proc = _python(
        "import jax, chip_smoke\n"
        "chip_smoke.require_tpu = lambda n: jax.devices()\n"
        "def boom(*a, **k): raise RuntimeError('phase failed')\n"
        "chip_smoke.phase_train = boom\n"
        "raise SystemExit(chip_smoke.main([]))\n")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "phase failed" in proc.stderr


CACHE_PROBE = (
    "import jax\n"
    "from t2omca_tpu.utils.compile_cache import enable_compile_cache\n"
    "seen = []\n"
    "update = jax.config.update\n"
    "jax.config.update = lambda k, v: (seen.append(k), update(k, v))\n"
    "{body}\n"
    "print(seen, jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it; nothing in the
    process updates ``jax_compilation_cache_dir``."""
    placed = str(tmp_path / "placed")
    proc = _python(CACHE_PROBE.format(body="print(enable_compile_cache())"),
                   JAX_COMPILATION_CACHE_DIR=placed)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.splitlines() == [
        placed, f"['jax_compilation_cache_include_metadata_in_key'] {placed}"]


def test_compile_cache_defaults_to_the_checkout():
    proc = _python(CACHE_PROBE.format(body="print(enable_compile_cache())"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    # the scopes of a program are metadata: they are part of the key
    assert proc.stdout.splitlines() == [
        want, f"['jax_compilation_cache_include_metadata_in_key', "
              f"'jax_compilation_cache_dir'] {want}"]


def test_serve_export_and_load_do_not_move_the_cache(trained, tmp_path,
                                                     monkeypatch):
    """Export and front-end load compile, and leave the process's cache
    directory where it was (the artifact ships none of its own)."""
    from t2omca_tpu.serve.export import export_artifact
    from t2omca_tpu.serve.frontend import ServeFrontend
    work, ledger, facts, ts, model_dir = trained
    updates = []
    real = jax.config.update
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: (updates.append(k), real(k, v)))
    art = str(tmp_path / "art")
    meta = export_artifact(tiny_cfg(), model_dir, art, buckets=(1,),
                           dtypes=("float32",))
    ServeFrontend.load(art).warmup()
    assert "jax_compilation_cache_dir" not in updates
    assert "compile_cache" not in meta
    assert not os.path.exists(os.path.join(art, "compile_cache"))
