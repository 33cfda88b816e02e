"""Compile-only guards against the chip's own compiler, at no chip time.

The TPU compiler is installed in the sandbox and compiles for a device
that is *described*, not attached (guide ``on-chip-measurement`` §2.3):
Mosaic refuses here what it would refuse on the chip — a block shape off
the (8, 128) tiling, a kernel that wants too much VMEM, a program that
does not fit HBM. Interpret mode (every other test in this directory)
shows none of that: the flash backward passed all of them for eight PRs
while no gradient had ever lowered.

Nothing runs, so nothing here says anything about results or times.
The compiles happen in THIS process, one at a time: two at once in
separate processes abort on libtpu's lock file (/tmp/libtpu_lockfile).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from t2omca_tpu.kernels.attention import flash_attention

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device, with the persistent compile cache off
    around the module: a described-device executable is written to the
    cache but cannot be read back without a chip, so the next compile
    would warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _place(tree, sharding):
    """``tree``'s shapes and dtypes on the described device."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


# (B, H, Tq, Tk, D) as configs/config3_tpu_northstar.yaml traces them
# (batch 32 x 64 AGVs, 65 agent / 131 mixer tokens, emb 256, 4 heads):
AGENT_QSLICE = (2048, 1, 4, 65, 256)     # learner unroll, sliced rows
MIXER_QSLICE = (32, 1, 268, 131, 256)    # learner unroll; 3 q-blocks
AGENT_DENSE = (2048, 4, 65, 65, 64)      # use_qslice=false learner
MIXER_DENSE = (32, 4, 131, 131, 64)
ACTING_TQ1 = (1024, 4, 1, 65, 64)        # one query row per env lane

# (shape, dtype, mask heads or None, causal)
CASES = {
    "agent-qslice-bf16": (AGENT_QSLICE, "bfloat16", None, False),
    "agent-qslice-f32": (AGENT_QSLICE, "float32", None, False),
    "mixer-qslice-bf16": (MIXER_QSLICE, "bfloat16", None, False),
    "mixer-qslice-f32": (MIXER_QSLICE, "float32", None, False),
    "agent-dense-bf16": (AGENT_DENSE, "bfloat16", None, False),
    "agent-dense-bf16-masked": (AGENT_DENSE, "bfloat16", 1, False),
    "agent-dense-f32-headmask": (AGENT_DENSE, "float32", 4, False),
    "mixer-dense-bf16": (MIXER_DENSE, "bfloat16", None, False),
    "mixer-dense-f32-masked": (MIXER_DENSE, "float32", 1, False),
    "mixer-dense-bf16-causal": (MIXER_DENSE, "bfloat16", None, True),
    "acting-tq1-bf16": (ACTING_TQ1, "bfloat16", None, False),
    "acting-tq1-f32-masked": (ACTING_TQ1, "float32", 1, False),
}


@pytest.mark.parametrize("shape,dtype,mask_heads,causal", CASES.values(),
                         ids=CASES.keys())
def test_flash_attention_lowers_to_mosaic(v5e, shape, dtype, mask_heads,
                                          causal):
    """Forward AND gradient compile for the chip as Mosaic kernels
    (``tpu_custom_call`` in the compiled text) — compiled geometry
    (16-row sublane quantum, 128-lane head pad), not the interpret
    one."""
    b, h, t_q, t_k, d = shape

    def aval(*s, dt=dtype):
        return jax.ShapeDtypeStruct(s, jnp.dtype(dt), sharding=v5e)

    q, k, v = aval(b, h, t_q, d), aval(b, h, t_k, d), aval(b, h, t_k, d)
    mask = (None if mask_heads is None
            else aval(b, mask_heads, t_q, t_k, dt="float32"))

    def fwd(q, k, v, mask):
        return flash_attention(q, k, v, mask, causal, interpret=False)

    def loss(q, k, v, mask):
        return (fwd(q, k, v, mask).astype(jnp.float32) ** 2).sum()

    for fn in (fwd, jax.grad(loss, argnums=(0, 1, 2))):
        text = jax.jit(fn).lower(q, k, v, mask).compile().as_text()
        assert "tpu_custom_call" in text


# (B, A, emb, heads) of acting's entity-table attention kernel
# (kernels/entity_attention.py): the north-star cell's, and a 16-AGV
# ``standard_heads`` shape (four heads of 32: one 128-lane group)
ENTITY_CASES = {
    "agv64-d256-bf16": ((1024, 64, 256, 4), "bfloat16"),
    "agv64-d256-f32": ((1024, 64, 256, 4), "float32"),
    "agv16-d128-bf16": ((256, 16, 128, 4), "bfloat16"),
    "agv16-d128-f32": ((256, 16, 128, 4), "float32"),
    "agv64-d256-2x128-bf16": ((1024, 64, 256, 2), "bfloat16"),
    "agv128-d256-bf16": ((64, 128, 256, 4), "bfloat16"),
}


@pytest.mark.parametrize("shape,dtype", ENTITY_CASES.values(),
                         ids=ENTITY_CASES.keys())
def test_entity_attention_lowers_to_mosaic(v5e, shape, dtype):
    """One block of acting's entity-table attention compiles for the chip
    as ONE Mosaic kernel, tables and all, under this suite's ``highest``
    default matmul precision too (bf16 operands take the MXU's one
    pass)."""
    from t2omca_tpu.kernels import entity_attention as ek
    b, a, emb, heads = shape
    dt = jnp.dtype(dtype)
    assert ek.eligible(b, a, emb, emb, heads)

    def aval(*s, dt=dt):
        return jax.ShapeDtypeStruct(s, jnp.dtype(dt), sharding=v5e)

    hp = {"wq": aval(emb, emb), "wk": aval(emb, emb), "wv": aval(emb, emb),
          "wu": aval(emb, emb), "u_bias": aval(emb, dt="float32"),
          "wek": aval(9, emb), "wev": aval(9, emb),
          "bek": aval(emb, dt="float32"), "bev": aval(emb, dt="float32")}

    def block(hp, x0, h_tok, feats, inv_self, seen):
        tables = ek.tables(feats, inv_self, seen, dt, ek.group(emb, heads))
        return ek.entity_attention(hp, x0, h_tok, *tables, heads=heads)

    text = jax.jit(block).lower(
        hp, aval(b, a, emb), aval(b, a, emb), aval(b, 2 * a, 9),
        aval(b, a, 1, dt="float32"), aval(b, 2 * a, a, dt="bool")
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_env_step_compiles_without_gathers(v5e):
    """The rollout program of the north-star file at a small shape (8
    lanes x 16 AGVs, 8 steps) holds no gather under an env scope once
    the chip's compiler is through with it: a TPU gather fetches its
    rows one after another (65,536 of them a step at the cell's size,
    PERF.md section 5), so the env's lookups by serving MEC are one-hot
    selects (``MultiAgvOffloadingEnv._mec_lookup``)."""
    from t2omca_tpu.config import load_config
    from t2omca_tpu.run import Experiment

    cfg = load_config(
        os.path.join(REPO, "configs", "config3_tpu_northstar.yaml"),
        ("batch_size_run=8", "batch_size=4", "replay.buffer_size=16",
         "env_args.agv_num=16", "env_args.mec_num=4",
         "env_args.num_channels=4", "env_args.episode_limit=8",
         "model.emb=32", "model.mixer_emb=32", "obs.pulse_port=0"))
    exp = Experiment.build(cfg)
    ts = _place(jax.eval_shape(lambda: exp.init_train_state(0)), v5e)
    rollout = exp.jitted_programs(donate=True)[0]
    text = rollout.lower(ts.learner.params["agent"], ts.runner,
                         test_mode=False).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/env.step/" in n for n in names)    # the scopes are there
    gathers = sorted(n for n in names if n.endswith("gather")
                     and ("env." in n or "rollout.reset" in n))
    assert not gathers, gathers


#: HLO opcodes (and the custom call XLA may put in ``top_k``'s place) that
#: the router must not compile to
_SERIAL = re.compile(r" (sort|gather|scatter)\(|custom_call_target=\"TopK")


@pytest.mark.parametrize("config", ["config8_trunk_smallthinker.yaml",
                                    "config9_trunk_trinity.yaml",
                                    "config10_trunk_kanana.yaml"])
@pytest.mark.parametrize("program", ["acting", "loss-gradient"])
def test_router_compiles_without_sort_gather_scatter(v5e, config, program):
    """The shipped trunks at their published widths, cut to 2 lanes, 2
    episodes, 4 steps and their first 2 layers (Trinity's and kanana's:
    the dense layer and a routed one): once the chip's compiler is
    through with the acting forward and with the learner's loss gradient,
    no instruction under ``agent.router`` is a sort (``lax.top_k`` orders
    all 64 / 128 experts to keep 6 / 8), a gather (``take_along_axis`` fetches one
    element a kept pair) or a scatter (its transpose): ``trunk.route``
    selects and reads through one-hot planes (PERF.md section 6, PR 32)."""
    from t2omca_tpu.config import load_config
    from t2omca_tpu.run import Experiment

    cfg = load_config(
        os.path.join(REPO, "configs", config),
        ("batch_size_run=2", "batch_size=2", "replay.buffer_size=4",
         "env_args.episode_limit=4", "obs.pulse_port=0", "model.depth=2",
         "model.trunk.num_hidden_layers=2"))
    exp = Experiment.build(cfg)
    ts = _place(jax.eval_shape(lambda: exp.init_train_state(0)), v5e)
    agent = ts.learner.params["agent"]
    if program == "acting":
        lowered = exp.jitted_programs(donate=True)[0].lower(
            agent, ts.runner, test_mode=False)
    else:
        batch = _place(jax.eval_shape(
            lambda p, r: exp.runner.run(p, r), agent, ts.runner)[1], v5e)
        w = jax.ShapeDtypeStruct((batch.reward.shape[0],), jnp.float32,
                                 sharding=v5e)
        lowered = jax.jit(jax.grad(
            lambda p, t, b, w: exp.learner._loss(p, t, b, w)[0])).lower(
                ts.learner.params, ts.learner.target_params, batch, w)
    routed = [line for line in lowered.compile().as_text().splitlines()
              if "agent.router" in line]
    assert routed                                   # the scope is there
    serial = [line.strip()[:200] for line in routed if _SERIAL.search(line)]
    assert not serial, serial


@pytest.mark.slow   # ~1 min: the whole fused program through the TPU compiler
def test_config3_programs_fit_one_v5e_chip(v5e):
    """The committed north-star file's training programs, at full
    width, compile for one 16 GB v5e chip from shapes alone — the
    compiler raises RESOURCE_EXHAUSTED for a program that does not fit
    (it did, "Used 28.54G of 15.75G hbm", before ``model.remat``)."""
    from t2omca_tpu.config import load_config
    from t2omca_tpu.run import Experiment, superstep_eligible

    cfg = load_config(os.path.join(REPO, "configs",
                                   "config3_tpu_northstar.yaml"))
    exp = Experiment.build(cfg)

    def place(tree):
        return _place(tree, v5e)

    ts = place(jax.eval_shape(lambda: exp.init_train_state(0)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    t_env = place(jax.ShapeDtypeStruct((), jnp.int32))
    rollout, insert, train_iter = exp.jitted_programs(donate=True)
    agent = ts.learner.params["agent"]
    lowered = [rollout.lower(agent, ts.runner, test_mode=True)]
    if superstep_eligible(cfg):
        k = cfg.superstep
        keys = place(jax.ShapeDtypeStruct((k,) + key.shape, key.dtype))
        lowered.append(exp.superstep_program(k, donate=True).lower(
            ts, keys, t_env))
    else:
        batch = place(jax.eval_shape(
            lambda p, r: rollout(p, r, test_mode=False), agent,
            ts.runner)[1])
        lowered += [rollout.lower(agent, ts.runner, test_mode=False),
                    insert.lower(ts.buffer, batch),
                    train_iter.lower(ts, place(key), t_env)]
    hbm = 15.75 * 2 ** 30               # what the compiler itself allows
    for low in lowered:
        compiled = low.compile()
        # every one of them acts, and acting's entity-table attention is
        # the Mosaic kernel wherever the lowering is for a TPU
        assert "tpu_custom_call" in compiled.as_text()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < hbm, (live, m)
