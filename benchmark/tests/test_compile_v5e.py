"""Every program of every cell of ``BENCHMARK.json``, compiled from the
files under ``benchmark/configs/`` for a DESCRIBED v5e chip (nothing
runs), with the compiler's ``memory_analysis()`` printed: a configuration
that stops fitting 16 GB fails here and not on the chip. The topology is
described inside a fixture, never at import (one process at a time may
load the TPU's library)."""

import json
import os
import tempfile

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import harness
from benchmark.tests import tiny

HBM = 15.75 * 2 ** 30                    # what the compiler itself allows

with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", CELLS)
def test_cells_programs_fit_one_v5e_chip(v5e, name):
    from t2omca_tpu.run import Experiment, superstep_eligible
    cell = harness.load_cell(name)
    cfg = harness.build_cfg(cell, 0, tempfile.gettempdir())
    exp = Experiment.build(cfg)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=v5e), tree)
    ts = place(jax.eval_shape(lambda: exp.init_train_state(0)))
    state_gb = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(ts)) / 1e9
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    t_env = place(jax.ShapeDtypeStruct((), jnp.int32))
    rollout, insert, train_iter = exp.jitted_programs(donate=True)
    agent = ts.learner.params["agent"]
    lowered = {"_rollout(test)": rollout.lower(agent, ts.runner,
                                               test_mode=True)}
    if superstep_eligible(cfg):
        k = cfg.superstep
        keys = place(jax.ShapeDtypeStruct((k,) + key.shape, key.dtype))
        lowered["_superstep"] = exp.superstep_program(k, donate=True).lower(
            ts, keys, t_env)
    else:
        batch = place(jax.eval_shape(
            lambda p, r: rollout(p, r, test_mode=False), agent,
            ts.runner)[1])
        lowered["_rollout"] = rollout.lower(agent, ts.runner,
                                            test_mode=False)
        lowered["_insert"] = insert.lower(ts.buffer, batch)
        lowered["_train_iter"] = train_iter.lower(ts, place(key), t_env)
    print(f"\n{name}: train state {state_gb:.2f} GB")
    for prog, low in lowered.items():
        m = low.compile().memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"{name} {prog}: live {live / 1e9:.2f} GB (arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f}, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f}, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f}, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f})")
        assert live < HBM, (prog, live, m)
