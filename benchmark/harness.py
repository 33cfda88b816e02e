"""The benchmark's harness: everything between ``run.py``'s argument
parsing and its last line, as phase functions that a CPU rehearsal can
call with a tiny cell (``benchmark/tests``).

A cell is found by its name in ``BENCHMARK.json``: its traffic file
``workloads/<cell>.json``, its configuration ``configs/<config>.json``,
and one reader per per-layer metric ``metrics/<metric>.py``. Nothing
here names a cell, a configuration or a metric.

The window drives ``t2omca_tpu.run.run`` — the entry ``python -m
t2omca_tpu train`` calls — with the configuration's own ``superstep`` and
cadences. The harness sits on the program's fault-injection hooks
(``utils/resilience.register_fault``): ``driver.iteration`` fires at every
dispatch boundary of the driver loop with the loop's ``ts``, ``key`` and
``train_infos``; ``guard.request`` from it ends the loop at that boundary.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import time
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)


# ------------------------------------------------------------------ cells

@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    chips: int
    period_iterations: int
    warmup_iterations: int
    config: dict                  # the configuration file, whole
    per_layer: tuple              # names of the per-layer metrics it reports
    end_to_end: tuple


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              manifest: Optional[str] = None) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` and the data files give it."""
    manifest = manifest or os.path.join(os.path.dirname(bench_dir),
                                        "BENCHMARK.json")
    with open(manifest) as f:
        bm = json.load(f)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bm['workloads']]}")
    with open(os.path.join(bench_dir, "workloads", name + ".json")) as f:
        mix = json.load(f)
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    with open(os.path.join(os.path.dirname(bench_dir), conf["file"])) as f:
        config = json.load(f)

    def of(group):
        return tuple(m["name"] for m in bm[group]
                     if name in m.get("workloads", [name]))
    return Cell(name=name, config_name=entry["config"],
                chips=int(entry["chips"]),
                period_iterations=int(mix["period_iterations"]),
                warmup_iterations=int(mix["warmup_iterations"]),
                config=config, per_layer=of("per_layer"),
                end_to_end=of("end_to_end"))


def build_cfg(cell: Cell, seed: int, workdir: str):
    """The program's ``TrainConfig`` of this cell, seeded."""
    from t2omca_tpu.config import from_dict
    cfg = from_dict(json.loads(json.dumps(cell.config["config"])))
    # numpy/jax seeds are 32-bit; the driver's seeds pass 2**31
    return cfg.replace(seed=int(seed) % (2 ** 31 - 1),
                       local_results_path=workdir)


def require_chips(n: int):
    """``jax.devices()`` when they are ``n`` or more TPU chips; otherwise
    the process ends non-zero with no result line."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (JAX found "
                         f"{devices[0].platform!r}); nothing is measured "
                         f"on another platform")
    if len(devices) < n:
        raise SystemExit(f"benchmark: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices


# ------------------------------------------------------- compile ledger

class CompileLedger:
    """Backend compiles seen by this process (``jax.monitoring``), by
    program name, with the persistent cache's hits. Copied from
    ``chip_smoke.py``; counts, where that one keeps seconds only."""

    def __init__(self) -> None:
        self.seconds: dict = {}
        self.cache_hits = 0
        self.events = 0

    def install(self) -> "CompileLedger":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1
            self.seconds.setdefault(kw.get("fun_name", "?"), []).append(
                round(secs, 3))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------- the window

class Window:
    """Opens, closes and follows the measured window from the driver's
    hooks. One *period* = ``period_iterations`` training iterations and
    the test rollout that follows them. Warm-up ends, and the window
    opens, at the boundary after ``warmup_iterations``; it closes at the
    first period boundary at or after ``seconds``; the dispatch after it
    is the one the comparison follows (``check.py``; the next one where
    the program's non-finite guard skipped that one's last update), and
    the loop is ended at the boundary after it. The device is waited for at the
    opening and the closing boundary, and nowhere between."""

    def __init__(self, cfg, cell: Cell, seconds: float, ledger,
                 t_process: float, trace_dir: Optional[str] = None):
        from t2omca_tpu.run import superstep_eligible
        self.cfg = cfg
        self.k = cfg.superstep if superstep_eligible(cfg) else 1
        self.spi = cfg.batch_size_run * cfg.env_args.episode_limit
        self.period = cell.period_iterations
        self.warm = cell.warmup_iterations
        if self.period % self.k or self.warm % self.k:
            raise ValueError("period and warm-up must be whole dispatches")
        self.seconds = float(seconds)
        self.ledger = ledger
        self.t_process = t_process
        self.trace_dir = trace_dir
        self.phase = "warmup"
        self.boundaries: list = []        # (iteration, host clock) in window
        self.t_open = self.t_close = None
        self.it_open = self.it_close = None
        self.compiles_open = self.compiles_close = None
        self.trace_t0_ns = None
        self.snap = None
        self.infos = None
        self.followed_from = None
        self.refollowed = 0
        self.memory = {}
        self.snapshot_s = None
        self.host_available = {}         # bytes, around the last snapshot

    # -- hooks ---------------------------------------------------------
    def on_boundary(self, t_env=None, guard=None, ts=None, key=None, **_):
        import jax
        if ts is None or key is None:
            raise RuntimeError(
                "benchmark: driver.iteration fired without ts= and key= "
                "(only run_sequential's loop hands them over; the harness "
                "drives no other loop)")
        it = t_env // self.spi
        now = time.perf_counter()
        if self.phase == "warmup":
            if it < self.warm:
                return
            jax.block_until_ready(jax.live_arrays())
            if self.trace_dir:
                self.trace_t0_ns = time.time_ns()   # the trace's clock zero
                jax.profiler.start_trace(self.trace_dir)
            self.compiles_open = self.ledger.events
            self.t_open = time.perf_counter()
            self.it_open = it
            self.boundaries.append((it, self.t_open))
            self.phase = "window"
        elif self.phase == "window":
            if (it - self.it_open) % self.period:
                return
            self.boundaries.append((it, now))
            # a traced run measures (and traces) one period: the trace of
            # a 10-second program holds every operation of every scan step
            if now - self.t_open < self.seconds and not self.trace_dir:
                return
            jax.block_until_ready(jax.live_arrays())
            self.t_close = time.perf_counter()
            self.boundaries[-1] = (it, self.t_close)
            if self.trace_dir:
                jax.profiler.stop_trace()
            self.it_close = it
            self.compiles_close = self.ledger.events
            self.memory = dict(jax.devices()[0].memory_stats() or {})
            self._snapshot(ts, key, t_env)
            self.followed_from = it
            self.phase = "check"
        elif self.phase == "check":
            if it < self.followed_from + self.k:
                return
            # the program's non-finite guard skips an update whose loss or
            # gradient overflowed (bf16): such a last update leaves nothing
            # to compare, so the next dispatch is followed instead
            ok = (self.infos is None or bool(jax.device_get(
                self.infos[-1]["all_finite"])))
            if not ok and self.refollowed < 4:
                self.refollowed += 1
                self._snapshot(ts, key, t_env)
                self.infos = None
                self.followed_from = it
                return
            guard.request("benchmark: window closed")
            self.phase = "done"

    def on_infos(self, train_infos=None, **_):
        """``fetch.train_infos``: the info rows of the dispatch the
        comparison follows, before the driver drops them."""
        if self.phase == "check":
            if train_infos is None:
                raise RuntimeError("benchmark: fetch.train_infos fired "
                                   "without train_infos=")
            self.infos = list(train_infos)

    def _snapshot(self, ts, key, t_env):
        before = host_available_bytes()
        t0 = time.perf_counter()
        self.snap = snapshot(ts, key, t_env)
        self.snapshot_s = time.perf_counter() - t0
        self.host_available = {"before": before,
                               "after": host_available_bytes()}

    # -- readings ------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def iterations(self) -> int:
        return self.it_close - self.it_open

    @property
    def env_steps(self) -> int:
        return self.iterations * self.spi

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_process

    @property
    def compiles_in_window(self) -> int:
        return self.compiles_close - self.compiles_open


def snapshot(ts, key, t_env) -> dict:
    """Host copies of what the comparison needs of the state at a
    boundary: the learner's state, the ring's priorities and counters, the
    driver's key and the runner's (raw ``uint32`` keys), as numpy leaves in
    the state's own tree structure. ``device_get`` waits for the state and
    returns before the next dispatch donates it; the chip holds no second
    copy of the learner state while the comparison is followed."""
    import jax
    import numpy as np
    host = jax.device_get({
        "learner": ts.learner,
        "priorities": ts.buffer.priorities,
        "max_priority": ts.buffer.max_priority,
        "insert_pos": ts.buffer.insert_pos,
        "episodes_in_buffer": ts.buffer.episodes_in_buffer,
        "episode": ts.episode,
        "key": key,
        "runner_key": ts.runner.key,
    })
    if jax.default_backend() == "cpu":
        # there ``device_get`` hands back views of the device buffers
        host = jax.tree.map(np.array, host)
    host["t_env"] = int(t_env)
    return host


def host_available_bytes() -> Optional[int]:
    """``MemAvailable`` of ``/proc/meminfo`` (None where there is none)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def drive(cfg, window: Window):
    """``run.run`` with the window on its hooks → the final TrainState."""
    from t2omca_tpu import run as run_mod
    from t2omca_tpu.utils import resilience
    from t2omca_tpu.utils.logging import Logger
    resilience.register_fault("driver.iteration", window.on_boundary)
    resilience.register_fault("fetch.train_infos", window.on_infos)
    logger = Logger()
    try:
        return run_mod.run(cfg, logger)
    finally:
        resilience.clear_faults("driver.iteration")
        resilience.clear_faults("fetch.train_infos")
        logger.close()
        if window.phase == "window" and window.trace_dir:
            import jax
            jax.profiler.stop_trace()


# ---------------------------------------------------------------- metrics

def load_reader(name: str, bench_dir: str = BENCH_DIR):
    """``metrics/<name>.py`` → its ``read(ctx)``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader may read."""
    cell: Cell
    cfg: Any
    window: Window
    trace: Optional[dict]        # ``trace.reduce`` of the traced window
    device_kind: str
    chips: int
    bench_dir: str = BENCH_DIR


def per_layer_metrics(ctx: MetricContext) -> dict:
    out = {}
    for name in ctx.cell.per_layer:
        mod = load_reader(name, ctx.bench_dir)
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def end_to_end_metrics(window: Window) -> dict:
    return {
        "env_steps_per_s": {"value": window.env_steps / window.window_s,
                            "unit": "env-steps/s"},
        "setup_s": {"value": window.setup_s, "unit": "s"},
    }
