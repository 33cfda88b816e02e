"""Set-up's layer of the telemetry (docs/OBSERVABILITY.md §1, §4): the
compile listener's attribution (``obs/compiles.py``) — driven through
its callbacks, so no test waits half a second for a compiler — the
``parent`` of nested spans and the self times it gives, the set-up spans
and the ``run`` mark's counters of a tiny ``run.run``, nothing at all
with telemetry off, and the set-up table of ``obs report``."""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from t2omca_tpu.config import (EnvConfig, ModelConfig, ObsConfig,
                               ReplayConfig, TrainConfig, sanity_check)
from t2omca_tpu.obs import compiles
from t2omca_tpu.obs import report as obs_report
from t2omca_tpu.obs.spans import COUNTER_FIELDS, KNOWN_PHASES, SpanRecorder
from t2omca_tpu.utils import resilience
from t2omca_tpu.utils.logging import Logger


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def _recorder(tmp_path):
    return SpanRecorder(jsonl_path=str(tmp_path / "spans.jsonl"),
                        flush_every=1)


# ------------------------------------------------------ the listener alone

def test_compilation_goes_to_the_innermost_span_of_its_own_thread(tmp_path):
    """Three spans open at once — two nested on this thread, one on
    another — and a compilation on each thread: each is booked to the
    innermost span of the thread it happened on and to no other."""
    rec = _recorder(tmp_path)
    lis = compiles.CompileListener(rec)
    opened, done = threading.Event(), threading.Event()

    def other():
        with rec.span("dispatch.test"):
            opened.set()
            assert done.wait(30)
            lis.on_duration(compiles.BACKEND, 0.25, fun_name="jit(g)")
    thread = threading.Thread(target=other)
    with rec.span("setup.init_state"):
        with rec.span("setup.restore"):
            thread.start()
            assert opened.wait(30)
            lis.on_duration(compiles.TRACE, 0.01, fun_name="f")
            lis.on_duration(compiles.LOWER, 0.02, fun_name="jit(f)")
            lis.on_duration(compiles.BACKEND, 0.125, fun_name="jit(f)")
            done.set()
            thread.join(30)
            assert not thread.is_alive()
    rec.close()
    by_phase = {e["phase"]: e for e in _events(rec.jsonl_path)}
    inner = by_phase["setup.restore"]
    assert (inner["compile_n"], inner["compile_ms"]) == (1, 125.0)
    assert (inner["trace_ms"], inner["lower_ms"]) == (10.0, 20.0)
    assert not set(COUNTER_FIELDS) & set(by_phase["setup.init_state"])
    assert by_phase["dispatch.test"]["compile_n"] == 1
    assert by_phase["dispatch.test"]["compile_ms"] == 250.0
    assert "trace_ms" not in by_phase["dispatch.test"]   # zero: left out
    assert rec.totals() == {"trace_ms": 10.0, "lower_ms": 20.0,
                            "compile_n": 2, "compile_ms": 375.0}
    assert lis.of("f", "g", "h") == {"f": [0.125], "g": [0.25]}


def test_long_compilation_writes_a_mark_with_program_phase_and_hit(tmp_path):
    """0.5 s or more of backend compile or cache retrieval: one
    ``compile`` mark. The hit and its retrieval seconds arrive on the
    compiling thread just before the duration that holds them, and
    belong to that one alone; the retrieval is split out of it."""
    rec = _recorder(tmp_path)
    lis = compiles.CompileListener(rec)
    with rec.span("dispatch.superstep"):
        lis.on_event(compiles.CACHE_HIT)
        lis.on_duration(compiles.CACHE_LOAD, 0.6)
        lis.on_duration(compiles.BACKEND, 0.7, fun_name="jit(_superstep)")
        lis.on_duration(compiles.BACKEND, 0.4, fun_name="jit(dot)")
    lis.on_event(compiles.CACHE_MISS)
    lis.on_duration(compiles.BACKEND, 2.0, fun_name="jit(_rollout)")
    rec.close()
    events = _events(rec.jsonl_path)
    marks = [e for e in events if e["event"] == "mark"]
    assert [(m["kind"], m["fun_name"], m["secs"], m["cache_hit"],
             m["phase"]) for m in marks] == [
        ("compile", "jit(_superstep)", 0.7, True, "dispatch.superstep"),
        ("compile", "jit(_rollout)", 2.0, False, None)]
    (span,) = [e for e in events if e["event"] == "span"]
    assert span["compile_n"] == 2 and span["cache_hits"] == 1
    assert span["cache_load_ms"] == 600.0
    assert span["compile_ms"] == pytest.approx(500.0)    # 0.1 + 0.4
    assert "cache_misses" not in span
    totals = rec.totals()
    assert totals["compile_n"] == 3 and totals["cache_misses"] == 1
    assert totals["compile_ms"] + totals["cache_load_ms"] == \
        pytest.approx(3100.0)
    assert lis.cache_hits == 1


def test_listener_hears_jax_and_stops_hearing_it(tmp_path):
    """Installed on ``jax.monitoring`` for real: a jitted function's
    compile lands in the open span and in the books by name; after
    ``uninstall`` nothing does. Without a recorder it keeps the books
    only."""
    rec = _recorder(tmp_path)
    with compiles.listening(rec) as lis:
        bare = compiles.CompileListener().install()
        with rec.span("setup.init_state"):

            def _first_program(x):
                return x * 3 + 1
            jax.jit(_first_program)(jnp.ones((3, 5))).block_until_ready()
        bare.uninstall()
    with rec.span("setup.programs"):

        def _second_program(x):
            return x * 5 - 2
        jax.jit(_second_program)(jnp.ones((3, 7))).block_until_ready()
    rec.close()
    spans = {e["phase"]: e for e in _events(rec.jsonl_path)
             if e["event"] == "span"}
    assert spans["setup.init_state"]["compile_n"] >= 1
    assert spans["setup.init_state"]["trace_ms"] > 0
    assert not set(COUNTER_FIELDS) & set(spans["setup.programs"])
    assert set(lis.of("_first_program", "_second_program")) == \
        {"_first_program"} == set(bare.of("_first_program"))


def test_disabled_recorder_installs_nothing(monkeypatch):
    from t2omca_tpu.obs.spans import NULL_RECORDER
    monkeypatch.setattr(
        compiles.CompileListener, "install",
        lambda self: pytest.fail("a listener was installed"))
    with compiles.listening(NULL_RECORDER) as lis:
        assert lis is None
    assert not hasattr(NULL_RECORDER, "count")


# --------------------------------------------------- parent and self time

def test_parent_is_the_enclosing_seq_and_self_times_add_up(tmp_path):
    rec = _recorder(tmp_path)
    seen = {}

    def other():
        with rec.span("pulse.scrape"):
            seen["done"] = True
    with rec.span("setup.init_state"):
        with rec.span("setup.restore"):
            with rec.span("memwatch.snapshot"):
                pass
        with rec.span("setup.restore"):
            t = threading.Thread(target=other)
            t.start()
            t.join(30)
            assert seen.get("done")
    with rec.span("setup.programs"):
        pass
    rec.close()
    spans = [e for e in _events(rec.jsonl_path)]
    by_seq = {e["seq"]: e for e in spans}
    outer = next(e for e in spans if e["phase"] == "setup.init_state")
    restores = [e for e in spans if e["phase"] == "setup.restore"]
    snap = next(e for e in spans if e["phase"] == "memwatch.snapshot")
    assert "parent" not in outer and outer["depth"] == 0
    assert [e["parent"] for e in restores] == [outer["seq"]] * 2
    assert snap["parent"] == restores[0]["seq"] and snap["depth"] == 2
    # another thread's span is nested in nothing of this thread's
    scrape = next(e for e in spans if e["phase"] == "pulse.scrape")
    assert "parent" not in scrape and scrape["depth"] == 0
    assert "parent" not in next(e for e in spans
                                if e["phase"] == "setup.programs")
    # self = wall less the children's walls; over a tree they add up to
    # the root's wall
    kids = {}
    for e in spans:
        if "parent" in e:
            kids.setdefault(e["parent"], []).append(e["wall_ms"])
    tree = [outer] + restores + [snap]
    selfs = [e["wall_ms"] - sum(kids.get(e["seq"], [])) for e in tree]
    assert all(s >= -1e-6 for s in selfs)
    assert sum(selfs) == pytest.approx(outer["wall_ms"], abs=1e-6)
    assert by_seq[snap["parent"]]["phase"] == "setup.restore"


# ------------------------------------------------- a tiny run, on and off

def _tiny(tmp_path, enabled):
    return sanity_check(TrainConfig(
        t_max=24, batch_size_run=2, batch_size=4, superstep=2,
        test_interval=1_000_000, log_interval=1_000_000,
        runner_log_interval=1_000_000, save_model=False,
        local_results_path=str(tmp_path), use_tensorboard=False,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8),
        obs=ObsConfig(enabled=enabled, flush_every=1)))


def _count_installs(monkeypatch):
    calls = {"install": 0, "uninstall": 0}
    for name in calls:
        real = getattr(compiles.CompileListener, name)

        def counted(self, _name=name, _real=real):
            calls[_name] += 1
            return _real(self)
        monkeypatch.setattr(compiles.CompileListener, name, counted)
    return calls


def test_run_writes_the_setup_spans_and_books_its_compilations(
        tmp_path, monkeypatch):
    """``run.run`` with telemetry on: one span per stage of set-up in the
    order the driver goes through them, the ``run`` mark after them with
    the process's counters, the state's many small programs booked under
    ``setup.init_state``, none under the spans that only build objects,
    and the loop's program named by a ``compile`` mark under its first
    dispatch. The listener is gone when the run returns."""
    from t2omca_tpu.run import run
    calls = _count_installs(monkeypatch)
    # a worker that has run these shapes before would compile nothing on
    # the way to the loop: start from what a new process has
    jax.clear_caches()
    stop = {}

    def after_one_dispatch(t_env=None, guard=None, **_):
        if stop.setdefault("first", t_env) != t_env:
            guard.request("test: set-up is over")
    resilience.register_fault("driver.iteration", after_one_dispatch)
    try:
        run(_tiny(tmp_path, True), Logger())
    finally:
        resilience.clear_faults("driver.iteration")
    assert calls == {"install": 1, "uninstall": 1}
    (path,) = glob.glob(os.path.join(str(tmp_path), "*", "spans.jsonl"))
    events = _events(path)
    order = [e["phase"] if e["event"] == "span" else "mark." + e["kind"]
             for e in sorted(events, key=lambda e: e["seq"])
             if e.get("kind") != "compile"]
    setup = order[:order.index("mark.run")]
    assert setup == ["backend.init", "setup.build", "setup.telemetry",
                     "setup.init_state", "setup.programs",
                     "setup.programs"]
    assert {e["phase"] for e in events if e["event"] == "span"} \
        <= KNOWN_PHASES
    spans = {e["phase"]: e for e in events if e["event"] == "span"
             and e.get("first")}
    init = spans["setup.init_state"]
    assert init["compile_n"] > 10 and init["compile_ms"] > 0
    assert init["trace_ms"] > 0 and init["lower_ms"] > 0
    for built in ("setup.build", "setup.telemetry", "setup.programs"):
        assert "compile_n" not in spans[built], spans[built]
    header = next(e for e in events if e.get("kind") == "run")
    assert header["compile_n"] >= init["compile_n"]
    assert header["compile_ms"] >= init["compile_ms"]
    first = spans["dispatch.superstep"]
    assert first["compile_n"] >= 1 and first["trace_ms"] > 0
    named = [e for e in events if e.get("kind") == "compile"
             and e["fun_name"] == "jit(_superstep)"]
    assert named and named[0]["phase"] == "dispatch.superstep"
    assert named[0]["secs"] >= compiles.MARK_SECS
    # the operator's reading of the same file
    table = "\n".join(obs_report.render_setup(events))
    assert "setup.init_state" in table and "jit(_superstep)" in table


def test_run_with_telemetry_off_installs_no_listener_and_writes_nothing(
        tmp_path, monkeypatch):
    from t2omca_tpu.run import run
    calls = _count_installs(monkeypatch)

    def at_once(guard=None, **_):
        guard.request("test: set-up is over")
    resilience.register_fault("driver.iteration", at_once)
    try:
        run(_tiny(tmp_path, False), Logger())
    finally:
        resilience.clear_faults("driver.iteration")
    assert calls == {"install": 0, "uninstall": 0}
    assert not glob.glob(os.path.join(str(tmp_path), "**", "spans.jsonl"),
                         recursive=True)
    assert not glob.glob(os.path.join(str(tmp_path), "**",
                                      "flight_recorder.json"),
                         recursive=True)


# ------------------------------------------------------- the report's table

def _fixture_events():
    """A recorded set-up, written out: nested restore under init_state,
    one compilation outside every span, a cache-served superstep."""
    t = 1_000.0
    ev = [
        {"event": "span", "seq": 1, "phase": "backend.init", "t0": t,
         "depth": 0, "wall_ms": 100.0, "outcome": "ok", "first": True},
        {"event": "span", "seq": 2, "phase": "setup.build", "t0": t + 0.1,
         "depth": 0, "wall_ms": 900.0, "outcome": "ok", "first": True},
        {"event": "span", "seq": 4, "phase": "setup.restore",
         "t0": t + 2.0, "depth": 1, "parent": 3, "wall_ms": 4000.0,
         "outcome": "ok", "first": True},
        {"event": "mark", "seq": 5, "kind": "compile", "t0": t + 9.0,
         "fun_name": "jit(_truncated_normal)", "secs": 3.0,
         "cache_hit": False, "phase": "setup.init_state"},
        {"event": "span", "seq": 3, "phase": "setup.init_state",
         "t0": t + 1.0, "depth": 0, "wall_ms": 10000.0, "outcome": "ok",
         "first": True, "compile_n": 300, "compile_ms": 5500.0,
         "trace_ms": 200.0, "lower_ms": 800.0, "cache_misses": 1},
        {"event": "mark", "seq": 6, "kind": "run", "t0": t + 12.0,
         "backend": "tpu", "batch_size_run": 2, "episode_limit": 6,
         "batch_size": 4, "superstep": 2, "compile_n": 301,
         "compile_ms": 5600.0, "trace_ms": 200.0, "lower_ms": 800.0,
         "cache_misses": 1},
        {"event": "mark", "seq": 8, "kind": "compile", "t0": t + 14.0,
         "fun_name": "jit(_superstep)", "secs": 1.5, "cache_hit": True,
         "phase": "dispatch.superstep"},
        {"event": "span", "seq": 7, "phase": "dispatch.superstep",
         "t0": t + 12.5, "depth": 0, "wall_ms": 2000.0, "outcome": "ok",
         "first": True, "compile_n": 1, "compile_ms": 100.0,
         "cache_load_ms": 1400.0, "cache_hits": 1},
        {"event": "span", "seq": 9, "phase": "dispatch.superstep",
         "t0": t + 15.0, "depth": 0, "wall_ms": 3.0, "outcome": "ok"},
        {"event": "mark", "seq": 10, "kind": "compile", "t0": t + 99.0,
         "fun_name": "jit(late)", "secs": 9.0, "cache_hit": False,
         "phase": None},
    ]
    return ev


def test_report_prints_the_setup_table(tmp_path, capsys):
    events = _fixture_events()
    su = obs_report.setup_summary(events)
    rows = {r["phase"]: r for r in su["rows"]}
    assert list(rows) == ["backend.init", "setup.build",
                          "setup.init_state", "setup.restore",
                          "dispatch.superstep"]
    assert rows["setup.init_state"]["wall_ms"] == 10000.0
    assert rows["setup.init_state"]["self_ms"] == 6000.0
    assert rows["setup.restore"]["self_ms"] == 4000.0
    assert rows["dispatch.superstep"]["n"] == 1      # the steady one is out
    assert rows["dispatch.superstep"]["cache_load_ms"] == 1400.0
    # the run mark's counters less the spans': compiled in no span
    assert su["outside"]["compile_n"] == 1
    assert su["outside"]["compile_ms"] == 100.0
    assert su["t_end"] == pytest.approx(1_014.5)
    assert [m["fun_name"] for m in su["longest"]] == [
        "jit(_truncated_normal)", "jit(_superstep)"]   # not the late one

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    with open(run_dir / "spans.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    from t2omca_tpu.obs.__main__ import main
    assert main(["report", str(run_dir)]) == 0
    out = capsys.readouterr().out
    assert "set-up: where the time before" in out
    table = out[out.index("set-up: where the time before"):]
    line = next(ln for ln in table.splitlines()
                if ln.startswith("setup.init_state"))
    assert line.split()[1:8] == ["1", "10.000", "6.000", "300", "0.200",
                                 "0.800", "5.500"]
    assert "(in no span)" in out
    assert "jit(_truncated_normal)" in out and "in setup.init_state" in out
    assert "cache hit" in out and "in dispatch.superstep" in out
    # a run recorded before the set-up spans has no such table
    old = [e for e in events if not str(e.get("phase", "")).startswith(
        "setup.")]
    assert obs_report.setup_summary(old) is None
    assert obs_report.render_setup(old) == []
