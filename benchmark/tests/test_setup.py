"""CPU rehearsal of the set-up readers (``benchmark/setup.py``,
``metrics/setup_*.py``): on the tiny cell's own ``spans.jsonl`` every
reader gives a finite number and the parts add up to ``setup_s``; on a
written-out set-up the reduction gives the numbers worked out by hand;
and where the program wrote no set-up spans every reader gives ``None``."""

import json
import math
import os
import shutil
import tempfile
import time

import jax
import pytest

from benchmark import harness, setup
from benchmark.tests import tiny

READERS = ("setup_entry_s", "setup_build_s", "setup_init_state_s",
           "setup_first_dispatch_s", "setup_warmup_s", "setup_compile_s",
           "setup_cold_compile_s", "setup_unattributed_pct")
PARTS = READERS[:5]


def test_every_reader_is_declared_under_setup_s():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["moves"] == "setup_s" and "workloads" not in m
        assert m["better"] == "lower"
        assert m["unit"] == harness.load_reader(name).UNIT


@pytest.fixture(scope="module")
def rehearsed():
    """One run of the tiny cell; the readers are called where the
    harness calls them — after the comparison, before the run's
    directory goes — and the run's events are kept."""
    from benchmark import run as brun
    root = tiny.make(k=2)
    bd = os.path.join(root, "benchmark")
    cell = harness.load_cell("tiny.train", bench_dir=bd)
    assert set(READERS) <= set(cell.per_layer)     # no `workloads` list
    ledger = harness.CompileLedger().install()
    work = tempfile.mkdtemp(prefix="tinybench_work_")
    kept = {}

    def read_all(_comparison, window):
        ctx = harness.MetricContext(
            cell=cell, cfg=window.cfg, window=window, trace=None,
            device_kind="cpu", chips=1, bench_dir=bd)
        kept["values"] = {n: harness.load_reader(n, bd).read(ctx)
                          for n in READERS}
        kept["events"] = setup.load(work)
        kept["window"] = window
    try:
        result = brun.run_cell(
            cell, 2 ** 31 + 7, 0.5, False, work, ledger,
            jax.devices()[:1], bench_dir=bd,
            t_process=time.perf_counter(), extra=read_all)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        shutil.rmtree(root, ignore_errors=True)
    assert result["correct"] is True
    kept["setup_s"] = result["metrics"]["setup_s"]["value"]
    return kept


def test_each_reader_gives_a_finite_number(rehearsed):
    for name, value in rehearsed["values"].items():
        assert value is not None and math.isfinite(value), name
    v = rehearsed["values"]
    assert v["setup_init_state_s"] > 0 and v["setup_first_dispatch_s"] > 0
    assert v["setup_warmup_s"] > 0 and v["setup_entry_s"] >= 0
    # the state's small programs and the loop's two all compile on the
    # way to the window; what a cache could have served is part of that
    assert v["setup_compile_s"] > 0
    # (a mark's seconds are rounded to the millisecond)
    assert 0 <= v["setup_cold_compile_s"] <= v["setup_compile_s"] + 0.01


def test_the_parts_add_up_to_setup_s(rehearsed):
    v = rehearsed["values"]
    left = rehearsed["setup_s"] - sum(v[n] for n in PARTS)
    assert left == pytest.approx(
        v["setup_unattributed_pct"] / 100 * rehearsed["setup_s"], abs=1e-6)
    assert 0 <= v["setup_unattributed_pct"] < 10


def test_no_setup_spans_reads_none_not_zero(rehearsed):
    """The parent's program (``backend.init`` round the build, a ``run``
    mark without counters, no ``setup.*`` span, no ``parent``), a
    program with telemetry off (no file), and a file cut before the
    ``run`` mark."""
    w = rehearsed["window"]
    offset = time.time() - time.perf_counter()
    t_process, t_open = w.t_process + offset, w.t_open + offset
    events = rehearsed["events"]
    assert setup.reduce(events, t_process, t_open) is not None
    parents = [dict(e, phase="backend.init") if e.get("phase") ==
               "setup.build" else e for e in events
               if not str(e.get("phase", "")).startswith("setup.")
               or e["phase"] == "setup.build"]
    assert setup.reduce(parents, t_process, t_open) is None
    assert setup.reduce([], t_process, t_open) is None
    assert setup.reduce([e for e in events if e.get("kind") != "run"],
                        t_process, t_open) is None
    # through the readers: a run whose directory holds no spans.jsonl
    empty = tempfile.mkdtemp(prefix="tinybench_empty_")
    try:
        ctx = harness.MetricContext(
            cell=None, cfg=w.cfg.replace(local_results_path=empty),
            window=w, trace=None, device_kind="cpu", chips=1)
        for name in READERS:
            assert harness.load_reader(name).read(ctx) is None, name
    finally:
        shutil.rmtree(empty, ignore_errors=True)


def test_listener_off_reads_the_times_and_no_compile_seconds(rehearsed):
    """A ``run`` mark without the counters (nothing listened): the five
    parts are there, the two compile metrics are ``None``."""
    w = rehearsed["window"]
    offset = time.time() - time.perf_counter()
    events = [{k: v for k, v in e.items() if k != "compile_n"}
              if e.get("kind") == "run" else e
              for e in rehearsed["events"]]
    p = setup.reduce(events, w.t_process + offset, w.t_open + offset)
    assert p["compile_s"] is None and p["cold_compile_s"] is None
    assert p["warmup_s"] > 0


def test_reduction_of_a_written_out_setup():
    """Process start 1000, first span 1020, ``run`` mark 1072, opening
    1100. Before the mark: build 1 + programs 2 + telemetry 3 (1 of it a
    nested snapshot: counted with it) = 6; init_state 40 of which a
    nested restore 10: 40 together; 6 s in no span. After it: a first
    dispatch of 9 s with a compilation, a steady one without, a driver
    span that compiled 0.2 s, and 1.5 s compiled in no span at all."""
    def span(seq, phase, t0, wall_s, **kw):
        return dict(event="span", seq=seq, phase=phase, t0=t0,
                    wall_ms=wall_s * 1e3, outcome="ok", **kw)
    events = [
        span(1, "backend.init", 1020.0, 0.0),
        span(2, "setup.build", 1020.0, 1.0),
        span(4, "memwatch.snapshot", 1022.0, 1.0, parent=3),
        span(3, "setup.telemetry", 1021.0, 3.0),
        span(6, "setup.restore", 1030.0, 10.0, parent=5),
        dict(event="mark", seq=7, kind="compile", t0=1050.0, secs=3.0,
             fun_name="jit(_truncated_normal)", cache_hit=False,
             phase="setup.init_state"),
        dict(event="mark", seq=8, kind="compile", t0=1051.0, secs=0.9,
             fun_name="jit(dot)", cache_hit=False,
             phase="setup.init_state"),
        span(5, "setup.init_state", 1024.0, 40.0, compile_n=300,
             compile_ms=20000.0),
        span(9, "setup.programs", 1064.0, 2.0),
        dict(event="mark", seq=10, kind="run", t0=1072.0, compile_n=300,
             compile_ms=20000.0, cache_load_ms=500.0),
        span(11, "driver.prepare", 1072.0, 0.5, compile_n=2,
             compile_ms=200.0),
        dict(event="mark", seq=13, kind="compile", t0=1081.0, secs=8.0,
             fun_name="jit(_superstep)", cache_hit=True,
             phase="dispatch.superstep"),
        span(12, "dispatch.superstep", 1073.0, 9.0, first=True,
             compile_n=1, compile_ms=1000.0, cache_load_ms=7000.0,
             cache_hits=1),
        dict(event="mark", seq=14, kind="compile", t0=1084.0, secs=1.5,
             fun_name="jit(late)", cache_hit=False, phase=None),
        span(15, "dispatch.superstep", 1085.0, 0.01),
        # the window is open: none of this counts
        span(16, "dispatch.superstep", 1100.5, 5.0, compile_n=1,
             compile_ms=5000.0),
        dict(event="mark", seq=17, kind="compile", t0=1105.0, secs=5.0,
             fun_name="jit(_superstep)", cache_hit=False,
             phase="dispatch.superstep"),
    ]
    p = setup.reduce(events, 1000.0, 1100.0)
    assert p["setup_s"] == 100.0
    assert p["entry_s"] == 20.0
    assert p["build_s"] == pytest.approx(6.0)
    assert p["init_state_s"] == pytest.approx(40.0)
    assert p["first_dispatch_s"] == pytest.approx(9.0)
    assert p["warmup_s"] == pytest.approx(28.0 - 9.0)
    assert p["unattributed_pct"] == pytest.approx(6.0)
    # 20.5 at the mark + 0.2 + 8.0 in spans after it + 1.5 in no span
    assert p["compile_s"] == pytest.approx(30.2)
    # a second or more and not from the cache: 3.0 and 1.5, not the 0.9
    assert p["cold_compile_s"] == pytest.approx(4.5)
