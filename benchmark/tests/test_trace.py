"""The reduction from trace to numbers (``benchmark/trace.py``) against the
small recorded trace in ``benchmark/data/recorded_trace.json`` (device
planes of a ``--trace 1`` run of ``agv64-d256.train`` on the chip, cut to
the first events of each line), recomputed here the slow way, and against
a trace made by hand."""

import json
import os

import pytest

from benchmark import trace
from benchmark.tests import tiny

RECORDED = os.path.join(tiny.BENCH, "data", "recorded_trace.json")


def _slow_union_ns(intervals):
    """Union length by marking every elementary segment."""
    cuts = sorted({t for iv in intervals for t in iv})
    total = 0.0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in intervals):
            total += b - a
    return total


def test_reduction_of_a_trace_made_by_hand():
    loaded = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__superstep(123)", 0.0, 6e9], ["jit__rollout(9)", 7e9, 2e9]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 0.0, 2e9], ["fusion.2", 1e9, 3e9],     # overlap
            ["copy.3", 5e9, 1e9], ["fusion.1", 7e9, 2e9]]}]}]}
    r = trace.reduce(loaded)
    assert r["window_s"] == pytest.approx(9.0)
    assert r["busy_s"] == pytest.approx(4.0 + 1.0 + 2.0)
    assert r["programs"]["_superstep"] == {"seconds": pytest.approx(6.0),
                                           "runs": 1}
    assert r["programs"]["_rollout"]["seconds"] == pytest.approx(2.0)
    assert r["device_ops"][0] == ("fusion.1", pytest.approx(4.0))
    assert [round((e - s) / 1e9, 6) for s, e in r["idle_gaps_ns"]] == [1, 1]
    spans = [("dispatch.superstep", 100.0, 100.1), ("fetch.x", 104.2, 104.9)]
    b = trace.breakdown(r, spans, int(100e9))
    assert b["device_ops"][0][0] == "fusion.1"
    labels = {lab for lab, _ in b["idle_gaps"]}
    assert labels == {"fetch.x", "in no span, after fetch.x"}


def test_program_names():
    assert trace.program_of("jit__superstep(8120373418953927862)") == \
        "_superstep"
    assert trace.program_of("jit__train_iter") == "_train_iter"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in benchmark/data")
def test_reduction_of_the_recorded_trace():
    with open(RECORDED) as f:
        loaded = json.load(f)
    r = trace.reduce(loaded)
    plane = next(p for p in loaded["planes"]
                 if any(ln["events"] for ln in p["lines"]))
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    base = lines.get("XLA Ops") or lines["XLA Modules"]
    iv = [(s, s + d) for _, s, d in base]
    assert r["n_planes"] >= 1 and r["n_events"] >= len(base)
    if r["n_planes"] == 1:
        assert r["busy_s"] * 1e9 == pytest.approx(_slow_union_ns(iv[:400]),
                                                  rel=1e-9)
        assert r["window_s"] * 1e9 == pytest.approx(
            max(e for _, e in iv) - min(s for s, _ in iv))
    assert 0 < r["busy_s"] <= r["window_s"]
    names = {trace.program_of(n) for n, _, _ in lines["XLA Modules"]}
    assert names <= set(r["programs"]) and names
