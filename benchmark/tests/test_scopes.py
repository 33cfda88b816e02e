"""The reduction from trace to per-scope and per-span numbers
(``benchmark/scopes.py``) against ``benchmark/data/recorded_scopes.json``
(the tiny rehearsal cell traced on the chip, cut to one update and one
test rollout) and against traces made by hand: self time under nesting,
scope tokens inside JAX's wrappers, closure, gap attribution, and the
clock's causality error."""

import copy
import json
import os

import pytest

from benchmark import scopes, trace
from benchmark.tests import tiny

RECORDED = os.path.join(tiny.BENCH, "data", "recorded_scopes.json")
VOCAB, PHASES = scopes.vocabulary()
PATTERN = scopes.scope_pattern(VOCAB)


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


# ------------------------------------------------------------- by hand

def test_self_time_under_nesting():
    #      0....................100   while
    #        10..30   40......90      fusion, inner while
    #                  50..60 70..80  two fusions of the inner loop
    #  120..130                        a copy after the loop
    start = [0, 10, 40, 50, 70, 120]
    dur = [100, 20, 50, 10, 10, 10]
    facts = [("a",), (None,), ("b",), (None,), ("c",), (None,)]
    events, top = scopes.sweep(start, dur, lambda i: (facts[i], i))
    own = {i: (t, f[0]) for t, f, i in events}
    assert own == {0: (30, "a"), 1: (20, "a"),        # inherits the loop's
                   2: (30, "b"), 3: (10, "b"), 4: (10, "c"),
                   5: (10, None)}                     # outside: unscoped
    assert top == [(0, 100, 0), (120, 130, 5)]
    assert sum(t for t, _ in own.values()) == 110     # = the union
    # the same events out of order
    order = [5, 3, 0, 4, 2, 1]
    events2, top2 = scopes.sweep([start[i] for i in order],
                                 [dur[i] for i in order],
                                 lambda j: (facts[order[j]], order[j]))
    assert sorted((i, t) for t, _, i in events2) == \
        sorted((i, t) for t, _, i in events)
    assert sorted((s, e) for s, e, _ in top2) == [(0, 100), (120, 130)]


@pytest.mark.parametrize("op_name, outer, inner, which", [
    ("jit(_superstep)/while/body/closed_call/env.step/vmap(am,ac->mc)/"
     "dot_general", "env.step", "env.step", "forward"),
    ("jit(_rollout)/while/body/vmap(env.step)/env.normalizer/add",
     "env.step", "env.normalizer", "forward"),
    ("jit(_superstep)/while/body/cond/branch_1_fun/jvp(learner.agent)/"
     "while/body/checkpoint/agent.attention/dot_general",
     "learner.agent", "agent.attention", "forward"),
    ("jit(_superstep)/while/body/cond/branch_1_fun/"
     "transpose(jvp(learner.agent))/while/body/checkpoint/agent.ff/mul",
     "learner.agent", "agent.ff", "backward"),
    ("jit(_superstep)/while/body/cond/branch_1_fun/"
     "transpose(jvp(learner.mixer))/while/body/checkpoint/"
     "rematted_computation/agent.ff/dot_general",
     "learner.mixer", "agent.ff", "recomputation"),
    ("jit(_superstep)/while/body/act.forward/agent.attention/"
     "transpose", "act.forward", "agent.attention", "forward"),
    ("ts.learner.target_params['agent']['params']['q_basic']['bias']",
     None, None, None),
    ("jit(_superstep)/while/body/closed_call/sight_extras/add",
     None, None, None),
    ("", None, None, None),
])
def test_scope_tokens_inside_jax_wrappers(op_name, outer, inner, which):
    assert scopes.classify(op_name, PATTERN) == (outer, inner, which)
    assert scopes.classify(op_name, scopes.scope_pattern(())) == \
        (None, None, None)


def test_operations_the_compiler_made_are_booked_by_what_they_came_from():
    learn = "jit(_superstep)/cond/jvp(learner.agent)/while/body"
    hlo = {
        "ops": {"fusion.1": f"{learn}/agent.ff/dot_general",
                "fusion.9": "jit(_superstep)/while/body/add"},
        "fused": {"fusion.2": [f"{learn}/agent.ff", f"{learn}/agent.ff",
                               "jit(_superstep)/while/body/env.step/vmap()"],
                  "fusion.1": [f"{learn}/agent.ff"]},
        "from": {"copy-done.3": "copy-start.3", "copy-start.3": "bitcast.7",
                 "bitcast.7": "fusion.1", "copy.4": "get-tuple-element.5",
                 "copy.5": "fusion.2", "copy.6": "fusion.9"},
    }
    own = scopes.named(hlo, "fusion.1", PATTERN)
    assert own == ("learner.agent", "agent.ff", "forward", ["learner.agent"])
    # a nameless fusion: the scope most of what it fused has; both named
    assert scopes.named(hlo, "fusion.2", PATTERN) == (
        "learner.agent", "agent.ff", "forward", ["env.step", "learner.agent"])
    # copies: what they copy, through three nameless hops
    assert scopes.named(hlo, "copy-done.3", PATTERN)[:3] == own[:3]
    assert scopes.named(hlo, "copy.5", PATTERN)[0] == "learner.agent"
    # a copy of a loop's carried value, or of what no scope names: unscoped
    assert scopes.named(hlo, "copy.4", PATTERN)[0] is None
    assert scopes.named(hlo, "copy.6", PATTERN)[0] is None
    assert scopes.named(hlo, "copy-done.3", PATTERN, hops=1)[0] is None
    assert scopes.named({}, "fusion.1", PATTERN) == (None, None, None, [])


def test_instruction_and_program_names():
    assert scopes.instruction_of(
        "%fusion.32 = bf16[2048,1024]{1,0:T(8,128)(2,1)} fusion(bf16[] "
        "%x), kind=kOutput") == "fusion.32"
    assert scopes.program_of("jit__superstep(6606159805834224643)") == \
        "_superstep"


def test_gap_attribution_innermost_span_and_bare_time():
    host = [["driver.log", 0.0, 100.0], ["sight.detect", 20.0, 30.0],
            ["dispatch.superstep", 150.0, 50.0]]
    assert scopes._segments(host) == [
        (0.0, 20.0, "driver.log"), (20.0, 50.0, "sight.detect"),
        (50.0, 100.0, "driver.log"), (150.0, 200.0, "dispatch.superstep")]
    gaps = [(10.0, 60.0),          # 30 of sight.detect, 20 of driver.log
            (90.0, 160.0),         # 10 + 10 covered, 50 bare
            (300.0, 300.0 + 1e6)]  # in no span, and long
    by, bare, longs = scopes.attribute_gaps(gaps, host)
    assert by["sight.detect"] == 50.0
    assert by["driver.log"] + by.get("dispatch.superstep", 0.0) == 70.0
    assert by["in no span"] == 1e6
    assert bare == 50.0 + 1e6
    assert longs == [["in no span", 1.0, 300.0]]


def _planes(dispatch_at, run_at, done_at=None):
    modules = [["jit__superstep(7)", run_at, 100.0, "1"]]
    host = [["dispatch.superstep", dispatch_at, 10.0]]
    return modules, host, ({} if done_at is None else {"1": done_at})


def test_clock_bracket_and_its_errors():
    # the device plane 40 ns behind at least, 70 at most: one clock exists
    least, most, n = scopes.clock_skew(*_planes(540.0, 500.0, 670.0))
    assert (least, most, n) == (40.0, 70.0, 1)
    # an execution began after its dispatch: nothing to correct
    assert scopes.clock_skew(*_planes(400.0, 500.0))[0] == -100.0
    # the client's own launch call, later than the span's begin, is the
    # sharper bound — used where every execution has its launch
    assert scopes.clock_skew(*_planes(400.0, 500.0, 670.0),
                             launches=[520.0])[:2] == (20.0, 70.0)
    assert scopes.clock_skew(*_planes(400.0, 500.0, 670.0),
                             launches=[520.0, 900.0])[0] == -100.0
    # seen done on the host before the device says it began it, while
    # dispatched after: no one offset puts the planes on one clock
    with pytest.raises(scopes.ClockError):
        scopes.clock_skew(*_planes(1e6, 0.0, 50.0))
    # an execution that no dispatch began for
    modules, host, _ = _planes(0.0, 500.0)
    modules.append(["jit__superstep(7)", 900.0, 50.0, "2"])
    with pytest.raises(scopes.ClockError):
        scopes.clock_skew(modules, host, {})
    # a program that opens no spans (the parent of PR 25): nothing checked
    assert scopes.clock_skew(modules, [], {}) == (None, None, 0)


# --------------------------------------------------- the recorded trace

def test_closure_on_the_recorded_trace(recorded):
    dev = recorded["devices"][0]
    red = scopes.reduce(recorded, VOCAB)
    union = trace._union([(s, s + d) for s, d in
                          zip(dev["start"], dev["dur"])]) / 1e9
    assert red["busy_s"] == pytest.approx(union, rel=1e-9)
    assert sum(red["scope_s"].values()) == pytest.approx(union, rel=1e-9)
    by_prog = sum(sum(v.values()) for v in red["by_program_s"].values())
    assert by_prog == pytest.approx(union, rel=1e-9)
    # the closure check itself
    scopes.reduce(recorded, VOCAB, busy_s=union)
    with pytest.raises(ValueError):
        scopes.reduce(recorded, VOCAB, busy_s=union * 1.02)
    # nested loops counted once: the events' durations sum to far more
    assert sum(dev["dur"]) / 1e9 > 1.5 * union


def test_self_times_of_the_recorded_trace_the_slow_way(recorded):
    dev = recorded["devices"][0]
    n = 600
    start, dur = dev["start"][:n], dev["dur"][:n]
    events, _ = scopes.sweep(start, dur, lambda i: ((None,), i))
    own = {i: t for t, _, i in events}
    for i in range(n):
        lo, hi = start[i], start[i] + dur[i]
        inside = [j for j in range(n) if j != i
                  and lo <= start[j] and start[j] + dur[j] <= hi
                  and (start[j], -dur[j]) > (lo, -dur[i])]
        # direct children: inside this event and inside no other child
        direct = [j for j in inside if not any(
            k != j and start[k] <= start[j]
            and start[j] + dur[j] <= start[k] + dur[k]
            and (start[k], -dur[k]) < (start[j], -dur[j])
            for k in inside)]
        assert own[i] == pytest.approx(dur[i] - sum(dur[j] for j in direct),
                                       abs=1e-6)


def test_scopes_of_the_recorded_trace(recorded):
    red = scopes.reduce(recorded, VOCAB)
    s = red["scope_s"]
    # one update and one test rollout were kept
    for scope in ("learner.agent", "learner.mixer", "learner.target",
                  "learner.loss", "learner.optimizer", "replay.sample",
                  "replay.priority", "sight", "env.step", "env.obs",
                  "act.forward", "act.select", "rollout.reset"):
        assert s.get(scope, 0.0) > 0.0, scope
    assert red["updates"] == 1
    # the wrappers split the learner: the target networks have no
    # backward pass, the online ones all three
    assert set(red["pass_s"]["learner.target"]) == {"forward"}
    assert set(red["pass_s"]["learner.agent"]) == {
        "forward", "recomputation", "backward"}
    assert set(red["pass_s"]["learner.mixer"]) == {
        "forward", "recomputation", "backward"}
    # the model's children are the same names under acting and learning
    assert "agent.attention" in red["inner_s"]["act.forward"]
    assert "agent.attention" in red["inner_s"]["learner.agent"]
    assert "env.normalizer" in red["inner_s"]["env.step"]
    # by program: the learner is in _superstep alone, acting in both
    assert "learner.agent" not in red["by_program_s"]["_rollout"]
    assert red["by_program_s"]["_rollout"]["act.forward"] > 0.0
    # fusions made from two scopes are named, with both
    assert all(len(m) > 1 for _, _, m in red["straddling"])
    # without a vocabulary (the parent) everything is unscoped, and the
    # metrics read nothing
    parent = dict(recorded, host=[])       # nor does it annotate spans
    bare = scopes.reduce(parent, frozenset())
    assert set(bare["scope_s"]) == {"unscoped"}
    assert set(bare["idle_by_span_s"]) == {"in no span"}
    assert bare["clock_checked"] == 0
    nums = scopes.numbers(bare, 4, 1)
    assert [k for k, v in nums.items() if k.endswith(("_ms", "_pct"))
            and v is not None] == []


def test_numbers_of_the_recorded_trace(recorded):
    red = scopes.reduce(recorded, VOCAB)
    nums = scopes.numbers(red, iterations=1, rollout_runs=1)
    s = red["scope_s"]
    assert nums["rollouts"] == 2 and nums["updates"] == 1
    assert nums["env_step_dev_ms"] == pytest.approx(
        (s["env.step"] + s["env.obs"] + s["rollout.reset"]) * 1e3 / 2)
    assert nums["acting_dev_ms"] == pytest.approx(
        (s["act.forward"] + s["act.select"]) * 1e3 / 2)
    assert nums["learner_update_dev_ms"] == pytest.approx(
        sum(v for k, v in s.items() if k.startswith("learner.")) * 1e3)
    assert 0.0 < nums["unscoped_dev_pct"] < 100.0
    assert 0.0 <= nums["idle_unattributed_pct"] <= 100.0


def test_gaps_and_clock_of_the_recorded_trace(recorded):
    red = scopes.reduce(recorded, VOCAB)
    (least, most), = red["device_behind_host_ms"]
    assert 0.0 < least < most < least + 0.5  # one clock exists, and the
    #                                          device plane lies behind it
    assert red["clock_checked"] == 6         # 2 supersteps + 4 rollouts
    assert red["idle_s"] > 0.0
    assert sum(red["idle_by_span_s"].values()) == pytest.approx(
        red["idle_s"])
    assert set(red["idle_by_span_s"]) <= PHASES | {"in no span"}
    # the host plane moved 50 ms later: every execution now begins
    # before its own dispatch, and is seen done before it — an error
    late = copy.deepcopy(recorded)
    late["host"] = [[p, s + 5e7, d] for p, s, d in late["host"]]
    late["completions"] = {k: v - 5e7
                           for k, v in late["completions"].items()}
    with pytest.raises(scopes.ClockError):
        scopes.reduce(late, VOCAB)


def test_offset_of_the_harness_alignment():
    host = [["dispatch.superstep", 2e6, 1e6], ["fetch.test_stats", 9e6, 1e6]]
    t0 = 1_000_000_000_000_000_000
    spans = [("dispatch.superstep", t0 / 1e9 + 0.002 - 0.0004, 0.0),
             ("fetch.test_stats", t0 / 1e9 + 0.009 - 0.0004, 0.0),
             ("fetch.test_stats", t0 / 1e9 - 5.0, 0.0)]
    assert scopes.clock_offset_ms(host, spans, t0) == pytest.approx(
        0.4, abs=1e-3)
    assert scopes.clock_offset_ms([], spans, t0) is None
