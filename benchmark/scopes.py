"""Device time by named scope, and idle time by host span, from the one
trace file the profiler wrote — the readers of the per-layer metrics
``env_step_dev_ms``, ``acting_dev_ms``, ``replay_dev_ms``,
``learner_update_dev_ms``, ``unscoped_dev_pct`` and
``idle_unattributed_pct`` (``metrics/<name>.py`` each call ``reduction``).

What a TPU trace holds (looked at by hand, PR 25): the device plane's
``XLA Ops`` line has one event per executed operation, named by the
operation's HLO text *without* its metadata (``%fusion.32 = bf16[...]
fusion(...)``), and nested: a ``while`` or ``conditional`` event contains
its body's events. The scopes are in no event. They are in the compiled
programs' HLO protos, which the profiler stores in the ``/host:metadata``
plane (one per program, named like the ``XLA Modules`` events): every
instruction carries ``metadata.op_name``, JAX's name stack
(``jit(_superstep)/while/body/env.step/vmap(...)/dot_general``).
``jax.profiler.ProfileData`` does not expose that plane's contents, so
the few fields needed are read from the file's bytes (protobuf wire
format; ``_fields``). Host spans are the ``TraceAnnotation`` events of
the ``/host:`` planes, on the same clock as the device events.

``load`` reads the file into plain lists; ``reduce`` works on those alone
and is checked against ``data/recorded_scopes.json``
(``tests/test_scopes.py``). With a program that opens no scopes and no
annotations (the parent of PR 25) everything reads as nothing: the
metrics return ``None``.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from array import array
from typing import Optional

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
METADATA_PLANE = "/host:metadata"
# the TPU runtime's own host events: an execution seen done (by run id),
# and the client's call that launches one (in the device's order)
COMPLETION = "CompleteCallbacks"
EXECUTE = "PJRT_LoadedExecutable_Execute"
TOP = 10
GAP_MS = 0.5                       # gaps at least this long are listed
CLOSURE = 0.01                     # Σ self times against the busy time

#: the layer each scope's time is reported under, and per what
ENV = ("rollout.reset", "env.obs", "env.step")
ACTING = ("act.forward", "act.select")
REPLAY = ("rollout.store", "replay.insert", "replay.sample",
          "replay.priority")
DISPATCHES = {"_superstep": ("dispatch.superstep",),
              "_rollout": ("dispatch.rollout", "dispatch.test"),
              "_train_iter": ("dispatch.train",)}


class ClockError(RuntimeError):
    """The host and device planes cannot be put on one clock (see
    ``clock_skew``)."""


def vocabulary():
    """(scopes, phases) as the program under test declares them; no
    scopes where it predates them."""
    from t2omca_tpu.obs import spans
    return (frozenset(getattr(spans, "KNOWN_SCOPES", ())),
            frozenset(spans.KNOWN_PHASES))


# --------------------------------------------------- protobuf wire format

def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b, i, end):
    """The fields of one message → (number, value): an int for a varint,
    ``(start, end)`` into ``b`` for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b, span):
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _hlo_module(b, span) -> dict:
    """HloProto bytes → {"ops": {instruction: op_name}, "fused":
    {fusion instruction: [distinct name stacks of its fused
    instructions]}, "from": {instruction that carries no name stack:
    its first operand}}.
    HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.name = 1, .instructions = 2, .id = 5;
    HloInstructionProto.name = 1, .opcode = 2, .metadata = 7, .id = 35,
    .operand_ids = 36, .called_computation_ids = 38;
    OpMetadata.op_name = 2."""
    ops, calls, bodies, ids, feeds = {}, {}, {}, {}, {}
    for f, mod in _fields(b, *span):
        if f != 1:
            continue
        for f2, comp in _fields(b, *mod):
            if f2 != 3:
                continue
            comp_id, names = None, []
            for f3, v in _fields(b, *comp):
                if f3 == 5:
                    comp_id = v
                elif f3 == 2:
                    name = opcode = uid = None
                    op_name, called, operands = "", [], []
                    for f4, w in _fields(b, *v):
                        if f4 == 1:
                            name = _text(b, w)
                        elif f4 == 2:
                            opcode = _text(b, w)
                        elif f4 == 7:
                            for f5, x in _fields(b, *w):
                                if f5 == 2:
                                    op_name = _text(b, x)
                        elif f4 == 35:
                            uid = w
                        elif f4 in (36, 38):
                            into = operands if f4 == 36 else called
                            if isinstance(w, tuple):      # packed
                                i = w[0]
                                while i < w[1]:
                                    c, i = _varint(b, i)
                                    into.append(c)
                            else:
                                into.append(w)
                    ops[name] = op_name
                    names.append(op_name)
                    ids[uid] = name
                    if not op_name and operands:
                        feeds[name] = operands[0]
                    if opcode == "fusion" and called:
                        calls[name] = called[0]
            # a fused computation's name stacks less their last component
            # (the primitive): what scopes it was fused from
            bodies[comp_id] = sorted({o.rsplit("/", 1)[0]
                                      for o in names if "/" in o})
    return {"ops": ops,
            "fused": {n: bodies.get(c, []) for n, c in calls.items()},
            "from": {n: ids[i] for n, i in feeds.items() if i in ids}}


def hlo_modules(raw: bytes) -> dict:
    """{program name as the module events give it: ``_hlo_module``} from
    the ``/host:metadata`` plane. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 (map: value = 2), .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .bytes_value = 6."""
    out = {}
    for f, plane in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name, metas = None, []
        for f2, v in _fields(raw, *plane):
            if f2 == 2:
                name = _text(raw, v)
            elif f2 == 4:
                metas.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in metas:
            for f3, meta in _fields(raw, *entry):
                if f3 != 2:
                    continue
                prog, proto = None, None
                for f4, v in _fields(raw, *meta):
                    if f4 == 2:
                        prog = _text(raw, v)
                    elif f4 == 5:
                        for f5, x in _fields(raw, *v):
                            if f5 in (5, 6) and isinstance(x, tuple):
                                proto = x
                if prog and proto:
                    out[prog] = _hlo_module(raw, proto)
    return out


# ------------------------------------------------------------------ load

def trace_file(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stat(ev, name):
    for k, v in ev.stats:
        if k == name:
            return str(v)
    return None


def load(trace_dir: str) -> dict:
    """→ {"devices": [{"modules": [[name, start_ns, dur_ns, run id]],
    "names": [operation text], "op": name index, "start": ns, "dur":
    ns}], "host": [[phase, start_ns, dur_ns]], "completions": {run id:
    ns at which the runtime's host thread saw that execution done},
    "launches": [ns at which the client began each launch], "hlo":
    ``hlo_modules``}."""
    from jax.profiler import ProfileData
    _, phases = vocabulary()
    path = trace_file(trace_dir)
    devices, host, done, launches = [], [], {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            dev = {"modules": [], "names": [], "op": array("l"),
                   "start": array("d"), "dur": array("d")}
            index = {}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    dev["modules"] = [[ev.name, float(ev.start_ns),
                                       float(ev.duration_ns),
                                       _stat(ev, "run_id")]
                                      for ev in line.events]
                elif line.name == OP_LINE:
                    for ev in line.events:
                        name = ev.name
                        i = index.get(name)
                        if i is None:
                            i = index[name] = len(dev["names"])
                            dev["names"].append(name)
                        dev["op"].append(i)
                        dev["start"].append(ev.start_ns)
                        dev["dur"].append(ev.duration_ns)
            if dev["modules"] or dev["op"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in phases:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
                    elif ev.name == COMPLETION:
                        done[_stat(ev, "run_id")] = float(ev.start_ns)
                    elif ev.name == EXECUTE:
                        launches.append(float(ev.start_ns))
    done.pop(None, None)
    with open(path, "rb") as f:
        hlo = hlo_modules(f.read())
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
            "completions": done, "launches": sorted(launches), "hlo": hlo}


# ---------------------------------------------------------------- reduce

def program_of(module_event: str) -> str:
    """``jit__superstep(1234...)`` → ``_superstep``."""
    m = re.match(r"jit_(.+?)(\(\d+\))?$", module_event)
    return m.group(1) if m else module_event


def instruction_of(op_event: str) -> str:
    """``%fusion.32 = bf16[...] fusion(...)`` → ``fusion.32``."""
    return op_event.split(" = ", 1)[0].lstrip("%")


def scope_pattern(scopes):
    """Scopes as tokens of a name stack: inside ``vmap(...)`` and
    ``transpose(jvp(...))``, never as part of a longer dotted name
    (``ts.learner.target_params`` names no scope)."""
    if not scopes:
        return None
    alt = "|".join(re.escape(s) for s in sorted(scopes, key=len,
                                                reverse=True))
    return re.compile(r"(?<![\w.])(" + alt + r")(?![\w.])")


def classify(op_name: str, pattern):
    """→ (outermost scope, innermost scope, pass) of a name stack; pass
    is ``recomputation`` under ``rematted_computation``, ``backward``
    where ``transpose(`` wraps the outermost scope, else ``forward``."""
    found = list(pattern.finditer(op_name)) if pattern else []
    if not found:
        return None, None, None
    first = found[0]
    if "rematted_computation" in op_name:
        which = "recomputation"
    elif "transpose(" in op_name[:first.start()].rsplit("/", 1)[-1]:
        which = "backward"
    else:
        which = "forward"
    return first.group(1), found[-1].group(1), which


def named(hlo: dict, instr: str, pattern, hops: int = 4):
    """→ (outermost scope, innermost, pass, [outermost scopes fused
    into it]) of one instruction of a compiled program. The compiler
    makes operations that carry no name stack: a fusion is then booked
    under the scope most of what it fused has, and a copy (``copy``,
    ``copy-start``/``copy-done``, ``bitcast``) under the scope that made
    what it copies — its first operand's, followed ``hops`` deep."""
    within = [c for c in (classify(o, pattern)
                          for o in hlo.get("fused", {}).get(instr, ()))
              if c[0]]
    outers = sorted({c[0] for c in within})
    ops, feeds = hlo.get("ops", {}), hlo.get("from", {})
    for _ in range(hops + 1):
        facts = classify(ops.get(instr, ""), pattern)
        if facts[0] is not None:
            return (*facts, outers)
        if within:
            best = max(within,
                       key=lambda c: sum(d[0] == c[0] for d in within))
            return (*best, outers)
        instr = feeds.get(instr)
        if instr is None:
            break
        within = [c for c in (classify(o, pattern)
                              for o in hlo.get("fused", {}).get(instr, ()))
                  if c[0]]
    return None, None, None, outers


def sweep(start, dur, facts_of):
    """One pass over the events of an operation line, in start order: the
    self time of each (its duration less what its child events cover — a
    ``while`` event contains its body's events) with what it is booked
    under, and the outermost events' intervals. ``facts_of(i)`` →
    (booking, identity), booking[0] the outermost scope or None; an event
    no scope names inherits the booking of the event it runs inside (a
    compiler-made copy in the body of a loop that ``learner.agent``
    opened is the learner's) and keeps its identity.
    → ([(self ns, booking, identity)], [(start, end, index)] of the
    outermost)."""
    n = len(start)
    order = range(n)
    if any(start[i] > start[i + 1] for i in range(n - 1)):
        order = sorted(range(n), key=lambda i: (start[i], -dur[i]))
    out, top, stack = [], [], []       # stack: [end, self, booking, ident]
    for i in order:
        s, d = start[i], dur[i]
        while stack and stack[-1][0] <= s:
            out.append(tuple(stack.pop()[1:]))
        booking, ident = facts_of(i)
        if stack:
            stack[-1][1] -= d
            if booking[0] is None and stack[-1][2][0] is not None:
                booking = stack[-1][2]
        else:
            top.append((s, s + d, i))
        stack.append([s + d, d, booking, ident])
    out.extend(tuple(e[1:]) for e in stack)
    return out, top


def host_summary(host) -> dict:
    """{phase: [count, total ms, longest ms]} of the host plane's spans:
    a span that lasts as long as the program it follows was blocked."""
    out = {}
    for ph, _, d in host:
        c = out.setdefault(ph, [0, 0.0, 0.0])
        c[0] += 1
        c[1] += d / 1e6
        c[2] = max(c[2], d / 1e6)
    return out


def _segments(host):
    """Host spans → [(t0, t1, phase)] in time order without overlap: at
    each instant the span that began last (the innermost)."""
    cuts = sorted({t for _, s, d in host for t in (s, s + d)})
    live = sorted(host, key=lambda e: e[1])
    segs = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for ph, s, d in live:
            if s > a:
                break
            if s + d >= b and (best is None or s >= best[1]):
                best = (ph, s)
        if best is None:
            continue
        if segs and segs[-1][2] == best[0] and segs[-1][1] == a:
            segs[-1] = (segs[-1][0], b, best[0])
        else:
            segs.append((a, b, best[0]))
    return segs


def attribute_gaps(gaps, host):
    """Each idle gap ``(start, end)`` (host clock, ns) → the span that
    covers most of it. → (idle ns by label, ns that no span covers,
    [[label, ms, start ns]] of the gaps of ``GAP_MS`` or more)."""
    segs = _segments(host)
    starts = [s[0] for s in segs]
    by_label, bare, long_gaps = {}, 0.0, []
    for g0, g1 in gaps:
        cover, covered = {}, 0.0
        j = max(bisect.bisect_right(starts, g0) - 1, 0)
        while j < len(segs) and segs[j][0] < g1:
            c = min(segs[j][1], g1) - max(segs[j][0], g0)
            if c > 0:
                cover[segs[j][2]] = cover.get(segs[j][2], 0.0) + c
                covered += c
            j += 1
        label = max(cover, key=cover.get) if cover else "in no span"
        by_label[label] = by_label.get(label, 0.0) + (g1 - g0)
        bare += (g1 - g0) - covered
        if g1 - g0 >= GAP_MS * 1e6:
            long_gaps.append([label, (g1 - g0) / 1e6, g0])
    return by_label, bare, long_gaps


def clock_skew(modules, host, completions, launches=()):
    """How far the device plane's clock lies behind the host plane's,
    bracketed by causality. Below: the k-th execution of ``_superstep``
    cannot begin before the k-th ``dispatch.superstep`` began; and, one
    device running its programs in the order they were launched, the
    k-th execution of any program cannot begin before the client's k-th
    launch call began (used where the trace holds as many launches as
    executions). Above: the runtime's host thread cannot see an execution
    done before the device finished it (``CompleteCallbacks``, matched
    by run id). → (least ns, most ns, executions checked); a bound is
    ``None`` where nothing gives it. An execution no dispatch began for,
    or bounds that no one offset satisfies, are a ``ClockError``: the
    planes cannot be put on one clock, and no gap can be given to a
    span. Nothing is an error where the program opens no spans."""
    lows, highs = [], []
    for prog, phases in DISPATCHES.items():
        began = sorted(s for ph, s, _ in host if ph in phases)
        runs = sorted(m[1] for m in modules if program_of(m[0]) == prog)
        if not began:
            continue
        if len(runs) > len(began):
            raise ClockError(
                f"{len(runs)} executions of {prog} in the device plane, "
                f"{len(began)} dispatches of it in the host plane")
        lows.extend(b - s for b, s in zip(began, runs))
    checked = len(lows)
    if modules and len(launches) == len(modules):
        lows.extend(b - s for b, s in zip(
            sorted(launches), sorted(m[1] for m in modules)))
    for _, s, d, run_id in modules:
        if run_id in completions:
            highs.append(completions[run_id] - (s + d))
    least = max(lows) if lows else None
    most = min(highs) if highs else None
    if (host and least is not None and most is not None
            and least > most + 5e4):
        raise ClockError(
            f"the device plane lies at least {least / 1e6:.3f} ms and at "
            f"most {most / 1e6:.3f} ms behind the host plane")
    return least, most, checked


def reduce(loaded: dict, scopes, busy_s: Optional[float] = None) -> dict:
    """Self time by outermost scope (and by program, by innermost scope,
    by pass for ``learner.*``), what no scope names, the idle gaps by
    host span. Seconds, averaged over the device planes. ``busy_s``: the
    busy time ``trace.reduce`` reports, for the closure check."""
    pattern = scope_pattern(scopes)
    devices = loaded["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane with events")
    n = len(devices)
    host = loaded["host"]
    scope_s, inner_s, pass_s, by_prog = {}, {}, {}, {}
    unscoped, counts, straddle = {}, {}, {}
    total = idle_ns = bare_ns = 0.0
    idle_by, long_gaps, checked = {}, [], 0
    skews = []
    for dev in devices:
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mod_start = [m[1] for m in mods]
        start, op, names = dev["start"], dev["op"], dev["names"]
        resolved = {}          # (module event, operation) → facts

        def facts_of(i):
            s = start[i]
            k = bisect.bisect_right(mod_start, s) - 1
            mod = (mods[k][0] if k >= 0 and s < mods[k][1] + mods[k][2]
                   else "?")
            key = (mod, op[i])
            facts = resolved.get(key)
            if facts is None:
                instr = instruction_of(names[op[i]])
                hlo = loaded["hlo"].get(mod) or {}
                outer, inner, which, outers = named(hlo, instr, pattern)
                prog = program_of(mod)
                facts = resolved[key] = (
                    (outer, inner, which,
                     outers if len(outers) > 1 else None),
                    (prog, f"{prog}:{instr}"))
            return facts

        events, top = sweep(start, dev["dur"], facts_of)
        for own, (outer, inner, which, mixed), (prog, name) in events:
            t = own / 1e9 / n
            total += t
            label = outer or "unscoped"
            scope_s[label] = scope_s.get(label, 0.0) + t
            p = by_prog.setdefault(prog, {})
            p[label] = p.get(label, 0.0) + t
            if outer is None:
                unscoped[name] = unscoped.get(name, 0.0) + t
                continue
            if inner != outer:
                d = inner_s.setdefault(outer, {})
                d[inner] = d.get(inner, 0.0) + t
            if outer.startswith("learner."):
                d = pass_s.setdefault(outer, {})
                d[which] = d.get(which, 0.0) + t
            if outer == "learner.optimizer":
                counts[name] = counts.get(name, 0) + 1
            if mixed:
                m = straddle.setdefault(name, [0.0, mixed])
                m[0] += t
        # the idle gaps between the outermost events, moved onto the host
        # plane's clock by the least shift causality asks for
        least, most, k = clock_skew(mods, host, loaded["completions"],
                                    loaded.get("launches", ()))
        checked += k
        skews.append((least, most))
        shift = 0.0
        if least is not None and least > 0:
            shift = least
        elif most is not None and most < 0:
            shift = most
        top.sort()
        gaps, around = [], {}
        for (_, end, before), (s, _, after) in zip(top, top[1:]):
            if s > end:
                gaps.append((end + shift, s + shift))
                around[end + shift] = (before, after)
        by, bare, longs = attribute_gaps(gaps, host)
        for label, ns in by.items():
            idle_by[label] = idle_by.get(label, 0.0) + ns / n
        idle_ns += sum(e - s for s, e in gaps) / n
        bare_ns += bare / n
        # each long gap with when it began (ms after the first operation)
        # and the outermost operations on either side of it
        for label, length, g0 in longs:
            before, after = around[g0]
            long_gaps.append([
                label, length, (g0 - shift - top[0][0]) / 1e6,
                instruction_of(names[op[before]]),
                instruction_of(names[op[after]])])
    if busy_s is not None and abs(total - busy_s) > CLOSURE * busy_s:
        raise ValueError(f"self times sum to {total:.6f} s, the busy time "
                         f"is {busy_s:.6f} s: the nesting was misread")
    per_name = sorted(counts.values())
    top_of = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:TOP]  # noqa: E731
    return {
        "busy_s": total, "scope_s": scope_s, "inner_s": inner_s,
        "pass_s": pass_s, "by_program_s": by_prog,
        "unscoped_ops": [list(kv) for kv in top_of(unscoped)],
        "straddling": [[k, v[0], v[1]] for k, v in sorted(
            straddle.items(), key=lambda kv: -kv[1][0])[:TOP]],
        # every operation of the optimizer runs once an update
        "updates": (per_name[len(per_name) // 2] / n) if per_name else 0,
        "idle_s": idle_ns / 1e9, "idle_bare_s": bare_ns / 1e9,
        "idle_by_span_s": {k: v / 1e9 for k, v in idle_by.items()},
        "long_gaps": sorted(long_gaps, key=lambda g: -g[1]),
        "host_events": len(host), "clock_checked": checked,
        "host_spans": host_summary(host),
        "device_behind_host_ms": [
            [None if b is None else b / 1e6 for b in pair]
            for pair in skews],
    }


# -------------------------------------------------------------- metrics

def clock_offset_ms(host, spans, trace_t0_ns) -> Optional[float]:
    """How far the harness's alignment of ``spans.jsonl`` with the
    trace (wall clock read before ``start_trace`` = the trace's zero) lies
    from the profiler's own clock: the median, over the spans both hold,
    of (trace zero + the host event's start) − the span's ``t0``."""
    if not host or not spans or trace_t0_ns is None:
        return None
    by_phase = {}
    for ph, a, _ in spans:
        by_phase.setdefault(ph, []).append(a)
    diffs = []
    for ph, s, _ in host:
        est = (trace_t0_ns + s) / 1e9
        t0s = by_phase.get(ph)
        if t0s:
            diffs.append(min((est - t for t in t0s), key=abs))
    if not diffs:
        return None
    diffs.sort()
    return diffs[len(diffs) // 2] * 1e3


def numbers(red: dict, iterations: int, rollout_runs: float) -> dict:
    """The six metrics (``None`` where there is nothing to read) and the
    divisors they use."""
    s = red["scope_s"]
    rollouts = iterations + rollout_runs
    updates = red["updates"]

    def per(names, count, prefix=False):
        hit = [v for k, v in s.items()
               if (k.startswith(names) if prefix else k in names)]
        return sum(hit) * 1e3 / count if hit and count else None

    scoped = red["busy_s"] - s.get("unscoped", 0.0)
    return {
        "rollouts": rollouts, "iterations": iterations, "updates": updates,
        "env_step_dev_ms": per(ENV, rollouts),
        "acting_dev_ms": per(ACTING, rollouts),
        "replay_dev_ms": per(REPLAY, iterations),
        "learner_update_dev_ms": per("learner.", updates, prefix=True),
        "unscoped_dev_pct": (100.0 * s.get("unscoped", 0.0) / red["busy_s"]
                             if scoped > 0 and red["busy_s"] else None),
        "idle_unattributed_pct": (100.0 * red["idle_bare_s"] / red["idle_s"]
                                  if red["host_events"] and red["idle_s"]
                                  else None),
    }


_CACHE: dict = {}


def reduction(ctx) -> dict:
    """``numbers`` of the traced window of ``ctx`` (a harness
    ``MetricContext``), reduced once per process; the first call prints
    the ``{"phase": "scopes", ...}`` line."""
    trace_dir = getattr(ctx.window, "trace_dir", None)
    if not trace_dir or not ctx.trace:
        return {}
    if trace_dir in _CACHE:
        return _CACHE[trace_dir]
    import time
    from benchmark import trace as trace_mod
    t0 = time.perf_counter()
    scopes, _ = vocabulary()
    loaded = load(trace_dir)
    red = reduce(loaded, scopes, busy_s=ctx.trace["busy_s"])
    planes = ctx.trace.get("n_planes") or 1
    runs = ctx.trace.get("programs", {}).get("_rollout", {}).get("runs", 0)
    out = numbers(red, ctx.window.iterations, runs / planes)
    offset = clock_offset_ms(
        loaded["host"], trace_mod.host_spans(ctx.cfg.local_results_path),
        ctx.window.trace_t0_ns)
    ms = lambda d: {k: v * 1e3 for k, v in sorted(d.items())}  # noqa: E731
    print(json.dumps({
        "phase": "scopes", "seconds": time.perf_counter() - t0,
        "busy_ms": red["busy_s"] * 1e3, "scope_ms": ms(red["scope_s"]),
        "inner_ms": {k: ms(v) for k, v in sorted(red["inner_s"].items())},
        "learner_pass_ms": {k: ms(v)
                            for k, v in sorted(red["pass_s"].items())},
        "by_program_ms": {k: ms(v) for k, v in red["by_program_s"].items()
                          if sum(v.values()) >= 1e-3},
        "unscoped_ops_ms": [[k, v * 1e3] for k, v in red["unscoped_ops"]],
        "straddling_ms": [[k, v * 1e3, m]
                          for k, v, m in red["straddling"]],
        "idle_ms": red["idle_s"] * 1e3,
        "idle_by_span_ms": ms(red["idle_by_span_s"]),
        "gaps_over_half_ms": red["long_gaps"][:4 * TOP],
        "gaps_over_half_ms_count": len(red["long_gaps"]),
        "host_events": red["host_events"],
        "host_spans_count_ms_max": red["host_spans"],
        "clock_checked": red["clock_checked"],
        "device_behind_host_ms": red["device_behind_host_ms"],
        "clock_offset_ms": offset, **out}), flush=True)
    _CACHE[trace_dir] = out
    return out
