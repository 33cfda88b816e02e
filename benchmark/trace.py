"""The reduction from the profiler's trace to numbers — the benchmark's
own, so that every PR reads a trace the same way.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote
(``jax.profiler.ProfileData``) into plain lists; ``reduce`` works on those
lists alone, so it can be checked against the small recorded trace in
``benchmark/data/recorded_trace.json``.

A TPU device plane (``/device:TPU:<n>``) carries, among others, a line of
whole programs (``XLA Modules``: one event per execution of a jitted
program, named ``jit__superstep(<fingerprint>)``) and a line of single
operations (``XLA Ops``). Busy time is the union of the operations'
intervals (the programs' where a trace has no operation line), averaged
over the device planes; a program's time is the sum of its executions.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Optional

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
TOP = 10


def load(trace_dir: str, max_ops: int = 4_000_000) -> dict:
    """→ {"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]} of the device planes, and of no other."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OP_LINE, "Steps"):
                continue
            events = []
            for ev in line.events:
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns)])
                if len(events) >= max_ops:
                    break
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, lo: float, hi: float):
    """The idle stretches of ``[lo, hi]`` → [(start, end), ...]."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
    if end < hi:
        out.append((end, hi))
    return out


def program_of(event_name: str) -> str:
    """``jit__superstep(1234...)`` → ``_superstep``."""
    m = re.match(r"jit_(.+?)(\(\d+\))?$", event_name)
    return m.group(1) if m else event_name


def reduce(loaded: dict) -> dict:
    """→ busy_s and window_s (averaged over the device planes), seconds
    and executions by program, the operations that took most time, the
    idle gaps (trace clock, ns)."""
    planes = [p for p in loaded["planes"]
              if any(ln["events"] for ln in p["lines"])]
    if not planes:
        raise ValueError("the trace holds no device plane with events")
    busy, window, n_events = [], [], 0
    programs: dict = {}
    ops: dict = {}
    gaps = []
    for p in planes:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        mods = lines.get(MODULE_LINE, [])
        op_ev = lines.get(OP_LINE, [])
        base = op_ev or mods
        n_events += len(base)
        iv = [(s, s + d) for _, s, d in base]
        lo = min(s for s, _ in iv)
        hi = max(e for _, e in iv)
        busy.append(_union(iv) / 1e9)
        window.append((hi - lo) / 1e9)
        gaps.extend(_gaps(iv, lo, hi))
        for name, _, d in mods:
            prog = programs.setdefault(program_of(name),
                                       {"seconds": 0.0, "runs": 0})
            prog["seconds"] += d / 1e9 / len(planes)
            prog["runs"] += 1
        for name, _, d in op_ev:
            # an event's name is the operation's whole HLO text
            name = name.split(" = ")[0]
            ops[name] = ops.get(name, 0.0) + d / 1e9 / len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": sum(window) / len(window),
        "programs": programs,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps_ns": gaps[:TOP],
        "n_events": n_events,
        "n_planes": len(planes),
    }


def host_spans(workdir: str) -> list:
    """The program's own host spans (``spans.jsonl`` of ``obs/spans.py``,
    wall clock) → [(phase, start_s, end_s)]; empty where there are none."""
    out = []
    for path in glob.glob(os.path.join(workdir, "**", "spans.jsonl"),
                          recursive=True):
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("event") != "span" or "wall_ms" not in ev:
                    continue
                out.append((str(ev["phase"]), float(ev["t0"]),
                            float(ev["t0"]) + float(ev["wall_ms"]) / 1e3))
    return out


def breakdown(reduced: dict, spans: list,
              trace_t0_ns: Optional[int]) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and the longest idle gaps, each named by the host span
    that covers most of it (the spans' wall clock against the wall clock
    read when the trace was started)."""
    named = []
    for s, e in reduced["idle_gaps_ns"]:
        label = "no host span read"
        if trace_t0_ns is not None and spans:
            # device timestamps count from the trace's start
            ws, we = trace_t0_ns / 1e9 + s / 1e9, trace_t0_ns / 1e9 + e / 1e9
            best, label = 0.0, None
            for name, a, b in spans:
                cover = min(b, we) - max(a, ws)
                if cover > best:
                    best, label = cover, name
            if label is None:
                before = [(b, name) for name, a, b in spans if b <= ws]
                label = ("in no span, after " + max(before)[1] if before
                         else "in no span")
        named.append([label, (e - s) / 1e9])
    return {"device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": named[:TOP]}
