"""What the readers of the set-up metrics share (``metrics/setup_*.py``):
the program's own account of the time before the window, read once a run
from the ``spans.jsonl`` its recorder wrote (``obs/spans.py``; where the
file lies: ``trace.host_spans``).

The program opens a span round every stage of set-up (``backend.init``,
``setup.build``, ``setup.telemetry``, ``setup.init_state``,
``setup.restore``, ``setup.programs``), writes a ``run`` mark where the
loop starts, books every compilation to the span it happened in
(``compile_n``, ``compile_ms``, ``cache_load_ms``, ... on the span's
line; the process's counters so far on the ``run`` mark) and writes a
``compile`` mark for each backend compilation or cache retrieval of half
a second or more (``fun_name``, ``secs``, ``cache_hit``, ``phase``).
A span's ``parent`` is the ``seq`` of the span it is nested in, so its
self time is its wall less its children's.

``setup_s`` — process start to the window's opening — is split into five
parts that lie end to end, and what is left over:

* ``entry``: process start → the first span's start;
* ``build``, ``init_state``: self time of the spans before the ``run``
  mark, by :data:`GROUPS`; a span of another phase nested in one of them
  (``memwatch.snapshot`` under ``setup.telemetry``) counts with the span
  it is nested in;
* ``first_dispatch``: the ``dispatch.*`` spans between the ``run`` mark
  and the opening that carry a compilation;
* ``warmup``: the rest of ``run`` mark → opening. In a traced run the
  profiler's start lies just before the opening and is counted here;
* unattributed: the gaps between the spans before the ``run`` mark.

Span times are epoch seconds, the window's stamps ``perf_counter``
readings of this process: they are brought together by the offset
between the two clocks, read when the reduction runs, and the result is
checked against the epoch stamp the window takes before it starts the
profiler.

With a program that writes none of this (its parent, or telemetry off)
everything here reads as nothing and every metric returns ``None``.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time
from typing import Optional

#: phase of a span before the ``run`` mark -> the part it is booked under
GROUPS = {"backend.init": "build", "setup.build": "build",
          "setup.telemetry": "build", "setup.programs": "build",
          "setup.init_state": "init_state", "setup.restore": "init_state"}

#: JAX writes a persistent-cache entry for a compilation of a second or more
CACHEABLE_SECS = 1.0

_CACHE: dict = {}


def load(workdir: str) -> list:
    """Every event of every ``spans.jsonl`` under ``workdir``."""
    events = []
    for path in glob.glob(os.path.join(workdir, "**", "spans.jsonl"),
                          recursive=True):
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if isinstance(ev, dict) and "t0" in ev:
                    events.append(ev)
    return events


def reduce(events: list, t_process: float, t_open: float) -> Optional[dict]:
    """The parts of ``t_open - t_process`` (both epoch seconds) in
    seconds, ``compile_s`` / ``cold_compile_s`` (``None`` where the
    program counted no compilations) and ``unattributed_pct``; ``None``
    without set-up spans or a ``run`` mark before the opening."""
    spans = [e for e in events if e.get("event") == "span"
             and "wall_ms" in e and e["t0"] < t_open]
    run = next((e for e in events if e.get("event") == "mark"
                and e.get("kind") == "run" and e["t0"] < t_open), None)
    if run is None or not any(str(e.get("phase", "")).startswith("setup.")
                              for e in spans):
        return None
    t_run = float(run["t0"])
    by_seq = {e["seq"]: e for e in spans}
    nested: dict = {}                    # seq -> wall of its children, ms
    for e in spans:
        if "parent" in e:
            nested[e["parent"]] = nested.get(e["parent"], 0.0) + e["wall_ms"]

    def group(e):
        while e is not None:
            if e.get("phase") in GROUPS:
                return GROUPS[e["phase"]]
            e = by_seq.get(e.get("parent"))
        return None

    out = {"build_s": 0.0, "init_state_s": 0.0, "first_dispatch_s": 0.0}
    loop_ms = 0.0                        # compile + cache load after `run`
    for e in spans:
        if e["seq"] < run["seq"]:        # it began before the mark
            g = group(e)
            if g is not None:
                out[g + "_s"] += (e["wall_ms"]
                                  - nested.get(e["seq"], 0.0)) / 1e3
            continue
        loop_ms += e.get("compile_ms", 0.0) + e.get("cache_load_ms", 0.0)
        if (str(e["phase"]).startswith("dispatch.")
                and e.get("compile_n", 0) > 0):
            out["first_dispatch_s"] += e["wall_ms"] / 1e3
    setup_s = t_open - t_process
    out["setup_s"] = setup_s
    out["entry_s"] = min(e["t0"] for e in spans) - t_process
    out["warmup_s"] = (t_open - t_run) - out["first_dispatch_s"]
    parts = sum(out[n] for n in ("entry_s", "build_s", "init_state_s",
                                 "first_dispatch_s", "warmup_s"))
    out["unattributed_pct"] = 100.0 * (setup_s - parts) / setup_s

    marks = [e for e in events if e.get("event") == "mark"
             and e.get("kind") == "compile" and e["t0"] < t_open]
    out["compile_s"] = out["cold_compile_s"] = None
    if "compile_n" in run:
        # before `run`: the process's counters, in a span or not; after
        # it: the spans', and the marks of what no span held
        out["compile_s"] = (
            run.get("compile_ms", 0.0) + run.get("cache_load_ms", 0.0)
            + loop_ms) / 1e3 + sum(
                float(m["secs"]) for m in marks
                if m["seq"] > run["seq"] and m.get("phase") is None)
        out["cold_compile_s"] = sum(
            float(m["secs"]) for m in marks
            if m["secs"] >= CACHEABLE_SECS and not m.get("cache_hit"))
    return out


def parts(ctx) -> Optional[dict]:
    """:func:`reduce` of this run, once."""
    key = ctx.cfg.local_results_path
    if key not in _CACHE:
        w = ctx.window
        # perf_counter -> epoch, the same offset for both stamps
        offset = time.time() - time.perf_counter()
        t_open = w.t_open + offset
        ahead = (None if w.trace_t0_ns is None
                 else t_open - w.trace_t0_ns / 1e9)
        if ahead is not None and not -0.25 <= ahead <= 600.0:
            # the opening is stamped just after the profiler's start, the
            # epoch stamp just before it
            print(f"benchmark/setup.py: the window's opening lies "
                  f"{ahead:.3f} s after the epoch stamp before the "
                  f"profiler's start: the clocks do not agree, no set-up "
                  f"metric is reported", file=sys.stderr)
            _CACHE[key] = None
        else:
            _CACHE[key] = reduce(load(key), w.t_process + offset, t_open)
    return _CACHE[key]


def read(ctx, name: str) -> Optional[float]:
    """One number of :func:`parts`, or ``None``."""
    p = parts(ctx)
    return None if p is None else p[name]
