"""Tracing / profiling (SURVEY.md §5(1) — absent in the reference).

The reference's only timing is wall-clock ETA logging
(``/root/reference/per_run.py:207-208,246-251``). Here:

* ``StageTimer`` — per-stage wall-clock accumulation (rollout / train /
  test) logged with the metrics, so throughput regressions show up in the
  same TensorBoard/JSONL stream as reward curves;
* ``TraceWindow`` — a ``jax.profiler`` trace capture over a configured
  ``t_env`` window, viewable in TensorBoard's profile tab or Perfetto.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Optional

import jax


class StageTimer:
    def __init__(self):
        self._acc: Dict[str, float] = defaultdict(float)
        self._n: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._acc[name] += time.perf_counter() - t0
            self._n[name] += 1

    def log_and_reset(self, logger, t_env: int) -> None:
        for name, total in self._acc.items():
            n = max(self._n[name], 1)
            logger.log_stat(f"time_{name}_ms", 1000.0 * total / n, t_env)
        self._acc.clear()
        self._n.clear()


class TraceWindow:
    """Start a jax profiler trace when ``t_env`` enters
    [start, start+duration_steps-ish]; stop after ``n_iterations`` driver
    iterations. No-op when ``trace_dir`` is empty."""

    def __init__(self, trace_dir: str, start_t_env: int = 0,
                 n_iterations: int = 3):
        self.trace_dir = trace_dir
        self.start_t_env = start_t_env
        self.n_iterations = n_iterations
        self._active: Optional[int] = None   # iterations remaining
        self._done = False

    def maybe_start(self, t_env: int) -> None:
        if (not self.trace_dir or self._done or self._active is not None
                or t_env < self.start_t_env):
            return
        jax.profiler.start_trace(self.trace_dir)
        self._active = self.n_iterations

    def tick(self, logger=None, t_env: int = 0) -> None:
        if self._active is None:
            return
        self._active -= 1
        if self._active <= 0:
            jax.profiler.stop_trace()
            self._active = None
            self._done = True
            if logger is not None:
                logger.console_logger.info(
                    f"profiler trace written to {self.trace_dir}")
