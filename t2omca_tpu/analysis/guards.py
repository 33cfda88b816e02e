"""Runtime tracing-hygiene enforcement: compile budgets + transfer guards.

The static side (``graftlint``) catches hazards visible in the AST; this
module catches the two failure modes that are only observable at run
time and that PR 2's superstep made expensive:

* **Silent retraces.** ``superstep_program`` amortizes dispatch
  overhead over K iterations — ONE compile, many dispatches. A
  weak-typed scalar, a shape wobble, or a changed static arg silently
  recompiles the whole fused program every iteration and
  erases the win (the exact bug class ``run._strong`` exists to stop).
  ``compile_budget(n)`` turns that into a hard test failure: it counts
  XLA compiles (via the ``jax.log_compiles`` log stream) inside the
  ``with`` block and raises ``CompileBudgetExceeded`` past ``n``.

* **Implicit host transfers.** The fused K>1 path promises "no host
  round-trip between dispatch boundaries". ``no_transfer()`` wraps
  ``jax.transfer_guard`` so any implicit device→host fetch (and, by
  default, any implicit host→device upload — a Python scalar sneaking
  into dispatch args is also a weak-type retrace hazard) raises instead
  of silently stalling. Explicit ``jax.device_get`` at cadence
  boundaries stays allowed — the guards police *implicit* traffic. On
  the CPU backend device→host copies are zero-copy and never trip the
  guard; the host→device direction still enforces, so the tests keep
  teeth under ``JAX_PLATFORMS=cpu`` and gain the full check on device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Iterator, List, Optional

import jax

#: loggers that carry the per-compile "Compiling <fn> ..." records
#: (jax._src.interpreters.pxla emits them for both the jit and the
#: pjit/sharded paths; dispatch logs the per-primitive ones)
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")


class CompileBudgetExceeded(RuntimeError):
    """More XLA compiles than the budget allows inside a
    ``compile_budget`` block — something is retracing."""


@dataclasses.dataclass
class CompileEvents:
    """Live view of compiles seen so far inside a ``compile_budget``
    block. ``names`` holds the jitted-function names in compile order
    (every jnp op outside jit is itself a tiny jitted program, hence the
    ``match`` filter on the budget)."""

    match: Optional[str] = None
    names: List[str] = dataclasses.field(default_factory=list)

    @property
    def count(self) -> int:
        if self.match is None:
            return len(self.names)
        return sum(self.match in n for n in self.names)


class _CompileCapture(logging.Handler):
    def __init__(self, events: CompileEvents):
        super().__init__(level=logging.DEBUG)
        self.events = events

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if not msg.startswith("Compiling "):
            return
        name = (str(record.args[0]) if record.args
                else msg.split(" ", 2)[1])
        self.events.names.append(name)


@contextlib.contextmanager
def compile_budget(n: int, match: Optional[str] = None
                   ) -> Iterator[CompileEvents]:
    """Assert at most ``n`` XLA compiles (of functions whose name
    contains ``match``, when given) happen inside the block.

    ::

        superstep = exp.superstep_program(k, donate=True)
        with compile_budget(1, match="_superstep") as log:
            for _ in range(10):
                ts, stats, infos = superstep(ts, keys, t0)
        assert log.count == 1          # also enforced on exit

    Without ``match`` EVERY compile counts — including the tiny
    per-primitive programs bare jnp ops build outside jit — so pin a
    specific program by its (inner) function name. Raises
    ``CompileBudgetExceeded`` on block exit when the matched count
    exceeds ``n``; nested budgets compose (each keeps its own counter).
    """
    events = CompileEvents(match=match)
    handler = _CompileCapture(events)
    loggers = [logging.getLogger(nm) for nm in _COMPILE_LOGGERS]
    for lg in loggers:
        lg.addHandler(handler)
    try:
        with jax.log_compiles(True):
            yield events
    finally:
        for lg in loggers:
            lg.removeHandler(handler)
    if events.count > n:
        what = f" of {match!r}" if match else ""
        raise CompileBudgetExceeded(
            f"{events.count} XLA compiles{what} inside a "
            f"compile_budget({n}) block — something is retracing "
            f"(weak-typed scalar? shape wobble? changed static arg?); "
            f"compile order: {events.names}")


@contextlib.contextmanager
def no_transfer(host_to_device: bool = True) -> Iterator[None]:
    """Raise on any *implicit* device→host transfer (and, unless
    ``host_to_device=False``, any implicit host→device upload) inside
    the block. Explicit transfers — ``jax.device_put``,
    ``jax.device_get`` — stay allowed: the driver's cadence-boundary
    fetches are deliberate, it's the silent ones that stall the
    pipeline (the PR 2 priority-feedback ``device_get`` blocked every
    train iteration before it was made async)."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.transfer_guard_device_to_host("disallow"))
        if host_to_device:
            stack.enter_context(
                jax.transfer_guard_host_to_device("disallow"))
        yield
