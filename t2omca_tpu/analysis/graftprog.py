"""graftprog — auditor for the *compiled* hot programs.

``graftlint`` reads source; this module reads what XLA was actually
handed. The fused superstep (docs/SPEC.md §8) concentrates the whole
rollout→insert→train pipeline into a handful of long-lived programs, so
one silent regression — an undonated buffer, a weight baked in as a
constant, a stray bf16→f32 upcast — doubles device memory or FLOPs with
every unit test still green (the PR 2 ``NormState`` donate-twice bug
surfaced only by accident). Each
registered program (``analysis/registry.py``) is traced, lowered and —
for the donated hot programs — compiled, then checked at two levels:

**Jaxpr rules** (structural; exact):

========  ==============================================================
GP201     undonated donation: an argument the driver marks donated that
          XLA could NOT alias into an output (``input_output_aliases``
          miss) — the silent 2× device-memory bug class.
GP202     large array constant baked into the program: weights/buffers
          captured by closure instead of passed as arguments (≥ the
          ``const_bytes`` threshold) are duplicated into every
          executable and silently pin stale values.
GP203     dtype churn: ``convert_element_type`` UP from the configured
          compute dtype (bf16→f32/f64) inside the program — the
          accidental-upcast class that doubles FLOPs/bytes in the hot
          loop. Intentional upcasts (f32 loss math) are baselined by
          count.
GP204     host callback (``pure_callback``/``io_callback``/
          ``debug_callback``) reached a hot program: every dispatch now
          blocks on a host round-trip.
========  ==============================================================

**HLO budgets** (ratcheted against ``analysis/programs.json`` with
per-entry tolerances + justifications):

========  ==============================================================
GP300     program has no baseline entry (or the audit level changed) —
          new programs must be consciously baselined.
GP301     ``cost_analysis()`` FLOPs grew past the entry's tolerance.
GP302     ``cost_analysis()`` bytes-accessed grew past tolerance.
GP303     ``memory_analysis()`` peak new-allocation bytes (temp +
          output − alias) grew past tolerance (compiled entries only).
GP304     stable-HLO fingerprint drift: the program the driver builds
          is no longer the audited one — unintended retrace/aval drift
          (weak-typed scalar, shape wobble, changed static) or an
          unbaselined intentional change.
========  ==============================================================

Shrinkage (a metric now *below* tolerance, a baselined rule count no
longer reached) is reported as a stale note, never a failure — rerun
``--write-programs`` to tighten, exactly like the lint ratchet.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
import warnings
from typing import Dict, Iterator, List, Optional, Tuple

from .registry import AuditProgram, SkipProgram

#: rule id -> one-line summary (full catalog: docs/ANALYSIS.md)
GP_RULES: Dict[str, str] = {
    "GP201": "donated argument not aliased into any output (silent 2x memory)",
    "GP202": "large array constant baked into the program (closure capture)",
    "GP203": "convert_element_type up from the compute dtype (hidden upcast)",
    "GP204": "host callback inside a hot program",
    "GP300": "program missing from programs.json (unbaselined)",
    "GP301": "FLOPs grew past the baseline tolerance",
    "GP302": "bytes-accessed grew past the baseline tolerance",
    "GP303": "peak memory grew past the baseline tolerance",
    "GP304": "stable-HLO fingerprint drift (retrace/aval drift)",
}

#: GP202 threshold: constants at or above this many bytes are findings.
#: Small trace-time scalars/index tables are normal; a (256,256) f32
#: weight is 256 KiB — comfortably past this.
CONST_BYTES_DEFAULT = 16_384

#: default per-entry tolerances written for NEW programs.json entries
DEFAULT_TOLERANCE = {"flops": 0.10, "bytes_accessed": 0.10,
                     "peak_bytes": 0.25}

#: jax's lowering warning: "Some donated buffers were not usable:
#: float32[3], bfloat16[8,8].\nSee an explanation at ..." — group 1 is
#: the comma-separated aval list
_DONATION_WARNING_RE = re.compile(
    r"donated buffers were not usable: (.*?)\.?\n")


@dataclasses.dataclass(frozen=True)
class ProgFinding:
    """One auditor hit against a named program (the program takes the
    place of the lint finding's file:line — compiled programs have no
    lines)."""

    program: str
    rule: str
    message: str

    def format(self) -> str:
        return f"{self.program}: {self.rule} {self.message}"


@dataclasses.dataclass
class ProgramReport:
    """Everything measured about one registered program."""

    name: str
    fingerprint: str = ""
    level: str = "lowered"             # "lowered" | "compiled"
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    peak_bytes: Optional[float] = None     # compiled entries only
    #: rule -> per-occurrence detail messages (len == occurrence count)
    rule_details: Dict[str, List[str]] = dataclasses.field(
        default_factory=dict)
    skipped: Optional[str] = None      # SkipProgram reason

    def rule_count(self, rule: str) -> int:
        return len(self.rule_details.get(rule, []))


# ------------------------------------------------------------- jaxpr walks

def _iter_closed_jaxprs(closed) -> Iterator[object]:
    """Yield ``closed`` and every ClosedJaxpr nested in equation params
    (pjit bodies, scan/cond/while branches, custom_* rules), each once.

    ``pallas_call`` equations are NOT descended into: their params hold
    the kernel jaxpr plus block-spec/index-map machinery (grid mapping,
    closed-over tile constants) that describes device-kernel plumbing,
    not host-side program structure — walking it would misreport the
    kernel's internal f32 accumulator casts as GP203 churn and its
    block-spec tables as GP202 baked constants. A Pallas kernel is
    audited as one opaque device op, like any other XLA custom call;
    pinned by tests/test_graftprog.py."""
    from jax.extend.core import ClosedJaxpr
    seen = set()
    stack = [closed]
    while stack:
        cj = stack.pop()
        if id(cj) in seen:
            continue
        seen.add(id(cj))
        yield cj
        for eqn in cj.jaxpr.eqns:
            if "pallas" in eqn.primitive.name:
                continue                 # opaque device kernel (above)
            for v in eqn.params.values():
                if isinstance(v, ClosedJaxpr):
                    stack.append(v)
                elif isinstance(v, (tuple, list)):
                    stack.extend(u for u in v if isinstance(u, ClosedJaxpr))


def _const_findings(closed, const_bytes: int) -> List[str]:
    """GP202: array constants at/above the size threshold, anywhere in
    the program (each distinct buffer once)."""
    out, seen = [], set()
    for cj in _iter_closed_jaxprs(closed):
        for c in cj.consts:
            nbytes = getattr(c, "nbytes", 0)
            if id(c) in seen or nbytes < const_bytes:
                continue
            seen.add(id(c))
            shape = getattr(c, "shape", ())
            dtype = getattr(c, "dtype", "?")
            out.append(f"{dtype}{list(shape)} constant ({nbytes} bytes) "
                       f"baked into the program — pass it as an argument "
                       f"instead of capturing it by closure")
    return out


def _upcast_findings(closed, compute_dtype: str) -> List[str]:
    """GP203: convert_element_type from the compute dtype to a wider
    float anywhere in the program."""
    import jax.numpy as jnp
    import numpy as np
    try:
        cd = np.dtype(jnp.dtype(compute_dtype))
    except TypeError:
        return []
    out = []
    for cj in _iter_closed_jaxprs(closed):
        for eqn in cj.jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (src.dtype == cd
                    and jnp.issubdtype(dst.dtype, jnp.floating)
                    and dst.dtype.itemsize > cd.itemsize):
                out.append(f"{src.dtype}{list(src.shape)} -> {dst.dtype} "
                           f"upcast crossing the compute dtype "
                           f"({compute_dtype})")
    return out


def _callback_findings(closed) -> List[str]:
    """GP204: host-callback primitives anywhere in the program.
    ``pallas_call`` is explicitly exempt: it is a device kernel launch
    (Mosaic custom call on TPU, interpreter evaluation on CPU), not a
    host round-trip — name-matching must never misclassify it even if a
    future jax release renames the primitive toward the callback
    family."""
    out = []
    for cj in _iter_closed_jaxprs(closed):
        for eqn in cj.jaxpr.eqns:
            name = eqn.primitive.name
            if "pallas" in name:
                continue                 # device kernel, not a callback
            if "callback" in name:
                out.append(f"`{name}` inside the program: every dispatch "
                           f"blocks on a host round-trip")
    return out


# ----------------------------------------------------------------- metrics

def _cost_dict(stage) -> Dict[str, float]:
    """``cost_analysis()`` of a lowered or compiled stage: a dict, or
    ``None`` where the backend has no cost model for that stage (a
    lowered stage on TPU)."""
    return dict(stage.cost_analysis() or {})


def fingerprint_text(text: str) -> str:
    """Stable-HLO fingerprint: sha256 over the lowered module text with
    line-edge whitespace normalized (formatting churn across jaxlib
    point releases must not read as a program change)."""
    norm = "\n".join(l.strip() for l in text.splitlines() if l.strip())
    return hashlib.sha256(norm.encode()).hexdigest()[:16]


# ------------------------------------------------------------------- audit

def audit_program(name: str, prog: AuditProgram, compute_dtype: str,
                  const_bytes: int = CONST_BYTES_DEFAULT) -> ProgramReport:
    """Trace + lower (+ optionally compile) one registered program and
    run every jaxpr-level rule. Never *executes* the program."""
    report = ProgramReport(name=name)
    if prog.skip is not None:
        report.skipped = prog.skip
        return report
    try:
        traced = prog.fn.trace(*prog.args, **prog.kwargs)
    except SkipProgram as e:
        report.skipped = str(e)
        return report
    closed = traced.jaxpr

    details: Dict[str, List[str]] = {}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = traced.lower()
    text = lowered.as_text()
    # GP201 primary signal: donated flat leaves minus the args the
    # lowering accepted for donation — `tf.aliasing_output` (alias
    # resolved at lowering, unsharded programs) or `jax.buffer_donor`
    # (deferred to XLA, sharded programs); a REJECTED donation carries
    # neither marker. Counting the text is authoritative; jax's
    # "donated buffers were not usable" warning (mlir.py) is only used
    # for the per-leaf aval detail — the lowering cache suppresses it
    # on any re-lower of the same jit+avals in-process, so a
    # warning-only check silently reports clean on the second audit of
    # a genuinely-broken program.
    if prog.donate_argnums:
        import jax
        donated = jax.tree_util.tree_leaves(
            [prog.args[i] for i in prog.donate_argnums
             if i < len(prog.args)])
        missing = (len(donated) - text.count("tf.aliasing_output")
                   - text.count("jax.buffer_donor"))
        if missing > 0:
            unaliased: List[str] = []
            for w in caught:
                m = _DONATION_WARNING_RE.search(str(w.message))
                if m:
                    unaliased.extend(
                        re.findall(r"\w+\[[\d,]*\]", m.group(1)))
            if len(unaliased) == missing:
                details["GP201"] = [
                    f"donated leaf {aval} has no input_output_alias — "
                    f"XLA copies instead of updating in place (donated "
                    f"args: {prog.donate_argnums})" for aval in unaliased]
            else:        # cached lowering: counts only, avals unknown
                details["GP201"] = [
                    f"donated leaf {i + 1}/{missing} (of {len(donated)} "
                    f"donated) has no input_output_alias — XLA copies "
                    f"instead of updating in place (donated args: "
                    f"{prog.donate_argnums})" for i in range(missing)]

    if (d := _const_findings(closed, const_bytes)):
        details["GP202"] = d
    if (d := _upcast_findings(closed, compute_dtype)):
        details["GP203"] = d
    if (d := _callback_findings(closed)):
        details["GP204"] = d
    report.rule_details = details
    report.fingerprint = fingerprint_text(text)

    if prog.compile:
        compiled = lowered.compile()
        report.level = "compiled"
        cost = _cost_dict(compiled)
        try:
            mem = compiled.memory_analysis()
            report.peak_bytes = float(
                mem.temp_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes)
        except Exception:  # noqa: BLE001 — not every backend reports it
            report.peak_bytes = None
    else:
        cost = _cost_dict(lowered)
    report.flops = cost.get("flops")
    report.bytes_accessed = cost.get("bytes accessed")
    return report


def audit_registry(reg: Dict[str, AuditProgram], compute_dtype: str,
                   const_bytes: int = CONST_BYTES_DEFAULT,
                   only: Optional[List[str]] = None) -> List[ProgramReport]:
    """Audit every (or the ``only``-selected) registered program."""
    names = list(reg) if not only else [n for n in reg if n in set(only)]
    if only:
        missing = set(only) - set(reg)
        if missing:
            raise KeyError(f"unknown audit program(s): {sorted(missing)}; "
                           f"registered: {sorted(reg)}")
    return [audit_program(n, reg[n], compute_dtype, const_bytes)
            for n in names]


# ----------------------------------------------------------------- ratchet

def _over(value: Optional[float], base: Optional[float],
          tol: float) -> bool:
    return (value is not None and base is not None
            and value > base * (1.0 + tol))


def _under(value: Optional[float], base: Optional[float],
           tol: float) -> bool:
    return (value is not None and base is not None
            and value < base * (1.0 - tol))


def compare_reports(reports: List[ProgramReport],
                    baseline: Dict[str, dict]
                    ) -> Tuple[List[ProgFinding], List[str]]:
    """-> (new_findings, stale_notes), the lint-ratchet contract:
    regressions past each entry's tolerance fail, improvements and
    vanished entries only warn (rerun ``--write-programs`` to tighten).
    """
    findings: List[ProgFinding] = []
    stale: List[str] = []
    seen = set()
    for rep in reports:
        seen.add(rep.name)
        if rep.skipped is not None:
            stale.append(f"{rep.name}: skipped ({rep.skipped})")
            continue
        entry = baseline.get(rep.name)
        if entry is None:
            findings.append(ProgFinding(
                rep.name, "GP300",
                "no baseline entry in programs.json — audit it and "
                "accept with --write-programs (plus a justification)"))
            # rule findings still surface raw so the report is actionable
            for rule, msgs in sorted(rep.rule_details.items()):
                findings.extend(ProgFinding(rep.name, rule, m)
                                for m in msgs)
            continue
        if entry.get("level", "lowered") != rep.level:
            findings.append(ProgFinding(
                rep.name, "GP300",
                f"audit level changed ({entry.get('level')!r} -> "
                f"{rep.level!r}) — costs are incomparable; re-baseline "
                f"with --write-programs"))
            continue
        tol = {**DEFAULT_TOLERANCE, **entry.get("tolerance", {})}
        base_fp = entry.get("fingerprint", "")
        if base_fp and rep.fingerprint != base_fp:
            findings.append(ProgFinding(
                rep.name, "GP304",
                f"stable-HLO fingerprint {rep.fingerprint} != baselined "
                f"{base_fp} — the driver now builds a different program "
                f"(aval drift? weak-typed scalar? intended change? "
                f"accept with --write-programs)"))
        for rule in ("GP201", "GP202", "GP203", "GP204"):
            allowed = int(entry.get("rules", {}).get(rule, {})
                          .get("count", 0))
            msgs = rep.rule_details.get(rule, [])
            if len(msgs) > allowed:
                for m in msgs[allowed:]:
                    findings.append(ProgFinding(rep.name, rule, m))
                findings.append(ProgFinding(
                    rep.name, rule,
                    f"{len(msgs)} occurrence(s) > {allowed} baselined"))
            elif len(msgs) < allowed:
                stale.append(f"{rep.name}: {rule} count dropped "
                             f"{allowed} -> {len(msgs)} (fixed? rerun "
                             f"--write-programs to tighten)")
        for metric, rule in (("flops", "GP301"),
                             ("bytes_accessed", "GP302"),
                             ("peak_bytes", "GP303")):
            value = getattr(rep, metric)
            base = entry.get(metric)
            t = tol.get(metric, 0.10)
            if _over(value, base, t):
                findings.append(ProgFinding(
                    rep.name, rule,
                    f"{metric} {value:.0f} > baselined {base:.0f} "
                    f"(+{(value / base - 1) * 100:.1f}%, tolerance "
                    f"{t * 100:.0f}%) — justify and --write-programs, "
                    f"or fix the regression"))
            elif _under(value, base, t):
                stale.append(f"{rep.name}: {metric} improved "
                             f"{base:.0f} -> {value:.0f} (rerun "
                             f"--write-programs to tighten)")
    for name in sorted(set(baseline) - seen):
        stale.append(f"{name}: baselined program no longer registered")
    return findings, stale
