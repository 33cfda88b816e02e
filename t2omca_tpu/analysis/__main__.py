"""``python -m t2omca_tpu.analysis`` — the graftlint/graftrace/
graftprog/graftshard CLI.

Exit codes (the contract ``scripts/lint.sh``, ``scripts/t1.sh`` and the
tier-1 gate rely on): 0 = no new findings (baselined accepted findings
are fine), 1 = new findings (lint: ``path:line:col: RULE message``;
``--programs``/``--comms``: ``program: RULE message``), 2 =
usage/internal error. Stale baseline entries are warned about but never
fail — re-run with ``--write-baseline`` / ``--write-programs`` to
tighten the ratchet.

The default (lint) path is deliberately jax-free: pure AST, runs in
front of every test batch, must not pay backend startup. ``--programs``
is the opposite: it lowers (and for the donated hot programs compiles)
the registered XLA programs on a tiny CPU config — it forces
``JAX_PLATFORMS=cpu`` and a 4-CPU-device host platform so the audited
programs (and their checked-in fingerprints, ``analysis/programs.json``)
are identical on every machine, TPU hosts included. ``--comms`` is the
third level (graftshard, docs/ANALYSIS.md): it compiles the MESH-placed
registry programs under their fixed audit meshes and ratchets the
collective census + sharding rules (GP4xx) plus the registered
cross-mesh transfers against the same baseline file.
"""

from __future__ import annotations

import argparse
import os
import sys
from collections import Counter
from pathlib import Path

from .baseline import (DEFAULT_BASELINE, DEFAULT_PROGRAMS, diff_baseline,
                       filter_family, load_baseline, load_programs,
                       save_baseline, save_comms, save_programs)
from .graftlint import RULES, lint_package
from .graftrace import GT_RULES, trace_package


def _pin_cpu_platform() -> None:
    """Pin the audit to the canonical platform BEFORE jax initializes:
    CPU backend, and at least the 4 host devices the fixed audit meshes
    need (the dp program's 2-device data mesh, and the sebulba
    actor_step/learner_step programs' 2+2-device split). The checked-in
    fingerprints/budgets are for exactly this platform — auditing on
    whatever backend happens to be attached would produce fiction. The
    ``*_pallas`` entries are the interpret lowering for the same reason,
    so the pin also turns the kernels' interpreter mode on. A no-op
    when jax is already imported (in-process callers — the tests — own
    their platform and their interpret flag, tests/conftest.py)."""
    if "jax" in sys.modules:
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    from ..kernels import attention
    attention.INTERPRET = True


def _refuse_small_host(jax, registry, tool: str) -> int:
    """Baseline writes need every fixed audit mesh buildable: on a
    host exposing fewer devices than the largest registered mesh the
    4-device entries (pop_dp, sebulba, dp×mp) would register as skips
    and a rewrite would silently carry stale sections for them forever
    (the ``--only`` refusal's silent-shrink bug class, PR 5). 0 = ok."""
    need = registry.required_audit_devices()
    have = len(jax.devices())
    if have < need:
        print(f"{tool}: error: baseline writes need the full fixed "
              f"audit meshes: {need} host devices, have {have} (hint: "
              f"XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{need}; unset any conflicting XLA_FLAGS)",
              file=sys.stderr)
        return 2
    return 0


def _comms_main(args) -> int:
    """The ``--comms`` audit level: collective census + sharding rules
    (GP4xx) of the mesh-placed registry programs and the registered
    cross-mesh transfers — graftshard (docs/ANALYSIS.md)."""
    if args.write_programs and args.only:
        print("graftshard: error: --write-programs re-baselines the "
              "FULL comms set; it cannot be combined with --only",
              file=sys.stderr)
        return 2
    _pin_cpu_platform()
    try:
        from . import graftshard, registry
        reg = registry.collect_default_programs()
        for extra in args.program_module:
            for name, prog in registry.load_programs_from(extra).items():
                reg[name] = prog
        reg = {n: p for n, p in reg.items()
               if graftshard.is_mesh_program(p)}
        transfers = registry.collect_transfer_audits()
    except Exception as e:  # noqa: BLE001 — CLI boundary
        print(f"graftshard: error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    if args.only:
        unknown = [n for n in args.only
                   if n not in reg and n not in transfers]
        if unknown:
            print(f"graftshard: error: unknown mesh program(s) "
                  f"{', '.join(sorted(unknown))} (known: "
                  f"{', '.join(sorted(list(reg) + list(transfers)))})",
                  file=sys.stderr)
            return 2
        reg = {n: p for n, p in reg.items() if n in args.only}
        transfers = {n: t for n, t in transfers.items()
                     if n in args.only}
    if args.list_programs:
        for name, prog in reg.items():
            what = (f"SKIP ({prog.skip})" if prog.skip is not None else
                    prog.description)
            print(f"{name:16s} {'compile':8s} {what}")
        for name, ta in transfers.items():
            what = (f"SKIP ({ta.skip})" if ta.skip is not None else
                    ta.description)
            print(f"{name:16s} {'transfer':8s} {what}")
        return 0

    # resolve the old baseline BEFORE the compile-heavy audit — the
    # _programs_main fast-exit-2 rationale
    old = None
    if args.write_programs:
        try:
            old = load_programs(args.programs_baseline)
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"graftshard: error: unreadable baseline "
                  f"{args.programs_baseline}: {e}", file=sys.stderr)
            return 2

    import jax
    if args.write_programs and (rc := _refuse_small_host(
            jax, registry, "graftshard")):
        return rc
    try:
        reports = graftshard.audit_comms_registry(reg)
        treports = [graftshard.audit_transfer(n, t)
                    for n, t in transfers.items()]
    except Exception as e:  # noqa: BLE001 — CLI boundary
        print(f"graftshard: error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.write_programs:
        save_comms(args.programs_baseline, reports, treports,
                   platform=jax.default_backend(), old=old or {})
        n = sum(r.skipped is None for r in reports)
        nt = sum(r.skipped is None for r in treports)
        print(f"graftshard: wrote {n} comms section(s) + {nt} "
              f"transfer entr{'y' if nt == 1 else 'ies'} to "
              f"{args.programs_baseline}")
        return 0

    if args.no_baseline:
        # raw audit: only the structural rules mean anything without a
        # baseline (GP401/402 are ratchets, like GP300-302)
        findings = graftshard.raw_findings(reports, treports)
        stale = [f"{r.name}: skipped ({r.skipped})"
                 for r in list(reports) + list(treports)
                 if r.skipped is not None]
    else:
        try:
            base = load_programs(args.programs_baseline)
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"graftshard: error: unreadable baseline "
                  f"{args.programs_baseline}: {e}", file=sys.stderr)
            return 2
        platform = jax.default_backend()
        if base["platform"] and base["platform"] != platform:
            print(f"graftshard: warning: baseline is for platform "
                  f"{base['platform']!r}, running on {platform!r} — "
                  f"the comms census is not comparable, skipping the "
                  f"ratchet (pin JAX_PLATFORMS=cpu)", file=sys.stderr)
            return 0
        findings, stale = graftshard.compare_comms(reports, treports,
                                                   base)
    for f in findings:
        print(f.format())
    for note in stale:
        print(f"graftshard: warning: stale/skip: {note}",
              file=sys.stderr)
    per_rule = Counter(f.rule for f in findings)
    summary = ", ".join(f"{r}x{c}" if c > 1 else r
                        for r, c in sorted(per_rule.items()))
    n_skip = sum(r.skipped is not None
                 for r in list(reports) + list(treports))
    print(f"graftshard: {len(reports)} mesh programs + {len(treports)} "
          f"transfer(s) audited"
          + (f" ({n_skip} skipped)" if n_skip else "")
          + f", {len(findings)} new finding(s)"
          + (f": {summary}" if summary else ""))
    return 1 if findings else 0


def _programs_main(args) -> int:
    if args.write_programs and args.only:
        # save_programs writes exactly the audited set — a partial
        # audit would silently drop every unselected entry
        print("graftprog: error: --write-programs re-baselines the FULL "
              "program set; it cannot be combined with --only",
              file=sys.stderr)
        return 2
    _pin_cpu_platform()
    try:
        from . import graftprog, registry
        reg = registry.collect_default_programs()
        for extra in args.program_module:
            for name, prog in registry.load_programs_from(extra).items():
                reg[name] = prog
    except Exception as e:  # noqa: BLE001 — CLI boundary
        print(f"graftprog: error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    if args.list_programs:
        for name, prog in reg.items():
            what = (f"SKIP ({prog.skip})" if prog.skip is not None else
                    prog.description)
            print(f"{name:16s} {'compile' if prog.compile else 'lower':8s}"
                  f" {what}")
        return 0

    # resolve the old baseline BEFORE the (minutes-long on a loaded
    # box) audit: a corrupt/version-mismatched programs.json must be a
    # fast exit-2 usage error, not a post-audit traceback
    old = None
    if args.write_programs and not args.no_baseline:
        try:
            old = load_programs(args.programs_baseline)
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"graftprog: error: unreadable baseline "
                  f"{args.programs_baseline}: {e}", file=sys.stderr)
            return 2

    import jax
    if args.write_programs and (rc := _refuse_small_host(
            jax, registry, "graftprog")):
        return rc
    compute_dtype = registry.audit_context().compute_dtype
    try:
        reports = graftprog.audit_registry(
            reg, compute_dtype, only=args.only or None)
    except KeyError as e:
        print(f"graftprog: error: {e}", file=sys.stderr)
        return 2

    if args.write_programs:
        save_programs(args.programs_baseline, reports,
                      platform=jax.default_backend(), old=old or {})
        n = sum(r.skipped is None for r in reports)
        print(f"graftprog: wrote {n} program entries to "
              f"{args.programs_baseline}")
        return 0

    if args.no_baseline:
        # raw audit: every rule occurrence is a finding, budgets skipped
        findings = [graftprog.ProgFinding(r.name, rule, m)
                    for r in reports if r.skipped is None
                    for rule, msgs in sorted(r.rule_details.items())
                    for m in msgs]
        stale = [f"{r.name}: skipped ({r.skipped})"
                 for r in reports if r.skipped is not None]
    else:
        try:
            base = load_programs(args.programs_baseline)
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"graftprog: error: unreadable baseline "
                  f"{args.programs_baseline}: {e}", file=sys.stderr)
            return 2
        platform = jax.default_backend()
        if base["platform"] and base["platform"] != platform:
            print(f"graftprog: warning: baseline is for platform "
                  f"{base['platform']!r}, running on {platform!r} — "
                  f"budgets/fingerprints are not comparable, skipping "
                  f"the ratchet (pin JAX_PLATFORMS=cpu)",
                  file=sys.stderr)
            return 0
        findings, stale = graftprog.compare_reports(reports,
                                                    base["programs"])
    for f in findings:
        print(f.format())
    for note in stale:
        print(f"graftprog: warning: stale/skip: {note}", file=sys.stderr)
    per_rule = Counter(f.rule for f in findings)
    summary = ", ".join(f"{r}x{c}" if c > 1 else r
                        for r, c in sorted(per_rule.items()))
    n_skip = sum(r.skipped is not None for r in reports)
    print(f"graftprog: {len(reports)} programs audited"
          + (f" ({n_skip} skipped)" if n_skip else "")
          + f", {len(findings)} new finding(s)"
          + (f": {summary}" if summary else ""))
    return 1 if findings else 0


def _ratchet_main(args, tool: str, family: str, run, root) -> int:
    """Shared source-ratchet leg: lint (GL) and threads (GT) differ only
    in the analyzer and the baseline family they own. ``run(root,
    paths)`` -> findings; exit 0/1/2 per the CLI contract."""
    try:
        findings = run(root, args.paths or None)
    except (OSError, SyntaxError, ValueError) as e:
        print(f"{tool}: error: {e}", file=sys.stderr)
        return 2

    try:
        full = {} if args.no_baseline else load_baseline(args.baseline)
    except (OSError, ValueError, KeyError, TypeError) as e:
        print(f"{tool}: error: unreadable baseline {args.baseline}: "
              f"{e}", file=sys.stderr)
        return 2
    if args.write_baseline:
        # scoped rewrite: the OTHER family's entries are carried verbatim
        save_baseline(args.baseline, findings, full, family=family)
        print(f"{tool}: wrote {len(set(f.key() for f in findings))} "
              f"accepted keys to {args.baseline}")
        return 0

    baseline = filter_family(full, family)
    new, stale = diff_baseline(findings, baseline)
    for f in new:
        print(f.format())
        print(f"    {f.code}")
    for key in stale:
        rule, path, code = key
        print(f"{tool}: warning: stale baseline entry {rule} {path}: "
              f"{code!r} (fixed? run --write-baseline to tighten)",
              file=sys.stderr)
    n_base = len(findings) - len(new)
    per_rule = Counter(f.rule for f in new)
    summary = ", ".join(f"{r}x{c}" if c > 1 else r
                        for r, c in sorted(per_rule.items()))
    print(f"{tool}: {len(findings)} findings "
          f"({n_base} baselined, {len(new)} new"
          + (f": {summary}" if summary else "") + ")")
    return 1 if new else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m t2omca_tpu.analysis",
        description="graftlint: JAX tracing-hygiene static analysis "
                    "(rule catalog: docs/ANALYSIS.md)")
    parser.add_argument(
        "paths", nargs="*", type=Path,
        help="files/directories to lint (default: the t2omca_tpu package)")
    parser.add_argument(
        "--root", type=Path, default=None,
        help="repo root findings are reported relative to (default: the "
             "package's parent directory)")
    parser.add_argument(
        "--baseline", type=Path, default=DEFAULT_BASELINE,
        help="accepted-findings file (default: analysis/baseline.json)")
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="report every finding as new (ignore the baseline)")
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="accept the current finding set as the baseline (keeps "
             "existing justifications; new keys get a TODO marker)")
    parser.add_argument(
        "--threads", action="store_true",
        help="run the graftrace thread-topology & lock-discipline "
             "audit (GT1xx) instead of the tracing lint — same "
             "baseline file, same exit-code contract, still jax-free")
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit")
    prog_group = parser.add_argument_group(
        "compiled-program audit (graftprog, docs/ANALYSIS.md)")
    prog_group.add_argument(
        "--programs", action="store_true",
        help="audit the registered compiled programs (GP rules + HLO "
             "budgets) instead of linting source")
    prog_group.add_argument(
        "--comms", action="store_true",
        help="audit the communication structure of the mesh-placed "
             "programs: collective census + GP4xx sharding rules "
             "(graftshard; reuses --programs-baseline, "
             "--write-programs, --program-module, --only)")
    prog_group.add_argument(
        "--programs-baseline", type=Path, default=DEFAULT_PROGRAMS,
        help="program budgets/fingerprints file "
             "(default: analysis/programs.json)")
    prog_group.add_argument(
        "--write-programs", action="store_true",
        help="accept the measured budgets/fingerprints as the baseline "
             "(keeps justifications + tolerances; new entries get TODO)")
    prog_group.add_argument(
        "--program-module", action="append", default=[], metavar="MOD",
        help="extra module (dotted path or .py file) whose "
             "register_audit_programs(ctx) adds programs — the seeded-"
             "regression test entry point; repeatable")
    prog_group.add_argument(
        "--only", action="append", default=[], metavar="NAME",
        help="audit only the named program(s); repeatable")
    prog_group.add_argument(
        "--list-programs", action="store_true",
        help="print the registered program names and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        from .graftprog import GP_RULES
        from .graftshard import GP4_RULES
        for rule, summary in sorted({**RULES, **GT_RULES, **GP_RULES,
                                     **GP4_RULES}.items()):
            print(f"{rule}  {summary}")
        return 0
    if args.comms:
        return _comms_main(args)
    # the program-audit flags imply --programs: falling through to the
    # lint path would silently ignore them (a bare `--write-programs`
    # after an intended change would exit 0 having written nothing,
    # and the next gate run would fail GP304 with no hint why)
    if (args.programs or args.list_programs or args.write_programs
            or args.program_module or args.only):
        return _programs_main(args)

    root = args.root or Path(__file__).resolve().parents[2]
    if args.threads:
        return _ratchet_main(args, "graftrace", "GT", trace_package,
                             root)
    return _ratchet_main(args, "graftlint", "GL", lint_package, root)


if __name__ == "__main__":
    sys.exit(main())
