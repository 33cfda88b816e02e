"""``python -m t2omca_tpu.obs`` — the graftscope/graftpulse CLI.

Subcommands:

``report <run_dir>``
    Join the run's span telemetry (``spans.jsonl``) against
    graftprog's FLOPs/bytes budgets (``analysis/programs.json``) into
    the per-program table (docs/OBSERVABILITY.md). Exit 0 = report
    printed, 2 = usage error. Degraded inputs render instead of
    raising: a torn final JSONL line (killed run) is skipped with a
    warning, and a run dir holding only a ``flight_recorder.json``
    reports from the flight tail.

``learning <run_dir>``
    The graftsight learning-health report (docs/OBSERVABILITY.md §6):
    per-module gradient norms, PER health, attention entropies, value
    histograms, detector verdicts and per-scenario-slice learning
    curves from the run's ``metrics.jsonl`` (tolerant reader — torn
    tails from killed runs are skipped with a warning). Answers "is
    this run learning?" post-mortem.

Both are deliberately jax-free — the post-mortem host may not be able
to initialize a backend at all.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m t2omca_tpu.obs",
        description="graftscope/graftpulse: run telemetry tools "
                    "(docs/OBSERVABILITY.md)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    rep = sub.add_parser(
        "report", help="per-program wall-time/budget report for a "
                       "recorded run")
    rep.add_argument("run_dir",
                     help="results directory of a run recorded with "
                          "obs.enabled=true (holds spans.jsonl)")
    rep.add_argument("--programs-json", default=None,
                     help="graftprog budgets to join against "
                          "(default: analysis/programs.json)")
    ln = sub.add_parser(
        "learning", help="graftsight learning-health report for a "
                         "recorded run (docs/OBSERVABILITY.md §6)")
    ln.add_argument("run_dir",
                    help="results directory of a run (holds "
                         "metrics.jsonl; obs.sight.enabled adds the "
                         "learning-dynamics keys)")
    args = parser.parse_args(argv)
    if args.cmd == "learning":
        from .sight import learning_main
        return learning_main(args.run_dir)
    if args.cmd == "report":
        from .report import report_main
        return report_main(args.run_dir, args.programs_json)
    parser.error(f"unknown command {args.cmd!r}")
    return 2


if __name__ == "__main__":
    sys.exit(main())
