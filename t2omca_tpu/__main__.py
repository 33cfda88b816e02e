"""CLI: ``python -m t2omca_tpu <train|evaluate|benchmark> [--config f]
[key=value ...]``.

Replaces the reference's sacred entry (M14): subcommands instead of sacred
command-line magic, ``key=value`` / ``section.key=value`` overrides instead
of ``with config.yaml``. Examples::

    python -m t2omca_tpu train t_max=50000 env_args.agv_num=16
    python -m t2omca_tpu evaluate checkpoint_path=results/models/<token>
    python -m t2omca_tpu benchmark checkpoint_path=... test_nepisode=32
"""

from __future__ import annotations

import argparse
import sys

from .config import load_config
from .run import run
from .utils.logging import Logger


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="t2omca_tpu")
    parser.add_argument("command",
                        choices=["train", "evaluate", "benchmark"])
    parser.add_argument("--config", default=None,
                        help="YAML/JSON config file")
    parser.add_argument("overrides", nargs="*",
                        help="key=value config overrides")
    args = parser.parse_args(argv)

    # multi-host (DCN) leg: no-op unless a coordinator topology is
    # configured in the environment (parallel/distributed.py)
    from .parallel import maybe_initialize_distributed
    maybe_initialize_distributed()
    from .utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = load_config(args.config, tuple(args.overrides))
    if args.command in ("evaluate", "benchmark"):
        cfg = cfg.replace(evaluate=True)
    if args.command == "benchmark":
        cfg = cfg.replace(benchmark_mode=True)
    run(cfg, Logger())
    return 0


if __name__ == "__main__":
    sys.exit(main())
