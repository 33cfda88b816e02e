"""``python -m t2omca_tpu.serve`` — the serving CLI.

Subcommands::

    # export a training checkpoint as a serving artifact
    python -m t2omca_tpu.serve export results/models/<token> \
        --config configs/serve_smoke.yaml --out /path/to/artifact \
        [--buckets 1,2,4,8] [--dtypes float32,bfloat16] [--load-step N] \
        [--no-blobs] [key=value overrides ...]

    # inspect an artifact
    python -m t2omca_tpu.serve info /path/to/artifact

    # hot-refresh dry run: would a live fleet accept this checkpoint?
    # (host-side re-fold + per-bucket program fingerprint check —
    # exactly what ServeFleet.refresh runs before any engine is
    # touched; a live fleet arms the real thing via its
    # <artifact>/FLEET_REFRESH trigger file)
    python -m t2omca_tpu.serve refresh /path/to/artifact <ckpt_dir> \
        [--dtype float32]

Exit codes: 0 ok (export written / refresh compatible), 2 usage error
(missing checkpoint / bad artifact / refresh REFUSED). The export
config must be the TRAINING run's config (the exporter rebuilds the
exact MAC from it and shape-validates the checkpoint against it; a
mismatch is a hard error, not a silent re-init).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _parse_int_list(s: str):
    try:
        return [int(x) for x in s.split(",") if x.strip()]
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected a comma-separated int list, got {s!r}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m t2omca_tpu.serve",
        description="AOT policy-serving artifacts (docs/SERVING.md)")
    sub = parser.add_subparsers(dest="command", required=True)

    exp = sub.add_parser("export",
                         help="export a checkpoint as a serving artifact")
    exp.add_argument("ckpt_dir",
                     help="checkpoint directory (the training run's "
                          "results/models/<token>)")
    exp.add_argument("--config", default=None,
                     help="the TRAINING config (YAML/JSON)")
    exp.add_argument("--out", default=None,
                     help="artifact output dir (default: <ckpt_dir>/serve)")
    exp.add_argument("--buckets", type=_parse_int_list, default=None,
                     metavar="1,2,4,...",
                     help="batch buckets (default: powers of 2 up to 64)")
    exp.add_argument("--dtypes", default="float32,bfloat16",
                     help="param variants to write (comma-separated)")
    exp.add_argument("--load-step", type=int, default=0,
                     help="checkpoint step to export (0 = newest)")
    exp.add_argument("--no-blobs", action="store_true",
                     help="skip the per-bucket jax.export program blobs "
                          "(the front-end then rebuilds from the config)")

    info = sub.add_parser("info", help="print an artifact's meta summary")
    info.add_argument("artifact_dir")

    ref = sub.add_parser("refresh",
                         help="hot-refresh dry run: fold a checkpoint "
                              "and fingerprint-check it against an "
                              "artifact's programs")
    ref.add_argument("artifact_dir")
    ref.add_argument("ckpt_dir",
                     help="checkpoint directory holding the NEW params")
    ref.add_argument("--dtype", choices=("float32", "bfloat16"),
                     default="float32",
                     help="the serving param variant to check")

    # key=value overrides ride as unrecognized trailing args (argparse
    # cannot mix a trailing nargs="*" positional with the option flags
    # above) — validate them here instead
    args, extra = parser.parse_known_args(argv)
    overrides = [a for a in extra if "=" in a and not a.startswith("-")]
    bad = [a for a in extra if a not in overrides]
    if bad:
        parser.error(f"unrecognized arguments: {' '.join(bad)}")
    if args.command != "export" and overrides:
        parser.error("key=value overrides only apply to `export`")
    args.overrides = overrides

    if args.command == "info":
        meta_path = os.path.join(args.artifact_dir, "meta.json")
        try:
            with open(meta_path) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            print(f"serve: error: unreadable artifact meta {meta_path}: "
                  f"{e}", file=sys.stderr)
            return 2
        ck = meta.get("checkpoint", {})
        print(f"format v{meta.get('format')} — checkpoint "
              f"{ck.get('dir')} @ t_env={ck.get('t_env')}")
        print(f"model: {meta.get('n_agents')} agents x "
              f"{meta.get('n_actions')} actions, obs {meta.get('obs_dim')}"
              f", emb {meta.get('emb')}, "
              f"folded={meta.get('folded')}")
        print(f"buckets: {meta.get('buckets')}")
        for dt, p in sorted(meta.get("params", {}).items()):
            n_prog = len(meta.get("programs", {}).get(dt, {}))
            print(f"params[{dt}]: {p.get('bytes')} bytes "
                  f"sha256={str(p.get('sha256'))[:12]}… "
                  f"({n_prog} exported programs)")
        prov = meta.get("provenance", {})
        print(f"provenance: git={str(prov.get('git_commit'))[:12]} "
              f"jax={prov.get('jax')} backend={prov.get('backend')}")
        return 0

    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.command == "refresh":
        if not os.path.isfile(os.path.join(args.artifact_dir,
                                           "meta.json")):
            print(f"serve: error: {args.artifact_dir} is not a serve "
                  f"artifact (no meta.json)", file=sys.stderr)
            return 2
        from .fleet import check_refresh
        out = check_refresh(args.artifact_dir, args.ckpt_dir,
                            dtype=args.dtype)
        if out["status"] != "compatible":
            print(f"serve: refresh REFUSED: {out.get('reason')}",
                  file=sys.stderr)
            return 2
        print(f"serve: refresh compatible (checkpoint "
              f"t_env={out.get('t_env')}, {out.get('buckets_checked')} "
              f"bucket programs fingerprint-checked)")
        return 0

    # ---- export ----
    from ..config import load_config
    try:
        cfg = load_config(args.config, tuple(args.overrides))
    except (OSError, KeyError, ValueError) as e:
        print(f"serve: error: bad config: {e}", file=sys.stderr)
        return 2
    from .export import DEFAULT_BUCKETS, PARAM_DTYPES, export_artifact
    out = args.out or os.path.join(args.ckpt_dir, "serve")
    try:
        meta = export_artifact(
            cfg, args.ckpt_dir, out,
            buckets=args.buckets or DEFAULT_BUCKETS,
            dtypes=tuple(d for d in args.dtypes.split(",") if d)
            or PARAM_DTYPES,
            load_step=args.load_step,
            export_blobs=not args.no_blobs)
    except (FileNotFoundError, ValueError) as e:
        print(f"serve: error: {e}", file=sys.stderr)
        return 2
    ck = meta["checkpoint"]
    print(f"serve: artifact written to {out} (checkpoint "
          f"t_env={ck['t_env']}, buckets {meta['buckets']}, "
          f"variants {sorted(meta['params'])})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
