#!/usr/bin/env bash
# Tier-1 verify gate — the ROADMAP.md pytest command, fronted by the two
# static/compiled analysis preludes. Run from the repo root:
# `bash scripts/t1.sh`. Prints DOTS_PASSED=<n> and exits with pytest's
# status.
#
# Prelude 1 (graftlint, ~1 s): AST lint over the package; any NEW
# finding fails the gate before backend startup.
# Prelude 1a (graftrace, ~1 s): the concurrency sibling — GT1xx
# thread-topology / lock-discipline audit, same ratchet contract.
# Prelude 2 (graftprog, ~45 s budgeted at 240 s for a loaded box):
# lower/compile the registered hot programs and ratchet their
# donation/dtype/constant rules + HLO budgets + fingerprints against
# t2omca_tpu/analysis/programs.json. An audit that hangs is a gate
# failure (timeout exit 124), not a silent skip.
#
# Both preludes pipe through tee for the log — hence pipefail +
# ${PIPESTATUS[0]}: without them tee's exit 0 swallows the gate status.
set -o pipefail
cd "$(dirname "$0")/.." || exit 2
bash scripts/lint.sh 2>&1 | tee /tmp/_t1_lint.log; lrc=${PIPESTATUS[0]}
[ $lrc -ne 0 ] && { [ $lrc -eq 1 ] && echo "graftlint gate failed (new findings above; docs/ANALYSIS.md)" || echo "graftlint internal error (exit $lrc; docs/ANALYSIS.md)"; exit 1; }
# Prelude 1a (graftrace, ~1 s, jax-free): thread-topology &
# lock-discipline audit (GT1xx) over the host concurrency plane —
# watchdog/fleet/sebulba/pulse threads. Same ratchet file, same
# contract: any NEW finding fails the gate before backend startup.
timeout -k 5 60 bash scripts/lint.sh --threads 2>&1 | tee /tmp/_t1_threads.log; trc=${PIPESTATUS[0]}
[ $trc -ne 0 ] && { [ $trc -eq 1 ] && echo "graftrace gate failed (new findings above; docs/ANALYSIS.md)" || echo "graftrace internal error (exit $trc; docs/ANALYSIS.md)"; exit 1; }
# Prelude 1c (obs learning, ~1 s, jax-free): the graftsight learning-
# health CLI over the seeded fixture run dir must exit 0 and render the
# health table + detector verdict — the post-mortem learning read must
# not rot (docs/OBSERVABILITY.md §6).
timeout -k 5 60 python -m t2omca_tpu.obs learning tests/fixtures_sight_run 2>&1 | tee /tmp/_t1_sight.log; slc=${PIPESTATUS[0]}
[ $slc -ne 0 ] && { echo "obs learning smoke failed (exit $slc; docs/OBSERVABILITY.md §6)"; exit 1; }
grep -q "learning health" /tmp/_t1_sight.log || { echo "obs learning smoke: health table missing (docs/OBSERVABILITY.md §6)"; exit 1; }
grep -q "TRIPPED" /tmp/_t1_sight.log || { echo "obs learning smoke: seeded detector verdict missing (docs/OBSERVABILITY.md §6)"; exit 1; }
# JAX_PLATFORMS pinned HERE, not just inside the CLI: the CLI's own pin
# is a setdefault, and a preset JAX_PLATFORMS=tpu would otherwise make
# the audit hit the platform-mismatch branch (warn + exit 0) — a silent
# gate no-op
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m t2omca_tpu.analysis --programs 2>&1 | tee /tmp/_t1_prog.log; prc=${PIPESTATUS[0]}
[ $prc -ne 0 ] && { [ $prc -eq 124 ] && echo "graftprog gate timed out (240s budget; docs/ANALYSIS.md)" || echo "graftprog gate failed (exit $prc; docs/ANALYSIS.md)"; exit 1; }
# Prelude 3 (graftshard, ~60 s budgeted at 180 s): compile the
# mesh-placed programs under the fixed audit meshes and ratchet their
# collective census + sharding rules (GP4xx) + the params.sync transfer
# table against the same programs.json. Same contract: a comms audit
# that hangs is a gate failure (timeout exit 124), never a silent skip.
timeout -k 10 180 env JAX_PLATFORMS=cpu python -m t2omca_tpu.analysis --comms 2>&1 | tee /tmp/_t1_comms.log; crc=${PIPESTATUS[0]}
[ $crc -ne 0 ] && { [ $crc -eq 124 ] && echo "graftshard gate timed out (180s budget; docs/ANALYSIS.md)" || echo "graftshard gate failed (exit $crc; docs/ANALYSIS.md)"; exit 1; }
rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
