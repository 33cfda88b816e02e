#!/usr/bin/env bash
# Chaos soak runner (docs/RESILIENCE.md §5): cycle the fault-injection
# battery — hang at dispatch.superstep, transient + persistent dispatch
# failures, flaky checkpoint gather, crash mid-checkpoint, SIGTERM — for
# N iterations against the real driver on the CPU backend, asserting
# after every scenario that the run ended in a RESUMABLE state (a
# verify_checkpoint-passing checkpoint a fresh driver carries to t_max).
#
# The serve scenario (tests/test_fleet.py, docs/SERVING.md §fleet) runs
# in the same battery: an engine killed mid-burst plus an injected
# dispatch hang must end with ZERO hung requests (every admitted
# request completes or resolves SHED/deadline/error) and a RESUMABLE
# fleet — the quarantined engines restarted, rejoined, and serving a
# fresh request.
#
# The graftmorph elastic scenarios (tests/test_elastic.py,
# docs/RESILIENCE.md §6) cycle too: a failed preemption barrier must
# degrade to the per-host shard save and resume elastically — the
# coordinated-preemption exit path soaks alongside the dispatch
# faults it shares machinery with. The multi-host leg (chaos-marked in
# tests/test_multihost.py) SIGKILLs one of two real gloo processes and
# asserts the survivor exits 0 with a resumable checkpoint.
#
# Usage: bash scripts/chaos.sh [N]      (default N=3)
#
# Slow by design (each driver scenario is a full run() with fresh
# compiles; the serve scenario, test_fleet_chaos_acceptance, exports an
# artifact and drives the fleet with open-loop traffic under a fault
# schedule) — this is the soak gate for resilience PRs, not part
# of the tier-1 budget (tier-1 excludes them via `-m 'not slow'`).
set -o pipefail
N=${1:-3}
cd "$(dirname "$0")/.." || exit 2
for i in $(seq 1 "$N"); do
  echo "== chaos cycle $i/$N =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py tests/test_fleet.py \
    tests/test_elastic.py tests/test_multihost.py \
    -m chaos -q -p no:cacheprovider -p no:randomly || {
      echo "chaos cycle $i/$N FAILED — a fault scenario left the run "
      echo "unresumable (see the assertion above; docs/RESILIENCE.md §5)"
      exit 1
    }
done
echo "chaos soak passed: $N cycle(s), every scenario ended resumable"
