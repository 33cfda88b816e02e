"""Terminal-info stat aggregation — the reference runner's contract.

Re-creates ``cur_stats`` / ``cur_returns`` semantics of
``/root/reference/parallel_runner.py:193-231`` exactly:

* only the info dict of the TERMINAL step of each episode enters the stats
  (the reference appends ``data["info"]`` to ``final_env_infos`` when an env
  reports ``terminated``, ``:168-170``);
* values are summed across envs AND across rollouts until a flush, with
  ``n_episodes`` accumulating ``batch_size`` per rollout (``:226-228``);
* a flush logs ``<k>_mean = Σv / n_episodes`` plus ``return_mean`` over the
  accumulated per-episode returns, then clears (``:222-231``);
* test stats flush only when exactly the rounded ``test_nepisode`` quota of
  returns has accumulated (quirk Q10, ``:212-214``); train stats flush on the
  ``runner_log_interval`` cadence with ``epsilon`` logged alongside
  (``:215-219``).
"""

from __future__ import annotations

from collections import defaultdict
from typing import List

import jax
import numpy as np

#: info keys present in the reference env's terminal-step info dict
#: (``/root/reference/environment_multi_mec.py:343-364``), plus the
#: graftworld deadline-miss rate (envs/mec_offload.StepInfo — the
#: per-slice generalization metric, docs/ENVS.md)
TERMINAL_INFO_KEYS = (
    "reward", "delay_reward", "overtime_penalty",
    "channel_utilization_rate", "conflict_ratio", "episode_limit",
    "task_completion_rate", "task_completion_delay",
    "deadline_miss_rate",
)

#: per-slice keys worth a slice breakdown (graftworld per-scenario
#: eval): return + the generalization-relevant rates — the full
#: TERMINAL set per slice would triple the metric stream for keys
#: (epsilon-like constants, episode_limit) that cannot differ by slice
SLICE_KEYS = ("conflict_ratio", "task_completion_rate",
              "deadline_miss_rate")

#: per-member keys emitted under the graftpop ``pop<i>_*`` rows
#: (docs/POPULATION.md): the experiment-comparison metrics — per-member
#: return rides separately as ``pop<i>_return_mean``. Same restraint as
#: SLICE_KEYS: the full TERMINAL set × P would flood the stream with
#: rows that cannot differ usefully by member.
POP_MEMBER_KEYS = ("task_completion_rate", "conflict_ratio",
                   "deadline_miss_rate")


class StatsAccumulator:
    """Accumulates RolloutStats across rollouts; flush = reference ``_log``.

    ``push`` only *references* the device arrays (shape-derived episode
    count, no transfer); the device→host fetch happens once per ``flush``.
    A fetch blocks on the dispatches before it, so per-rollout fetching
    would serialize the driver loop; deferring it lets dispatch run ahead
    between log cadences. Aggregation semantics are unchanged."""

    #: fold threshold: each un-fetched RolloutStats ref pins its device
    #: buffers alive, so when ``runner_log_interval`` spans many rollouts
    #: ``_pending`` would grow without bound; past this many pushes the
    #: partial results are folded into host-side sums (one extra fetch per
    #: FOLD_EVERY rollouts — negligible against the interval it bounds)
    FOLD_EVERY = 64

    def __init__(self, population: int = 0):
        self.n_episodes = 0
        #: device→host round-trips this accumulator has performed
        #: (folds + mid-interval epsilon reads) — graftscope surfaces it
        #: as ``stat_fetches`` so sync-point cost is attributable from
        #: telemetry alone
        self.fetches = 0
        #: graftpop population axis (docs/POPULATION.md): P > 0 means
        #: every pushed stats leaf carries a LEADING (P,) member axis
        #: (the population superstep's vmapped output). The fold then
        #: ALSO aggregates per member — riding the same single fetch,
        #: zero extra dispatches — and flush emits ``pop<i>_*`` rows
        #: next to the aggregate stream when P > 1. ``n_episodes``
        #: counts TOTAL episodes across members (P·K·B per push).
        self.population = population
        #: per-member return EMA surviving across flushes — the PBT
        #: ranking signal (population.pbt_step member_perf); None until
        #: a member has flushed at least once
        self.member_return_ema: List = [None] * max(population, 0)
        self._pending = []          # un-fetched RolloutStats device refs
        self._eps_ref = None        # epsilon pushed since the last fetch
        self._eps_val = 0.0         # cached host value
        self._returns: List[float] = []   # folded per-episode returns
        self._stats = defaultdict(float)  # folded terminal-info sums
        # member id -> {n, return_sum, <TERMINAL_INFO_KEYS sums>}
        self._members = defaultdict(lambda: defaultdict(float))
        # graftworld per-scenario-slice aggregation (docs/ENVS.md):
        # family id -> {n, return_sum, <SLICE_KEYS sums>}; fed by the
        # SAME fold fetch as the overall sums — a stats object without a
        # ``scenario`` field (older tests, fakes) skips slice tracking
        self._slices = defaultdict(lambda: defaultdict(float))

    def push(self, rollout_stats) -> None:
        self._pending.append(rollout_stats)
        self._eps_ref = rollout_stats.epsilon
        # episode count is static shape info — reading it syncs nothing
        self.n_episodes += int(
            np.prod(rollout_stats.episode_return.shape) or 1)
        if len(self._pending) >= self.FOLD_EVERY:
            self._fold()

    def _fold(self) -> None:
        """Fetch every pending device ref (ONE host round-trip) and fold
        it into the host-side sums; clears ``_pending``. A pushed stats
        object may be one rollout's ``(B,)`` arrays or a fused
        superstep's stacked ``(K, B)`` — flattening makes both the same
        per-episode stream (the episode count in ``push`` already used
        the full shape product)."""
        if not self._pending:
            return
        self.fetches += 1
        fetched = jax.device_get(self._pending)
        for s in fetched:
            ret = np.asarray(s.episode_return).reshape(-1)
            self._returns.extend(float(x) for x in ret)
            for k in TERMINAL_INFO_KEYS:
                # absent keys (older fakes without the graftworld
                # fields) simply don't aggregate
                v = getattr(s, k, None)
                if v is not None:
                    self._stats[k] += float(np.sum(v))
            # a catalog trunk's routing counters (RolloutStats.moe: one
            # scalar a rollout, absent for every other agent) sum like
            # the terminal-info keys and flush as ``<k>_mean`` per episode
            for k, v in (getattr(s, "moe", None) or {}).items():
                self._stats[k] += float(np.sum(v))
            if self.population:
                # per-member aggregation off the SAME fetched arrays:
                # leaf layout (P, ...) — member i is row i
                for m in range(self.population):
                    mem = self._members[m]
                    r_m = np.asarray(s.episode_return)[m].reshape(-1)
                    mem["n"] += float(r_m.size)
                    mem["return"] += float(r_m.sum())
                    for k in TERMINAL_INFO_KEYS:
                        v = getattr(s, k, None)
                        if v is not None:
                            mem[k] += float(np.sum(np.asarray(v)[m]))
            scenario = getattr(s, "scenario", None)
            if scenario is not None:
                fam = np.asarray(scenario).reshape(-1).astype(np.int64)
                for f in np.unique(fam):
                    sel = fam == f
                    sl = self._slices[int(f)]
                    sl["n"] += float(sel.sum())
                    sl["return"] += float(ret[sel].sum())
                    for k in SLICE_KEYS:
                        v = getattr(s, k, None)
                        if v is not None:
                            sl[k] += float(
                                np.asarray(v).reshape(-1)[sel].sum())
        # the last pending entry owns the epsilon ref — same fetch; a
        # stacked push's most recent value is its LAST row. Under a
        # population the logged aggregate `epsilon` is MEMBER 0's (the
        # un-scaled schedule — the solo run's value); pop<i> epsilons
        # differ only by the static eps_scale grid, not worth P rows
        eps = np.asarray(fetched[-1].epsilon)
        if self.population:
            eps = eps[0]
        self._eps_val = float(np.mean(eps.reshape(-1)[-1:]))
        self._eps_ref = None
        self._pending.clear()

    @property
    def epsilon(self) -> float:
        """Exploration rate of the most recent rollout (reference logs it
        alongside each train-stat flush, ``parallel_runner.py:217-218``).

        NOTE: when pushes happened since the last fetch, reading this
        property performs a BLOCKING device→host fetch — treat
        mid-interval reads as costly.
        ``flush`` refreshes the cached value inside its own single fetch,
        which is where cadenced callers should get it."""
        if self._eps_ref is not None:
            # a stacked (K,) superstep push reports its LAST sub-iteration
            # (member 0's under a population — see _fold)
            self.fetches += 1
            eps = np.asarray(jax.device_get(self._eps_ref))
            if self.population:
                eps = eps[0]
            self._eps_val = float(eps.reshape(-1)[-1])
            self._eps_ref = None
        return self._eps_val

    def flush(self, logger, t_env: int, prefix: str = "") -> None:
        """Log ``return_mean`` + every ``<k>_mean`` and clear
        (``/root/reference/parallel_runner.py:222-231``). When the
        accumulated episodes span MORE than one scenario-family slice
        (a graftworld distribution), per-slice rows follow under
        ``<prefix>slice<fam>_*`` keys — single-scenario runs keep the
        exact pre-graftworld metric stream. A graftpop population
        (P > 1) additionally emits per-member ``<prefix>pop<i>_*`` rows
        and refreshes :attr:`member_return_ema` (the PBT ranking
        signal) — same fetch, zero extra dispatches; P <= 1 keeps the
        exact single-experiment stream (the P=1 parity contract)."""
        self._fold()                              # ONE host round-trip
        if self._returns:
            logger.log_stat(prefix + "return_mean",
                            float(np.mean(self._returns)), t_env)
        n = max(self.n_episodes, 1)
        for k, v in self._stats.items():
            logger.log_stat(prefix + k + "_mean", v / n, t_env)
        if self.population:
            for m in sorted(self._members):
                mem = self._members[m]
                if not mem.get("n"):
                    continue
                mn = max(mem["n"], 1.0)
                r = mem["return"] / mn
                ema = self.member_return_ema[m]
                self.member_return_ema[m] = (
                    r if ema is None else 0.7 * ema + 0.3 * r)
                if self.population > 1:
                    tag = f"{prefix}pop{m}_"
                    logger.log_stat(tag + "return_mean", r, t_env)
                    for k in POP_MEMBER_KEYS:
                        if k in mem:
                            logger.log_stat(tag + k + "_mean",
                                            mem[k] / mn, t_env)
        if len(self._slices) > 1:
            for fam in sorted(self._slices):
                sl = self._slices[fam]
                sn = max(sl["n"], 1.0)
                tag = f"{prefix}slice{fam}_"
                logger.log_stat(tag + "n", sl["n"], t_env)
                logger.log_stat(tag + "return_mean", sl["return"] / sn,
                                t_env)
                for k in SLICE_KEYS:
                    logger.log_stat(tag + k + "_mean", sl[k] / sn, t_env)
        self._returns.clear()
        self._stats.clear()
        self._members.clear()
        self._slices.clear()
        self.n_episodes = 0
