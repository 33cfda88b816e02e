"""What decides ``correct``: the dispatch that follows the window's close
— the same loop, the same compiled programs, the same state — against
the plain reference (``benchmark/reference``), at the timed sizes.

Between two precisions the actions, and with them every trajectory,
cannot be compared (arg-max over bf16 Q-values). So the reference is
*handed* what the program itself produced and is compared on continuous
quantities:

1. **ring and PER** — from the state before the dispatch, the ring's
   bookkeeping and the stratified proportional draws are replayed through
   the dispatch's K sub-iterations with the driver's own key stream; the
   program's per-episode |TD| are fed back as it feeds them. Counters,
   drawn indices and the priority vector it ends with must come out as
   the program's.
2. **learner** — the last sub-iteration's batch is gathered from the
   program's ring at those indices; the reference computes loss and
   per-episode |TD| from the parameters before that update (K = 1: the
   state before the dispatch; K > 1: the program's last Adam step taken
   back, ``qmix.adam_undo``).
3. **optimizer** (K = 1, where the state before the update is known) —
   the program's own clipped gradient, read leaf by leaf off Adam's
   first moment, is held to the clip and handed to the reference's Adam
   step, whose change of the parameters must be the program's.
4. **env** — for every step of the batch's episodes: ACKs and reward
   from the recorded observation rows and joint action; availability.
5. **acting** — the first steps of episodes taken from the newest
   rollout's slots of the ring (acted with exactly those parameters).
   The epsilon-greedy coin and the random action are replayed from the
   runner's key, so every recorded action is known to be an explored or
   a greedy one: an explored one must be the replayed random action; a
   greedy one of the episode's first step — the hidden token is its zero
   start, so nothing has been re-fed and no rounding amplified — is held
   to the reference's Q-values by how far it lies below their best.

Each number is printed beside its limit (``configs/<config>.limits.json``).
"""

from __future__ import annotations

import json
import os

import numpy as np

B1, B2 = 0.9, 0.999


# ------------------------------------------------------------- small helpers

def _find_adam(state):
    """The ``ScaleByAdamState`` (count, mu, nu) inside an optax chain."""
    if hasattr(state, "mu") and hasattr(state, "nu"):
        return state
    if isinstance(state, (tuple, list)):
        for s in state:
            found = _find_adam(s)
            if found is not None:
                return found
    return None


def _strip(t):
    return {"agent": t["agent"]["params"], "mixer": t["mixer"]["params"]}


def sizes_of(cfg) -> dict:
    m, e = cfg.model, cfg.env_args
    return dict(n_agents=e.agv_num, emb=m.emb, heads=m.heads, depth=m.depth,
                mixer_emb=m.mixer_emb, mixer_heads=m.mixer_heads,
                mixer_depth=m.mixer_depth, standard_heads=m.standard_heads,
                n_actions=e.num_channels + 1, n_mec=e.mec_num)


def check_supported(cfg, k: int) -> None:
    """The comparison follows this mathematics and no other; a
    configuration outside it is an error, never a silent pass."""
    bad = []
    if cfg.optimizer != "adam":
        bad.append("optimizer != adam")
    if cfg.td_loss != "mse" or cfg.reward_unit != 1.0 or not cfg.double_q:
        bad.append("td_loss/reward_unit/double_q")
    if cfg.mixer != "transformer" or cfg.agent != "transformer":
        bad.append("agent/mixer family")
    if cfg.model.qmix_pos_func != "abs" or cfg.model.mixer_zero_init:
        bad.append("mixer readout")
    if cfg.action_selector != "epsilon_greedy" or cfg.model.dropout:
        bad.append("noise/dropout")
    if not cfg.replay.prioritized or cfg.replay.buffer_cpu_only:
        bad.append("replay")
    if cfg.env_args.reward_scaling or cfg.env_args.edge_only:
        bad.append("env reward_scaling/edge_only")
    if cfg.batch_size_run < cfg.target_update_interval:
        bad.append("target sync not every iteration")
    if cfg.accumulated_episodes or cfg.batch_size > cfg.batch_size_run:
        bad.append("train gate")
    if bad:
        raise ValueError("check.py does not cover this configuration: "
                         + "; ".join(bad))


# ------------------------------------------------ what the program produced

def gather_program(cfg, k: int, snap: dict, infos: list, ts) -> dict:
    """Everything the comparison takes of the program, small enough to
    outlive the TrainState: host copies of scalars and vectors, device
    copies of the learner's trees, and a *reference* to the ring (the
    batch is gathered from it before the state is dropped)."""
    import jax
    if infos is None or len(infos) != k:
        raise RuntimeError(f"expected {k} info rows of the followed "
                           f"dispatch, got {None if infos is None else len(infos)}")
    keys = ("loss", "grad_norm", "td_errors_abs", "all_finite")
    rows = jax.device_get([{n: i[n] for n in keys} for i in infos])
    adam = _find_adam(ts.learner.opt_state)
    return {
        "infos": rows,
        "priorities": np.asarray(ts.buffer.priorities),
        "max_priority": float(ts.buffer.max_priority),
        "counters": {
            "insert_pos": int(ts.buffer.insert_pos),
            "episodes_in_buffer": int(ts.buffer.episodes_in_buffer),
            "episode": int(ts.episode),
            "train_steps": int(ts.learner.train_steps),
            "t_env": int(ts.runner.t_env),
            "adam_count": int(adam.count),
        },
        "params_after": ts.learner.params,
        "mu_after": adam.mu, "nu_after": adam.nu,
    }


# ---------------------------------------------------------- ring and PER

def replay_ring(cfg, k: int, snap: dict, prog: dict):
    """Follow ring bookkeeping and PER through the K sub-iterations →
    (numbers, idx of the last draw, its importance weights, slots of the
    newest rollout).

    The cumulative sum of the priorities rounds differently in another
    program, so a draw within a rounding of a slot's boundary may land on
    the neighbouring slot (it does, one to three times in 128 draws on the
    chip). Each draw is therefore settled among the replayed slot and its
    two neighbours by the program's final priority vector: the slot that
    holds this row's ``(|TD| + 1e-6)^alpha``; where none does, the slot
    that something later wrote over (a later insert's stamp, a later or a
    duplicate draw). The vector the program ends with is then held, slot
    by slot, to the value(s) its last writer may have left there."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import replay
    lanes, cap = cfg.batch_size_run, cfg.replay.buffer_size
    bt, alpha = cfg.batch_size, cfg.replay.per_alpha
    spi = lanes * cfg.env_args.episode_limit
    pri = jnp.asarray(snap["priorities"])
    maxp = jnp.asarray(snap["max_priority"])
    pos = int(snap["insert_pos"])
    filled = int(snap["episodes_in_buffer"])
    episode = int(snap["episode"])
    key = snap["key"]
    weights = None
    final = np.asarray(prog["priorities"], np.float64)
    near = lambda x, y: abs(x - y) <= 1e-4 * abs(y)          # noqa: E731
    groups = []             # per sub-iteration: stamp slots/value, draws
    steps_taken = 0         # updates the non-finite guard let through
    for i in range(k):
        slots = np.asarray(replay.ring_slots(pos, lanes, cap))
        stamp = float(maxp ** alpha)
        pri = replay.stamp_inserted(pri, maxp, jnp.asarray(slots), alpha)
        pos, filled = (pos + lanes) % cap, min(filled + lanes, cap)
        episode += lanes
        key, k_iter = jax.random.split(key)           # the driver's split
        k_sample, _ = jax.random.split(k_iter)        # the train program's
        drawn = np.asarray(replay.sample(pri, filled, k_sample, bt))
        td = np.asarray(prog["infos"][i]["td_errors_abs"], np.float32)
        finite = bool(prog["infos"][i]["all_finite"])
        steps_taken += finite
        if not finite:
            # the program's non-finite guard (docs/RESILIENCE.md): the
            # update is skipped whole: parameters, moments, priorities
            # and the running maximum stay as they are
            groups.append({"slots": slots, "stamp": stamp, "drawn": drawn,
                           "idx": drawn[:0], "settled": np.ones(0, bool),
                           "want": np.zeros(0)})
            continue
        want = np.asarray((jnp.asarray(td) + 1e-6) ** alpha, np.float64)
        idx, settled = drawn.copy(), np.zeros(bt, bool)
        for j in range(bt):
            for c in (drawn[j], drawn[j] - 1, drawn[j] + 1):
                if 0 <= c < cap and near(final[c], want[j]):
                    idx[j], settled[j] = c, True
                    break
        groups.append({"slots": slots, "stamp": stamp, "drawn": drawn,
                       "idx": idx, "settled": settled, "want": want})
        t_env_i = snap["t_env"] + (i + 1) * spi
        beta = cfg.replay.per_beta + (1.0 - cfg.replay.per_beta) * min(
            max(t_env_i / cfg.t_max, 0.0), 1.0)
        weights = replay.importance_weights(pri, filled, jnp.asarray(idx),
                                            beta)
        pri, maxp = replay.feed_back(pri, maxp, jnp.asarray(idx),
                                     jnp.asarray(td), alpha)
    # draws whose |TD| the final vector no longer shows: something later
    # must have written over their slot
    for i, g in enumerate(groups):
        later = set()
        for h in groups[i + 1:]:
            later.update(h["slots"].tolist())
            later.update(h["idx"].tolist())
        for j in np.flatnonzero(~g["settled"]):
            same = set(np.delete(g["idx"], j).tolist())
            for c in (g["drawn"][j], g["drawn"][j] - 1, g["drawn"][j] + 1):
                if c in later or c in same:
                    g["idx"][j] = c
                    break
    # the last writer of every slot, and what it may have left there
    may = {}
    for i, g in enumerate(groups):
        for s in g["slots"].tolist():
            may[s] = (("stamp", i), [g["stamp"]])
        for s, v in zip(g["idx"].tolist(), g["want"].tolist()):
            if may.get(s, (None,))[0] == ("draw", i):
                may[s][1].append(v)
            else:
                may[s] = (("draw", i), [v])
    before = np.asarray(snap["priorities"], np.float64)
    scale = max(float(final.max()), 1e-30)
    gap = 0.0
    for s in range(cap):
        values = may[s][1] if s in may else [before[s]]
        gap = max(gap, min(abs(final[s] - v) for v in values) / scale)
    moved = sum(int((g["idx"] != g["drawn"]).sum()) for g in groups)
    want_counters = {
        "insert_pos": pos, "episodes_in_buffer": filled, "episode": episode,
        "train_steps": int(snap["learner"].train_steps) + k,
        "t_env": snap["t_env"] + k * spi,
        "adam_count": (int(_find_adam(snap["learner"].opt_state).count)
                       + steps_taken),
    }
    off = sum(prog["counters"][n] != v for n, v in want_counters.items())
    numbers = {
        "counters_off": float(off),
        "draws_moved": float(moved),
        "priority_gap": float(gap),
        "updates_skipped": float(k - steps_taken),
    }
    return (numbers, jnp.asarray(groups[-1]["idx"]), weights,
            jnp.asarray(groups[-1]["slots"]))


def gather_batch(ts, idx) -> dict:
    """The sampled episodes, from the program's ring, time-major, in the
    reference's layout (float32 where the ring stores bf16)."""
    import jax.numpy as jnp
    st = ts.buffer.storage
    tm = lambda x: jnp.swapaxes(x[idx], 0, 1)           # noqa: E731
    f32 = lambda x: tm(x).astype(jnp.float32)           # noqa: E731
    return {
        "rows": f32(st.obs.rows), "mec": tm(st.obs.mec_index).astype(jnp.int32),
        "mean": f32(st.obs.mean), "std": f32(st.obs.std),
        "state": f32(st.state), "avail": tm(st.avail_actions),
        "actions": tm(st.actions), "reward": f32(st.reward),
        "terminated": tm(st.terminated), "filled": tm(st.filled),
    }


# ------------------------------------------------------------- the learner

def load_reference(config_name: str, bench_dir: str, cfg=None):
    """``configs/<config>.reference.py`` — the configuration's plain
    reference, beside its file of sizes."""
    import importlib.util
    path = os.path.join(bench_dir, "configs", config_name + ".reference.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + config_name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if cfg is not None:
        mine = sizes_of(cfg)
        if mod.SIZES != mine or mod.GAMMA != cfg.gamma:
            raise ValueError(f"{path} states {mod.SIZES}, the program's "
                             f"configuration {mine}")
    return mod


def reference_step(ref, params, target, batch, weights, *, prec="f32",
                   half_batch=False) -> dict:
    """Loss and per-episode |TD| of one update's batch, by the
    configuration's reference ``ref`` at ``prec`` (the forward pass)."""
    import jax

    def fwd(p, tp, b, w):
        loss, aux = ref.episode_loss(p, tp, b, w, prec=prec,
                                     half_batch=half_batch)
        return dict(aux, loss=loss)
    return jax.jit(fwd)(_strip(params), _strip(target), batch, weights)


def params_before(cfg, k: int, snap: dict, prog: dict):
    """The parameters before the last update: the state before the
    dispatch (K = 1), or the program's last Adam step taken back."""
    import jax
    from benchmark.reference import qmix
    if k == 1:
        return snap["learner"].params
    return jax.jit(lambda p, m, v: qmix.adam_undo(
        p, m, v, prog["counters"]["adam_count"], lr=cfg.lr, b1=B1, b2=B2,
        eps=cfg.optim_eps))(prog["params_after"], prog["mu_after"],
                            prog["nu_after"])


def learner_numbers(prog_out: dict, ref_out: dict) -> dict:
    td_r = np.asarray(ref_out["td_errors_abs"], np.float64)
    td_p = np.asarray(prog_out["td_errors_abs"], np.float64)
    return {
        "loss_gap": abs(float(prog_out["loss"]) - float(ref_out["loss"]))
        / abs(float(ref_out["loss"])),
        "td_gap": float((np.abs(td_p - td_r)
                         / np.maximum(td_r, np.median(td_r))).max()),
        "td_rms_gap": float(np.linalg.norm(td_p - td_r)
                            / np.linalg.norm(td_r)),
    }


def optimizer_numbers(cfg, snap: dict, prog: dict) -> dict:
    """K = 1: the clipped gradient the program's optimizer got, leaf by
    leaf, is Adam's first moment after the step less ``b1`` times the one
    before. ``clip_gap``: its global norm against the clip (or the
    gradient's own norm, where that is smaller). ``adam_gap``: the
    reference's Adam step on that gradient, from the state before the
    update, against the change the program made, by the worst leaf."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import qmix
    adam0 = _find_adam(snap["learner"].opt_state)
    before = _strip(snap["learner"].params)

    def f(before, after, mu0, nu0, mu1):
        g = jax.tree.map(lambda m1, m0: (m1 - B1 * m0) / (1 - B1), mu1, mu0)
        new, _, _ = qmix.adam_step(before, g, mu0, nu0, adam0.count,
                                   lr=cfg.lr, b1=B1, b2=B2,
                                   eps=cfg.optim_eps)
        # the norm of (program's change - reference's), by the worst leaf,
        # against the reference's norm of that leaf or of the median leaf
        norms = lambda t: jnp.stack([jnp.sqrt(jnp.sum(x ** 2))  # noqa: E731
                                     for x in jax.tree.leaves(t)])
        ref = norms(jax.tree.map(lambda n, b: n - b, new, before))
        off = norms(jax.tree.map(lambda a, n: a - n, after, new))
        return (qmix.global_norm(g),
                (off / jnp.maximum(ref, jnp.median(ref))).max())
    g_norm, adam_gap = jax.jit(f)(
        before, _strip(prog["params_after"]), _strip(adam0.mu),
        _strip(adam0.nu), _strip(prog["mu_after"]))
    want = min(float(cfg.grad_norm_clip),
               float(prog["infos"][-1]["grad_norm"]))
    return {"clip_gap": abs(float(g_norm) - want) / want,
            "adam_gap": float(adam_gap)}


# ------------------------------------------------------------ env and acting

def env_numbers(cfg, batch: dict, dtype=None) -> dict:
    import jax.numpy as jnp
    from benchmark.reference import env
    e = cfg.env_args
    n_act = e.num_channels + 1
    rows, mec, actions = batch["rows"][:-1], batch["mec"][:-1], batch["actions"]
    ack = env.acks(mec, actions)
    r = env.reward(rows, ack, user_compute_cap=e.user_compute_cap,
                   latency_max_ms=e.latency_max_ms,
                   dtype=dtype or jnp.float32)
    stored = batch["reward"]
    # many steps pay exactly 0: hold each against the median or the mean
    # step, whichever is larger
    floor = jnp.maximum(jnp.maximum(jnp.median(jnp.abs(r)),
                                    jnp.abs(r).mean()), 1e-30)
    gap = jnp.abs(stored - r) / jnp.maximum(jnp.abs(r), floor)
    ack_next = jnp.argmax(batch["rows"][1:, ..., 0:3], axis=-1) - 1
    ack_off = int((ack_next != ack).sum())
    avail_off = int((env.avail(batch["rows"], n_act) != batch["avail"]).sum())
    return {"reward_gap": float(gap.max()),
            "ack_off": float(ack_off), "avail_off": float(avail_off)}


ACT_EPISODES, ACT_STEPS, ACT_BLOCK = 128, 8, 32


def gather_acting(cfg, ts, newest_slots) -> dict:
    """The first ``ACT_STEPS`` steps of ``ACT_EPISODES`` episodes of the
    newest rollout (lanes evenly spaced), from the program's ring,
    time-major, in the reference's layout."""
    import jax.numpy as jnp
    lanes = cfg.batch_size_run
    n = min(lanes, ACT_EPISODES)
    t1 = min(ACT_STEPS, cfg.env_args.episode_limit)
    lane = jnp.arange(n) * (lanes // n)
    slots = newest_slots[lane]
    st = ts.buffer.storage
    tm = lambda x: jnp.swapaxes(x[slots, :t1], 0, 1)    # noqa: E731
    f32 = lambda x: tm(x).astype(jnp.float32)           # noqa: E731
    return {"lane": lane, "rows": f32(st.obs.rows),
            "mec": tm(st.obs.mec_index).astype(jnp.int32),
            "mean": f32(st.obs.mean), "std": f32(st.obs.std),
            "avail": tm(st.avail_actions), "actions": tm(st.actions)}


def replay_exploration(cfg, k: int, snap: dict, acting: dict):
    """The epsilon-greedy selector's draws for the sampled agent-steps,
    from the runner's key at the dispatch's start → (explore ``(t, n, A)``
    bool, gumbel ``(t, n, A, n_actions)``). The key stream is the
    rollout's: per rollout ``key, k_reset, k_scan = split(key, 3)``; per
    step ``split(k_scan, T)[t]`` → act / env → noise / select → coin /
    random action; the coin is uniform over (lanes, agents), the random
    action the arg-max of Gumbel noise over the available ones."""
    import jax
    import jax.numpy as jnp
    lanes, t_len = cfg.batch_size_run, cfg.env_args.episode_limit
    t1, _, a, n_act = acting["avail"].shape
    key = snap["runner_key"]
    for _ in range(k - 1):                  # the dispatch's earlier rollouts
        key = jax.random.split(key, 3)[0]
    k_scan = jax.random.split(key, 3)[2]
    t0 = snap["t_env"] + (k - 1) * lanes * t_len

    @jax.jit
    def draws(key_t, t_env):
        k_act, _ = jax.random.split(key_t)
        _, k_sel = jax.random.split(k_act)
        k_coin, k_rand = jax.random.split(k_sel)
        frac = jnp.clip(t_env / cfg.epsilon_anneal_time, 0.0, 1.0)
        eps = cfg.epsilon_start + frac * (cfg.epsilon_finish
                                          - cfg.epsilon_start)
        explore = jax.random.uniform(k_coin, (lanes, a)) < eps
        gumbel = jax.random.gumbel(k_rand, (lanes, a, n_act))
        return explore[acting["lane"]], gumbel[acting["lane"]]
    out = [draws(key_t, jnp.asarray(t0 + t * lanes, jnp.int32))
           for t, key_t in enumerate(jax.random.split(k_scan, t_len)[:t1])]
    return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])


def agent_qs(ref, agent_params, acting: dict, prec="f32"):
    """The reference's Q-values of the acting sample ``(t, n, A,
    n_actions)``, in blocks of episodes so that the dense forward fits."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda p, b: ref.agent_qs(p, b, prec=prec))
    n = acting["mec"].shape[1]
    obs = {x: acting[x] for x in ("rows", "mec", "mean", "std")}
    return jnp.concatenate(
        [f(agent_params, {x: v[:, i:i + ACT_BLOCK] for x, v in obs.items()})
         for i in range(0, n, ACT_BLOCK)], axis=1)


def regret(q_ref, avail, actions, where) -> dict:
    """How far the Q-value (the reference's) of ``actions`` lies below the
    best available one, over the agent-steps ``where`` of the episodes'
    first step that have a choice: the mean, against the mean spread of
    the available Q-values over the whole sample (``greedy_regret``), with
    the share of such steps that are not the best and the widest single
    gap beside it."""
    import jax.numpy as jnp
    where = where & (jnp.arange(where.shape[0]) == 0)[:, None, None]
    top = jnp.where(avail, q_ref, -jnp.inf).max(-1)
    low = jnp.where(avail, q_ref, jnp.inf).min(-1)
    choice = avail.sum(-1) > 1
    spread = ((top - low) * choice).sum() / choice.sum()
    taken = jnp.take_along_axis(q_ref, actions[..., None], -1)[..., 0]
    where = where & choice
    gap = jnp.where(where, top - taken, 0.0) / spread
    n = jnp.maximum(where.sum(), 1)
    return {"greedy_regret": float(gap.sum() / n),
            "greedy_flips": float((gap > 0).sum() / n),
            "greedy_regret_max": float(gap.max()),
            "greedy_steps": float(where.sum())}


def acting_numbers(q_ref, acting: dict, explore, gumbel) -> dict:
    """``selector_off`` (exact, every step of the sample): explored
    agent-steps whose recorded action is not the replayed random one, and
    greedy ones whose action is not available. ``greedy_regret`` (the
    first step): see ``regret``."""
    import jax.numpy as jnp
    avail, actions = acting["avail"], acting["actions"]
    pick = lambda x: jnp.take_along_axis(                # noqa: E731
        x, actions[..., None], -1)[..., 0]
    legal = pick(avail)
    # the replayed noise is the program's to a rounding of the logarithm
    drawn = legal & (pick(gumbel) >= jnp.where(avail, gumbel, -jnp.inf
                                               ).max(-1) - 1e-4)
    off = jnp.where(explore, ~drawn, ~legal)
    return {"selector_off": float(off.sum()),
            "explored_share": float(explore.mean()),
            **regret(q_ref, avail, actions, ~explore & legal)}


def policy_regret(q_ref, q_other, avail) -> dict:
    """``regret`` of the greedy policy of other Q-values (the control's,
    a planted fault's) put in the program's place."""
    import jax.numpy as jnp
    actions = jnp.argmax(jnp.where(avail, q_other, -jnp.inf), -1)
    return regret(q_ref, avail, actions, jnp.ones(actions.shape, bool))


# ------------------------------------------------------------------ verdict

def load_limits(config_name: str, bench_dir: str) -> dict:
    path = os.path.join(bench_dir, "configs", config_name + ".limits.json")
    with open(path) as f:
        return json.load(f)["limits"]


def verdict(numbers: dict, limits: dict):
    """→ (correct, {name: {"value", "limit"}}). A number the cell compares
    and that is missing, or not finite, fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and bool(np.isfinite(v))
        compared[name] = {"value": v if good else repr(v), "limit": limit}
        if not (good and v <= limit):
            ok = False
    return ok, compared


class Comparison:
    """One run's comparison. Built while the TrainState lives (it gathers
    the batch and the acting sample from the program's ring); ``finish``
    runs the reference and is called once the caller has dropped the
    state."""

    def __init__(self, cfg, k: int, snap: dict, infos: list, ts, ref):
        check_supported(cfg, k)
        self.cfg, self.k, self.ref, self.snap = cfg, k, ref, snap
        prog = self.prog = gather_program(cfg, k, snap, infos, ts)
        self.numbers, self.idx, self.weights, newest = replay_ring(
            cfg, k, snap, prog)
        self.prog_out = prog["infos"][-1]
        self.td = {}
        if not self.prog_out["all_finite"]:
            return              # five dispatches in a row ended non-finite
        self.before = params_before(cfg, k, snap, prog)
        self.target = (self.before if k > 1
                       else snap["learner"].target_params)
        self.batch = gather_batch(ts, self.idx)
        self.acting = gather_acting(cfg, ts, newest)

    def reference(self, prec="f32", half_batch=False) -> dict:
        return reference_step(self.ref, self.before, self.target, self.batch,
                              self.weights, prec=prec, half_batch=half_batch)

    def agent_qs(self, prec="f32"):
        return agent_qs(self.ref, self.before["agent"]["params"],
                        self.acting, prec)

    def finish(self) -> dict:
        """Run the reference → every reading of this run."""
        numbers = self.numbers
        if not self.prog_out["all_finite"]:
            numbers["last_update_skipped"] = 1.0
            return numbers
        numbers["last_update_skipped"] = 0.0
        ref_out = self.ref_out = self.reference()
        numbers.update(learner_numbers(self.prog_out, ref_out))
        # the per-episode |TD| themselves, for the record
        self.td = {
            "program": np.asarray(self.prog_out["td_errors_abs"]).tolist(),
            "reference": np.asarray(ref_out["td_errors_abs"]).tolist()}
        if self.k == 1:
            numbers.update(optimizer_numbers(self.cfg, self.snap, self.prog))
        numbers.update(env_numbers(self.cfg, self.batch))
        self.q_ref = self.agent_qs()
        self.explore, self.gumbel = replay_exploration(
            self.cfg, self.k, self.snap, self.acting)
        numbers.update(acting_numbers(self.q_ref, self.acting, self.explore,
                                      self.gumbel))
        return numbers
