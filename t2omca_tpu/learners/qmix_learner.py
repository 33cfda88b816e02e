"""QMIX TD learner (M8, the unreleased ``learners`` package).

Contract pinned by the call sites (SURVEY.md §2.3 M8, §3.3): per-agent Qs
from the TransformerAgent, chosen-action Qs mixed by the TransformerMixer
into ``q_tot``, target-network + double-Q targets, importance-weighted MSE on
the TD error, and ``info["td_errors_abs"]`` flowing back as PER priorities
(``/root/reference/per_run.py:233-238``, Q9).

TPU shape: the reference's sequential Python ``for t in range(T)`` becomes a
``lax.scan`` over the time axis carrying BOTH recurrent streams — the agent
hidden token (``transf_agent.py:71``) and the mixer's 3 hyper tokens
(``n_transf_mixer.py:91``) — for the online and target networks. The whole
train step (two unrolls, loss, grads, optimizer update, conditional hard
target sync) is one pure function → one XLA program; batch and agent axes
ride the MXU, the only sequential dimension is episode time.

Masking: sampled episodes keep static length ``T`` (no ``max_t_filled``
truncation — XLA wants static shapes); the ``filled`` mask plays the role of
the reference's truncation (``per_run.py:226-227``), and time-limit episodes
bootstrap because ``terminated`` excludes the time-limit step (Q7).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..components.episode_buffer import EpisodeBatch
from ..config import TrainConfig
from ..controllers.basic_mac import BasicMAC
from ..models.ff_mixer import QMixFFMixer, VDNMixer
from ..models.mixer import TransformerMixer

#: mixer families (parent PyMARL lineage registry pattern); all share the
#: TransformerMixer call signature so the learner scan is mixer-agnostic
MIXER_REGISTRY = {"transformer": TransformerMixer, "qmix_ff": QMixFFMixer,
                  "vdn": VDNMixer}


@struct.dataclass
class LearnerState:
    params: Any                   # {"agent": ..., "mixer": ...}
    target_params: Any
    opt_state: Any
    train_steps: jnp.ndarray      # () int32
    last_target_update: jnp.ndarray  # () int32 — episode of last hard sync


def _make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    if cfg.optimizer == "rmsprop":
        opt = optax.rmsprop(cfg.lr, decay=cfg.optim_alpha, eps=cfg.optim_eps)
    else:
        opt = optax.adam(cfg.lr, eps=cfg.optim_eps)
    return optax.chain(optax.clip_by_global_norm(cfg.grad_norm_clip), opt)


@dataclasses.dataclass(frozen=True)
class QMixLearner:
    mac: BasicMAC
    mixer: Any                  # any MIXER_REGISTRY family
    cfg: TrainConfig
    obs_dim: int
    state_dim: int

    @classmethod
    def build(cls, cfg: TrainConfig, mac: BasicMAC,
              env_info: dict) -> "QMixLearner":
        n_entities = cfg.model.n_entities_state or env_info["n_entities"]
        state_entity_mode = "state_entity_feats" in env_info
        if state_entity_mode:
            feat = env_info["state_entity_feats"]
        else:
            # Q12 fallback: mixer tokenizes all agents' obs entities
            feat = env_info["obs_entity_feats"]
            n_entities = env_info["n_entities"]
        mixer = MIXER_REGISTRY[cfg.mixer](
            n_agents=env_info["n_agents"],
            n_entities=n_entities,
            feat_dim=feat,
            emb=cfg.model.mixer_emb,
            heads=cfg.model.mixer_heads,
            depth=cfg.model.mixer_depth,
            ff_hidden_mult=cfg.model.ff_hidden_mult,
            dropout=cfg.model.dropout,
            qmix_pos_func=cfg.model.qmix_pos_func,
            qmix_pos_func_beta=cfg.model.qmix_pos_func_beta,
            state_entity_mode=state_entity_mode,
            standard_heads=cfg.model.standard_heads,
            use_orthogonal=cfg.model.use_orthogonal,
            dtype=jnp.dtype(cfg.model.dtype),
            attn_impl=cfg.kernels.attention,
            zero_init_gate=cfg.model.mixer_zero_init,
        )
        return cls(mac=mac, mixer=mixer, cfg=cfg,
                   obs_dim=env_info["obs_shape"],
                   state_dim=env_info["state_shape"])

    # ------------------------------------------------------------------ state

    def init_state(self, key: jax.Array) -> LearnerState:
        k_agent, k_mixer = jax.random.split(key)
        agent_params = self.mac.init_params(k_agent, self.obs_dim)
        b, a, e = 1, self.mac.n_agents, self.cfg.model.mixer_emb
        mixer_params = self.mixer.init(
            k_mixer,
            jnp.zeros((b, 1, a)),                      # qvals
            jnp.zeros((b, a, self.cfg.model.emb)),     # agent hiddens
            self.mixer.initial_hyper(b),               # 3 hyper tokens
            jnp.zeros((b, self.state_dim)),            # state
            jnp.zeros((b, a, self.obs_dim)),           # obs (Q12 path)
        )
        params = {"agent": agent_params, "mixer": mixer_params}
        return LearnerState(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=_make_optimizer(self.cfg).init(params),
            train_steps=jnp.zeros((), jnp.int32),
            last_target_update=jnp.zeros((), jnp.int32),
        )

    # ------------------------------------------------------------------ unrolls

    @property
    def _agent_qslice(self) -> bool:
        """Learner-side qslice eligibility (the shared predicate — same
        fast path as acting, exact and differentiable)."""
        from ..ops.query_slice import agent_qslice_eligible
        return agent_qslice_eligible(self.cfg)

    @property
    def _mixer_qslice(self) -> bool:
        from ..ops.query_slice import mixer_qslice_eligible
        return mixer_qslice_eligible(self.cfg)

    @property
    def _mask_padded(self) -> bool:
        """STATIC gate for the mixer-side padding mask (ROADMAP item 3's
        open remainder): True only when the config's scenario
        distribution can draw ``n_active < n_agents``. Every
        non-padding config (the classic fixed scenario, the audit
        config) compiles the exact pre-mask loss — graftprog
        fingerprints of the hot train programs stay byte-identical."""
        from ..envs.graftworld import distribution_can_pad
        from ..envs.registry import make_scenario_distribution
        return distribution_can_pad(
            make_scenario_distribution(self.cfg.env_args),
            self.mac.n_agents)


    def _scan_body(self, body):
        """Wrap a scan body with jax.checkpoint when ``model.remat``: the
        backward pass then recomputes each timestep's forward instead of
        keeping O(T) residuals — the long-horizon HBM lever (exact: same
        values, same gradients)."""
        import jax as _jax
        return _jax.checkpoint(body) if self.cfg.model.remat else body

    @property
    def needs_rngs(self) -> bool:
        """True when training must sample noise/dropout masks: NoisyNet
        sigma params only receive gradient if noise is drawn during the
        loss unroll (``/root/reference/transf_agent.py:37-48``), and
        dropout>0 must be active in training."""
        return (self.cfg.action_selector == "noisy-new"
                or self.cfg.model.dropout > 0.0)

    def _fold_params(self, agent_params):
        from ..ops.query_slice import fold_agent_params
        a = self.mac.agent
        return fold_agent_params(
            agent_params, emb=a.emb, heads=a.heads, depth=a.depth,
            standard_heads=a.standard_heads, dtype=a.dtype)

    def _unroll_agent(self, agent_params, obs_tm: jnp.ndarray,
                      key: Optional[jax.Array] = None,
                      compact_tm=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """obs_tm ``(T1, B, A, O)`` → (q ``(T1, B, A, n_actions)``,
        hiddens ``(T1, B, A, emb)``); carries the recurrent hidden token.
        ``key`` (when the config is noisy / has dropout) drives per-step
        noise resampling, matching a fresh draw per forward. With
        ``compact_tm`` (time-major ``(rows, same_mec, mean, std)`` from
        compact entity storage) the unroll runs the entity-table forward —
        same function, ~20× less input data (obs_tm may be None).

        Fast-path coverage: qslice/entity unrolls serve the deterministic
        AND the noisy configs (noise is q-head-only, applied per step from
        the split keys — ops/query_slice._q_head); only dropout>0 falls
        back to the dense flax unroll."""
        if compact_tm is not None:
            b = compact_tm[0].shape[1]
            agent_params = self._fold_params(agent_params)

            if key is None:
                def body(h, xs):
                    q, h = self.mac.forward_entity(agent_params, xs, h)
                    return h, (q, h)

                _, (qs, hs) = jax.lax.scan(
                    self._scan_body(body), self.mac.init_hidden(b),
                    compact_tm)
            else:
                def body(h, xs):
                    compact_t, k_t = xs
                    q, h = self.mac.forward_entity(
                        agent_params, compact_t, h, key=k_t,
                        deterministic=False)
                    return h, (q, h)

                keys = jax.random.split(key, compact_tm[0].shape[0])
                _, (qs, hs) = jax.lax.scan(
                    self._scan_body(body), self.mac.init_hidden(b),
                    (compact_tm, keys))
            return qs, hs

        b = obs_tm.shape[1]

        if key is None:
            # the query-slice forward is the same function up to float
            # reassociation (forward+gradient equivalence pinned in
            # tests/test_qslice.py), so the deterministic unroll uses it
            # whenever eligible; the weight fold happens here, outside the
            # scan (differentiable, loop-invariant)
            if self._agent_qslice:
                agent_params = self._fold_params(agent_params)
                # the learner unroll is where kernels.attention lands on
                # the qslice path: under "pallas" the sliced attention
                # (and, through jax.grad, its flash BACKWARD kernels)
                # lowers into the train step at the train dtype; acting/
                # serving callers keep the einsum default (basic_mac)
                fwd = functools.partial(self.mac.forward_qslice,
                                        attn_impl=self.cfg.kernels.attention)
            else:
                fwd = self.mac.forward

            def body(h, obs_t):
                q, h = fwd(agent_params, obs_t, h)
                return h, (q, h)

            _, (qs, hs) = jax.lax.scan(self._scan_body(body),
                                       self.mac.init_hidden(b), obs_tm)
        else:
            if self._agent_qslice:
                # noisy config on the fast path: sliced stack + per-step
                # noise keys into the q-head
                agent_params = self._fold_params(agent_params)

                def body(h, xs):
                    obs_t, k_t = xs
                    q, h = self.mac.forward_qslice(
                        agent_params, obs_t, h, key=k_t,
                        deterministic=False,
                        attn_impl=self.cfg.kernels.attention)
                    return h, (q, h)
            else:
                def body(h, xs):
                    obs_t, k_t = xs
                    q, h = self.mac.forward(agent_params, obs_t, h,
                                            key=k_t, deterministic=False)
                    return h, (q, h)

            keys = jax.random.split(key, obs_tm.shape[0])
            _, (qs, hs) = jax.lax.scan(
                self._scan_body(body), self.mac.init_hidden(b),
                (obs_tm, keys))
        return qs, hs

    def _unroll_trunk(self, agent_params, obs_tm, compact_tm):
        """``_unroll_agent`` for a catalog trunk (``model.trunk``,
        models/trunk.py) → (qs, hiddens, aux) with ``aux`` the routed-pair
        counts of every step: a scan of the acting forward
        (``trunk.unroll``) over the entity tokens — rebuilt from
        ``compact_tm`` where the ring stores the factored form, else
        ``obs_tm``. The matmul weights are cast to the compute dtype once,
        outside the scan (rounded inside it, every step reads the float32
        leaves, and the compiler keeps float32 gradient sums per scan:
        2 GB more of temporaries at the benchmark's size, PERF.md par.4);
        the scan then sums its steps' weight gradients in that dtype."""
        from ..models import trunk
        mac = self.mac
        with jax.named_scope("agent.embed"):
            tokens = (jax.vmap(mac.trunk_tokens)(obs_tm)
                      if compact_tm is None else
                      jax.vmap(lambda c: mac.trunk_tokens(None, c))(
                          compact_tm))
        return trunk.unroll(
            trunk.cast_weights(agent_params, mac.agent.dtype), tokens,
            mac.init_hidden(tokens.shape[1]), tk=mac.trunk,
            dtype=mac.agent.dtype, wrap=self._scan_body)

    def _unroll_mixer(self, mixer_params, q_tm: jnp.ndarray,
                      hid_tm: jnp.ndarray, state_tm: jnp.ndarray,
                      obs_tm: jnp.ndarray,
                      key: Optional[jax.Array] = None) -> jnp.ndarray:
        """q_tm ``(T, B, A)`` → ``q_tot (T, B)``; carries the 3 hyper tokens
        across time (``n_transf_mixer.py:91``)."""
        b = q_tm.shape[1]

        if key is None:
            if self._mixer_qslice:
                from ..ops.query_slice import make_mixer_qslice
                fold, mix = make_mixer_qslice(self.mixer)
                # fold once, outside the scan (differentiable)
                mixer_params = fold(mixer_params)
            else:
                mix = self.mixer.apply

            def body(hyper, xs):
                qv, h, s, o = xs
                q_tot, hyper = mix(mixer_params, qv[:, None, :], h, hyper,
                                   s, o)
                return hyper, q_tot[:, 0, 0]

            _, q_tots = jax.lax.scan(
                self._scan_body(body), self.mixer.initial_hyper(b),
                (q_tm, hid_tm, state_tm, obs_tm))
        else:
            def body(hyper, xs):
                qv, h, s, o, k_t = xs
                q_tot, hyper = self.mixer.apply(
                    mixer_params, qv[:, None, :], h, hyper, s, o,
                    deterministic=False, rngs={"dropout": k_t})
                return hyper, q_tot[:, 0, 0]

            keys = jax.random.split(key, q_tm.shape[0])
            _, q_tots = jax.lax.scan(
                self._scan_body(body), self.mixer.initial_hyper(b),
                (q_tm, hid_tm, state_tm, obs_tm, keys))
        return q_tots

    # ------------------------------------------------------------------ loss

    def _loss(self, params, target_params, batch: EpisodeBatch,
              weights: jnp.ndarray, key: Optional[jax.Array] = None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        # time-major views; obs/state may be stored bf16 (ReplayConfig
        # store_dtype) — lift back to f32 for the loss math. Compact entity
        # storage (CompactEntityObs) unrolls through the entity-table
        # forward instead of reconstructing the flat obs (the mixer never
        # reads obs in state_entity_mode, which the storage gate requires).
        from ..components.episode_buffer import CompactEntityObs
        with jax.named_scope("learner.loss"):
            if isinstance(batch.obs, CompactEntityObs):
                co = batch.obs
                mec = jnp.swapaxes(co.mec_index, 0, 1)
                compact_tm = (
                    jnp.swapaxes(co.rows, 0, 1).astype(jnp.float32),
                    mec[..., :, None] == mec[..., None, :],
                    jnp.swapaxes(co.mean, 0, 1),
                    jnp.swapaxes(co.std, 0, 1),
                )
                obs = None
            else:
                compact_tm = None
                obs = jnp.swapaxes(batch.obs, 0, 1).astype(jnp.float32)
            state = jnp.swapaxes(batch.state, 0, 1).astype(jnp.float32)
            avail = jnp.swapaxes(batch.avail_actions, 0, 1)   # (T+1, B, A, n)
            actions = jnp.swapaxes(batch.actions, 0, 1)       # (T, B, A)
            reward = jnp.swapaxes(batch.reward, 0, 1)         # (T, B)
            term = jnp.swapaxes(batch.terminated, 0, 1).astype(jnp.float32)
            mask = jnp.swapaxes(batch.filled, 0, 1).astype(jnp.float32)

            if key is not None:
                k_ag, k_tag, k_mx, k_tmx = jax.random.split(key, 4)
                if cfg.model.dropout == 0.0:
                    # noisy-only configs: the mixer has no noise source
                    # (NoisyLinear lives in the agent q-head only), so its
                    # unroll stays on the deterministic fast path — passing
                    # keys here forced the dense flax mixer scan for nothing
                    k_mx = k_tmx = None
            else:
                k_ag = k_tag = k_mx = k_tmx = None

        # the two unrolls stay SEPARATE deliberately: the target unroll
        # feeds only stop_gradient-terminated consumers, so partial eval
        # prunes its backward pass and saves no residuals for it — fusing
        # both into one stacked scan would re-attach the target lane to the
        # VJP (zero cotangents still cost full backward matmuls + 2x scan
        # residual memory), trading a halved forward for a heavier backward
        trunk = self.mac.trunk
        with jax.named_scope("learner.agent"):
            if trunk is not None:
                qs, hs, moe_aux = self._unroll_trunk(params["agent"], obs,
                                                     compact_tm)
            else:
                qs, hs = self._unroll_agent(params["agent"], obs, k_ag,
                                            compact_tm=compact_tm)
        with jax.named_scope("learner.target"):
            if trunk is not None:
                target_qs, target_hs, _ = self._unroll_trunk(
                    target_params["agent"], obs, compact_tm)
            else:
                target_qs, target_hs = self._unroll_agent(
                    target_params["agent"], obs, k_tag,
                    compact_tm=compact_tm)

        # mixer-side padding mask (graftworld fleet-size randomization,
        # ROADMAP item 3's open remainder): padded agents are
        # action-0-only at EVERY step by construction (the env masks
        # them at reset and they can never acquire a job) AND always
        # occupy the TRAILING agent slots (EnvParams.agent_mask is
        # `arange < n_active`), so the maximal trailing block of agents
        # with "no non-idle action ever available across the episode
        # incl. the bootstrap step" identifies them from the stored
        # avail mask alone — no schema change, works for dense AND
        # compact storage. The suffix rule matters: an ACTIVE agent
        # whose job stream delivered nothing all episode is also
        # idle-only-forever, and a plain any-step test would zero its
        # (real) idle-Q contribution; with the suffix rule it is only
        # conservatively masked when every agent after it is idle-only
        # too (rare — and its sole contribution would have been the
        # idle-action Q of an agent that never interacted). Masked
        # agents' chosen/target Qs and hidden tokens enter the mixer
        # multiplied by 0.0 (the neutral contribution of a monotonic
        # mixer); active agents multiply by 1.0, which is bitwise-
        # identity, so a full-fleet batch where any tail agent saw a
        # job is bit-identical to the unmasked loss (pinned by
        # tests/test_population.py). The gate is config-STATIC
        # (_mask_padded): non-padding configs never compile any of
        # this.
        with jax.named_scope("learner.loss"):
            if self._mask_padded:
                saw_job = (avail[..., 1:] > 0).any(axis=(0, -1))  # (B, A)
                # active = suffix-any of saw_job: agent i is masked only
                # when agents i..A-1 ALL never saw a job (the padded tail)
                act_m = jnp.flip(jax.lax.cummax(
                    jnp.flip(saw_job.astype(jnp.int32), -1), axis=1),
                    -1) > 0

                def _padmask(x):
                    # zero padded agents along the trailing agent axis
                    # (x: (T?, B, A) or (T?, B, A, F))
                    m = act_m.astype(x.dtype)
                    return x * (m[None] if x.ndim == 3 else m[None, ..., None])

                hs, target_hs = _padmask(hs), _padmask(target_hs)
                if obs is not None:
                    # Q12 fallback path: the mixer tokenizes all agents'
                    # obs — padded rows go in as zeros too
                    obs = _padmask(obs)
            else:
                _padmask = lambda x: x  # noqa: E731 — static no-op branch

            chosen = _padmask(jnp.take_along_axis(
                qs[:-1], actions[..., None], axis=-1)[..., 0])  # (T, B, A)

            # illegal actions suppressed in targets (MAC masking contract);
            # computed over ALL T+1 steps so the target mixer can unroll its
            # hyper-token recurrence from t=0 with the same history depth as
            # the online mixer (the targets themselves use steps [1:])
            masked_all = jnp.where(avail > 0, qs, -jnp.inf)
            if cfg.double_q:
                best = jnp.argmax(masked_all, axis=-1)         # online argmax
                target_max = jnp.take_along_axis(
                    target_qs, best[..., None], axis=-1)[..., 0]
            else:
                target_max = jnp.where(
                    avail > 0, target_qs, -jnp.inf).max(axis=-1)
            target_max = _padmask(target_max)

            obs_m = None if obs is None else obs[:-1]
        with jax.named_scope("learner.mixer"):
            q_tot = self._unroll_mixer(
                params["mixer"], chosen, hs[:-1], state[:-1], obs_m, k_mx)
        # target unroll spans t=0..T (recurrence semantics of
        # /root/reference/n_transf_mixer.py:55,91: both nets start their
        # hyper recurrence at the episode start); outputs [1:] are the
        # bootstrap values
        with jax.named_scope("learner.target"):
            target_q_tot = self._unroll_mixer(
                target_params["mixer"], target_max, target_hs, state,
                obs, k_tmx)[1:]   # obs may be None (compact storage: the
            # state-entity mixer never reads it)

        with jax.named_scope("learner.loss"):
            # reward_unit: static train-time unit normalization (the value
            # function is learned in reward/reward_unit units; logged returns
            # stay raw — see config.py loss-scale levers). 1.0 = off, exact.
            if cfg.reward_unit != 1.0:
                reward = reward / cfg.reward_unit
            targets = reward + cfg.gamma * (1.0 - term) * target_q_tot
            td = (q_tot - jax.lax.stop_gradient(targets)) * mask

            denom = jnp.maximum(mask.sum(), 1.0)
            if cfg.td_loss == "huber":
                # 2x-scaled Huber: td^2 inside |td|<=delta (matches the MSE
                # branch exactly), linear with slope 2*delta outside — bounds
                # each element's dLoss/dq_tot at 2*delta (config.py rationale).
                # Deliberately NOT optax.huber_loss: its min()-based form
                # accumulates backward cotangents as q + delta - delta, which
                # cancels catastrophically in f32 once delta >> |td| (grads of
                # small TDs round to 0 at delta=1e9, breaking the delta->inf
                # == MSE identity the tests pin); branch selection via where
                # keeps each cotangent path exact at any delta.
                d = cfg.huber_delta
                abs_td = jnp.abs(td)
                elem = jnp.where(abs_td <= d, td ** 2,
                                 2.0 * d * abs_td - d * d)
            else:
                elem = td ** 2
            loss = (weights[None, :] * elem).sum() / denom

            ep_mask = jnp.maximum(mask.sum(axis=0), 1.0)
            info = {
                "loss": loss,
                "td_error_abs": jnp.abs(td).sum() / denom,
                "q_taken_mean": (chosen.mean(axis=-1) * mask).sum() / denom,
                "target_mean": (targets * mask).sum() / denom,
                # per-episode priorities (Q9): masked mean |TD| per sample
                "td_errors_abs": jnp.abs(td).sum(axis=0) / ep_mask,   # (B,)
            }
            if trunk is not None:
                # the online unroll's routed pairs (the target's routing
                # is the last sync's parameters' and is not counted)
                from ..models.trunk import moe_counters
                a = self.mac.n_agents
                info.update(moe_counters(
                    jax.lax.stop_gradient(moe_aux),
                    avail.shape[0] * avail.shape[1] * a * (a + 1), trunk))
        if cfg.obs.sight.enabled:
            # graftsight in-graph diagnostics (docs/OBSERVABILITY.md §6):
            # value-scale histograms + one-timestep attention-entropy
            # probes, reduced on device into the info dict so they ride
            # the log-cadence fetch. STATIC gate — off leaves this
            # program byte-identical (graftprog fingerprints pinned);
            # stop_gradient severs every probe from the backward pass.
            from ..obs import sight as graftsight
            sg = jax.lax.stop_gradient
            info.update(graftsight.loss_sight_info(
                cfg.obs.sight, sg(td), sg(chosen), sg(targets), mask))
            if graftsight.agent_probe(cfg):
                info["sight_attn_entropy_agent"] = \
                    graftsight.agent_attention_entropy(
                        self, params["agent"],
                        None if obs is None else obs[0],
                        None if compact_tm is None
                        else tuple(x[0] for x in compact_tm))
            if cfg.mixer == "transformer":
                info["sight_attn_entropy_mixer"] = \
                    graftsight.mixer_attention_entropy(
                        self, params["mixer"], state[0],
                        None if obs is None else obs[0], sg(hs[0]))
        return loss, info

    # ------------------------------------------------------------------ train

    def train_info_zeros(self, batch_size: int) -> Dict[str, jnp.ndarray]:
        """Aval-matched zero info dict for a SKIPPED train step — the
        superstep's ``lax.cond`` needs both branches to return identical
        pytrees (``run.Experiment.superstep_program``). Must mirror the
        keys/shapes/dtypes ``train`` emits; ``all_finite=True`` so skipped
        sub-iterations never feed the driver's non-finite streak
        accounting."""
        z = jnp.zeros((), jnp.float32)
        out = {
            "loss": z, "td_error_abs": z, "q_taken_mean": z,
            "target_mean": z, "grad_norm": z,
            "td_errors_abs": jnp.zeros((batch_size,), jnp.float32),
            "all_finite": jnp.ones((), bool),
        }
        if self.mac.trunk is not None:
            from ..models.trunk import MOE_COUNTERS
            out.update({k: z for k in MOE_COUNTERS})
        if self.cfg.obs.sight.enabled:
            # graftsight keys are part of the emitted pytree when the
            # static gate is on — the skip branch must mirror them
            # (aval-exact; the key set is a function of the CONFIG)
            from ..obs import sight as graftsight
            out.update(graftsight.train_info_extras_zeros(self.cfg))
        return out

    def train(self, ls: LearnerState, batch: EpisodeBatch,
              weights: jnp.ndarray, t_env: jnp.ndarray,
              episode: jnp.ndarray, key: Optional[jax.Array] = None,
              spec=None) -> Tuple[LearnerState, Dict[str, jnp.ndarray]]:
        """One importance-weighted QMIX update; hard target sync every
        ``target_update_interval`` episodes (PyMARL convention, M8).
        ``key`` drives NoisyLinear/dropout sampling and is required when the
        config uses either (otherwise sigma params get zero gradient).

        ``spec`` (a graftpop ``PopulationSpec`` of traced per-member
        scalars, ``None`` for every pre-population caller) applies the
        member's learning rate as an update-tree scale: lr enters
        optax's adam/rmsprop linearly AFTER the moment statistics, so
        ``updates · (lr_i/lr)`` is exactly training at ``lr_i`` — and
        the clip-by-global-norm rung acts on raw gradients, which are
        lr-independent. 1.0 multiplies bitwise-identically (the P=1
        parity contract).

        Non-finite guard rail (docs/RESILIENCE.md): ``info["all_finite"]``
        flags whether loss AND gradients came out finite; when it trips,
        params and optimizer state pass through UNCHANGED (elementwise
        select inside jit — no host sync, the async dispatch pipeline
        stays unblocked) and the driver decides at its log cadence whether
        the streak warrants a checkpoint restore. ``train_steps`` counts
        train-step *invocations* (skipped updates included) so fault
        injection and step-indexed diagnostics stay monotonic across
        skips. ``isfinite(global_norm)`` covers every grad leaf: one
        NaN/Inf anywhere poisons the norm."""
        del t_env
        if self.needs_rngs and key is None:
            raise ValueError(
                "QMixLearner.train needs a PRNG key when "
                "action_selector='noisy-new' or dropout>0 (noise/dropout "
                "must be sampled during the loss unroll)")
        if not self.needs_rngs:
            key = None   # identical program for all callers in the pure path
        opt = _make_optimizer(self.cfg)

        inject_at = self.cfg.resilience.inject_nan_at_step

        def loss_fn(params):
            loss, info = self._loss(params, ls.target_params, batch,
                                    weights, key)
            if inject_at >= 0:       # fault injection (static: free when off)
                trip = ls.train_steps == inject_at
                loss = loss * jnp.where(trip, jnp.float32(jnp.nan),
                                        jnp.float32(1.0))
                info = dict(info, loss=loss)
            return loss, info

        grads, info = jax.grad(loss_fn, has_aux=True)(ls.params)
        with jax.named_scope("learner.optimizer"):
            info["grad_norm"] = optax.global_norm(grads)
            all_finite = (jnp.isfinite(info["loss"])
                          & jnp.isfinite(info["grad_norm"]))
            info["all_finite"] = all_finite
            updates, opt_state = opt.update(grads, ls.opt_state, ls.params)
            if spec is not None:
                # graftpop per-member lr: scale the update tree (exact —
                # see the docstring; opt_state is lr-independent by
                # construction)
                updates = jax.tree.map(
                    lambda u: u * spec.lr_scale.astype(u.dtype), updates)
            params = optax.apply_updates(ls.params, updates)
            # guard rail: a tripped step is a no-op on params AND opt
            # state (a NaN grad corrupts Adam's mu/nu permanently, so
            # opt_state must pass through too, not just params)
            params = jax.tree.map(
                lambda n, o: jnp.where(all_finite, n, o), params, ls.params)
            opt_state = jax.tree.map(
                lambda n, o: jnp.where(all_finite, n, o), opt_state,
                ls.opt_state)
        if self.cfg.obs.sight.enabled:
            # graftsight learner-tail block: per-module grad/update
            # norms, importance-weight ESS, target drift — computed
            # AFTER the guard select so a tripped step reports the
            # surviving (unchanged) params' drift, not the poisoned ones
            from ..obs import sight as graftsight
            info.update(graftsight.learner_train_info(
                self.cfg, grads, updates, params, ls.target_params,
                weights))

        with jax.named_scope("learner.optimizer"):
            episode = jnp.asarray(episode, jnp.int32)
            sync = (episode - ls.last_target_update
                    ) >= self.cfg.target_update_interval
            target_params = jax.tree.map(
                lambda p, tp: jnp.where(sync, p, tp), params,
                ls.target_params)
            return LearnerState(
                params=params,
                target_params=target_params,
                opt_state=opt_state,
                train_steps=ls.train_steps + 1,
                last_target_update=jnp.where(sync, episode,
                                             ls.last_target_update),
            ), info


LEARNER_REGISTRY = {"qmix_learner": QMixLearner}


def register_audit_programs(ctx):
    """graftprog registry hook (``analysis/registry.py``): the bare
    learner update as its own named program — the narrowest surface the
    dtype-churn rule (GP203) watches, so an upcast introduced in the
    loss/optimizer math is attributed to the learner even before it
    shows up in the fused superstep's budgets. Audited from abstract
    avals only (the replay sample's eval_shape); never executed."""
    import jax

    from ..analysis.registry import AuditProgram, kernels_audit_context

    def entry(c, description):
        exp, ts, cfg = c.exp, c.ts_shape, c.cfg
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        batch, _, weights = jax.eval_shape(
            lambda b, k, t: exp.buffer.sample(b, k, cfg.batch_size, t),
            ts.buffer, key, ts.runner.t_env)
        train = jax.jit(exp.learner.train)
        return AuditProgram(
            train, (ts.learner, batch, weights, ts.runner.t_env,
                    ts.episode, key),
            description=description)

    out = {"learner_train": entry(
        ctx, "one importance-weighted QMIX update (loss + optimizer + "
             "target sync)")}
    # kernel-mode byte-comparison pair (PR 13): the bare learner update
    # under each kernels.attention mode at the kernel audit scale —
    # narrows the train_iter_pallas[_ref] comparison to the learner
    # alone, so a bytes regression is attributable before it shows up in
    # the composite program (lowered level; pallas pinned strictly below
    # the _ref twin by tests/test_graftprog.py)
    for mode, name in (("pallas", "learner_train_pallas"),
                       ("xla", "learner_train_pallas_ref")):
        out[name] = entry(
            kernels_audit_context(mode),
            f"one QMIX update under kernels.attention={mode} at the "
            f"kernel audit scale — the flash-vs-einsum learner byte "
            f"comparison (pallas must stay strictly below the _ref "
            f"twin)")
    return out
