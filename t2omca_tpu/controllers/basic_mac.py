"""Multi-agent controller (M7, the unreleased ``controllers`` package).

Contract pinned by the call sites (SURVEY.md §2.3 M7): owns the shared-
parameter agent network and the action selector; ``init_hidden(batch)``;
``select_actions(batch_slice, t_env, key, test_mode)`` masking illegal
actions with ``avail_actions``; agents grouped under ``"agents"`` share one
parameter set (the reference folds the agent axis into the batch axis,
``/root/reference/transf_agent.py:56-59`` — we do the same inside
``TransformerAgent``).

Functional form: the MAC is a frozen descriptor (module + selector); all
state (params, hidden tokens) is passed explicitly, so the same MAC drives
the jitted rollout scan, the learner's time unroll, and greedy evaluation.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from ..components.action_selectors import SELECTOR_REGISTRY
from ..components.schedules import DecayThenFlatSchedule
from ..config import TrainConfig
from ..models.agent import TransformerAgent
from ..models.rnn_agent import RNNAgent
from ..models.trunk import TrunkAgent

#: agent families (parent PyMARL lineage registry pattern, SURVEY.md §2.3 M7)
AGENT_REGISTRY = {"transformer": TransformerAgent, "rnn": RNNAgent}


@dataclasses.dataclass(frozen=True)
class BasicMAC:
    agent: TransformerAgent
    selector: object            # EpsilonGreedySelector | NoisySelector
    n_agents: int
    n_actions: int
    emb: int
    use_qslice: bool = False    # exact token-0-only forward (ops/query_slice)
    use_entity_tables: bool = False   # table-contracted entity acting
    # acting's entity-table attention may run as the Pallas kernel
    # (kernels/entity_attention.py; engaged from shapes and platform, see
    # agent_forward_qslice_entity). Off where the env lanes of one program
    # span devices or members: the kernel is one custom call, which GSPMD
    # cannot partition (dp_devices, sebulba), and under a population's vmap
    # it has never run on the chip — those keep the XLA association
    entity_kernel: bool = True
    # acting-path compute dtype (model.act_dtype, docs/PERF.md): None =
    # inherit the agent's (train) dtype — byte-identical to pre-act_dtype
    # builds. When it differs, select_actions runs its forwards in this
    # dtype over params pre-cast once per rollout
    # (prepare_acting_params), while the learner unrolls keep the train
    # dtype (acting=False default on the forwards below).
    act_dtype: object = None
    # dense-path module clone at act_dtype (None = share `agent`); the
    # qslice/entity forwards take the dtype as an argument instead
    act_agent: object = None
    # a catalog trunk (config.TrunkConfig; models/trunk.py) in place of
    # the T2OMCA stack: `agent` is then a TrunkAgent and every forward
    # below that slices or tables is off (no token is pinned to layer 0)
    trunk: object = None

    @classmethod
    def build(cls, cfg: TrainConfig, env_info: dict) -> "BasicMAC":
        n_agents = env_info["n_agents"]
        n_entities = cfg.model.n_entities_obs or env_info["n_entities"]
        feat = env_info.get("obs_entity_feats")
        if feat is None or cfg.agent == "rnn":
            # flat-obs mode / flat-input agents: the whole obs vector is one
            # entity token
            n_entities, feat = 1, env_info["obs_shape"]
        if cfg.model.trunk is not None:
            # a catalog trunk as the token stack (models/trunk.py): the
            # eligibility predicates below all read False for it
            agent = TrunkAgent(
                n_agents=n_agents, n_entities=n_entities, feat_dim=feat,
                emb=cfg.model.emb, n_actions=env_info["n_actions"],
                trunk=cfg.model.trunk, dtype=jnp.dtype(cfg.model.dtype))
        else:
            agent = AGENT_REGISTRY[cfg.agent](
                n_agents=n_agents,
                n_entities=n_entities + 0,
                feat_dim=feat,
                emb=cfg.model.emb,
                heads=cfg.model.heads,
                depth=cfg.model.depth,
                n_actions=env_info["n_actions"],
                ff_hidden_mult=cfg.model.ff_hidden_mult,
                dropout=cfg.model.dropout,
                noisy=cfg.action_selector == "noisy-new",
                standard_heads=cfg.model.standard_heads,
                use_orthogonal=cfg.model.use_orthogonal,
                dtype=jnp.dtype(cfg.model.dtype),
                attn_impl=cfg.kernels.attention,
            )
        schedule = DecayThenFlatSchedule(
            cfg.epsilon_start, cfg.epsilon_finish, cfg.epsilon_anneal_time)
        selector = SELECTOR_REGISTRY[cfg.action_selector](schedule)
        # query-slice eligibility (shared predicate, ops/query_slice.py)
        from ..ops.query_slice import (agent_qslice_eligible,
                                       entity_tables_eligible)
        use_qslice = agent_qslice_eligible(cfg)
        act_dtype = jnp.dtype(cfg.model.act_dtype or cfg.model.dtype)
        # param shapes are dtype-independent, so the acting clone applies
        # the SAME param tree — only the activation casts differ
        act_agent = (agent.clone(dtype=act_dtype)
                     if act_dtype != agent.dtype else None)
        return cls(agent=agent, selector=selector, n_agents=n_agents,
                   n_actions=env_info["n_actions"], emb=cfg.model.emb,
                   use_qslice=use_qslice,
                   use_entity_tables=(use_qslice
                                      and entity_tables_eligible(cfg)),
                   entity_kernel=not (cfg.dp_devices > 1
                                      or cfg.sebulba.actor_devices > 1
                                      or cfg.population.size > 0),
                   act_dtype=act_dtype, act_agent=act_agent,
                   trunk=cfg.model.trunk)

    # ------------------------------------------------------------------ state

    def init_params(self, key: jax.Array, obs_dim: int):
        obs = jnp.zeros((1, self.n_agents, obs_dim))
        h = self.init_hidden(1)
        return self.agent.init(key, obs, h)

    def init_hidden(self, batch_size: int) -> jnp.ndarray:
        """Zeros ``(batch, n_agents, emb)`` (``transf_agent.py:50-52``)."""
        return self.agent.initial_hidden(batch_size)

    # ------------------------------------------------------------------ forward

    @property
    def _acting_dtype(self):
        """Acting-path compute dtype (falls back to the train dtype for
        MACs constructed directly in tests/legacy callers)."""
        return (self.act_dtype if self.act_dtype is not None
                else self.agent.dtype)

    def forward(self, params, obs: jnp.ndarray, hidden: jnp.ndarray,
                key: jax.Array | None = None, deterministic: bool = True,
                acting: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """obs ``(B, A, obs_dim)`` → (q ``(B, A, n_actions)``, hidden').
        ``key`` seeds NoisyLinear resampling and dropout when
        ``deterministic`` is False. ``acting=True`` (select_actions)
        runs the act_dtype module clone; the learner unroll keeps the
        default (train dtype)."""
        if key is not None:
            k_noise, k_drop = jax.random.split(key)
            rngs = {"noise": k_noise, "dropout": k_drop}
        else:
            rngs = None
        module = (self.act_agent if acting and self.act_agent is not None
                  else self.agent)
        return module.apply(params, obs, hidden,
                            deterministic=deterministic, rngs=rngs)

    def _noise_key(self, key, deterministic: bool):
        """Noise key for the qslice/entity q-head: only noisy agents in
        non-deterministic (train rollout / learner) mode sample noise —
        mirroring ``TransformerAgent``'s eval-mode mu path."""
        if key is None or deterministic or not self.agent.noisy:
            return None
        return key

    def forward_qslice(self, params, obs: jnp.ndarray, hidden: jnp.ndarray,
                       key: jax.Array | None = None,
                       deterministic: bool = True,
                       acting: bool = False,
                       attn_impl: str | None = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Exact token-0-only forward over the same param tree
        (ops/query_slice). Plain jnp, differentiable — also used by the
        learner's deterministic AND noisy unrolls (the noise lives only in
        the q-head). ``params`` may be the raw tree or a
        ``prepare_acting_params`` result; ``acting=True`` computes in the
        act_dtype (and must be paired with the acting-dtype fold — the
        folded tree short-circuits the per-call fold).

        ``attn_impl`` selects the sliced-attention lowering
        (``kernels.attention``); ``None`` keeps the einsum path, so
        acting, serving and every legacy caller stay byte-identical —
        ONLY the learner unroll passes the config switch (the flash
        kernel's win is the train-path backward, docs/PERF.md)."""
        from ..ops.query_slice import agent_forward_qslice
        a = self.agent
        return agent_forward_qslice(
            params, obs, hidden,
            n_entities=a.n_entities, feat_dim=a.feat_dim, emb=a.emb,
            heads=a.heads, depth=a.depth, n_actions=a.n_actions,
            standard_heads=a.standard_heads,
            dtype=self._acting_dtype if acting else a.dtype,
            noise_key=self._noise_key(key, deterministic),
            attn_impl=attn_impl or "xla")

    def forward_entity(self, params, compact, hidden: jnp.ndarray,
                       key: jax.Array | None = None,
                       deterministic: bool = True,
                       acting: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Entity-table forward (ops/query_slice): ``compact`` is the
        ``env.compact_obs`` tuple, batched over envs."""
        from ..ops.query_slice import agent_forward_qslice_entity
        rows, same_mec, mean, std = compact
        a = self.agent
        return agent_forward_qslice_entity(
            params, rows, same_mec, mean, std, hidden,
            emb=a.emb, heads=a.heads, depth=a.depth, n_actions=a.n_actions,
            standard_heads=a.standard_heads,
            dtype=self._acting_dtype if acting else a.dtype,
            noise_key=self._noise_key(key, deterministic),
            kernel=acting and self.entity_kernel)

    def describe_acting(self, lanes: int, platform: str) -> str:
        """What ``act`` compiles for ``lanes`` envs on ``platform``, for
        the run's start-up log: its forward (trunk / entity tables /
        qslice / obs — the order ``act`` tries them in) and that forward's
        attention (``kernel``: acting's entity kernel, engaged from the
        same shapes ``agent_forward_qslice_entity`` reads; ``pallas``: the
        flash kernel of the dense module under ``kernels.attention``;
        ``xla`` otherwise)."""
        a = self.agent
        attn = "xla"
        if self.trunk is not None:
            fwd = "trunk"
        elif self.use_entity_tables:
            fwd = "entity tables"
            from ..kernels import entity_attention as ek
            if (self.entity_kernel and platform == "tpu"
                    and a.standard_heads and a.heads > 1
                    and ek.eligible(lanes, self.n_agents, a.emb, a.emb,
                                    a.heads)):
                attn = "kernel"
        elif self.use_qslice:
            fwd = "qslice"
        else:
            fwd = "obs"
            attn = getattr(a, "attn_impl", "none")
        return f"acting forward: {fwd}, attention: {attn}"

    def trunk_tokens(self, obs, compact=None) -> jnp.ndarray:
        """The normalised entity tokens ``(B, A, A, 9)`` a catalog trunk
        reads: rebuilt from the factored observation where there is one
        (``compact``: the ``env.compact_obs`` tuple or compact storage's),
        else the dense ``obs (B, A, obs_dim)`` reshaped."""
        from ..models.trunk import entity_tokens
        if compact is not None:
            return entity_tokens(*compact)
        a = self.agent
        return obs.reshape(obs.shape[:2] + (a.n_entities, a.feat_dim))

    def forward_trunk(self, params, obs, hidden: jnp.ndarray,
                      compact=None, acting: bool = False):
        """The catalog trunk's forward (models/trunk.py) → (q, hidden',
        aux): every token through every layer (``trunk_tokens`` for its
        inputs). ``aux`` feeds the ``moe_*`` counters
        (``models/trunk.moe_counters``). ``params``: the raw tree or a
        ``prepare_acting_params`` / ``cast_weights`` result."""
        from ..models.trunk import agent_forward_trunk
        with jax.named_scope("agent.embed"):
            tokens = self.trunk_tokens(obs, compact)
        return agent_forward_trunk(
            params, tokens, hidden, tk=self.trunk,
            dtype=self._acting_dtype if acting else self.agent.dtype)

    def prepare_acting_params(self, params, dtype=None):
        """Pre-fold the qslice projection products ONCE, outside any scan
        that calls ``select_actions``/``forward_qslice`` in its body (the
        fold is loop-invariant; XLA is not guaranteed to hoist it). The
        fold runs in the ACTING dtype; under the bf16-acting mode
        (model.act_dtype over an f32 train dtype) the remaining float
        leaves are pre-cast here too, so every scan step reads half the
        param bytes instead of re-casting f32 storage per step. No-op
        on the dense path with the default act_dtype.

        ``dtype`` overrides the fold dtype (the serving exporter passes
        the TRAIN dtype so the artifact's canonical f32 variant stays
        act_dtype-free — serving's dtype story is the per-variant cast,
        not the training run's rollout knob)."""
        ad = jnp.dtype(dtype) if dtype is not None else self._acting_dtype
        with jax.named_scope("act.forward"):
            if self.trunk is not None:
                # the trunk's matmul weights at the acting dtype, once a
                # rollout (the router, the norms and the head stay f32)
                from ..models.trunk import cast_weights
                return cast_weights(params, ad)
            if not self.use_qslice:
                return self._cast_acting(params, ad)
            from ..ops.query_slice import fold_agent_params
            a = self.agent
            folded = fold_agent_params(params, emb=a.emb, heads=a.heads,
                                       depth=a.depth,
                                       standard_heads=a.standard_heads,
                                       dtype=ad)
            return self._cast_acting(folded, ad)

    def _cast_acting(self, tree, ad):
        """Pre-cast f32 param leaves to the acting dtype — only in the
        explicit mixed mode (act_dtype != train dtype), so every default
        config keeps its exact pre-act_dtype numerics. LayerNorm/softmax
        STATISTICS stay f32 regardless (computed in f32 inside the
        forwards; docs/PERF.md dtype policy)."""
        if ad == self.agent.dtype:
            return tree
        cast = lambda x: (x.astype(ad)
                          if (hasattr(x, "dtype")
                              and x.dtype == jnp.float32) else x)
        return jax.tree.map(cast, tree)

    def select_actions(self, params, obs: jnp.ndarray, avail: jnp.ndarray,
                       hidden: jnp.ndarray, key: jax.Array,
                       t_env: jnp.ndarray, test_mode: bool = False,
                       compact=None, eps_scale=None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """``act`` without its counters → (actions, hidden', epsilon)."""
        return self.act(params, obs, avail, hidden, key, t_env,
                        test_mode=test_mode, compact=compact,
                        eps_scale=eps_scale)[:3]

    def act(self, params, obs: jnp.ndarray, avail: jnp.ndarray,
            hidden: jnp.ndarray, key: jax.Array, t_env: jnp.ndarray,
            test_mode: bool = False, compact=None, eps_scale=None):
        """→ (actions ``(B, A)`` int32, hidden', epsilon, aux). ``aux`` is
        what the forward counts of itself — a catalog trunk's routed
        pairs (``forward_trunk``); ``{}`` for every other agent, which
        adds nothing to a traced program. The avail mask is
        applied inside the selector (illegal-action masking, M7).
        ``compact`` (the batched ``env.compact_obs`` tuple) activates the
        entity-table forward when the MAC was built eligible.
        ``eps_scale`` (optional traced scalar) is the graftpop
        per-member epsilon multiplier, forwarded to the selector."""
        k_noise, k_sel = jax.random.split(key)
        aux = {}
        with jax.named_scope("act.forward"):
            if self.trunk is not None:
                q, hidden, aux = self.forward_trunk(params, obs, hidden,
                                                    compact=compact,
                                                    acting=True)
            elif self.use_entity_tables and compact is not None:
                q, hidden = self.forward_entity(params, compact, hidden,
                                                key=k_noise,
                                                deterministic=test_mode,
                                                acting=True)
            elif self.use_qslice:
                q, hidden = self.forward_qslice(params, obs, hidden,
                                                key=k_noise,
                                                deterministic=test_mode,
                                                acting=True)
            else:
                q, hidden = self.forward(params, obs, hidden, key=k_noise,
                                         deterministic=test_mode,
                                         acting=True)
        actions, eps = self.selector.select(k_sel, q, avail, t_env,
                                            test_mode=test_mode,
                                            eps_scale=eps_scale)
        return actions.astype(jnp.int32), hidden, eps, aux


MAC_REGISTRY = {"basic_mac": BasicMAC}
