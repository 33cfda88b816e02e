"""Configuration system.

Replaces the reference's sacred config dict → ``SimpleNamespace`` flow
(``/root/reference/per_run.py:20-66,292-309``). The full flag inventory is the
set of ``args.*`` / ``config[...]`` accesses in the released reference slice
(SURVEY.md §5.6); every one of those flags exists here with the same name so a
reference user can carry their config across.

Config objects are frozen dataclasses (hashable → usable as jit static
arguments). ``load_config`` merges: defaults → optional YAML/JSON file →
``key=value`` CLI overrides, then runs the same sanity pass the reference
applies in ``args_sanity_check`` (``/root/reference/per_run.py:292-309``).
"""

from __future__ import annotations

import dataclasses
import functools
import json
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple, Union


@dataclass(frozen=True)
class ScenarioConfig:
    """graftworld scenario-distribution surface (``env_args.scenario.*``,
    envs/graftworld.py, docs/ENVS.md). Every collection field is a tuple
    — the config tree stays hashable, so jitted programs can close over
    the resolved distribution as static structure. ``kind`` empty (the
    default) means "this env key's registry default scenario"
    (envs/registry.py ``scenario_config``), which for the classic
    ``multi_agv_offloading`` key is the fixed baseline — byte-identical
    behavior for every pre-graftworld config. An EXPLICIT kind always
    wins over the registry default, even when it names the baseline
    point (the empty sentinel exists exactly so that explicit-baseline-
    over-a-family-key stays expressible)."""

    # "" = the env key's registry default; fixed = one parameter point;
    # uniform = uniform ranges over knobs; mixture = weighted mixture
    # over family distributions
    kind: str = ""
    # the scenario family (fixed/uniform kinds): baseline | hetfleet |
    # interference | surge (envs/graftworld.FAMILY_NAMES)
    family: str = "baseline"
    # uniform kind: ((knob, lo, hi), ...); empty = the family's
    # canonical envelope (graftworld.FAMILY_RANGES)
    ranges: Tuple[Tuple[str, float, float], ...] = ()
    # fixed/uniform kinds: ((knob, value), ...) applied over the
    # family preset before any range draws
    overrides: Tuple[Tuple[str, float], ...] = ()
    # mixture kind: component family names; empty = all families
    families: Tuple[str, ...] = ()
    # mixture kind: component weights; empty = uniform
    weights: Tuple[float, ...] = ()
    # fleet-size randomization (the padding axis): each lane draws
    # n_active ~ U{min_agents..agv_num} at reset; 0 = always the full
    # static fleet
    min_agents: int = 0


@dataclass(frozen=True)
class EnvConfig:
    """Environment flags (reference ``env_args``, SURVEY.md §5.6)."""

    key: str = "multi_agv_offloading"     # env registry name (ref: env / map_name)
    map_name: str = "multi_agv"
    seed: int = 0
    mec_num: int = 2
    agv_num: int = 4
    num_channels: int = 2
    episode_limit: int = 150
    obs_entity_mode: bool = True
    state_entity_mode: bool = True
    state_last_action: bool = False
    edge_only: bool = False
    # one order-free batched Welford update per step instead of the
    # reference's sequential per-agent loop (O(A/n) transient deviation;
    # see envs/normalization.py:welford_update_batch). Default ON: it gates
    # the whole fast-path stack (entity-table acting + compact entity
    # storage, ops/query_slice.py eligibility predicates) and is validated
    # end-to-end by the config-1 faststack sweep
    # (runs/config1_faststack/SUMMARY.md). Reference-exact parity configs
    # (sequential normalizer ordering) opt out with fast_norm=False.
    fast_norm: bool = True
    # train-time reward scaling (the reference env imports RewardScaling
    # but the released slice never instantiates it — provided wired): each
    # env lane divides its recorded rewards by the running std of its
    # discounted return (envs/normalization.py scale_reward; the
    # discounted-return accumulator resets at episode start, the running
    # std persists across episodes). Logged returns/metrics stay RAW;
    # only the replay-recorded reward the learner trains on is scaled.
    # Off by default — changes the loss scale, so parity configs must not
    # enable it.
    reward_scaling: bool = False

    # ----- physics / M1 spec values (frozen in docs/SPEC.md §1; the reference
    # does not release data_struct_multiagv, so these are our pinned choices)
    mec_radius_m: float = 50.0            # placement radius & spacing/2 (ref env :23-24)
    communication_range_m: float = 50.0   # MEC.communication_range (M1)
    mec_compute_cap: float = 20e9         # cycles/s (M1)
    user_compute_cap: float = 5e9         # cycles/s (M1)
    transmit_power_w: float = 0.5         # W (M1)
    latency_max_ms: float = 100.0         # job deadline budget (M1)
    job_prob: float = 0.5                 # P(generate_job emits a job) per slot (M1)
    data_size_min: float = 4000.0         # bits (M1)
    data_size_max: float = 12000.0        # bits (M1)

    # graftworld scenario distribution (envs/graftworld.py, docs/ENVS.md):
    # which EnvParams each env lane samples at reset. Default = the env
    # key's registry default (fixed baseline for the classic key).
    scenario: "ScenarioConfig" = field(default_factory=lambda: ScenarioConfig())


@dataclass(frozen=True)
class LayerSpec:
    """What one held layer IS (``TrunkSpec.layers``)."""

    rope: bool                # rotary positions on q and k
    window: int               # keys this far back are masked; 0: none are
    dense_width: int          # a dense feed-forward of this width; 0: routed


@dataclass(frozen=True)
class TrunkSpec:
    """A trunk's layers as MECHANISMS — the one description
    ``models/trunk.py`` reads, resolved from any of the three families'
    published keys (``TrunkConfig.spec``, ``AfmoeTrunkConfig.spec``,
    ``DeepseekV3TrunkConfig.spec``). Nothing here names a model: a layer
    function branches on these fields alone."""

    hidden_size: int
    head_dim: int             # width of a query / key head
    heads_held: int
    kv_heads_held: int
    rms_norm_eps: float
    rope_theta: float
    # residual form: pre-norm (a norm on each sublayer's input), or
    # sandwich (a second norm on each sublayer's OUTPUT, before the add)
    sandwich_norm: bool
    qk_norm: bool             # RMSNorm over head_dim on q and k, pre-RoPE
    attn_gate: bool           # sigmoid(u W_g) on the heads' output
    # router: what it reads (the layer's un-normed input, or the normed
    # feed-forward input), its scores (softmax | sigmoid), a bias added
    # for the SELECTION only, renormalisation over the kept, a scale
    router_reads_input: bool
    router_scores: str
    router_bias: bool
    route_norm: bool
    route_scale: float
    experts: int              # the router's outputs
    top_k: int
    experts_held: int
    expert_offset: int
    expert_width: int
    expert_act: str           # relu | silu, gating every feed-forward
    shared_width: int         # a shared expert beside the routed; 0: none
    layers: Tuple[LayerSpec, ...]
    # the attention KIND. kv_latent 0: grouped-query — every key/value
    # head its own W_k / W_v of head_dim, RoPE over the whole head.
    # kv_latent > 0: latent — ONE down-projection a token to a latent of
    # this width (RMS-normed, then up-projected to every held head's
    # no-position key of qk_nope_dim and value of v_head_dim) and to ONE
    # rotary key of qk_rope_dim that all heads read; a query head is
    # [qk_nope_dim | qk_rope_dim] = head_dim wide and RoPE turns its
    # qk_rope_dim alone (no q/k norm, no kv_heads_held in this kind)
    kv_latent: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0       # width of a value head; 0: head_dim
    # RoPE's pairing: (2i, 2i + 1) turn together, or (i, i + D/2)
    rope_interleave: bool = False

    @property
    def expert_layers(self) -> int:
        return sum(not layer.dense_width for layer in self.layers)

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim


class _TrunkShare:
    """Which part of every layer THIS chip holds, from the fields both
    families carry (``num_attention_heads``, ``num_key_value_heads``,
    ``heads_held``, ``experts_held``, ``share_index``)."""

    @property
    def attention_ways(self) -> int:
        return self.num_attention_heads // self.heads_held

    @property
    def kv_heads_held(self) -> int:
        return self.num_key_value_heads // self.attention_ways

    @property
    def expert_offset(self) -> int:
        """The first expert this chip holds."""
        return self.share_index * self.experts_held


@dataclass(frozen=True)
class TrunkConfig(_TrunkShare):
    """A decoder trunk from the public catalog as the transformer agent's
    token stack (``models/trunk.py``; ``model.trunk`` — absent, the agent
    is the T2OMCA stack and nothing here is read). Three families are
    written down, each under the key names of its own published
    ``config.json``: this class (no ``model_type`` key; SmallThinker's
    names: pre-norm residuals, a softmax top-k router reading the layer's
    input, ReGLU experts, ``rope_layout`` / ``sliding_window_layout``),
    ``AfmoeTrunkConfig`` (``model_type: afmoe``) and
    ``DeepseekV3TrunkConfig`` (``model_type: deepseek_v3``: latent
    attention). All resolve to one ``TrunkSpec`` (``.spec``), which is
    all the layer function reads.

    The last three keys say which part of each layer THIS chip holds: the
    deployment divides a layer's experts ``moe_num_primary_experts /
    experts_held`` ways and its attention ``num_attention_heads /
    heads_held`` ways (each attention share goes with
    ``num_key_value_heads`` over that many ways key/value heads), and
    ``share_index`` is this chip's place in the expert group — expert
    block ``share_index``, attention share ``share_index`` modulo the
    attention ways. The router keeps all ``moe_num_primary_experts``
    outputs and ``moe_num_active_primary_experts`` experts a token at
    every share. The layer runs without its exchange: the partial sums of
    this share are what the next layer reads."""

    hidden_size: int = 2560
    head_dim: int = 128
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    num_hidden_layers: int = 4
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # per layer, as published: 1 = rotary positions / sliding window,
    # 0 = no positional encoding / the whole prefix. Layer l reads entry
    # l; entries past num_hidden_layers belong to layers on other chips
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    sliding_window_size: int = 4096
    rope_theta: float = 1_500_000.0
    experts_held: int = 8
    heads_held: int = 7
    share_index: int = 0

    def check(self) -> None:
        """The family's own refusals (``sanity_check`` holds the shared
        ones)."""
        if (len(self.rope_layout) < self.num_hidden_layers
                or len(self.sliding_window_layout) < self.num_hidden_layers):
            raise ValueError("model.trunk: rope_layout / "
                             "sliding_window_layout need an entry per layer")
        if (not self.moe_primary_router_apply_softmax
                or not self.norm_topk_prob):
            raise ValueError("model.trunk covers the softmax top-k router "
                             "with renormalised weights")

    @functools.cached_property
    def spec(self) -> TrunkSpec:
        return TrunkSpec(
            hidden_size=self.hidden_size, head_dim=self.head_dim,
            heads_held=self.heads_held, kv_heads_held=self.kv_heads_held,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            sandwich_norm=False, qk_norm=False, attn_gate=False,
            router_reads_input=True, router_scores="softmax",
            router_bias=False, route_norm=True, route_scale=1.0,
            experts=self.moe_num_primary_experts,
            top_k=self.moe_num_active_primary_experts,
            experts_held=self.experts_held,
            expert_offset=self.expert_offset,
            expert_width=self.moe_ffn_hidden_size, expert_act="relu",
            shared_width=0,
            layers=tuple(
                LayerSpec(rope=bool(self.rope_layout[i]),
                          window=(self.sliding_window_size
                                  if self.sliding_window_layout[i] else 0),
                          dense_width=0)
                for i in range(self.num_hidden_layers)))


@dataclass(frozen=True)
class AfmoeTrunkConfig(_TrunkShare):
    """``model.trunk`` with ``model_type: afmoe`` (Trinity's family; the
    keys by the names of its published ``config.json``): sandwich norms
    (a norm on each sublayer's input AND on its output), RMSNorm on q and
    k per head, an output gate on attention, rotary positions and a
    window on the ``sliding_attention`` layers only; the first
    ``num_dense_layers`` layers a dense SwiGLU feed-forward of
    ``intermediate_size``, the others ``num_experts`` routed SwiGLU
    experts of ``moe_intermediate_size`` (``num_experts_per_tok`` a
    token: sigmoid scores of the normed feed-forward input, a bias that
    enters the selection only, the kept scores renormalised and scaled by
    ``route_scale``) beside ``num_shared_experts`` shared ones.

    The share is ``TrunkConfig``'s (``experts_held``, ``heads_held``,
    ``share_index``), and ``first_layer`` besides: the held layers are the
    published layers ``first_layer … first_layer + num_hidden_layers -
    1``, and ``layer_types`` / ``num_dense_layers`` are read at those
    published indices (``num_dense_layers`` keeps its published value).
    The shared experts and a dense layer's feed-forward are held whole:
    every chip of the group computes them alike. Not carried: the load
    statistic's update of the selection bias (``load_balance_coeff``) —
    the bias is a parameter the optimizer never moves — and muP's
    embedding multiplier (the trunk holds no vocabulary)."""

    model_type: str = "afmoe"
    hidden_size: int = 2048
    head_dim: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    num_hidden_layers: int = 5
    num_dense_layers: int = 2
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 2
    sliding_window: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    experts_held: int = 8
    heads_held: int = 8
    share_index: int = 0
    first_layer: int = 1

    def check(self) -> None:
        last = self.first_layer + self.num_hidden_layers
        if self.first_layer < 0 or len(self.layer_types) < last:
            raise ValueError(
                f"model.trunk: layer_types has {len(self.layer_types)} "
                f"entries, the held layers are {self.first_layer} … "
                f"{last - 1}")
        kinds = {"sliding_attention", "full_attention"}
        if set(self.layer_types) - kinds:
            raise ValueError(f"model.trunk: layer_types holds "
                             f"{sorted(set(self.layer_types) - kinds)}")
        if not 0 <= self.num_dense_layers < last:
            raise ValueError(
                f"model.trunk: num_dense_layers={self.num_dense_layers} "
                f"lies past the held layers ({self.first_layer} … "
                f"{last - 1}): no held layer would route")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("model.trunk: group-limited routing "
                             "(n_group / topk_group other than 1) is not "
                             "written")
        if (self.score_func != "sigmoid" or self.hidden_act != "silu"
                or self.num_shared_experts < 0):
            raise ValueError("model.trunk (afmoe) covers sigmoid scores "
                             "and SiLU-gated feed-forwards")

    @functools.cached_property
    def spec(self) -> TrunkSpec:
        held = range(self.first_layer,
                     self.first_layer + self.num_hidden_layers)
        return TrunkSpec(
            hidden_size=self.hidden_size, head_dim=self.head_dim,
            heads_held=self.heads_held, kv_heads_held=self.kv_heads_held,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            sandwich_norm=True, qk_norm=True, attn_gate=True,
            router_reads_input=False, router_scores=self.score_func,
            router_bias=True, route_norm=self.route_norm,
            route_scale=self.route_scale, experts=self.num_experts,
            top_k=self.num_experts_per_tok,
            experts_held=self.experts_held,
            expert_offset=self.expert_offset,
            expert_width=self.moe_intermediate_size,
            expert_act=self.hidden_act,
            shared_width=self.num_shared_experts * self.moe_intermediate_size,
            layers=tuple(
                LayerSpec(
                    rope=self.layer_types[i] == "sliding_attention",
                    window=(self.sliding_window
                            if self.layer_types[i] == "sliding_attention"
                            else 0),
                    dense_width=(self.intermediate_size
                                 if i < self.num_dense_layers else 0))
                for i in held))


@dataclass(frozen=True)
class DeepseekV3TrunkConfig(_TrunkShare):
    """``model.trunk`` with ``model_type: deepseek_v3`` (kanana-2's
    family; the keys by the names of its published ``config.json``):
    pre-norm residuals and multi-head LATENT attention — keys and values
    of every head come from one ``kv_lora_rank``-wide latent a token
    (RMS-normed) and one ``qk_rope_head_dim``-wide rotary key that all
    heads share; a query/key head is ``qk_nope_head_dim +
    qk_rope_head_dim`` wide with RoPE (``rope_interleave``: pairs
    ``(2i, 2i + 1)``) on the rotary part only, a value head
    ``v_head_dim``; the softmax scale is ``qk_head_dim ** -0.5``. The
    first ``first_k_dense_replace`` layers have a dense SwiGLU
    feed-forward of ``intermediate_size``, the others ``n_routed_experts``
    routed SwiGLU experts of ``moe_intermediate_size``
    (``num_experts_per_tok`` a token: sigmoid scores of the normed
    feed-forward input, ``topk_method: noaux_tc`` — a bias,
    ``e_score_correction_bias``, that enters the selection only — the
    kept scores renormalised under ``norm_topk_prob`` and scaled by
    ``routed_scaling_factor``) beside ``n_shared_experts`` shared ones,
    held as ONE feed-forward of ``n_shared_experts *
    moe_intermediate_size``.

    The share is ``AfmoeTrunkConfig``'s (``experts_held``, ``heads_held``,
    ``share_index``, ``first_layer``: ``first_k_dense_replace`` is read at
    the published layer indices and keeps its published value). Latent
    attention has no key/value heads to split: ``heads_held`` of the
    ``num_attention_heads`` columns of ``W_q`` and ``W_kv_b`` and rows of
    ``W_o`` are held, and the latent's down-projection with its norm
    WHOLE — every attention share computes them alike, as the shared
    experts and a dense layer's feed-forward. ``head_dim`` is carried as
    published (transformers sets it to the rotary width) and not read.
    Not written, and refused: a query latent (``q_lora_rank``), a RoPE
    scaling, attention biases, group-limited selection, ``moe_layer_freq``
    other than 1. Not carried: the selection bias's update from the load
    — the bias is a parameter the optimizer never moves."""

    model_type: str = "deepseek_v3"
    hidden_size: int = 2048
    head_dim: int = 64
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"
    attention_bias: bool = False
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    qk_head_dim: int = 192
    v_head_dim: int = 128
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    experts_held: int = 8
    heads_held: int = 16
    share_index: int = 0
    first_layer: int = 0

    def check(self) -> None:
        last = self.first_layer + self.num_hidden_layers
        if self.first_layer < 0 or not 0 <= self.first_k_dense_replace < last:
            raise ValueError(
                f"model.trunk: first_k_dense_replace="
                f"{self.first_k_dense_replace} lies past the held layers "
                f"({self.first_layer} … {last - 1}): no held layer would "
                f"route")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("model.trunk: group-limited routing "
                             "(n_group / topk_group other than 1) is not "
                             "written")
        if self.q_lora_rank is not None or self.rope_scaling is not None:
            raise ValueError("model.trunk (deepseek_v3): a query latent "
                             "(q_lora_rank) and a RoPE scaling are not "
                             "written")
        if (self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc"
                or self.hidden_act != "silu" or self.attention_bias
                or self.moe_layer_freq != 1 or self.n_shared_experts < 0):
            raise ValueError(
                "model.trunk (deepseek_v3) covers sigmoid scores with a "
                "selection bias (noaux_tc), SiLU-gated feed-forwards in "
                "every layer past the dense ones, and no attention bias")
        if (self.kv_lora_rank < 1 or self.qk_rope_head_dim % 2
                or min(self.qk_nope_head_dim, self.qk_rope_head_dim,
                       self.v_head_dim) < 1
                or self.qk_head_dim != (self.qk_nope_head_dim
                                        + self.qk_rope_head_dim)):
            raise ValueError(
                "model.trunk (deepseek_v3): qk_head_dim must be "
                "qk_nope_head_dim + qk_rope_head_dim, the rotary width "
                "even, the latent and the head widths positive")

    @functools.cached_property
    def spec(self) -> TrunkSpec:
        held = range(self.first_layer,
                     self.first_layer + self.num_hidden_layers)
        return TrunkSpec(
            hidden_size=self.hidden_size, head_dim=self.qk_head_dim,
            heads_held=self.heads_held, kv_heads_held=self.heads_held,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            sandwich_norm=False, qk_norm=False, attn_gate=False,
            router_reads_input=False, router_scores=self.scoring_func,
            router_bias=True, route_norm=self.norm_topk_prob,
            route_scale=self.routed_scaling_factor,
            experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            experts_held=self.experts_held,
            expert_offset=self.expert_offset,
            expert_width=self.moe_intermediate_size,
            expert_act=self.hidden_act,
            shared_width=self.n_shared_experts * self.moe_intermediate_size,
            layers=tuple(
                LayerSpec(rope=True, window=0,
                          dense_width=(self.intermediate_size
                                       if i < self.first_k_dense_replace
                                       else 0))
                for i in held),
            kv_latent=self.kv_lora_rank, qk_nope_dim=self.qk_nope_head_dim,
            qk_rope_dim=self.qk_rope_head_dim, v_head_dim=self.v_head_dim,
            rope_interleave=self.rope_interleave)


#: ``model.trunk``'s dataclass by its ``model_type`` key (absent: the
#: family whose published config has none) — three families, two
#: attention kinds (grouped-query: the first two; latent: the third)
TRUNK_FAMILIES = {None: TrunkConfig, "afmoe": AfmoeTrunkConfig,
                  "deepseek_v3": DeepseekV3TrunkConfig}


@dataclass(frozen=True)
class ModelConfig:
    """Agent/mixer model flags (SURVEY.md §5.6 'model')."""

    emb: int = 32
    heads: int = 3
    depth: int = 2
    ff_hidden_mult: int = 4
    dropout: float = 0.0
    mixer_emb: int = 32                   # must equal emb when mixer consumes agent hiddens
    mixer_heads: int = 3
    mixer_depth: int = 2
    qmix_pos_func: str = "abs"            # abs | softplus | quadratic | none
    qmix_pos_func_beta: float = 1.0
    use_orthogonal: bool = False
    standard_heads: bool = False          # perf mode: per-head dim = emb//heads (quirk Q1 off)
    dtype: str = "float32"                # compute dtype: float32 | bfloat16 (perf mode)
    # exact token-0-only agent forward (ops/query_slice.py): on by default,
    # auto-disabled where inapplicable (non-transformer agent, dropout>0);
    # noisy selectors STAY eligible — the noise is q-head-only, sampled
    # post-slice from an explicit key (round 5)
    use_qslice: bool = True
    # entity-table acting (ops/query_slice.agent_forward_qslice_entity):
    # contract attention against per-env (A, E) tables instead of
    # materializing per-agent token embeddings; exact for entity-mode obs
    # under fast_norm, auto-disabled otherwise
    use_entity_tables: bool = True
    # ReZero-style zero-init gate on the mixer output (q_tot = gate * y,
    # gate a scalar param init 0). The transformer mixer's readout
    # contracts emb-many O(1) post-LN token entries against abs-positive
    # weights, so its INIT output scale grows ~linearly with emb
    # (measured O(+-600) at emb=128/16 agents) — garbage early bootstrap
    # targets that dwarf unit-normalized rewards. Off by default
    # (reference-parity init); the config-2 learning recipe turns it on
    # together with reward_unit/td_loss (scripts/campaign_config2_r5.sh).
    mixer_zero_init: bool = False
    # rematerialize the learner's per-timestep forwards in the backward
    # pass (jax.checkpoint around the scan bodies): trades ~1 extra
    # forward for O(T) less residual HBM — the standard TPU lever for
    # long-horizon episode unrolls (config 3/4: T=150)
    remat: bool = False
    # entity counts: filled from env info when 0
    n_entities_obs: int = 0
    n_entities_state: int = 0
    # acting-path compute dtype (docs/PERF.md dtype policy): the dtype
    # select_actions/rollout forwards run in, threaded the same way
    # replay.store_dtype is. "" (default) inherits model.dtype — every
    # existing config is byte-identical. "bfloat16" over a float32
    # model.dtype is the bf16-acting mode: the per-rollout acting fold
    # (BasicMAC.prepare_acting_params) casts params once per rollout
    # and the scan-step forwards compute in bf16, while softmax
    # statistics, LayerNorm statistics, the carried hidden token, the
    # q-head output and the env normalizer all stay f32, and the TRAIN
    # path keeps model.dtype untouched (f32 parity configs stay
    # bit-identical between acting and learner unroll).
    act_dtype: str = ""
    # a catalog decoder trunk in place of the T2OMCA stack (TrunkConfig;
    # emb must equal its hidden_size and depth its num_hidden_layers —
    # heads / ff_hidden_mult / standard_heads are then the mixer's alone)
    trunk: Optional[Union[TrunkConfig, AfmoeTrunkConfig,
                          DeepseekV3TrunkConfig]] = None


@dataclass(frozen=True)
class ReplayConfig:
    buffer_size: int = 500                # episodes
    buffer_cpu_only: bool = False         # kept for parity; device-resident by default
    prioritized: bool = True
    per_alpha: float = 0.6
    per_beta: float = 0.4
    # storage dtype for the big obs/state arrays in episode batches and the
    # replay ring (HBM is the budget; bf16 halves it — the TPU analog of the
    # reference's buffer_cpu_only escape hatch)
    store_dtype: str = "float32"          # float32 | bfloat16
    # store the factored entity obs (rows + MEC index + normalizer stats,
    # ~20x smaller, exact reconstruction) instead of the flattened entity
    # obs; auto-disabled where inapplicable (ops/query_slice.py
    # entity_store_eligible)
    compact_entity_store: bool = True


@dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance knobs (docs/RESILIENCE.md). All of these govern the
    driver/checkpoint layer only — the train math is untouched, so every
    default is safe for parity configs."""

    # SIGTERM/SIGINT → flag → orderly loop exit with one final emergency
    # checkpoint and a resume hint (utils/resilience.ShutdownGuard). TPU
    # preemption then loses at most one iteration instead of up to
    # save_model_interval env steps.
    handle_signals: bool = True
    emergency_checkpoint: bool = True
    # non-finite guard (learners/qmix_learner.py): the jitted train step
    # skips the parameter+priority update when loss/grads go non-finite
    # (params pass through unchanged); the driver counts CONSECUTIVE
    # tripped steps at the log cadence (async pipeline stays unblocked)
    # and, at this threshold, restores the newest valid checkpoint and
    # continues. 0 disables the restore escalation (guard still skips).
    nonfinite_tolerance: int = 3
    # guard-triggered restores allowed before the run aborts with a
    # diagnosis (a deterministic NaN source would otherwise loop forever)
    max_restores: int = 2
    # checkpoint retention (utils/checkpoint.prune_checkpoints): keep the
    # newest keep_last steps, plus every step divisible by keep_every
    # (0 = no modular survivors). keep_last=0 disables pruning entirely.
    keep_last: int = 0
    keep_every: int = 0
    # fault injection (tests/test_resilience.py ONLY): multiply the loss
    # by NaN at exactly this learner train step (-1 = off). Static config,
    # so the disabled case costs nothing inside jit.
    inject_nan_at_step: int = -1
    # ---- hang detection & degradation ladder (docs/RESILIENCE.md §5) ----
    # watchdog stall threshold in seconds for any device-facing call
    # (dispatch, collective, checkpoint gather). 0 = watchdog fully
    # disabled — the driver behaves bit-identically to a build without it.
    # Size it to a few× the slowest expected dispatch (superstep K ×
    # iteration time, or the checkpoint gather at cadence).
    dispatch_timeout: float = 0.0
    # the FIRST occurrence of each watched phase includes the XLA compile
    # (tens of seconds on CPU, minutes at production shapes) and is
    # therefore exempt from dispatch_timeout; this key bounds it instead.
    # 0 = unbounded first occurrence (the default — compile times are
    # config-dependent); set it explicitly to catch startup hangs.
    # Only meaningful alongside dispatch_timeout > 0 (the watchdog is
    # not constructed otherwise — sanity_check rejects the dead combo).
    first_dispatch_timeout: float = 0.0
    # after the watchdog fired (diagnosis persisted + emergency checkpoint
    # attempted), how long to wait for the stalled call to return before a
    # hard process exit with stall_exit_code. 0 = never hard-exit (rely on
    # the orderly ShutdownGuard path once the call returns).
    stall_grace_s: float = 300.0
    # process exit code of the hard watchdog exit — distinct from 0
    # (orderly) and 1 (crash) so supervisors can count stall restarts
    stall_exit_code: int = 17
    # degradation ladder (utils/watchdog.py): in-place retries of a failed
    # dispatch before escalating a rung (transient-classified errors only;
    # deterministic errors propagate immediately). Exponential backoff
    # from retry_backoff_s with jitter between attempts.
    dispatch_retries: int = 2
    retry_backoff_s: float = 0.5
    # ladder rung 1: on exhausted retries of the fused superstep, fall
    # back to K=1 (smaller blast radius — each dispatch then risks one
    # iteration, not K) before restoring a checkpoint
    degrade_superstep: bool = True
    # coordinated multi-host preemption (docs/RESILIENCE.md §6): how long
    # the signaled hosts wait at the stop-step barrier for their peers
    # before degrading to the per-host shard save. Bounds the exit path
    # against a peer that died mid-preemption; single-host runs never
    # wait.
    preempt_barrier_timeout_s: float = 10.0


@dataclass(frozen=True)
class SightConfig:
    """graftsight learning-dynamics telemetry (``obs/sight.py``,
    docs/OBSERVABILITY.md §6). ``enabled`` is a STATIC gate compiled
    into the train step: off (the default) leaves every jitted program
    byte-identical (graftprog fingerprints pinned); on, the train step
    additionally reduces per-module gradient/update norms, fixed-bin
    masked histograms, PER importance/priority health, per-layer
    attention entropy and target drift ON DEVICE into ``train_info`` —
    the diagnostics then ride the existing log-cadence fetch (zero
    extra dispatches, zero extra device→host syncs). The host-side
    ``SightMonitor`` runs the windowed detectors below over that
    stream; each registers a pulse ``/healthz`` check when the live
    plane is up (``pulse_port``) and a flight-recorder mark when span
    telemetry is on (``enabled`` here does NOT require ``obs.enabled``
    — the metrics.jsonl stream and the jax-free ``obs learning`` CLI
    work standalone; the pulse/flight integrations simply no-op
    without their planes)."""

    enabled: bool = False
    # fixed-bin masked histograms (TD error symmetric over ±td_range;
    # q_taken/targets over ±q_range; outliers clip into the edge bins —
    # an edge-bin pileup IS the signal the ranges exist to surface)
    bins: int = 16
    td_range: float = 10.0
    q_range: float = 50.0
    # detector window, in log cadences (plateau/starvation detectors
    # need history; collapse/divergence detectors trip on one sample)
    window: int = 5
    # loss plateau: relative spread of the windowed loss below this
    # fraction of its mean over a FULL window
    plateau_rel: float = 0.02
    # Q divergence: |q_taken_mean| or |target_mean| beyond this (raw
    # value units — NaN-free blow-ups, the guard rail catches NaNs)
    q_div: float = 1e4
    # PER health: importance-weight effective sample size below this
    # fraction of the batch, or priority-distribution entropy below
    # this fraction of log(episodes_in_buffer) — the classic silent
    # PER collapse (a handful of episodes soak all sampling mass)
    ess_min: float = 0.05
    priority_entropy_min: float = 0.1
    # attention collapse: any layer's mean attention entropy below this
    # fraction of log(n_keys) (0 = every head a delta function)
    attn_entropy_min: float = 0.05
    # per-module gradient starvation: a module's share of the total
    # gradient norm below this for a FULL window
    grad_starvation: float = 1e-6


@dataclass(frozen=True)
class ObsConfig:
    """graftscope runtime-telemetry knobs (docs/OBSERVABILITY.md). All
    host-side: nothing here touches the jitted programs, so the
    graftprog fingerprints are identical at any setting — and with
    ``enabled=False`` (the default) the driver paths are
    behaviorally identical to a build without the obs layer."""

    # master switch: span recording around every watchdog-stamped
    # boundary, the spans.jsonl sink, and flight-recorder persistence
    # on stall/crash/non-finite/SIGTERM. Off by default — telemetry is
    # opt-in, parity/test configs pay nothing.
    enabled: bool = False
    # flight-recorder capacity: the last ring_size completed events
    # (plus every still-open span) survive into stall_diagnosis.json /
    # flight_recorder.json
    ring_size: int = 256
    # spans.jsonl flush cadence in events (amortizes the write syscall;
    # the flight ring covers the unflushed tail on a crash)
    flush_every: int = 32
    # Logger per-key in-memory history cap (0 = unbounded, the pre-PR-6
    # behavior): self.stats held every (t, value) pair for the life of
    # the run — unbounded host-RAM growth on long runs now that the
    # JSONL sink is the durable record. print_recent_stats only reads
    # the last 5 entries, so any cap >= 5 is observationally identical.
    stats_history: int = 1024
    # ---- graftpulse live telemetry plane (obs/pulse.py) ----------------
    # TCP port for the stdlib-only HTTP metrics endpoint (Prometheus-text
    # /metrics + JSON /healthz + /trace trigger). 0 (default) = no
    # server, no socket, driver byte-identical to a build without the
    # plane. Independent of `enabled`: the gauges need no span recorder
    # (span decoration of the scrape path simply degrades to no-ops when
    # telemetry is off).
    pulse_port: int = 0
    # bind address for the endpoint. Loopback by default: /trace is an
    # unauthenticated state-changing route (arms live profiler
    # captures), so reaching it from off-host is an explicit "0.0.0.0"
    # opt-in, never a default.
    pulse_host: str = "127.0.0.1"
    # sliding-sample window for the pulse quantile gauges (serve p50/p99
    # etc.) — bounds the hub's memory, not a statistics knob
    pulse_window: int = 512
    # HBM memwatch (obs/memwatch.py): per-device memory snapshots at
    # phase boundaries with phase-attributed high-water tracking, merged
    # into flight_recorder.json / stall_diagnosis.json. Requires
    # `enabled` (the snapshots ride the span/flight machinery; without
    # it the key would be silently dead).
    memwatch: bool = False
    # graftsight learning-dynamics telemetry (obs/sight.py): in-graph
    # train-step diagnostics + host-side RL-health detectors. See
    # SightConfig — deliberately NOT gated on `enabled` (its primary
    # sink is the metrics.jsonl scalar stream, not the span plane).
    sight: "SightConfig" = field(default_factory=lambda: SightConfig())


@dataclass(frozen=True)
class SebulbaConfig:
    """Sebulba-style decoupled actor/learner (Podracer, PAPERS.md arXiv
    2104.06272; ``parallel/sebulba.py``, docs/PERF.md). The visible
    devices are partitioned into a disjoint actor set (runs the rollout)
    and learner set (owns the replay ring and the train step), with a
    bounded device-resident trajectory queue between them so both stay
    saturated instead of idling through each other's phase. Off by
    default (``actor_devices=0``): the driver is byte-identical to the
    fused/classic loop and no compiled-program fingerprint changes."""

    # disjoint device counts: devices[0:actor] act, the next `learner`
    # devices train. Both 0 = disabled; both must be set together.
    actor_devices: int = 0
    learner_devices: int = 0
    # trajectory-queue capacity in rollout batches (ring of slots on the
    # learner devices). The actor blocks putting into a full queue; the
    # learner blocks getting from an empty one. 1 + staleness=0 is the
    # lockstep mode — bit-identical to the classic K=1 loop (pinned by
    # tests/test_sebulba.py).
    queue_slots: int = 2
    # parameter-staleness bound: how many rollout batches the actor may
    # run ahead of the learner's last processed batch. 0 = lockstep
    # (every rollout waits for the params from the previous train step);
    # S > 0 lets the actor act with params up to S learner updates old —
    # the overlap that keeps both device sets busy.
    staleness: int = 1


@dataclass(frozen=True)
class PBTConfig:
    """Population-based-training exploit/explore (``population.pbt.*``,
    t2omca_tpu/population.py). Host-side select-and-perturb on the
    population axis at checkpoint-save boundaries ONLY — zero extra
    steady-state dispatches. Off by default; enabling it deliberately
    breaks the member-0/solo bit-parity contract (that is its job)."""

    enabled: bool = False
    # exploit fraction: the bottom `frac` members copy the full train
    # state of the top `frac` at each save boundary (clamped so the two
    # sets never overlap)
    frac: float = 0.25
    # explore: copied members multiply each spec leaf (lr_scale,
    # eps_scale, per_alpha) by `perturb` or `1/perturb` (coin flip,
    # deterministic in (seed, t_env))
    perturb: float = 1.2


@dataclass(frozen=True)
class PopulationConfig:
    """graftpop population axis (``population.*``, docs/POPULATION.md,
    t2omca_tpu/population.py): ``size=P`` vmaps the WHOLE training
    state — params, opt_state, replay ring + PER priorities, runner
    state, RNG keys, per-member EnvParams scenario draws — over a
    leading ``(P,)`` axis, so ONE donated superstep dispatch advances P
    seed/hyperparameter variants. ``size=0`` (default) leaves every
    compiled program byte-identical (graftprog fingerprints pinned —
    zero re-baseline). Per-member grids are tuples of length P (empty =
    replicate the base config's value, the bit-parity-neutral default);
    P=1 with empty grids is bit-identical to the classic loop and
    member 0 of any un-gridded population is bit-exactly the solo run
    at ``cfg.seed`` (tests/test_population.py)."""

    size: int = 0
    # per-member ABSOLUTE learning rates (len P; empty = cfg.lr for
    # every member). Applied as an update-tree scale of lr_i/cfg.lr —
    # exact for adam/rmsprop, where lr enters linearly after the
    # moment statistics.
    lr: Tuple[float, ...] = ()
    # per-member multipliers on the epsilon-greedy schedule (len P;
    # empty = 1.0 — bitwise-neutral)
    eps_scale: Tuple[float, ...] = ()
    # per-member ABSOLUTE PER priority exponents (len P; empty =
    # replay.per_alpha). Traced into the store-side pow — value-
    # identical to the static exponent at the default.
    per_alpha: Tuple[float, ...] = ()
    # member i seeds from cfg.seed + i*seed_stride: 1 (default) = seed
    # replication (member 0 == the solo run), 0 = identical seeds
    # (controlled grid comparisons; combine with scenario_salt below)
    seed_stride: int = 1
    # fold the member index into the graftworld scenario sampler key
    # (envs/graftworld.member_scenario_key) so members draw DIFFERENT
    # scenario instances even at seed_stride=0. Off by default: the
    # fold is not bitwise-neutral, so member 0 would no longer match
    # the solo run's env streams.
    scenario_salt: bool = False
    pbt: "PBTConfig" = field(default_factory=lambda: PBTConfig())


@dataclass(frozen=True)
class KernelsConfig:
    """Rollout hot-path kernel selection (``t2omca_tpu/kernels/``,
    docs/PERF.md). Every entry keeps the XLA lowering as the default
    with CPU-gate parity tests pinning the hand-written kernel against
    it, so flipping a switch is a performance decision, never a
    semantics one.

    Not governed from here: acting's entity-table attention kernel
    (``kernels/entity_attention.py``). It engages by itself — from the
    head-width kernels' shapes, the lowering platform (TPU) and the env
    lanes sitting on one device — and no key selects it
    (``ops/query_slice.agent_forward_qslice_entity``, docs/PERF.md §1)."""

    # attention kernel for MultiHeadAttention (per-agent transformer AND
    # the mixer): "xla" = the einsum→softmax→einsum path (materializes
    # the (B·A, H, Q, K) logits tensor every env step); "pallas" = the
    # fused flash-style kernel (kernels/attention.py — tiled QK^T →
    # masked online softmax → PV, f32 accumulators, logits live only in
    # VMEM). It compiles for the chip; without one the lowering fails
    # (only the CPU test harness interprets it, tests/conftest.py).
    attention: str = "xla"


@dataclass(frozen=True)
class TrainConfig:
    """Top-level run flags (reference run-control set, SURVEY.md §5.6)."""

    name: str = "qmix_transf"
    seed: int = 0
    t_max: int = 205_000
    test_interval: int = 10_000
    test_nepisode: int = 32
    log_interval: int = 10_000
    runner_log_interval: int = 10_000
    batch_size_run: int = 8               # parallel envs (vmapped, not subprocesses)
    batch_size: int = 32                  # train batch (episodes)
    accumulated_episodes: int = 0         # min episodes collected before training
    # Anakin-style fused training superstep (Podracer, PAPERS.md): K > 1
    # fuses rollout → ring insert → gated sample+train into ONE donated
    # XLA program and lax.scan-s it K iterations per dispatch — amortizing
    # the per-dispatch overhead over K full train iterations (how much
    # that is on a local chip is not measured) and never materializing
    # the episode batch between rollout and insert (the rollout's scan
    # outputs scatter straight into the replay ring). 1 = the classic
    # three-program loop (bit-identical training either way — pinned by
    # tests/test_superstep.py). Requires the device-resident ring:
    # buffer_cpu_only configs stay on the three-program path
    # (run.superstep_eligible, the ops/query_slice.py predicate pattern).
    # Cadences (test/log/save) and preemption/checkpoint boundaries land
    # between dispatches, so they coarsen to every K iterations and a
    # preemption loses at most K iterations (docs/SPEC.md §8).
    superstep: int = 1
    use_cuda: bool = False                # parity flag; device selection is JAX's
    # data parallelism (SURVEY.md §7.2(6)): shard env lanes + replay
    # episodes over a `dp_devices`-wide mesh data axis (parallel/mesh.py);
    # 0 = single-device programs. Replaces the reference's single-device
    # select (/root/reference/per_run.py:26).
    dp_devices: int = 0
    # PRNG implementation for every key in the run: "threefry" (JAX
    # default — counter-based, reproducible across backends; all parity
    # and learning-evidence configs use it) or "rbg" (XLA
    # RngBitGenerator — the TPU hardware generator, far cheaper for the
    # rollout's many small draws: teleports, job generation, exploration
    # noise; streams differ from threefry, so trajectories are not
    # bit-comparable across the two)
    prng_impl: str = "threefry"
    evaluate: bool = False
    benchmark_mode: bool = False          # export per-episode CSV during eval
    checkpoint_path: str = ""
    load_step: int = 0
    save_model: bool = True
    save_model_interval: int = 50_000
    local_results_path: str = "results"
    use_tensorboard: bool = False
    save_replay: bool = False
    save_animation: bool = False
    animation_interval: int = 200_000
    animation_interval_evaluation: int = 0

    # tracing/profiling (capability upgrade over the reference, SURVEY.md §5(1))
    profile_dir: str = ""                 # jax.profiler trace output ("" = off)
    profile_start: int = 0                # t_env at which to start the trace
    profile_iterations: int = 3           # driver iterations to capture
    # block after each driver stage so StageTimer attributes real device
    # time instead of dispatch-enqueue time; costs one host round-trip per
    # stage, so off in production — the async loop then only syncs at
    # log/test/save cadences
    profile_stages: bool = False

    # component selection (registries, reference §5.6; agent/mixer families
    # follow the parent PyMARL lineage's registry pattern — the released
    # slice hardcodes the transformer pair)
    runner: str = "parallel"
    mac: str = "basic_mac"
    learner: str = "qmix_learner"
    env: str = "multi_agv_offloading"
    agent: str = "transformer"            # transformer | rnn
    mixer: str = "transformer"            # transformer | qmix_ff | vdn

    # learning hyperparameters (M8 spec — the learner itself is unreleased;
    # values start from the PyMARL/TransfQMIX lineage and are then pinned
    # by our 4-config x 5-seed config-1 stability sweep,
    # runs/config1_stable/SUMMARY.md: lr 5e-4 + epsilon floor 0.1 is the
    # only combination where all 5 seeds clear the +2-sigma learning bar —
    # at lr 1e-3 / floor 0.05 the greedy policy intermittently collapses
    # into the all-agents-conflict channel mode)
    gamma: float = 0.99
    lr: float = 0.0005
    optimizer: str = "adam"               # adam | rmsprop
    optim_alpha: float = 0.99             # rmsprop smoothing
    optim_eps: float = 1e-5
    grad_norm_clip: float = 10.0
    target_update_interval: int = 200     # episodes between hard target syncs
    double_q: bool = True
    # ----- loss-scale levers. Per-step rewards are O(10^2) (latency units,
    # docs/SPEC.md §1), so unweighted MSE on TD errors of that scale drives
    # grad_norm to 1e4-1e5 against grad_norm_clip=10 — every update is a
    # direction-only step of size clip*lr (measured:
    # runs/config1_stable/metrics_rbg_seed0.jsonl grad_norm=193k). Two
    # spec-level remedies, both OFF by default so reference-parity configs
    # and all committed learning evidence are byte-identical:
    # td_loss="huber": elementwise 2x-scaled Huber — td^2 inside
    # |td|<=huber_delta, 2*delta*|td|-delta^2 outside — so the quadratic
    # region matches the default MSE exactly and delta->inf recovers it.
    # The DQN-lineage gradient bound: each TD element contributes at most
    # 2*delta to dLoss/dq_tot.
    td_loss: str = "mse"                  # mse | huber
    huber_delta: float = 10.0             # Huber transition point (TD units)
    # reward_unit: divide the TRAIN-TIME reward by this constant (e.g.
    # latency_max_ms=100 makes per-step rewards O(1)); the value function
    # and the learner's logged metrics (loss/td_error_abs/target_mean)
    # are in reward/reward_unit units, while the runner's episode
    # returns/rewards stay raw. Unlike env_args.reward_scaling
    # (running-std, state-dependent — provably harmful at config 2,
    # runs/config2_scaling/SUMMARY.md) this is a static unit choice: no
    # state, no checkpoint migration, exact. Mutually exclusive with
    # reward_scaling (sanity_check) — combining would double-scale.
    reward_unit: float = 1.0

    # action selection
    action_selector: str = "epsilon_greedy"   # epsilon_greedy | noisy-new
    epsilon_start: float = 1.0
    # 0.1 floor: see the lr comment above — the residual exploration breaks
    # the symmetric conflict-mode lock-in (reference lineage uses 0.05)
    epsilon_finish: float = 0.1
    epsilon_anneal_time: int = 50_000

    env_args: EnvConfig = field(default_factory=EnvConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    kernels: KernelsConfig = field(default_factory=KernelsConfig)
    sebulba: SebulbaConfig = field(default_factory=SebulbaConfig)
    population: PopulationConfig = field(default_factory=PopulationConfig)

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


def sanity_check(cfg: TrainConfig) -> TrainConfig:
    """Mirror of the reference ``args_sanity_check``
    (``/root/reference/per_run.py:292-309``): round ``test_nepisode`` down to a
    multiple of ``batch_size_run`` (quirk Q10)."""
    tn = cfg.test_nepisode
    if tn < cfg.batch_size_run:
        tn = cfg.batch_size_run
    else:
        tn = (tn // cfg.batch_size_run) * cfg.batch_size_run
    if cfg.prng_impl not in ("threefry", "rbg", "unsafe_rbg"):
        raise ValueError(f"prng_impl must be threefry/rbg/unsafe_rbg, "
                         f"got {cfg.prng_impl!r}")
    if cfg.td_loss not in ("mse", "huber"):
        raise ValueError(f"td_loss must be mse/huber, got {cfg.td_loss!r}")
    if cfg.td_loss == "huber" and cfg.huber_delta <= 0:
        raise ValueError(f"huber_delta must be > 0, got {cfg.huber_delta}")
    if cfg.reward_unit <= 0:
        raise ValueError(f"reward_unit must be > 0, got {cfg.reward_unit}")
    if cfg.superstep < 1:
        raise ValueError(f"superstep must be >= 1 (1 = the unfused "
                         f"three-program loop), got {cfg.superstep}")
    if cfg.reward_unit != 1.0 and cfg.env_args.reward_scaling:
        raise ValueError(
            "reward_unit and env_args.reward_scaling are alternative "
            "reward-scale remedies; enabling both would double-scale the "
            "train-time reward (running-std AND /reward_unit) — pick one")
    if cfg.model.standard_heads:
        # a trunk's heads are its own (head_dim is published, not
        # emb / heads): model.heads is not read by it
        if ((cfg.model.trunk is None and cfg.model.emb % cfg.model.heads)
                or cfg.model.mixer_emb % cfg.model.mixer_heads):
            raise ValueError(
                f"standard_heads requires emb divisible by heads: got "
                f"emb={cfg.model.emb}/heads={cfg.model.heads}, "
                f"mixer_emb={cfg.model.mixer_emb}/mixer_heads={cfg.model.mixer_heads}."
            )
    # valid family names; mirrored from controllers.AGENT_REGISTRY /
    # learners.MIXER_REGISTRY (config cannot import them — circular) and
    # pinned by tests/test_model_families.py
    _agents, _mixers = {"transformer", "rnn"}, {"transformer", "qmix_ff",
                                                "vdn"}
    if cfg.agent not in _agents:
        raise ValueError(f"unknown agent '{cfg.agent}'; valid: "
                         f"{sorted(_agents)}")
    if cfg.mixer not in _mixers:
        raise ValueError(f"unknown mixer '{cfg.mixer}'; valid: "
                         f"{sorted(_mixers)}")
    if (cfg.model.dropout > 0.0 and cfg.agent != "transformer"
            and cfg.mixer != "transformer"):
        # transformer modules implement dropout; with neither family
        # selected a configured rate would be a silent no-op. (A transformer
        # mixer alone still applies it in the mixer blocks, so rnn agent +
        # transformer mixer stays valid.)
        raise ValueError(
            "dropout is only implemented by the transformer families; "
            f"agent='{cfg.agent}' + mixer='{cfg.mixer}' configures no "
            "module that would apply it")
    if cfg.dp_devices:
        if cfg.dp_devices < 0:
            raise ValueError(f"dp_devices must be >= 0, got {cfg.dp_devices}")
        if cfg.replay.buffer_cpu_only:
            raise ValueError(
                "dp_devices shards the device-resident replay ring; "
                "buffer_cpu_only keeps storage in host RAM — pick one")
        if not cfg.population.size:
            # Under population-over-dp the mesh shards the leading (P,)
            # member axis, not episode lanes — the episode-axis invariant
            # is replaced by P % dp_devices (checked in the population
            # block below).
            check_dp_divisibility(cfg, cfg.dp_devices)
    res = cfg.resilience
    if res.nonfinite_tolerance < 0:
        raise ValueError(f"resilience.nonfinite_tolerance must be >= 0 "
                         f"(0 disables the restore escalation), got "
                         f"{res.nonfinite_tolerance}")
    if res.max_restores < 0:
        raise ValueError(f"resilience.max_restores must be >= 0, got "
                         f"{res.max_restores}")
    if res.keep_last < 0 or res.keep_every < 0:
        raise ValueError(
            f"resilience.keep_last/keep_every must be >= 0, got "
            f"keep_last={res.keep_last}, keep_every={res.keep_every}")
    if res.dispatch_timeout < 0:
        raise ValueError(f"resilience.dispatch_timeout must be >= 0 "
                         f"(0 disables the watchdog), got "
                         f"{res.dispatch_timeout}")
    if res.first_dispatch_timeout < 0:
        raise ValueError(f"resilience.first_dispatch_timeout must be >= 0 "
                         f"(0 leaves first occurrences unbounded), got "
                         f"{res.first_dispatch_timeout}")
    if res.first_dispatch_timeout > 0 and res.dispatch_timeout == 0:
        raise ValueError(
            "resilience.first_dispatch_timeout only bounds the compile-"
            "exempt FIRST occurrence of each watched phase — with "
            "dispatch_timeout=0 the watchdog is never constructed and "
            "the key is silently dead; set dispatch_timeout > 0 too")
    if res.stall_grace_s < 0:
        raise ValueError(f"resilience.stall_grace_s must be >= 0 "
                         f"(0 disables the hard exit), got "
                         f"{res.stall_grace_s}")
    if not 1 <= res.stall_exit_code <= 255:
        raise ValueError(f"resilience.stall_exit_code must be in 1..255 "
                         f"(0 means orderly exit to supervisors), got "
                         f"{res.stall_exit_code}")
    if res.dispatch_retries < 0 or res.retry_backoff_s < 0:
        raise ValueError(
            f"resilience.dispatch_retries/retry_backoff_s must be >= 0, "
            f"got dispatch_retries={res.dispatch_retries}, "
            f"retry_backoff_s={res.retry_backoff_s}")
    if res.preempt_barrier_timeout_s <= 0:
        raise ValueError(
            f"resilience.preempt_barrier_timeout_s must be > 0 (it bounds "
            f"the coordinated-preemption peer barrier against dead peers; "
            f"an unbounded wait would hang the exit path forever), got "
            f"{res.preempt_barrier_timeout_s}")
    if res.inject_nan_at_step >= 0 and res.nonfinite_tolerance == 0:
        raise ValueError(
            "resilience.inject_nan_at_step is a fault-injection knob whose "
            "point is exercising the restore escalation — enabling it with "
            "nonfinite_tolerance=0 (escalation off) tests nothing")
    o = cfg.obs
    if o.ring_size < 1:
        raise ValueError(f"obs.ring_size must be >= 1, got {o.ring_size}")
    if o.flush_every < 1:
        raise ValueError(f"obs.flush_every must be >= 1, got "
                         f"{o.flush_every}")
    if o.stats_history < 0:
        raise ValueError(f"obs.stats_history must be >= 0 (0 = "
                         f"unbounded), got {o.stats_history}")
    if not 0 <= o.pulse_port <= 65535:
        raise ValueError(f"obs.pulse_port must be in 0..65535 (0 = no "
                         f"metrics endpoint), got {o.pulse_port}")
    if o.pulse_window < 16:
        raise ValueError(f"obs.pulse_window must be >= 16 (quantiles "
                         f"over fewer samples are noise), got "
                         f"{o.pulse_window}")
    if o.memwatch and not o.enabled:
        raise ValueError(
            "obs.memwatch merges its snapshots into the span/flight "
            "artifacts — with obs.enabled=false none of those exist and "
            "the key is silently dead; set obs.enabled=true too")
    sg = o.sight
    if sg.bins < 4:
        raise ValueError(f"obs.sight.bins must be >= 4 (a histogram "
                         f"needs bins to be one), got {sg.bins}")
    if sg.td_range <= 0 or sg.q_range <= 0:
        raise ValueError(
            f"obs.sight.td_range/q_range must be > 0, got "
            f"td_range={sg.td_range}, q_range={sg.q_range}")
    if sg.window < 2:
        raise ValueError(f"obs.sight.window must be >= 2 (plateau/"
                         f"starvation detectors need history), got "
                         f"{sg.window}")
    if not 0.0 <= sg.ess_min <= 1.0:
        raise ValueError(f"obs.sight.ess_min is a fraction of the batch "
                         f"— must be in [0, 1], got {sg.ess_min}")
    if not 0.0 <= sg.priority_entropy_min <= 1.0 \
            or not 0.0 <= sg.attn_entropy_min <= 1.0:
        raise ValueError(
            f"obs.sight.priority_entropy_min/attn_entropy_min are "
            f"fractions of the max entropy — must be in [0, 1], got "
            f"{sg.priority_entropy_min}/{sg.attn_entropy_min}")
    if sg.plateau_rel < 0 or sg.q_div <= 0 or sg.grad_starvation < 0:
        raise ValueError(
            f"obs.sight thresholds out of range: plateau_rel="
            f"{sg.plateau_rel} (>= 0), q_div={sg.q_div} (> 0), "
            f"grad_starvation={sg.grad_starvation} (>= 0)")
    sb = cfg.sebulba
    if (sb.actor_devices > 0) != (sb.learner_devices > 0):
        raise ValueError(
            f"sebulba.actor_devices and sebulba.learner_devices must be "
            f"set together (both 0 disables the decoupled loop), got "
            f"actor_devices={sb.actor_devices}, "
            f"learner_devices={sb.learner_devices}")
    if sb.actor_devices < 0 or sb.learner_devices < 0:
        raise ValueError(
            f"sebulba device counts must be >= 0, got "
            f"actor_devices={sb.actor_devices}, "
            f"learner_devices={sb.learner_devices}")
    if sb.queue_slots < 1:
        raise ValueError(f"sebulba.queue_slots must be >= 1, got "
                         f"{sb.queue_slots}")
    if sb.staleness < 0:
        raise ValueError(f"sebulba.staleness must be >= 0 (0 = lockstep), "
                         f"got {sb.staleness}")
    if sb.actor_devices:
        if cfg.replay.buffer_cpu_only:
            raise ValueError(
                "sebulba runs the replay ring + train step on the learner "
                "device set; buffer_cpu_only keeps storage in host RAM — "
                "drop buffer_cpu_only (the learner mesh holds the ring) "
                "or run the classic loop for host-RAM replay")
        if cfg.dp_devices:
            raise ValueError(
                "sebulba partitions the visible devices itself (actor + "
                "learner sets); it does not compose with dp_devices — "
                "scale the actor set instead")
        if cfg.superstep > 1:
            raise ValueError(
                "sebulba decouples rollout from training onto disjoint "
                "device sets; the fused superstep re-serializes them into "
                "one program — pick one (superstep=1 under sebulba)")
        # under a population the (P,) MEMBER axis shards over each set
        # (whole members per device — the graftlattice placement), not
        # the env-lane/episode axes, so these tilings only bind at P=0
        # (the population block below checks P % set size instead)
        if not cfg.population.size:
            if cfg.batch_size_run % sb.actor_devices:
                raise ValueError(
                    f"batch_size_run={cfg.batch_size_run} must be "
                    f"divisible by sebulba.actor_devices="
                    f"{sb.actor_devices} (env lanes shard over the actor "
                    f"mesh)")
            if cfg.batch_size % sb.learner_devices \
                    or cfg.replay.buffer_size % sb.learner_devices:
                raise ValueError(
                    f"batch_size={cfg.batch_size} and replay.buffer_size="
                    f"{cfg.replay.buffer_size} must be divisible by "
                    f"sebulba.learner_devices={sb.learner_devices} "
                    f"(replay episodes shard over the learner mesh)")
    pp = cfg.population
    if pp.size < 0:
        raise ValueError(f"population.size must be >= 0 (0 = no "
                         f"population axis), got {pp.size}")
    if pp.size:
        if cfg.replay.buffer_cpu_only:
            raise ValueError(
                "the population superstep vmaps the device-resident "
                "replay ring; buffer_cpu_only keeps storage in host RAM "
                "outside any jitted program — drop buffer_cpu_only (the "
                "vmapped ring already lives on device) or train members "
                "as separate solo runs")
        if cfg.dp_devices and pp.size % cfg.dp_devices:
            # population-over-dp (graftlattice): the leading (P,) member
            # axis shards over the 'data' mesh — whole members per
            # device, so P must tile the mesh
            raise ValueError(
                f"population-over-dp shards the (P,) member axis over "
                f"the 'data' mesh (whole members per device — members "
                f"never communicate); population.size={pp.size} is not "
                f"divisible by dp_devices={cfg.dp_devices} — pick a "
                f"divisible P or drop dp_devices")
        if cfg.sebulba.actor_devices:
            sb_ = cfg.sebulba
            if sb_.queue_slots != 1 or sb_.staleness != 0:
                raise ValueError(
                    f"population x sebulba composes only in LOCKSTEP "
                    f"(queue_slots=1, staleness=0): the vmapped learner "
                    f"trains all P members behind the device-resident "
                    f"queue in publish order, and an overlapped queue "
                    f"(queue_slots={sb_.queue_slots}, staleness="
                    f"{sb_.staleness}) would let members act on params "
                    f"of different staleness — set queue_slots=1 and "
                    f"staleness=0, or drop one of population/sebulba")
            if pp.pbt.enabled:
                raise ValueError(
                    "population.pbt exploits/explores at the classic "
                    "loop's checkpoint-save boundary; the decoupled "
                    "sebulba loop cannot re-salt the actor thread's "
                    "in-flight rollouts mid-epoch — run PBT under the "
                    "classic loop (drop sebulba) or disable "
                    "population.pbt")
            for what, n in (("actor_devices", sb_.actor_devices),
                            ("learner_devices", sb_.learner_devices)):
                if pp.size % n:
                    raise ValueError(
                        f"population x sebulba shards the (P,) member "
                        f"axis over each device set; population.size="
                        f"{pp.size} is not divisible by sebulba.{what}="
                        f"{n} — pick a divisible P or shrink the set")
        if cfg.evaluate or cfg.save_replay or cfg.save_animation:
            raise ValueError(
                "population trains P stacked members; the evaluate/"
                "replay/animation paths run a single-member policy — "
                "evaluate a member by exporting its slice (docs/"
                "POPULATION.md)")
        for name, grid in (("lr", pp.lr), ("eps_scale", pp.eps_scale),
                           ("per_alpha", pp.per_alpha)):
            if grid and len(grid) != pp.size:
                raise ValueError(
                    f"population.{name} has {len(grid)} entries for "
                    f"population.size={pp.size} — per-member grids must "
                    f"have exactly P entries (or be empty = replicate)")
            if any(v <= 0 for v in grid):
                raise ValueError(f"population.{name} entries must be > 0, "
                                 f"got {grid}")
        if any(v > 1.0 for v in pp.per_alpha):
            raise ValueError(f"population.per_alpha entries must be in "
                             f"(0, 1], got {pp.per_alpha}")
        if pp.per_alpha and not cfg.replay.prioritized:
            raise ValueError(
                "population.per_alpha grids the PER exponent — with "
                "replay.prioritized=false the knob is silently dead "
                "(same policy as first_dispatch_timeout without "
                "dispatch_timeout)")
        if pp.seed_stride < 0:
            raise ValueError(f"population.seed_stride must be >= 0, got "
                             f"{pp.seed_stride}")
        if not 0.0 < pp.pbt.frac <= 0.5:
            raise ValueError(f"population.pbt.frac must be in (0, 0.5] "
                             f"(exploit/explore sets must not overlap), "
                             f"got {pp.pbt.frac}")
        if pp.pbt.perturb <= 1.0:
            raise ValueError(f"population.pbt.perturb must be > 1.0 (the "
                             f"multiplicative explore factor), got "
                             f"{pp.pbt.perturb}")
        if pp.pbt.enabled and not cfg.save_model:
            raise ValueError(
                "population.pbt runs at checkpoint-save boundaries — "
                "with save_model=false it never fires (dead-knob "
                "policy); set save_model=true too")
    if cfg.kernels.attention not in ("xla", "pallas"):
        raise ValueError(f"kernels.attention must be xla/pallas, got "
                         f"{cfg.kernels.attention!r}")
    if cfg.model.act_dtype not in ("", "float32", "bfloat16"):
        raise ValueError(
            f"model.act_dtype must be ''/float32/bfloat16 ('' inherits "
            f"model.dtype), got {cfg.model.act_dtype!r}")
    # graftworld scenario surface (env_args.scenario.*). Name sets are
    # mirrored from envs/graftworld.py (config cannot import it —
    # circular) and pinned by tests/test_graftworld.py, the same pattern
    # as the agent/mixer registries above.
    _scn_kinds = {"", "fixed", "uniform", "mixture"}
    _scn_families = {"baseline", "hetfleet", "interference", "surge"}
    _scn_fields = {"n_active", "gain_scale", "interference_w", "mec_scale",
                   "teleport_prob", "job_prob", "surge_amp", "surge_period",
                   "deadline_ms", "mec_compute_scale", "compute_scale",
                   "tx_scale"}
    scn = cfg.env_args.scenario
    if scn.kind not in _scn_kinds:
        raise ValueError(f"env_args.scenario.kind must be one of "
                         f"{sorted(_scn_kinds)}, got {scn.kind!r}")
    if scn.family not in _scn_families:
        raise ValueError(f"env_args.scenario.family must be one of "
                         f"{sorted(_scn_families)}, got {scn.family!r}")
    for f in scn.families:
        if f not in _scn_families:
            raise ValueError(f"env_args.scenario.families entry {f!r} "
                             f"unknown; valid: {sorted(_scn_families)}")
    if scn.weights and len(scn.weights) != len(scn.families or
                                               _scn_families):
        raise ValueError(
            f"env_args.scenario.weights ({len(scn.weights)}) must match "
            f"the mixture component count "
            f"({len(scn.families or _scn_families)})")
    if any(w < 0 for w in scn.weights) or (scn.weights
                                           and sum(scn.weights) <= 0):
        raise ValueError("env_args.scenario.weights must be non-negative "
                         "with a positive sum")
    for name, *bounds in tuple(scn.ranges) + tuple(scn.overrides):
        if name not in _scn_fields:
            raise ValueError(
                f"env_args.scenario knob {name!r} is not a randomizable "
                f"EnvParams field; valid: {sorted(_scn_fields)}")
        if name == "deadline_ms":
            hi = max(float(b) for b in bounds)
            lo = min(float(b) for b in bounds)
            if hi > cfg.env_args.latency_max_ms or lo <= 0:
                raise ValueError(
                    f"env_args.scenario deadline_ms values must lie in "
                    f"(0, latency_max_ms={cfg.env_args.latency_max_ms}] "
                    f"— latency_max fixes the static job-queue shape "
                    f"(got {bounds})")
        if name == "n_active":
            if (min(float(b) for b in bounds) < 1
                    or max(float(b) for b in bounds)
                    > cfg.env_args.agv_num):
                raise ValueError(
                    f"env_args.scenario n_active values must lie in "
                    f"[1, agv_num={cfg.env_args.agv_num}], got {bounds}")
    for name, lo, hi in scn.ranges:
        if not float(lo) <= float(hi):
            raise ValueError(f"env_args.scenario.ranges[{name!r}]: "
                             f"lo={lo} > hi={hi}")
    if not 0 <= scn.min_agents <= cfg.env_args.agv_num:
        raise ValueError(
            f"env_args.scenario.min_agents must be in "
            f"[0, agv_num={cfg.env_args.agv_num}], got {scn.min_agents}")
    tk = cfg.model.trunk
    if tk is not None:
        if cfg.agent != "transformer":
            raise ValueError("model.trunk is the transformer agent's token "
                             f"stack; agent={cfg.agent!r} has none")
        if (cfg.model.emb != tk.hidden_size
                or cfg.model.depth != tk.num_hidden_layers):
            raise ValueError(
                f"model.trunk: emb must equal hidden_size and depth "
                f"num_hidden_layers (got emb={cfg.model.emb}/"
                f"{tk.hidden_size}, depth={cfg.model.depth}/"
                f"{tk.num_hidden_layers})")
        if (tk.heads_held < 1 or tk.experts_held < 1
                or tk.num_attention_heads % tk.heads_held):
            raise ValueError(
                f"model.trunk: heads_held={tk.heads_held} must divide "
                f"num_attention_heads={tk.num_attention_heads}")
        tk.check()
        sp = tk.spec
        if (tk.num_key_value_heads % tk.attention_ways
                or tk.heads_held % tk.kv_heads_held
                or sp.experts % tk.experts_held):
            raise ValueError(
                f"model.trunk: heads_held={tk.heads_held} / experts_held="
                f"{tk.experts_held} must divide the published counts "
                f"({tk.num_attention_heads} heads over "
                f"{tk.num_key_value_heads} key/value heads, "
                f"{sp.experts} experts) evenly")
        ways = sp.experts // tk.experts_held
        if ways % tk.attention_ways or not 0 <= tk.share_index < ways:
            raise ValueError(
                f"model.trunk: share_index={tk.share_index} must lie in "
                f"[0, {ways}) and the {tk.attention_ways} attention shares "
                f"must divide the {ways} expert shares")
        if tk.head_dim % 2:
            raise ValueError("model.trunk: head_dim must be even")
        if (cfg.model.dropout or cfg.action_selector == "noisy-new"
                or not cfg.env_args.obs_entity_mode
                or cfg.model.n_entities_obs):
            raise ValueError(
                "model.trunk covers entity observations, no dropout and "
                "no noisy head")
    if cfg.mixer == "transformer" and cfg.model.mixer_emb != cfg.model.emb:
        raise ValueError(
            "mixer_emb must equal emb: the transformer mixer concatenates "
            "agent hidden tokens (dim emb) with its own embeddings (dim "
            "mixer_emb) (reference n_transf_mixer.py:69)."
        )
    return cfg.replace(test_nepisode=tn)


def check_dp_divisibility(cfg: TrainConfig, n: int,
                          axis_label: str = "dp_devices") -> None:
    """The data-parallel shape invariant, shared by ``sanity_check`` (early,
    at config load) and ``parallel.DataParallel`` (late, at mesh build):
    every episode-axis quantity must split evenly over the mesh."""
    if (cfg.batch_size_run % n or cfg.batch_size % n
            or cfg.replay.buffer_size % n):
        raise ValueError(
            f"batch_size_run={cfg.batch_size_run}, "
            f"batch_size={cfg.batch_size} and "
            f"replay.buffer_size={cfg.replay.buffer_size} must all be "
            f"divisible by {axis_label}={n}")


def _coerce_scenario(base: ScenarioConfig, kw: dict) -> ScenarioConfig:
    """Normalize a scenario dict (YAML lists, JSON round trips) onto the
    tuple-typed frozen ScenarioConfig."""
    kw = dict(kw)
    if "ranges" in kw:
        kw["ranges"] = tuple(
            (str(n), float(lo), float(hi)) for n, lo, hi in kw["ranges"])
    if "overrides" in kw:
        kw["overrides"] = tuple(
            (str(n), float(v)) for n, v in kw["overrides"])
    if "families" in kw:
        kw["families"] = tuple(str(f) for f in kw["families"])
    if "weights" in kw:
        kw["weights"] = tuple(float(w) for w in kw["weights"])
    return dataclasses.replace(base, **kw)


def _coerce_trunk(base, kw: dict):
    """``model.trunk`` in any of its written forms onto the frozen
    dataclass of its family (``TRUNK_FAMILIES``, by the ``model_type``
    key; lists become tuples); ``None`` with no keys stays ``None``."""
    if base is None and not kw:
        return None
    if dataclasses.is_dataclass(base):
        base = dataclasses.asdict(base)
    kw = dict(base or {}, **kw)
    family = kw.get("model_type")
    if family not in TRUNK_FAMILIES:
        raise ValueError(f"model.trunk: model_type={family!r} is not "
                         f"written; known: {sorted(map(str, TRUNK_FAMILIES))}")
    for k in ("rope_layout", "sliding_window_layout"):
        if k in kw:
            kw[k] = tuple(int(v) for v in kw[k])
    if "layer_types" in kw:
        kw["layer_types"] = tuple(str(v) for v in kw["layer_types"])
    try:
        return TRUNK_FAMILIES[family](**kw)
    except TypeError as e:
        raise KeyError(f"unknown config key under model.trunk: {e}") from e


def _merge_nested(cfg: TrainConfig, updates: dict) -> TrainConfig:
    """Merge a (possibly nested) dict of overrides into the config tree."""
    env_kw = dict(updates.pop("env_args", {}) or {})
    model_kw = dict(updates.pop("model", {}) or {})
    replay_kw = dict(updates.pop("replay", {}) or {})
    resilience_kw = dict(updates.pop("resilience", {}) or {})
    obs_kw = dict(updates.pop("obs", {}) or {})
    kernels_kw = dict(updates.pop("kernels", {}) or {})
    sebulba_kw = dict(updates.pop("sebulba", {}) or {})
    # `population: 4` (bare int, YAML/CLI shorthand) means {size: 4} —
    # the ISSUE-15 config surface; a dict/PopulationConfig is the full
    # block form
    pop_raw = updates.pop("population", None)
    if isinstance(pop_raw, PopulationConfig):
        pop_raw = dataclasses.asdict(pop_raw)
    if isinstance(pop_raw, (int, float)) and not isinstance(pop_raw, bool):
        pop_raw = {"size": int(pop_raw)}
    population_kw = dict(pop_raw or {})

    # route flat keys to their sub-config for reference-style flat configs
    env_fields = {f.name for f in dataclasses.fields(EnvConfig)}
    model_fields = {f.name for f in dataclasses.fields(ModelConfig)}
    replay_fields = {f.name for f in dataclasses.fields(ReplayConfig)}
    resilience_fields = {f.name for f in dataclasses.fields(ResilienceConfig)}
    obs_fields = {f.name for f in dataclasses.fields(ObsConfig)}
    kernels_fields = {f.name for f in dataclasses.fields(KernelsConfig)}
    sebulba_fields = {f.name for f in dataclasses.fields(SebulbaConfig)}
    top_fields = {f.name for f in dataclasses.fields(TrainConfig)}
    flat = dict(updates)
    for k, v in flat.items():
        if k in top_fields:
            continue
        if k in model_fields:
            model_kw.setdefault(k, v)
            updates.pop(k)
        elif k in replay_fields:
            replay_kw.setdefault(k, v)
            updates.pop(k)
        elif k in env_fields:
            env_kw.setdefault(k, v)
            updates.pop(k)
        elif k in resilience_fields:
            resilience_kw.setdefault(k, v)
            updates.pop(k)
        elif k in obs_fields:
            obs_kw.setdefault(k, v)
            updates.pop(k)
        elif k in kernels_fields:
            kernels_kw.setdefault(k, v)
            updates.pop(k)
        elif k in sebulba_fields:
            sebulba_kw.setdefault(k, v)
            updates.pop(k)
        else:
            raise KeyError(f"unknown config key: {k}")

    if env_kw:
        # scenario sub-tree: a nested dict (YAML), dotted keys (CLI
        # `env_args.scenario.kind=...` arrives here as "scenario.kind"),
        # or an already-built ScenarioConfig (from_dict re-entry)
        scn_kw = env_kw.pop("scenario", None)
        scn_kw = ({} if scn_kw is None
                  else dataclasses.asdict(scn_kw)
                  if isinstance(scn_kw, ScenarioConfig) else dict(scn_kw))
        for k in [k for k in env_kw if k.startswith("scenario.")]:
            scn_kw[k.split(".", 1)[1]] = env_kw.pop(k)
        if scn_kw:
            env_kw["scenario"] = _coerce_scenario(cfg.env_args.scenario,
                                                  scn_kw)
        updates["env_args"] = dataclasses.replace(cfg.env_args, **env_kw)
    if model_kw:
        # trunk sub-tree: a nested dict (YAML / JSON round trip), dotted
        # keys (CLI `model.trunk.share_index=...` arrives here as
        # "trunk.share_index"), an already-built TrunkConfig, or None
        trunk_kw = {k.split(".", 1)[1]: model_kw.pop(k)
                    for k in [k for k in model_kw if k.startswith("trunk.")]}
        if "trunk" in model_kw or trunk_kw:
            model_kw["trunk"] = _coerce_trunk(
                model_kw.get("trunk", cfg.model.trunk), trunk_kw)
        updates["model"] = dataclasses.replace(cfg.model, **model_kw)
    if replay_kw:
        updates["replay"] = dataclasses.replace(cfg.replay, **replay_kw)
    if resilience_kw:
        updates["resilience"] = dataclasses.replace(cfg.resilience,
                                                    **resilience_kw)
    if obs_kw:
        # sight sub-tree: a nested dict (YAML), dotted keys (CLI
        # `obs.sight.enabled=...` arrives here as "sight.enabled"), or
        # an already-built SightConfig (from_dict re-entry) — the
        # env_args.scenario pattern
        sight_kw = obs_kw.pop("sight", None)
        sight_kw = ({} if sight_kw is None
                    else dataclasses.asdict(sight_kw)
                    if isinstance(sight_kw, SightConfig) else dict(sight_kw))
        for k in [k for k in obs_kw if k.startswith("sight.")]:
            sight_kw[k.split(".", 1)[1]] = obs_kw.pop(k)
        if sight_kw:
            obs_kw["sight"] = dataclasses.replace(cfg.obs.sight, **sight_kw)
        updates["obs"] = dataclasses.replace(cfg.obs, **obs_kw)
    if kernels_kw:
        updates["kernels"] = dataclasses.replace(cfg.kernels, **kernels_kw)
    if sebulba_kw:
        updates["sebulba"] = dataclasses.replace(cfg.sebulba, **sebulba_kw)
    if population_kw:
        # pbt sub-tree: a nested dict (YAML), dotted keys (CLI
        # `population.pbt.enabled=...` arrives here as "pbt.enabled"),
        # or an already-built PBTConfig (from_dict re-entry)
        pbt_kw = population_kw.pop("pbt", None)
        pbt_kw = ({} if pbt_kw is None
                  else dataclasses.asdict(pbt_kw)
                  if isinstance(pbt_kw, PBTConfig) else dict(pbt_kw))
        for k in [k for k in population_kw if k.startswith("pbt.")]:
            pbt_kw[k.split(".", 1)[1]] = population_kw.pop(k)
        if pbt_kw:
            population_kw["pbt"] = dataclasses.replace(cfg.population.pbt,
                                                       **pbt_kw)
        # YAML lists → the frozen tuples the hashable config needs
        for k in ("lr", "eps_scale", "per_alpha"):
            if k in population_kw:
                population_kw[k] = tuple(float(v)
                                         for v in population_kw[k])
        updates["population"] = dataclasses.replace(cfg.population,
                                                    **population_kw)
    return cfg.replace(**updates)


def from_dict(data: dict) -> TrainConfig:
    """Rebuild a TrainConfig from its ``dataclasses.asdict`` form (the
    serving artifact's ``meta.json`` round trip, serve/export.py) —
    defaults → nested dict → the same sanity pass as every other
    construction path, so a config that trained is a config that
    loads."""
    return sanity_check(_merge_nested(TrainConfig(), dict(data)))


def _coerce(s: str) -> Any:
    if s.lower() in ("true", "false"):
        return s.lower() == "true"
    for cast in (int, float):
        try:
            return cast(s)
        except ValueError:
            pass
    return s


def load_config(path: Optional[str] = None,
                overrides: Tuple[str, ...] = ()) -> TrainConfig:
    """defaults → file → ``key=value`` / ``section.key=value`` overrides."""
    cfg = TrainConfig()
    if path:
        with open(path) as f:
            if path.endswith((".yaml", ".yml")):
                import yaml  # baked into the image via other deps; gated import
                data = yaml.safe_load(f)
            else:
                data = json.load(f)
        cfg = _merge_nested(cfg, data or {})
    updates: dict = {}
    for ov in overrides:
        k, _, v = ov.partition("=")
        val = _coerce(v)
        if "." in k:
            sec, sub = k.split(".", 1)
            if sec == "population" and isinstance(updates.get(sec),
                                                 (int, float)):
                # the bare-int shorthand already stored —
                # `population=4 population.seed_stride=1` — lift it to
                # its dict form so the dotted key composes instead of
                # crashing on int.__setitem__
                updates[sec] = {"size": int(updates[sec])}
            updates.setdefault(sec, {})[sub] = val
        elif (k == "population" and isinstance(updates.get(k), dict)
                and not isinstance(val, dict)):
            # the reversed order: dotted keys first, then the bare-int
            # shorthand — merge instead of silently replacing the dict
            # (dropping `population.seed_stride=0` would turn a
            # controlled grid comparison into divergent seeds with no
            # error)
            updates[k]["size"] = int(val)
        else:
            updates[k] = val
    cfg = _merge_nested(cfg, updates)
    return sanity_check(cfg)


def unique_token(cfg: TrainConfig) -> str:
    """Run-naming scheme of the reference (``/root/reference/per_run.py:42``):
    ``{name}_seed{seed}_{map}_{datetime}``."""
    import datetime

    ts = datetime.datetime.now().strftime("%Y-%m-%d_%H-%M-%S")
    return f"{cfg.name}_seed{cfg.seed}_{cfg.env_args.map_name}_{ts}"
