"""Program registry: one place where the hot compiled programs get names.

The driver builds its XLA programs inline (``run.run_sequential`` calls
``Experiment.jitted_programs`` / ``superstep_program`` and throws the
handles into the loop), so before this module nothing in the repo could
*enumerate* them — the auditor (``graftprog``), the budget baseline
(``analysis/programs.json``) and the compile-count tests each need a
stable name → buildable-program mapping. The registry provides it:
``run.py``, ``parallel/mesh.py`` and ``learners/qmix_learner.py`` each
expose a ``register_audit_programs(reg)`` hook that names its programs
once, and ``collect_default_programs()`` gathers them on demand.

Programs are built against ``audit_config()`` — a frozen tiny CPU
config (bf16 compute so the dtype-churn rule GP203 has teeth) — and are
**lowered from abstract avals only** (``jax.eval_shape`` state +
``ShapeDtypeStruct`` keys): the audit never runs an env step or a train
step, so it fits the tier-1 gate without a TPU and without paying real
rollout compute. Only entries marked ``compile=True`` pay an XLA
compile (for ``memory_analysis`` and optimized-HLO costs); the rest are
audited at the lowered (stable-HLO) level.

The example arguments deliberately mimic the DRIVER's avals — e.g.
``t_env`` is the weak-typed ``jnp.asarray(int)`` scalar the loop
passes — so the recorded fingerprint is the fingerprint of the program
the driver actually dispatches, and an aval drift between driver and
registry (say a weak-type fix on one side only) shows up as GP304.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple


class SkipProgram(RuntimeError):
    """Raised by a builder whose program cannot be built in this
    environment (e.g. the data-parallel program on a 1-device host);
    the auditor reports the skip and moves on — a skip is never a
    finding, matching the lint ratchet's stale-entry semantics.
    Hooks that detect the condition up front can instead register
    ``AuditProgram.skipped(reason)``."""


@dataclasses.dataclass(frozen=True)
class AuditProgram:
    """One buildable named program.

    ``fn`` is the *jitted* callable (so ``fn.trace``/``fn.lower`` serve
    the auditor); ``args``/``kwargs`` are example arguments — abstract
    ``ShapeDtypeStruct``/``eval_shape`` trees wherever possible.
    ``donate_argnums`` mirrors what the driver donates (the auditor
    checks every donated leaf is actually aliased — GP201).
    ``compile=True`` opts into the XLA compile for ``memory_analysis``
    + optimized-HLO costs (expensive: reserve it for the donated hot
    programs)."""

    fn: object
    args: Tuple = ()
    kwargs: Dict = dataclasses.field(default_factory=dict)
    donate_argnums: Tuple[int, ...] = ()
    compile: bool = False
    description: str = ""
    #: set when the program cannot be built in this environment; the
    #: auditor records the reason instead of tracing
    skip: Optional[str] = None
    #: declared output shardings (a pytree of ``NamedSharding``/None
    #: matching the program's outputs) derived from
    #: ``parallel.mesh.LOGICAL_AXIS_RULES`` — when set, the comms audit
    #: (``--comms``) checks the compiled ``output_shardings`` against it
    #: (GP405, the partitioner dry-run gate). ``None`` = not declared.
    expected_output_shardings: object = None

    @classmethod
    def skipped(cls, reason: str) -> "AuditProgram":
        return cls(fn=None, skip=reason)


@dataclasses.dataclass(frozen=True)
class TransferAudit:
    """One named cross-mesh transfer (the ``params.sync`` publish
    class). A cross-mesh ``jax.device_put`` never lowers to HLO — the
    runtime executes it directly — so its audit is the static
    src-sharding → dst-sharding comparison (``graftshard.
    audit_transfer``): ``src`` is a pytree of ShapeDtypeStructs stamped
    with the SOURCE shardings (the learner-mesh layout the donor
    produces), ``dst_shardings`` the matching pytree of destination
    ``Sharding``\\ s (what the publish requests). The audit classifies
    every leaf as local / pure d2d copy / reshard — reshard is the
    GP404 host-round-trip class."""

    src: object = None
    dst_shardings: object = None
    description: str = ""
    skip: Optional[str] = None

    @classmethod
    def skipped(cls, reason: str) -> "TransferAudit":
        return cls(skip=reason)


@dataclasses.dataclass
class AuditContext:
    """Shared build products every hook draws from: the tiny-config
    ``Experiment`` plus the ``eval_shape`` of its initial TrainState
    (abstract — building it allocates nothing)."""

    cfg: object
    exp: object
    ts_shape: object
    superstep_k: int

    @property
    def compute_dtype(self) -> str:
        return self.cfg.model.dtype


#: the registry: insertion-ordered name -> AuditProgram
Registry = Dict[str, AuditProgram]

_ctx_lock = threading.Lock()
_ctx: Optional[AuditContext] = None

#: the superstep depth every audit builds with — small (cheap compile)
#: but > 1 so the scan/gate structure is the real fused program's
AUDIT_SUPERSTEP_K = 2


def audit_config():
    """The frozen tiny CPU config all default programs are built on.

    bf16 compute + f32 replay storage: the mixed-precision path is the
    one where a stray ``convert_element_type`` (GP203) or a baked f32
    constant (GP202) silently doubles bytes, so that is the path the
    canary watches. Shapes are test-scale — program *structure* (scan
    bodies, donation aliasing, dtype churn, callbacks) is shape-
    independent, and that structure is what the jaxpr rules audit;
    the cost ratchets are relative to this config's own baseline."""
    from ..config import (EnvConfig, ModelConfig, ReplayConfig, TrainConfig,
                          sanity_check)
    return sanity_check(TrainConfig(
        batch_size_run=2, batch_size=4, superstep=AUDIT_SUPERSTEP_K,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6, fast_norm=False),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1, dtype="bfloat16"),
        replay=ReplayConfig(buffer_size=8),
    ))


def kernels_audit_config(attention: str = "xla"):
    """The frozen config for the KERNEL-MODE byte comparison
    (``train_iter_pallas``/``learner_train_pallas`` vs their ``_ref``
    einsum twins): the ``audit_config`` recipe at token counts where the
    attention logits tensor is material. At the shared tiny audit scale
    (3 AGVs, 7 tokens) the ``(S, R·H, T)`` logits the flash path
    eliminates are a few hundred bytes inside a ~2 MB program — the
    comparison would measure interpreter scaffolding, not the kernel.
    16 AGVs / 4 MECs / emb 16 puts the mixer attention at ~19 query
    rows × 2 heads against ~39 keys, where the eliminated forward
    logits + backward recompute dominate the mode delta and the
    lowered-level GP302 ratchet pins pallas STRICTLY below xla
    (tests/test_graftprog.py). Lowered level only — a compiled
    comparison on the CPU gate would measure the interpret-mode grid
    emulation (serial block copies the Mosaic lowering never performs),
    not the program structure."""
    from ..config import (EnvConfig, KernelsConfig, ModelConfig,
                          ReplayConfig, TrainConfig, sanity_check)
    return sanity_check(TrainConfig(
        batch_size_run=2, batch_size=4, superstep=AUDIT_SUPERSTEP_K,
        env_args=EnvConfig(agv_num=16, mec_num=4, num_channels=2,
                           episode_limit=6, fast_norm=False),
        model=ModelConfig(emb=16, heads=2, depth=1, mixer_emb=16,
                          mixer_heads=2, mixer_depth=1, dtype="bfloat16"),
        replay=ReplayConfig(buffer_size=8),
        kernels=KernelsConfig(attention=attention),
    ))


def sight_audit_config():
    """The frozen config for the graftsight-on twin entries
    (``train_iter_sight``/``superstep_sight`` — run.py's
    ``_sight_twin_programs``): ``audit_config`` with ONLY the static
    ``obs.sight.enabled`` gate flipped, so the twin-vs-base budget
    delta IS the in-graph diagnostic overhead and nothing else. Tiny
    bins keep the histogram scatters audit-scale."""
    import dataclasses as _dc

    from ..config import SightConfig
    cfg = audit_config()
    return cfg.replace(obs=_dc.replace(
        cfg.obs, sight=SightConfig(enabled=True, bins=8)))


def population_audit_config():
    """The frozen config for the graftpop twin entry (``superstep_pop``
    — run.py's ``_population_twin_programs``): ``audit_config`` with a
    FIXED P=2 population, so the twin-vs-base budget delta is the
    vmapped population axis and nothing else. The population-OFF
    fingerprints of every other entry are unaffected (the spec seams
    default to ``None``)."""
    from ..config import PopulationConfig
    cfg = audit_config()
    return cfg.replace(population=PopulationConfig(size=2))


def population_kernels_audit_config():
    """The frozen config for the vmap-over-pallas twin entry
    (``superstep_pop_pallas`` — run.py's ``_population_twin_programs``):
    ``kernels_audit_config("pallas")`` with a FIXED P=2 population, so
    the entry audits the flash kernels UNDER the population vmap at the
    kernel audit scale (token counts where the logits tensor the flash
    path eliminates is material — the tiny shared audit scale would
    measure scaffolding). Neither parent baseline moves: the
    population-OFF pallas fingerprints (``train_iter_pallas``) and the
    xla-mode population fingerprint (``superstep_pop``) are built from
    their own unchanged configs."""
    from ..config import PopulationConfig
    cfg = kernels_audit_config("pallas")
    return cfg.replace(population=PopulationConfig(size=2))


_pkctx: Optional[AuditContext] = None


def population_kernels_audit_context() -> AuditContext:
    """Build (once per process) the population×pallas audit context —
    the ``population_audit_context`` pattern: ``ts_shape`` is the
    ``(ts, spec)`` PAIR of stacked ``init_population`` avals."""
    global _pkctx
    with _ctx_lock:
        if _pkctx is None:
            import jax

            from .. import population as graftpop
            from ..run import Experiment
            cfg = population_kernels_audit_config()
            exp = Experiment.build(cfg)
            ts_shape = jax.eval_shape(
                lambda: graftpop.init_population(exp, cfg))
            _pkctx = AuditContext(cfg=cfg, exp=exp, ts_shape=ts_shape,
                                  superstep_k=AUDIT_SUPERSTEP_K)
        return _pkctx


_pctx: Optional[AuditContext] = None


def population_audit_context() -> AuditContext:
    """Build (once per process) the population audit context — the
    ``sight_audit_context`` caching pattern. ``ts_shape`` follows the
    context convention of being the aval the audit program takes: here
    the ``(ts, spec)`` PAIR of ``population.init_population`` avals —
    every leaf (P,)-STACKED — since ``superstep_pop`` consumes both
    (an unstacked TrainState aval would fail its vmap at trace time)."""
    global _pctx
    with _ctx_lock:
        if _pctx is None:
            import jax

            from .. import population as graftpop
            from ..run import Experiment
            cfg = population_audit_config()
            exp = Experiment.build(cfg)
            ts_shape = jax.eval_shape(
                lambda: graftpop.init_population(exp, cfg))
            _pctx = AuditContext(cfg=cfg, exp=exp, ts_shape=ts_shape,
                                 superstep_k=AUDIT_SUPERSTEP_K)
        return _pctx


_sctx: Optional[AuditContext] = None


def sight_audit_context() -> AuditContext:
    """Build (once per process) the sight-on audit context — the
    ``kernels_audit_context`` caching pattern."""
    global _sctx
    with _ctx_lock:
        if _sctx is None:
            import jax

            from ..run import Experiment
            cfg = sight_audit_config()
            exp = Experiment.build(cfg)
            ts_shape = jax.eval_shape(lambda: exp.init_train_state(
                cfg.seed))
            _sctx = AuditContext(cfg=cfg, exp=exp, ts_shape=ts_shape,
                                 superstep_k=AUDIT_SUPERSTEP_K)
        return _sctx


_kctx: Dict[str, AuditContext] = {}


def kernels_audit_context(attention: str) -> AuditContext:
    """Build (once per process, per kernel mode) the kernel-comparison
    audit context — same caching rationale as ``audit_context``; the
    run.py and learner hooks each consume both modes."""
    with _ctx_lock:
        if attention not in _kctx:
            import jax

            from ..run import Experiment
            cfg = kernels_audit_config(attention)
            exp = Experiment.build(cfg)
            ts_shape = jax.eval_shape(lambda: exp.init_train_state(
                cfg.seed))
            _kctx[attention] = AuditContext(
                cfg=cfg, exp=exp, ts_shape=ts_shape,
                superstep_k=AUDIT_SUPERSTEP_K)
        return _kctx[attention]


def audit_context(rebuild: bool = False) -> AuditContext:
    """Build (once per process) the shared audit context. Cached: the
    ``Experiment`` build pins the process-global PRNG impl and costs
    ~1 s, and every hook needs the same one for fingerprint stability."""
    global _ctx
    with _ctx_lock:
        if _ctx is None or rebuild:
            import jax

            from ..run import Experiment
            cfg = audit_config()
            exp = Experiment.build(cfg)
            ts_shape = jax.eval_shape(lambda: exp.init_train_state(cfg.seed))
            _ctx = AuditContext(cfg=cfg, exp=exp, ts_shape=ts_shape,
                                superstep_k=AUDIT_SUPERSTEP_K)
        return _ctx


def collect_default_programs() -> Registry:
    """Gather every registered program from the component hooks, in a
    stable order (run.py's driver programs, then the data-parallel,
    learner and serving surfaces). Each module names its own programs —
    the registry stays free of program-construction knowledge."""
    from .. import run as run_mod
    from ..envs import graftworld as graftworld_mod
    from ..kernels import attention as kernels_mod
    from ..learners import qmix_learner as learner_mod
    from ..parallel import mesh as mesh_mod
    from ..parallel import sebulba as sebulba_mod
    from ..serve import program as serve_mod

    reg: Registry = {}
    ctx = audit_context()
    for mod in (run_mod, mesh_mod, sebulba_mod, learner_mod, serve_mod,
                kernels_mod, graftworld_mod):
        hook = getattr(mod, "register_audit_programs", None)
        if hook is None:
            continue
        for name, prog in hook(ctx).items():
            if name in reg:
                raise ValueError(
                    f"audit program {name!r} registered twice "
                    f"({mod.__name__} collides with an earlier hook)")
            reg[name] = prog
    return reg


def required_audit_devices() -> int:
    """The host-device count the FULL default registry needs: the
    largest fixed audit mesh any hook builds. Baseline writes
    (``--write-programs``) refuse to run below this — a 2-device run
    would silently drop the 4-device pop_dp / sebulba / dp×mp entries
    from programs.json (the same silent-shrink bug class the ``--only``
    refusal from the graftprog CLI guards against)."""
    from ..parallel import mesh as mesh_mod
    from ..parallel import sebulba as sebulba_mod
    dpmp = 1
    for d in getattr(mesh_mod, "AUDIT_DPMP_MESH", ()):
        dpmp *= d
    return max(mesh_mod.AUDIT_MESH_DEVICES,
               sum(sebulba_mod.AUDIT_SPLIT), dpmp)


def collect_transfer_audits() -> Dict[str, TransferAudit]:
    """Gather every registered cross-mesh transfer from the component
    ``register_transfer_audits(ctx)`` hooks — today only the Sebulba
    params.sync publish, but the hook shape mirrors
    ``collect_default_programs`` so new publish paths (fleet hot param
    refresh, dp×mp resharding sync) register next to it."""
    from ..parallel import sebulba as sebulba_mod

    out: Dict[str, TransferAudit] = {}
    ctx = audit_context()
    for mod in (sebulba_mod,):
        hook = getattr(mod, "register_transfer_audits", None)
        if hook is None:
            continue
        for name, ta in hook(ctx).items():
            if name in out:
                raise ValueError(
                    f"transfer audit {name!r} registered twice")
            out[name] = ta
    return out


def load_programs_from(path_or_module: str) -> Registry:
    """Load extra programs from a module path or a ``.py`` file that
    defines ``register_audit_programs(ctx) -> dict`` — the seeded-
    regression entry point for the CLI tests (``--program-module``)."""
    import importlib
    import importlib.util

    if path_or_module.endswith(".py"):
        spec = importlib.util.spec_from_file_location(
            "_graftprog_extra", path_or_module)
        if spec is None or spec.loader is None:
            raise ValueError(f"cannot import {path_or_module!r}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    else:
        mod = importlib.import_module(path_or_module)
    hook = getattr(mod, "register_audit_programs", None)
    if hook is None:
        raise ValueError(
            f"{path_or_module!r} defines no register_audit_programs")
    return dict(hook(audit_context()))
