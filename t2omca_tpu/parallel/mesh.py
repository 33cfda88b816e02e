"""Data-parallel scaling over a device mesh (SURVEY.md §2.2, §7.2(6)).

The reference has no multi-device story at all — its only "distributed"
tier is the subprocess env farm over Pipes (``parallel_runner.py:21-32``) and
a single CUDA device for the learner (``per_run.py:26``). The TPU-native
replacement (SURVEY.md §2.2 table): a ``jax.sharding.Mesh`` with a ``data``
axis; env lanes and replay episodes are sharded along it, model/optimizer
state is replicated, and XLA inserts the gradient ``psum`` over ICI when the
jitted train step consumes sharded batches — no hand-written collectives, no
NCCL/MPI equivalent to port.

Axis layout (why DP only): agent/entity token axes are tiny (≤ a few hundred
entries even at 256 AGVs, SURVEY.md §5.7) and models are ≤ a few M params, so
TP/PP/SP would ship more bytes over ICI than they save in FLOPs; the scaling
dimension of this workload is *environments*. The mesh helpers still accept
extra axes so a ``model`` axis can be added without restructuring
(extension point noted in SURVEY.md §2.2).

Multi-host: the same code scales to DCN via ``jax.distributed.initialize``
— ``jax.devices()`` then spans hosts and ``make_mesh`` lays the data axis
across them; nothing else changes (XLA routes collectives ICI-first).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Sequence[str] = ("data",)) -> Mesh:
    """1-D (default) mesh over the first ``n_devices`` devices."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)} "
                f"(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        devs = devs[:n_devices]
    shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    return Mesh(np.asarray(devs).reshape(shape), axis_names)


def partition_devices(n_actor: int, n_learner: int,
                      devices: Optional[Sequence] = None
                      ) -> tuple:
    """Disjoint (actor, learner) device sets for the Sebulba decoupled
    loop (``parallel/sebulba.py``): the first ``n_actor`` visible devices
    act, the next ``n_learner`` train. Disjointness is the point — the
    two meshes never contend for a chip, so rollout and training overlap
    instead of serializing (Podracer's Sebulba split, PAPERS.md)."""
    devs = list(devices) if devices is not None else jax.devices()
    need = n_actor + n_learner
    if n_actor < 1 or n_learner < 1:
        raise ValueError(f"actor/learner device counts must be >= 1, got "
                         f"({n_actor}, {n_learner})")
    if len(devs) < need:
        raise ValueError(
            f"sebulba needs {n_actor}+{n_learner}={need} devices, have "
            f"{len(devs)} (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")
    return tuple(devs[:n_actor]), tuple(devs[n_actor:need])


def population_shardings(mesh: Mesh, tree_like, axis: str = "data"):
    """NamedSharding pytree for population-over-dp (graftlattice): every
    leaf of the (P,)-stacked population state — TrainState halves AND the
    ``PopulationSpec`` — sharded on its LEADING member axis over the mesh.

    This is deliberately simpler than ``DataParallel.state_shardings``:
    the population superstep vmaps over members and members never
    communicate, so the mesh cuts between whole members (P must divide
    the axis size — ``sanity_check`` enforces it) and no leaf needs a
    per-field placement rule. Replicated-vs-sharded parity: no
    cross-member collective is ever inserted, so control/integer state
    is bit-equal; float leaves sit at f32 ULP scale, NOT bitwise —
    partitioning retiles the batched reduces (batch-P arrays on one
    device vs batch-P/n shards), measured ~1e-7 absolute / up to
    2.4e-5 rel on small adam moments after a train step
    (tests/test_lattice.py)."""
    member = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda _: member, tree_like)


@dataclasses.dataclass(frozen=True)
class DataParallel:
    """Sharded program wrapper for an ``Experiment`` (``run.Experiment``).

    Usage::

        dp = DataParallel(exp, make_mesh(8))
        ts = dp.init_sharded(seed)          # fresh state, born sharded
        rollout, insert, train_iter = dp.jitted_programs()

    (``dp.shard(restored_ts)`` places an EXISTING state — the resume
    path; for fresh states prefer ``init_sharded``, which never holds a
    single-device copy of the replay ring.)

    The jitted programs are the experiment's own pure functions; sharding
    comes entirely from the placement of their inputs (GSPMD propagates it),
    so the single-chip and multi-chip paths are the same code. Requirements:
    ``batch_size_run`` and ``batch_size`` divisible by the data-axis size.
    """

    exp: object                  # run.Experiment (duck-typed to avoid cycle)
    mesh: Mesh
    axis: str = "data"

    def __post_init__(self):
        from ..config import check_dp_divisibility
        check_dp_divisibility(self.exp.cfg, self.mesh.shape[self.axis],
                              axis_label=f"the '{self.axis}' axis size")

    # ------------------------------------------------------------------ state

    def state_shardings(self, ts_like):
        """NamedSharding pytree for a TrainState (or its
        ``jax.eval_shape`` struct): learner replicated, env lanes and
        replay episodes sharded over the data axis. Single source of the
        placement rules — consumed by ``shard`` (device_put of an
        existing state) and ``init_sharded`` (jit out_shardings, so big
        states are BORN sharded)."""
        lane = NamedSharding(self.mesh, P(self.axis))
        rep = NamedSharding(self.mesh, P())

        def fill(subtree, s):
            return jax.tree.map(lambda _: s, subtree)

        runner = ts_like.runner.replace(
            env_states=fill(ts_like.runner.env_states, lane),
            key=rep, t_env=rep,
            # reward-scale state is per-lane except the scalar Welford count
            rscale=jax.tree.map(
                lambda x: lane if getattr(x, "ndim", 0) else rep,
                ts_like.runner.rscale),
            # graftworld scenario instances: every EnvParams leaf is
            # batched (B, ...) — sharded with its env lane
            env_params=fill(ts_like.runner.env_params, lane))
        buffer = ts_like.buffer.replace(
            storage=fill(ts_like.buffer.storage, lane),
            insert_pos=rep, episodes_in_buffer=rep,
            priorities=rep, max_priority=rep)
        return ts_like.replace(
            learner=fill(ts_like.learner, rep),
            runner=runner, buffer=buffer, episode=rep)

    def shard(self, ts):
        """Place an existing TrainState onto the mesh (host→device copy;
        peak = old + new. For states whose replay ring is a large share
        of host/device memory prefer ``init_sharded``)."""
        return jax.device_put(ts, self.state_shardings(ts))

    def init_sharded(self, seed: int):
        """Build the initial TrainState DIRECTLY under the mesh sharding:
        jit with out_shardings means XLA materializes each leaf (notably
        the replay ring's zeros) as per-device shards only — no
        full-state single-device transient, which at config-5 ring sizes
        (~59 GiB bf16) is the difference between fitting and OOM at
        startup. Equivalent to ``shard(exp.init_train_state(seed))`` up
        to jit-fusion float reassociation in the env-reset math (measured
        rel ~1e-8 on 3 env-state leaves; params bit-identical)."""
        shapes = jax.eval_shape(lambda: self.exp.init_train_state(seed))
        return jax.jit(
            lambda: self.exp.init_train_state(seed),
            out_shardings=self.state_shardings(shapes))()

    # ------------------------------------------------------------------ programs

    def jitted_programs(self, donate: bool = False):
        """The experiment's own three programs with
        ``with_sharding_constraint`` injected on every chained value:
        episode batches (episode axis distributed end-to-end: rollout →
        insert → sample → train; grads are psum'd by GSPMD since params
        are replicated and the loss averages over a sharded batch) AND
        the runner/replay/learner states the driver loop feeds back in.
        Output constraints pin each program's outputs to the exact
        placement ``shard`` gives its inputs — otherwise GSPMD may pick
        different output shardings and every later loop iteration would
        compile and run a second, differently-sharded executable.

        ``donate`` has the same contract as
        ``Experiment.jitted_programs(donate=...)``: in-place replay ring and
        train state for drivers that never reuse the pre-call value."""
        return self.exp.jitted_programs(donate=donate,
                                        **self._constraint_hooks())

    def superstep_program(self, k: int, donate: bool = False):
        """The fused K-iteration superstep
        (``run.Experiment.superstep_program``) under the mesh: the same
        constraint hooks pin every value the scan carries across
        sub-iterations — env lanes / replay episodes stay sharded on the
        data axis, learner state replicated (grads psum'd by GSPMD) — so
        one executable serves every dispatch, exactly like
        ``jitted_programs``."""
        return self.exp.superstep_program(k, donate=donate,
                                          **self._constraint_hooks())

    def audit_avals(self, ts_like):
        """The TrainState avals the DRIVER hands this wrapper's
        programs: each eval_shape leaf annotated with its canonical
        ``state_shardings`` placement, so the auditor lowers the same
        SPMD program ``run_sequential`` dispatches (unsharded avals
        would lower a different — single-device — executable and the
        recorded fingerprint/budgets would be fiction)."""
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            ts_like, self.state_shardings(ts_like))

    def _constraint_hooks(self):
        """The shared ``constrain_*`` kwargs: one source for the canonical
        placement of every value the driver loop (or the superstep scan)
        chains back in."""
        data = NamedSharding(self.mesh, P(self.axis))
        rep = NamedSharding(self.mesh, P())
        wsc = jax.lax.with_sharding_constraint

        def constrain_runner(rs):
            return rs.replace(
                env_states=jax.tree.map(lambda x: wsc(x, data),
                                        rs.env_states),
                key=wsc(rs.key, rep),
                t_env=wsc(rs.t_env, rep),
                rscale=jax.tree.map(
                    lambda x: wsc(x, data if x.ndim else rep), rs.rscale),
                env_params=jax.tree.map(lambda x: wsc(x, data),
                                        rs.env_params))

        def constrain_buffer(buf):
            return buf.replace(
                storage=jax.tree.map(lambda x: wsc(x, data), buf.storage),
                insert_pos=wsc(buf.insert_pos, rep),
                episodes_in_buffer=wsc(buf.episodes_in_buffer, rep),
                priorities=wsc(buf.priorities, rep),
                max_priority=wsc(buf.max_priority, rep))

        return dict(
            constrain_batch=lambda b: wsc(b, data),
            constrain_runner=constrain_runner,
            constrain_buffer=constrain_buffer,
            constrain_learner=lambda l: jax.tree.map(
                lambda x: wsc(x, rep), l))


#: data-axis width the audit builds with — the smallest real mesh, so
#: the SPMD program structure (partitioned scatter/psum) is audited
#: without depending on how many devices the auditing host happens to
#: expose beyond two
AUDIT_MESH_DEVICES = 2

# --------------------------------------------------------------- dp × mp
#
# ROADMAP item 3: T5X-style 2-D (dp, mp) partitioning. The PARTITIONER
# is not built yet — what lives here is its declared-intent artifact
# (the logical axis rules, SNIPPETS.md [2]/[3] pattern) plus the fixed
# synthetic 2×2 audit mesh the comms gate (analysis/graftshard.py,
# GP405) dry-runs a transformer block under, so sharding regressions
# against the declared rules fail statically before the first real
# dp×mp line is written.

#: logical axis name -> mesh axis (None = replicated). First match
#: wins, T5X `logical_axis_rules` semantics. The model axes that grow
#: with entity-transformer width ("joined_kv": the fused heads*head_dim
#: projection output of the full-emb head geometry Q1; "mlp": the
#: ff_hidden_mult*emb hidden) shard over ``model``; "embed" stays
#: replicated (it is every block's residual/LayerNorm axis — splitting
#: it would put a collective inside every residual add); "batch"
#: follows the data axis like every env-lane tensor. "expert" is the
#: leading axis of a catalog trunk's expert weights (models/trunk.py
#: ``w_gate`` / ``w_up`` / ``w_down``): declared here with every other
#: axis, over ``model``; today one chip holds its share of it
#: (``TrunkConfig.experts_held``) and no program shards it.
LOGICAL_AXIS_RULES = (
    ("batch", "data"),
    ("heads", "model"),
    ("joined_kv", "model"),
    ("mlp", "model"),
    ("expert", "model"),
    ("embed", None),
    ("tokens", None),
    ("kv", None),
)

#: the fixed synthetic (dp, mp) audit mesh shape — 2×2 is the smallest
#: mesh where BOTH axes are real, so the lowered program carries the
#: genuine dp psum AND mp contraction collectives
AUDIT_DPMP_MESH = (2, 2)


def make_dpmp_mesh(shape: Sequence[int] = AUDIT_DPMP_MESH) -> Mesh:
    """2-D ("data", "model") mesh over the first prod(shape) devices."""
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"dp x mp mesh {tuple(shape)} needs {need} devices, have "
            f"{len(devs)} (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")
    return Mesh(np.asarray(devs[:need]).reshape(tuple(shape)),
                ("data", "model"))


def logical_to_mesh_axes(logical_axes: Sequence[Optional[str]]) -> P:
    """Logical axis names -> PartitionSpec under ``LOGICAL_AXIS_RULES``
    (first match wins; unknown names are an error — an unmapped axis is
    a rules-table gap, not a replication decision)."""
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        for logical, mesh_axis in LOGICAL_AXIS_RULES:
            if logical == name:
                out.append(mesh_axis)
                break
        else:
            raise ValueError(
                f"logical axis {name!r} has no LOGICAL_AXIS_RULES entry "
                f"(parallel/mesh.py) — declare it before sharding by it")
    return P(*out)


def transformer_block_logical_axes(params) -> object:
    """Logical-axes pytree (tuples of axis names, one per leaf) for a
    ``models.transformer.TransformerBlock`` param tree — the declared
    sharding intent GP405 validates lowered programs against. Matches
    by the flax module-path names, so a renamed/added projection fails
    loudly here instead of silently replicating."""
    import jax.tree_util as jtu

    def axes_for(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        leaf_name = names[-1]
        if any(n in ("tokeys", "toqueries", "tovalues") for n in names):
            return ("embed", "joined_kv")
        if "unifyheads" in names:
            return (("joined_kv", "embed") if leaf_name == "kernel"
                    else ("embed",))
        if "ff1" in names:
            return (("embed", "mlp") if leaf_name == "kernel"
                    else ("mlp",))
        if "ff2" in names:
            return (("mlp", "embed") if leaf_name == "kernel"
                    else ("embed",))
        if any(n.startswith("norm") for n in names):
            return ("embed",)
        raise ValueError(
            f"TransformerBlock param {'/'.join(names)!r} has no logical-"
            f"axes mapping (parallel/mesh.py transformer_block_logical_"
            f"axes) — extend the table before sharding the new module")

    return jtu.tree_map_with_path(axes_for, params)


def register_audit_programs(ctx):
    """graftprog registry hook: the data-parallel superstep under a
    fixed ``AUDIT_MESH_DEVICES``-wide mesh (fingerprints must not vary
    with the host's device count), plus the population-over-dp twin
    (graftlattice — the member axis sharded over the same mesh).
    Skipped — never failed — on hosts exposing fewer CPU devices."""
    from ..analysis.registry import AuditProgram
    import jax.numpy as jnp
    if len(jax.devices()) < AUDIT_MESH_DEVICES:
        skip = AuditProgram.skipped(
            f"needs >= {AUDIT_MESH_DEVICES} devices (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count="
            f"{AUDIT_MESH_DEVICES})")
        return {"dp_superstep": skip, "pop_dp_superstep": skip,
                **_dpmp_block_twin(ctx)}
    dp = DataParallel(ctx.exp, make_mesh(AUDIT_MESH_DEVICES))
    k = ctx.superstep_k
    sup = dp.superstep_program(k, donate=True)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    keys = jax.ShapeDtypeStruct((k,) + key.shape, key.dtype)
    return {
        "dp_superstep": AuditProgram(
            sup, (dp.audit_avals(ctx.ts_shape), keys, jnp.asarray(0)),
            donate_argnums=(0,),
            description=f"fused K={k} superstep sharded over a "
                        f"{AUDIT_MESH_DEVICES}-device data axis"),
        **_pop_dp_twin(k, key),
        **_dpmp_block_twin(ctx),
    }


def _dpmp_block_twin(ctx):
    """The dp×mp dry-run audit entry (graftshard / ROADMAP item 3): a
    ``TransformerBlock`` at the audit model scale lowered under the
    fixed 2×2 ("data", "model") mesh with every param leaf stamped from
    ``LOGICAL_AXIS_RULES`` via ``transformer_block_logical_axes`` and
    activations on ("batch", "tokens", "embed"). The program's
    ``expected_output_shardings`` declares the same logical spec for the
    block output, so the comms audit's GP405 check IS the partitioner
    dry-run: if GSPMD stops honoring a declared rule (or the rules table
    drifts from what lowering produces) the gate fails statically. Its
    collective census (the mp all-reduces the sharded contractions
    insert) is ratcheted like every mesh program's."""
    from ..analysis.registry import AuditProgram
    from ..models.transformer import TransformerBlock
    import jax.numpy as jnp

    need = int(np.prod(AUDIT_DPMP_MESH))
    if len(jax.devices()) < need:
        return {"dpmp_block": AuditProgram.skipped(
            f"needs >= {need} devices (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")}
    mesh = make_dpmp_mesh()
    m = ctx.cfg.model
    dt = jnp.dtype(m.dtype)
    b, t = 4, 8                         # tiny token grid, audit-scale
    block = TransformerBlock(emb=m.emb, heads=m.heads,
                             standard_heads=m.standard_heads, dtype=dt)
    q0 = jnp.zeros((b, t, m.emb), dt)
    k0 = jnp.zeros((b, t, m.emb), dt)
    params = jax.eval_shape(lambda: block.init(
        jax.random.PRNGKey(0), q0, k0))

    logical = transformer_block_logical_axes(params)
    shardings = jax.tree.map(
        lambda ax: NamedSharding(mesh, logical_to_mesh_axes(ax)),
        logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))
    params_aval = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params, shardings)
    act = jax.ShapeDtypeStruct(
        (b, t, m.emb), dt,
        sharding=NamedSharding(
            mesh, logical_to_mesh_axes(("batch", "tokens", "embed"))))

    def apply(p, q, kk):
        return block.apply(p, q, kk)
    apply.__name__ = apply.__qualname__ = "_dpmp_block"
    return {"dpmp_block": AuditProgram(
        jax.jit(apply), (params_aval, act, act),
        expected_output_shardings=act.sharding,
        description=f"TransformerBlock under the fixed "
                    f"{AUDIT_DPMP_MESH[0]}x{AUDIT_DPMP_MESH[1]} "
                    f"(data, model) audit mesh, params stamped from "
                    f"LOGICAL_AXIS_RULES — the ROADMAP item 3 dry-run "
                    f"gate (GP405) plus its collective census")}


def _pop_dp_twin(k, key):
    """The population-over-dp audit entry (graftlattice): the SAME
    ``superstep_pop`` program (``run.population_superstep_program``,
    P=2 population audit scale) lowered with every state/spec leaf
    annotated with its ``population_shardings`` member-axis placement —
    the SPMD executable ``run_sequential`` dispatches when
    ``population.size`` and ``dp_devices`` are both set. Unsharded avals
    would lower the single-device ``superstep_pop`` again and the
    recorded budgets would be fiction (the ``DataParallel.audit_avals``
    rationale)."""
    from ..analysis.registry import AuditProgram, population_audit_context
    pctx = population_audit_context()
    mesh = make_mesh(AUDIT_MESH_DEVICES)
    p, kk = pctx.cfg.population.size, pctx.superstep_k
    ts_shape, spec_shape = pctx.ts_shape

    def annotate(tree):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            tree, population_shardings(mesh, tree))

    keys = jax.ShapeDtypeStruct((p, kk) + key.shape, key.dtype)
    prog = pctx.exp.population_superstep_program(kk, donate=True)
    import jax.numpy as jnp
    return {"pop_dp_superstep": AuditProgram(
        prog, (annotate(ts_shape), annotate(keys), jnp.asarray(0),
               annotate(spec_shape)),
        donate_argnums=(0,),
        description=f"fused K={kk} population superstep with the P={p} "
                    f"member axis sharded over a {AUDIT_MESH_DEVICES}-"
                    f"device data axis (population-over-dp: whole "
                    f"members per device, no cross-member collectives)")}
