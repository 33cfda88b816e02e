"""graftprog — the compiled-program auditor (t2omca_tpu/analysis,
docs/ANALYSIS.md): seeded-regression fixtures per GP rule, the
programs.json round-trip/ratchet/tolerance semantics, fingerprint
drift on a weak-typed scalar, and the CLI exit-code contract. The
default-registry audit itself (the same thing the scripts/t1.sh
prelude runs) is the slow half."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from t2omca_tpu.analysis import (load_programs, save_programs)
from t2omca_tpu.analysis.graftprog import (GP_RULES, ProgFinding,
                                           ProgramReport, audit_program,
                                           compare_reports,
                                           fingerprint_text)
from t2omca_tpu.analysis.registry import AuditProgram

pytestmark = [pytest.mark.analysis, pytest.mark.graftprog]

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures_graftprog.py"


def _audit(fn, args, donate=(), compile=False, dtype="bfloat16"):
    return audit_program(
        "toy", AuditProgram(fn, args, donate_argnums=donate,
                            compile=compile), dtype)


# ------------------------------------------------- seeded jaxpr rules

def test_gp201_undonated_donation():
    def f(x, y):
        return x + 1.0 + 0.0 * jnp.sum(y)
    rep = _audit(jax.jit(f, donate_argnums=(0, 1)),
                 (jax.ShapeDtypeStruct((8, 8), jnp.float32),
                  jax.ShapeDtypeStruct((3,), jnp.float32)),
                 donate=(0, 1))
    assert rep.rule_count("GP201") == 1
    assert "float32[3]" in rep.rule_details["GP201"][0]


def test_gp201_survives_reaudit_of_cached_lowering():
    """jax's lowering cache suppresses the donated-buffers warning on a
    re-lower of the same jit+avals — the text-level aliasing count must
    still report the miss on the second audit."""
    def f(x, y):
        return x + 1.0 + 0.0 * jnp.sum(y)
    jf = jax.jit(f, donate_argnums=(0, 1))
    args = (jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((3,), jnp.float32))
    first = _audit(jf, args, donate=(0, 1))
    second = _audit(jf, args, donate=(0, 1))
    assert first.rule_count("GP201") == 1
    assert second.rule_count("GP201") == 1
    assert "no input_output_alias" in second.rule_details["GP201"][0]


def test_gp201_negative_fully_aliased():
    def f(x):
        return x + 1.0
    rep = _audit(jax.jit(f, donate_argnums=(0,)),
                 (jax.ShapeDtypeStruct((8, 8), jnp.float32),),
                 donate=(0,))
    assert rep.rule_count("GP201") == 0


def test_gp202_baked_constant_and_threshold():
    big = jnp.ones((256, 256), jnp.float32)      # 256 KiB: flagged
    small = jnp.ones((4, 4), jnp.float32)        # 64 B: below threshold

    def f(x):
        return x @ big + jnp.sum(small)
    rep = _audit(jax.jit(f), (jax.ShapeDtypeStruct((8, 256),
                                                   jnp.float32),))
    assert rep.rule_count("GP202") == 1
    assert "262144 bytes" in rep.rule_details["GP202"][0]


def test_gp203_upcast_counts_and_direction():
    def f(x):
        down = x.astype(jnp.bfloat16)            # downcast: not counted
        return jnp.sum(down.astype(jnp.float32))  # upcast: counted

    rep = _audit(jax.jit(f), (jax.ShapeDtypeStruct((16,), jnp.float32),))
    assert rep.rule_count("GP203") == 1
    assert "bfloat16[16] -> float32" in rep.rule_details["GP203"][0]


def test_gp204_callback():
    def f(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    rep = _audit(jax.jit(f), (jax.ShapeDtypeStruct((4,), jnp.float32),))
    assert rep.rule_count("GP204") == 1
    assert "pure_callback" in rep.rule_details["GP204"][0]


def test_gp204_pallas_call_is_not_a_host_callback():
    """A ``pallas_call`` is a device kernel launch (Mosaic custom call /
    CPU interpreter), not a host round-trip — graftprog must never
    classify it under GP204, whatever substring its primitive name
    grows (PR 9 kernels/ layer)."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    rep = _audit(jax.jit(f), (jax.ShapeDtypeStruct((8, 128),
                                                   jnp.float32),))
    assert rep.rule_count("GP204") == 0


def test_gp202_skips_pallas_kernel_block_specs():
    """The kernel jaxpr's closed-over block-spec/grid machinery (and any
    constants the kernel body materializes, like a large iota grid) is
    device-kernel plumbing, not a baked host array — the GP202 walk
    treats the pallas_call as opaque. A genuine host-level closure
    constant NEXT TO the kernel must still be flagged."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        # a >16 KiB constant INSIDE the kernel body (64x128 f32 iota =
        # 32 KiB): must not trip the host-constant rule
        grid = jax.lax.broadcasted_iota(jnp.float32, (64, 128), 0)
        o_ref[...] = x_ref[...] + grid

    def gridded(x):
        return pl.pallas_call(
            kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    rep = _audit(jax.jit(gridded),
                 (jax.ShapeDtypeStruct((128, 128), jnp.float32),))
    assert rep.rule_count("GP202") == 0
    assert rep.rule_count("GP204") == 0

    big = jnp.ones((256, 256), jnp.float32)      # host-level: still flagged

    def with_host_const(x):
        return gridded(x) @ big

    rep = _audit(jax.jit(with_host_const),
                 (jax.ShapeDtypeStruct((128, 256), jnp.float32),))
    assert rep.rule_count("GP202") == 1


def test_flash_backward_pallas_calls_stay_opaque():
    """PR 13 backward kernels: differentiating through the flash
    attention lowers the dq/dkv pallas programs — they must get the
    SAME treatment as the forward kernel: never GP204 (a kernel launch
    is not a host callback), block-spec/grid params and kernel-internal
    f32 accumulator casts opaque to GP202/GP203. The only counted
    upcasts are the caller's own seams (here: none — all-f32 toy), and
    a genuine host constant NEXT TO the backward still trips GP202."""
    from t2omca_tpu.kernels.attention import flash_attention

    aval = jax.ShapeDtypeStruct((2, 2, 24, 8), jnp.float32)

    def loss(q, k, v):
        return (flash_attention(q, k, v, interpret=True,
                                block_q=8, block_k=8) ** 2).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    rep = _audit(grad, (aval, aval, aval), dtype="float32")
    assert rep.rule_count("GP204") == 0
    assert rep.rule_count("GP202") == 0
    assert rep.rule_count("GP203") == 0

    big = jnp.ones((256, 256), jnp.float32)

    def loss_with_const(q, k, v):
        dq, _, _ = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return (dq.reshape(-1, 8) @ big[:8, :8]).sum() + jnp.sum(big)

    rep = _audit(jax.jit(loss_with_const), (aval, aval, aval),
                 dtype="float32")
    assert rep.rule_count("GP202") == 1          # the host const, only
    assert rep.rule_count("GP204") == 0


def test_flash_backward_is_pallas_not_einsum_recompute():
    """The gradient of the flash kernel must run the flash BACKWARD
    kernels (three pallas_calls: residual-emitting forward, dq, dkv) —
    NOT the pre-PR-13 einsum-reference recompute, whose jaxpr had ONE
    pallas_call and a (B, H, Q, K)-shaped softmax chain in the host
    program."""
    from jax.extend.core import ClosedJaxpr
    from t2omca_tpu.kernels.attention import flash_attention

    x = jnp.zeros((2, 2, 24, 8), jnp.float32)

    def loss(q, k, v):
        return (flash_attention(q, k, v, interpret=True,
                                block_q=8, block_k=8) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)

    def count_pallas(closed):
        n = 0
        stack = [closed]
        seen = set()
        while stack:
            cj = stack.pop()
            if id(cj) in seen:
                continue
            seen.add(id(cj))
            for eqn in cj.jaxpr.eqns:
                if "pallas" in eqn.primitive.name:
                    n += 1
                    continue            # opaque, like the auditor
                for v in eqn.params.values():
                    if isinstance(v, ClosedJaxpr):
                        stack.append(v)
                    elif isinstance(v, (tuple, list)):
                        stack.extend(u for u in v
                                     if isinstance(u, ClosedJaxpr))
        return n

    assert count_pallas(jaxpr) == 3


def test_programs_json_pins_pallas_train_bytes_below_xla():
    """The PR 13 acceptance relation, enforced against the checked-in
    ratchet file (no jax, no lowering — the audit prelude keeps the
    numbers honest): under ``kernels.attention: pallas`` the lowered
    GP302 bytes AND GP301 flops of the train-path programs sit STRICTLY
    below their einsum (_ref) twins at the kernel audit scale."""
    data = json.loads(
        (REPO / "t2omca_tpu/analysis/programs.json").read_text())
    progs = data["programs"]
    for name in ("train_iter_pallas", "learner_train_pallas"):
        pal, ref = progs[name], progs[f"{name}_ref"]
        assert pal["level"] == ref["level"] == "lowered"
        assert pal["bytes_accessed"] < ref["bytes_accessed"], (
            name, pal["bytes_accessed"], ref["bytes_accessed"])
        assert pal["flops"] < ref["flops"]


def test_clean_program_no_findings_and_metrics():
    def f(x):
        return x * 2.0
    rep = _audit(jax.jit(f, donate_argnums=(0,)),
                 (jax.ShapeDtypeStruct((32, 32), jnp.float32),),
                 donate=(0,), compile=True)
    assert rep.rule_details == {}
    assert rep.level == "compiled"
    assert rep.flops and rep.flops > 0
    assert rep.peak_bytes is not None
    assert len(rep.fingerprint) == 16


def test_skip_marker_short_circuits():
    rep = audit_program("dp", AuditProgram.skipped("needs 2 devices"),
                        "float32")
    assert rep.skipped == "needs 2 devices"
    assert rep.fingerprint == ""


# ------------------------------------------------- fingerprint drift

def test_fingerprint_drift_on_weak_typed_scalar():
    """The retrace bug class ``run._strong`` exists for: a weak-typed
    scalar produces a DIFFERENT program aval than the strong input the
    driver chains back — the fingerprint must see it."""
    f = jax.jit(lambda x, t: x * t)
    x = jax.ShapeDtypeStruct((4,), jnp.bfloat16)
    weak = jnp.asarray(0.5)                # weak f32 (Python scalar):
    # adapts to x's bf16 — the compute stays narrow
    strong = jnp.zeros((), jnp.float32)    # strong f32: promotes the
    # whole expression to f32 — a different (upcast) program
    assert weak.aval.weak_type and not strong.aval.weak_type
    fp_weak = fingerprint_text(f.trace(x, weak).lower().as_text())
    fp_strong = fingerprint_text(f.trace(x, strong).lower().as_text())
    assert fp_weak != fp_strong


def test_fingerprint_stable_across_retrace():
    f = jax.jit(lambda x: x + 1)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    assert (fingerprint_text(f.trace(x).lower().as_text())
            == fingerprint_text(
                jax.jit(lambda x: x + 1).trace(x).lower().as_text()))


# ------------------------------------- programs.json ratchet semantics

def _report(name="prog", fp="aaaa", flops=100.0, by=1000.0, peak=None,
            level="lowered", rules=None):
    return ProgramReport(name=name, fingerprint=fp, level=level,
                         flops=flops, bytes_accessed=by, peak_bytes=peak,
                         rule_details=rules or {})


def _entry(fp="aaaa", flops=100.0, by=1000.0, peak=None, tol=None,
           level="lowered", rules=None):
    e = {"fingerprint": fp, "level": level, "flops": flops,
         "bytes_accessed": by, "tolerance": tol or {},
         "justification": "test"}
    if peak is not None:
        e["peak_bytes"] = peak
    if rules:
        e["rules"] = rules
    return e


def test_ratchet_clean_match():
    new, stale = compare_reports([_report()], {"prog": _entry()})
    assert new == [] and stale == []


def test_ratchet_gp300_missing_entry_surfaces_rule_details():
    rep = _report(rules={"GP204": ["`pure_callback` ..."]})
    new, _ = compare_reports([rep], {})
    assert [f.rule for f in new] == ["GP300", "GP204"]


def test_ratchet_gp301_302_303_tolerance_boundaries():
    rep = _report(flops=112.0, by=1000.0, peak=130.0)
    base = {"prog": _entry(flops=100.0, by=1000.0, peak=100.0,
                           tol={"flops": 0.10, "peak_bytes": 0.25})}
    new, _ = compare_reports([rep], base)
    assert sorted(f.rule for f in new) == ["GP301", "GP303"]
    # exactly at tolerance: not a finding
    rep2 = _report(flops=110.0, by=1000.0, peak=125.0)
    new2, _ = compare_reports([rep2], base)
    assert new2 == []


def test_ratchet_improvement_is_stale_not_failure():
    new, stale = compare_reports(
        [_report(flops=50.0)],
        {"prog": _entry(flops=100.0, tol={"flops": 0.10})})
    assert new == []
    assert any("improved" in s for s in stale)


def test_ratchet_gp304_fingerprint_drift():
    new, _ = compare_reports([_report(fp="bbbb")],
                             {"prog": _entry(fp="aaaa")})
    assert [f.rule for f in new] == ["GP304"]


def test_ratchet_rule_count_excess_and_drop():
    rules = {"GP203": ["up1", "up2", "up3"]}
    base = {"prog": _entry(rules={"GP203": {"count": 2,
                                            "justification": "x"}})}
    new, stale = compare_reports([_report(rules=rules)], base)
    assert [f.rule for f in new] == ["GP203", "GP203"]   # excess + summary
    new2, stale2 = compare_reports(
        [_report(rules={"GP203": ["up1"]})], base)
    assert new2 == [] and any("dropped" in s for s in stale2)


def test_ratchet_level_change_and_vanished_program():
    new, stale = compare_reports(
        [_report(level="compiled")], {"prog": _entry(level="lowered"),
                                      "gone": _entry()})
    assert [f.rule for f in new] == ["GP300"]
    assert any("no longer registered" in s for s in stale)


def test_ratchet_skip_never_fails():
    rep = ProgramReport(name="dp", skipped="needs 2 devices")
    new, stale = compare_reports([rep], {"dp": _entry()})
    assert new == [] and any("skipped" in s for s in stale)


# ------------------------------------------- programs.json round-trip

def test_programs_roundtrip_preserves_justifications(tmp_path):
    p = tmp_path / "programs.json"
    rep = _report(peak=55.0, level="compiled",
                  rules={"GP203": ["up1", "up2"]})
    save_programs(p, [rep], platform="cpu")
    data = load_programs(p)
    assert data["platform"] == "cpu"
    entry = data["programs"]["prog"]
    assert entry["fingerprint"] == "aaaa"
    assert entry["peak_bytes"] == 55.0
    assert entry["rules"]["GP203"]["count"] == 2
    assert "TODO" in entry["justification"]          # new entries marked
    # hand-edit the justification + tolerance, re-save: both survive
    raw = json.loads(p.read_text())
    raw["programs"]["prog"]["justification"] = "deliberate"
    raw["programs"]["prog"]["tolerance"]["flops"] = 0.5
    raw["programs"]["prog"]["rules"]["GP203"]["justification"] = "f32 loss"
    p.write_text(json.dumps(raw))
    save_programs(p, [_report(flops=123.0, rules={"GP203": ["a", "b"]},
                              peak=55.0, level="compiled")],
                  platform="cpu", old=load_programs(p))
    entry = load_programs(p)["programs"]["prog"]
    assert entry["justification"] == "deliberate"
    assert entry["tolerance"]["flops"] == 0.5
    assert entry["rules"]["GP203"]["justification"] == "f32 loss"
    assert entry["flops"] == 123.0                   # value updated


def test_programs_save_keeps_skipped_entry(tmp_path):
    p = tmp_path / "programs.json"
    save_programs(p, [_report(name="dp")], platform="cpu")
    skipped = ProgramReport(name="dp", skipped="needs 2 devices")
    save_programs(p, [skipped], platform="cpu", old=load_programs(p))
    assert load_programs(p)["programs"]["dp"]["fingerprint"] == "aaaa"


def test_programs_version_guard(tmp_path):
    p = tmp_path / "programs.json"
    p.write_text(json.dumps({"version": 99, "programs": {}}))
    with pytest.raises(ValueError, match="version"):
        load_programs(p)


def test_checked_in_programs_baseline_is_justified():
    """Every entry (and every per-rule count) in the checked-in
    programs.json carries a real justification — the TODO marker the
    writer plants must never land on main."""
    data = load_programs()
    assert data["programs"], "checked-in programs.json is empty"
    for name, entry in data["programs"].items():
        assert "TODO" not in entry["justification"], name
        for rule, info in entry.get("rules", {}).items():
            assert rule in GP_RULES, (name, rule)
            assert "TODO" not in info["justification"], (name, rule)


def test_finding_format_and_catalog():
    f = ProgFinding("superstep", "GP201", "donated leaf x")
    assert f.format() == "superstep: GP201 donated leaf x"
    assert set(GP_RULES) == {"GP201", "GP202", "GP203", "GP204", "GP300",
                             "GP301", "GP302", "GP303", "GP304"}


# --------------------------------------------------------- CLI contract

def _cli(*args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "t2omca_tpu.analysis", *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_cli_seeded_regressions_flip_exit_1():
    """The ISSUE acceptance gate: each planted hazard flips the CLI to
    exit 1 with the matching GP rule id (one subprocess for all four —
    a fresh jax import per rule would cost the gate ~30 s)."""
    r = _cli("--programs", "--no-baseline",
             "--program-module", str(FIXTURES),
             "--only", "seeded_gp201", "--only", "seeded_gp202",
             "--only", "seeded_gp203", "--only", "seeded_gp204")
    assert r.returncode == 1, r.stderr
    for rule, prog in [("GP201", "seeded_gp201"), ("GP202", "seeded_gp202"),
                       ("GP203", "seeded_gp203"), ("GP204", "seeded_gp204")]:
        assert f"{prog}: {rule}" in r.stdout, (rule, r.stdout)


def test_cli_clean_seeded_program_exits_0():
    r = _cli("--programs", "--no-baseline",
             "--program-module", str(FIXTURES), "--only", "seeded_clean")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s)" in r.stdout


def test_cli_unknown_program_is_usage_error():
    r = _cli("--programs", "--only", "nope")
    assert r.returncode == 2
    assert "unknown audit program" in r.stderr


def test_cli_write_programs_refuses_partial_set():
    """--write-programs writes exactly the audited set, so combining it
    with --only would silently drop every unselected baseline entry.
    Also pins that the audit flags IMPLY --programs: without the
    implication this invocation would silently run the lint path and
    exit 0 having written nothing."""
    r = _cli("--write-programs", "--only", "superstep")
    assert r.returncode == 2
    assert "cannot be combined with --only" in r.stderr


def test_cli_write_programs_corrupt_baseline_is_usage_error(tmp_path):
    """A corrupt programs.json must fail fast with the exit-2 contract
    (checked BEFORE the minutes-long audit), not a post-audit
    traceback."""
    bad = tmp_path / "programs.json"
    bad.write_text("{not json")
    r = _cli("--programs", "--write-programs",
             "--programs-baseline", str(bad), timeout=60)
    assert r.returncode == 2
    assert "unreadable baseline" in r.stderr


@pytest.mark.slow
def test_cli_default_registry_matches_checked_in_baseline():
    """The real gate prelude: the full registered-program audit against
    the checked-in programs.json exits 0 on a clean tree (and the
    seeded fixtures, which are NOT baselined, are absent)."""
    r = _cli("--programs")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s)" in r.stdout


@pytest.mark.slow
def test_registry_names_and_structure():
    from t2omca_tpu.analysis.registry import collect_default_programs
    reg = collect_default_programs()
    assert set(reg) == {"rollout", "insert", "train_iter", "superstep",
                        "dp_superstep", "learner_train", "serve_step",
                        "attn_xla", "attn_pallas", "attn_pallas_bwd",
                        "train_iter_pallas", "train_iter_pallas_ref",
                        "learner_train_pallas", "learner_train_pallas_ref",
                        "actor_step", "learner_step",
                        "env_reset", "env_step",
                        "train_iter_sight", "superstep_sight",
                        "superstep_pop", "superstep_pop_pallas",
                        "pop_dp_superstep", "pop_learner_step",
                        "dpmp_block"}
    # the donated hot programs are the compiled (memory-audited) ones
    assert reg["superstep"].compile and reg["train_iter"].compile
    assert reg["superstep"].donate_argnums == (0,)
    # mesh-bound programs exist on this host (conftest forces 8 CPU
    # devices: enough for the dp 2-mesh and the sebulba 2+2 split)
    assert reg["dp_superstep"].skip is None
    assert reg["actor_step"].skip is None
    assert reg["learner_step"].skip is None
    assert reg["learner_step"].donate_argnums == (0,)
