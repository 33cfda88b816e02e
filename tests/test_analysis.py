"""Tracing-hygiene enforcement (t2omca_tpu/analysis, docs/ANALYSIS.md):
per-rule positive/negative fixtures for graftlint, baseline round-trip,
the zero-new-findings ratchet over the real package, and the runtime
guards (compile_budget / no_transfer) on toy programs — the cheap,
always-in-gate half; the superstep-program-level enforcement lives in
tests/test_superstep.py (slow: full jit compiles)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from t2omca_tpu.analysis import (RULES, CompileBudgetExceeded,
                                 compile_budget, diff_baseline,
                                 lint_package, lint_source, load_baseline,
                                 no_transfer, save_baseline)

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parents[1]


def rules_of(src, path="fixture.py", hot=None):
    return [f.rule for f in lint_source(src, path, hot=hot)]


# --------------------------------------------------------------- GL101

def test_gl101_if_on_traced_param_in_jitted_fn():
    src = """
import jax
@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""
    fs = lint_source(src, "fixture.py")
    assert [f.rule for f in fs] == ["GL101"]
    assert fs[0].line == 5 and "if" in fs[0].code


def test_gl101_while_in_scan_body_and_derived_local():
    src = """
import jax, jax.numpy as jnp
def outer(xs):
    def body(c, x):
        y = jnp.abs(x)
        while y > 1:
            y = y - 1
        return c, y
    return jax.lax.scan(body, 0, xs)
"""
    assert rules_of(src) == ["GL101"]


def test_gl101_negatives_static_none_isinstance_config():
    src = """
import jax
from functools import partial

@partial(jax.jit, static_argnames="mode")
def f(x, mode):
    if mode:                     # static arg: branch is fine
        return x
    return -x

@jax.jit
def g(x, key):
    if key is None:              # identity vs None: static on tracers
        return x
    if isinstance(x, tuple):     # type test: static
        return x[0]
    return x + 1

def h(cfg, x):                   # not traced at all
    if cfg:
        return x
"""
    assert rules_of(src) == []


def test_gl101_static_argnums_call_site():
    src = """
import jax
def f(x, n):
    if n > 2:
        return x
    return -x
jf = jax.jit(f, static_argnums=(1,))
"""
    assert rules_of(src) == []


# --------------------------------------------------------------- GL102

def test_gl102_concretizing_calls_on_tracers():
    src = """
import jax, jax.numpy as jnp, numpy as np
@jax.jit
def f(x):
    a = float(x)
    b = jnp.sum(x).item()
    c = np.square(x)
    jax.device_get(x)
    return a + b + c
"""
    assert sorted(rules_of(src)) == ["GL102"] * 4


def test_gl102_negative_static_numpy_and_host_code():
    src = """
import jax, numpy as np
@jax.jit
def f(x):
    n = np.prod((2, 3))          # static shape math: no tracer touched
    return x * n

def host(arr):
    return float(np.asarray(arr).mean())   # not traced code
"""
    assert rules_of(src) == []


# --------------------------------------------------------------- GL103

def test_gl103_host_rng_in_traced_code():
    src = """
import jax, random
import numpy as np
@jax.jit
def f(x):
    return x + np.random.randn(3) * random.random()
"""
    assert sorted(rules_of(src)) == ["GL103", "GL103"]


def test_gl103_negative_jax_random():
    src = """
import jax
@jax.jit
def f(x, key):
    return x + jax.random.normal(key, x.shape)
"""
    assert rules_of(src) == []


# --------------------------------------------------------------- GL104

def test_gl104_jnp_in_python_for_loop():
    src = """
import jax, jax.numpy as jnp
@jax.jit
def f(x):
    for _ in range(100):
        x = jnp.sin(x)
    return x
"""
    fs = lint_source(src, "fixture.py")
    assert [f.rule for f in fs] == ["GL104"]
    assert "lax.scan" in fs[0].message


def test_gl104_negative_host_loop():
    src = """
import jax.numpy as jnp
def driver(prog, ts):
    out = []
    for i in range(3):           # host loop around dispatches: fine
        ts, info = prog(ts, jnp.asarray(i))
        out.append(info)
    return ts, out
"""
    assert rules_of(src) == []


# --------------------------------------------------------------- GL105

HOST_SYNC = """
import jax
def poll(x):
    jax.block_until_ready(x)
    return jax.device_get(x)
"""


def test_gl105_hot_path_only():
    hot = lint_source(HOST_SYNC, "t2omca_tpu/run.py")
    assert [f.rule for f in hot] == ["GL105", "GL105"]
    assert lint_source(HOST_SYNC, "t2omca_tpu/utils/stats.py") == []
    # runners/* glob
    assert rules_of(HOST_SYNC, "t2omca_tpu/runners/episode_runner.py") \
        == ["GL105", "GL105"]


def test_gl105_method_style_block_until_ready():
    src = "def wait(arr):\n    arr.block_until_ready()\n"
    assert rules_of(src, "t2omca_tpu/learners/qmix_learner.py") == ["GL105"]


# --------------------------------------------------------------- GL106

def test_gl106_time_in_traced_code():
    src = """
import jax, time, datetime
@jax.jit
def f(x):
    return x + time.time()

def host_cadence():
    return time.time(), datetime.datetime.now()   # host code: fine
"""
    assert rules_of(src) == ["GL106"]


# --------------------------------------------------------------- GL107

def test_gl107_shared_allocation_across_fields():
    """The exact NormState.create bug class PR 2 hit: one zeros buffer
    for mean/s/std trips XLA's donate-twice check."""
    src = """
import jax.numpy as jnp
def create(shape):
    z = jnp.zeros(shape)
    return NormState(mean=z, s=z, std=z)
"""
    fs = lint_source(src, "fixture.py")
    assert [f.rule for f in fs] == ["GL107"]
    assert "donate" in fs[0].message


def test_gl107_negative_distinct_buffers_and_read_aliasing():
    src = """
import jax.numpy as jnp
def create(shape):
    return NormState(mean=jnp.zeros(shape), s=jnp.zeros(shape),
                     std=jnp.zeros(shape))

def read_alias(shape):
    z = jnp.zeros(shape)
    return jnp.maximum(z, z)     # reads may alias; only state may not
"""
    assert rules_of(src) == []


# --------------------------------------------------------------- GL108

def test_gl108_dead_import():
    src = "import os\nimport sys\nprint(sys.argv)\n"
    fs = lint_source(src, "fixture.py")
    assert [f.rule for f in fs] == ["GL108"]
    assert "`os`" in fs[0].message


def test_gl108_negatives_init_all_and_annotations():
    # __init__.py is a re-export surface
    assert rules_of("import os\n", "t2omca_tpu/sub/__init__.py") == []
    # __all__ strings count as use
    assert rules_of('from a import b\n__all__ = ["b"]\n') == []
    # annotation-only use counts (PEP 563 keeps Name nodes in the AST)
    assert rules_of(
        "from typing import Optional\ndef f(x: Optional[int]): pass\n"
    ) == []


# --------------------------------------------------------------- GL109

def test_gl109_module_level_capture():
    src = """
import jax, jax.numpy as jnp
TABLE = jnp.arange(1000)
@jax.jit
def f(x):
    return x + TABLE
"""
    fs = lint_source(src, "fixture.py")
    assert [f.rule for f in fs] == ["GL109"]
    assert "`TABLE`" in fs[0].message and "line 3" in fs[0].message


def test_gl109_nontraced_builder_capture():
    src = """
import jax, jax.numpy as jnp
def make():
    table = jnp.ones((256, 256))
    @jax.jit
    def f(x):
        return x @ table
    return f
"""
    assert rules_of(src) == ["GL109"]


def test_gl109_negatives_param_shadow_and_traced_source():
    src = """
import jax, jax.numpy as jnp

def init():                      # unrelated scope, same name
    weights = jnp.zeros((8, 8))
    return weights

def apply(weights, x):           # the capture resolves to THIS param
    def body(c, t):
        return c @ weights + t, None
    return jax.lax.scan(body, x, None, length=3)

@jax.jit
def g(x):
    y = jnp.abs(x)               # bound locally: a tracer, not a const
    def inner(z):
        return z + y
    return inner(x)
"""
    assert rules_of(src) == []


def test_gl109_negative_nested_param_shadows_module_array():
    src = """
import jax, jax.numpy as jnp
W = jnp.ones((256, 256))
@jax.jit
def f(x, ws):
    def body(carry, W):              # param shadows the module array
        return carry @ W, None
    return jax.lax.scan(body, x, ws)
"""
    assert rules_of(src) == []


def test_gl109_negative_class_attribute_is_not_a_closure_binding():
    src = """
import jax, jax.numpy as jnp
class Cfg:
    TABLE = jnp.arange(1000)     # attribute (Cfg.TABLE), not a capture
@jax.jit
def f(x):
    return x + Cfg.TABLE.shape[0]
"""
    assert rules_of(src) == []
    # ...and a class attr must not shadow a REAL module-level array
    src2 = """
import jax, jax.numpy as jnp
class C:
    TABLE = jnp.zeros(())
TABLE = jnp.arange(1000)
@jax.jit
def f(x):
    return x + TABLE
"""
    assert rules_of(src2) == ["GL109"]


def test_gl109_negative_static_metadata_capture():
    src = """
import jax, jax.numpy as jnp
sd = jnp.dtype("bfloat16")       # static metadata, not an array
@jax.jit
def f(x):
    return x.astype(sd)
"""
    assert rules_of(src) == []


def test_gl109_suppression():
    src = """
import jax, jax.numpy as jnp
TABLE = jnp.arange(10)
@jax.jit
def f(x):
    return x + TABLE  # graftlint: disable=GL109
"""
    assert rules_of(src) == []


# --------------------------------------------------------------- GL110

_GL110_SRC = """
def loop(_watched, _sync_point, _dispatch):
    with _watched("dispatch.superstep", None):
        pass
    _sync_point("fetch.train_stats", lambda: None)
    _dispatch("dispatch.bogus", lambda: None, None)
    _dispatch(phase="fetch.bogus", fn=lambda: None)
"""


def test_gl110_unregistered_phase_flagged():
    phases = {"dispatch.superstep", "fetch.train_stats"}
    fs = lint_source(_GL110_SRC, "fixture.py", span_phases=phases)
    assert sorted(f.rule for f in fs) == ["GL110", "GL110"]
    msgs = " | ".join(f.message for f in fs)
    assert "dispatch.bogus" in msgs           # positional literal
    assert "fetch.bogus" in msgs              # phase= keyword literal
    assert "KNOWN_PHASES" in msgs


def test_gl110_disabled_without_registry_and_skips_dynamic():
    # no span_phases (registry absent) -> rule disarmed entirely
    assert lint_source(_GL110_SRC, "fixture.py") == []
    # dynamic phases are invisible to AST: never flagged
    src = """
def f(_watched, name):
    with _watched(name, None):
        pass
"""
    assert lint_source(src, "fixture.py", span_phases=set()) == []


def test_gl110_registry_parsed_from_spans_module(tmp_path):
    """``lint_package`` arms GL110 from the real obs/spans.py — parsed
    by AST, never imported — and the real driver is clean against it."""
    from t2omca_tpu.analysis.graftlint import collect_span_phases
    phases = collect_span_phases(REPO)
    assert phases is not None
    assert "dispatch.superstep" in phases and "fleet.dispatch" in phases
    # a repo without the registry file disarms the rule (None)
    assert collect_span_phases(tmp_path) is None
    # and an unregistered phase in a package file WOULD be a gate
    # failure: prove the plumbing end-to-end through lint_package
    pkg = tmp_path / "t2omca_tpu"
    (pkg / "obs").mkdir(parents=True)
    (pkg / "obs" / "spans.py").write_text(
        'KNOWN_PHASES = frozenset({"dispatch.good"})\n')
    (pkg / "driver.py").write_text(_GL110_SRC.replace(
        "dispatch.superstep", "dispatch.good"))
    found = lint_package(tmp_path)
    gl110 = [f for f in found if f.rule == "GL110"]
    assert {f.message.split("'")[1] for f in gl110} == \
        {"dispatch.bogus", "fetch.bogus", "fetch.train_stats"}


# --------------------------------------------------------------- GL112

_GL112_SRC = """
from flax import serialization

def load(blob, template, raw):
    params = serialization.msgpack_restore(blob)
    agent = serialization.from_state_dict(template, raw)
    return params, agent
"""


def test_gl112_raw_deserialize_in_ckpt_modules():
    """Both flax deserializers flag in the driver and serve modules —
    the checkpoint-door contract (docs/ANALYSIS.md GL112)."""
    for path in ("t2omca_tpu/run.py", "t2omca_tpu/serve/export2.py"):
        fs = lint_source(_GL112_SRC, path)
        assert [f.rule for f in fs] == ["GL112", "GL112"], path
        msgs = " | ".join(f.message for f in fs)
        assert "msgpack_restore" in msgs
        assert "from_state_dict" in msgs
        assert "utils/checkpoint" in msgs


def test_gl112_scoped_to_ckpt_globs_and_alias_resolved():
    # utils/checkpoint.py IS the sanctioned door; library code elsewhere
    # may deserialize whatever it owns — neither is in CKPT_PATH_GLOBS
    assert lint_source(_GL112_SRC, "t2omca_tpu/utils/checkpoint.py") == []
    assert lint_source(_GL112_SRC, "t2omca_tpu/components/foo.py") == []
    # alias-resolved: `import flax.serialization as ser` still flags,
    # and an unresolvable receiver falls back to the attribute name
    src = """
import flax.serialization as ser

def load(blob, codec):
    a = ser.msgpack_restore(blob)
    b = codec().from_state_dict(None, blob)
    return a, b
"""
    fs = lint_source(src, "t2omca_tpu/serve/x.py")
    assert [f.rule for f in fs] == ["GL112", "GL112"]
    # a same-named call on a RESOLVABLE non-flax receiver is not a raw
    # checkpoint load (the fallback only covers opaque receivers)
    clean = """
import mylib

def load(blob):
    return mylib.msgpack_restore(blob)
"""
    assert lint_source(clean, "t2omca_tpu/serve/x.py") == []


# ---------------------------------------------------------- suppression

def test_inline_suppression_and_skip_file():
    src = """
import jax
@jax.jit
def f(x):
    if x > 0:  # graftlint: disable=GL101
        return x
    return -x
"""
    assert rules_of(src) == []
    # disabling a DIFFERENT rule does not suppress
    assert rules_of(src.replace("GL101", "GL105")) == ["GL101"]
    skip = "# graftlint: skip-file\n" + src
    assert rules_of(skip) == []
    # a lowercase/typo'd rule list suppresses THAT rule (normalized),
    # never the whole line; a junk list suppresses nothing
    assert rules_of(src.replace("GL101", "gl101")) == []
    assert rules_of(src.replace("GL101", "bogus")) == ["GL101"]


def test_traced_dataflow_reaches_fixpoint():
    """Taint chains written in reverse definition order still propagate
    (the fixpoint loop must iterate until the set stops growing)."""
    src = """
import jax
@jax.jit
def f(x):
    w = 0
    z = 0
    y = 0
    for _ in range(2):
        w = z
        z = y
        y = x
    if w > 0:
        return w
    return -w
"""
    assert "GL101" in rules_of(src)


# ------------------------------------------------------------- baseline

def test_baseline_round_trip_and_ratchet(tmp_path):
    src_v1 = """
import jax
@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""
    findings = lint_source(src_v1, "pkg/mod.py")
    assert len(findings) == 1
    bl_path = tmp_path / "baseline.json"
    save_baseline(bl_path, findings)
    baseline = load_baseline(bl_path)
    # round-trip: the same findings are fully baselined
    new, stale = diff_baseline(findings, baseline)
    assert new == [] and stale == []
    # a SECOND occurrence of the same hazard (same code text, new line)
    # exceeds the baselined count -> new
    src_v2 = src_v1 + """
@jax.jit
def g(x):
    if x > 0:
        return x
    return -x
"""
    new, stale = diff_baseline(lint_source(src_v2, "pkg/mod.py"), baseline)
    assert len(new) == 1 and new[0].rule == "GL101"
    # fixing the hazard leaves a stale entry, never a failure
    new, stale = diff_baseline(lint_source("", "pkg/mod.py"), baseline)
    assert new == [] and len(stale) == 1
    # unjustified entries carry the TODO marker for review
    assert json.loads(bl_path.read_text())["findings"][0][
        "justification"].startswith("TODO")


def test_baseline_identity_survives_line_shift(tmp_path):
    src = """
import jax
@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""
    bl_path = tmp_path / "baseline.json"
    save_baseline(bl_path, lint_source(src, "pkg/mod.py"))
    shifted = "\n\n# a new header comment\n" + src
    new, stale = diff_baseline(lint_source(shifted, "pkg/mod.py"),
                               load_baseline(bl_path))
    assert new == [] and stale == []


# ------------------------------------------------- the real package gate

def test_real_package_zero_new_findings():
    """The ratchet over t2omca_tpu/ itself: every current finding is
    either fixed or baselined with a justification — new hazards fail
    here (and in scripts/lint.sh before the tier-1 pytest batch)."""
    findings = lint_package(REPO)
    baseline = load_baseline()
    new, _stale = diff_baseline(findings, baseline)
    assert new == [], "new graftlint findings:\n" + "\n".join(
        f.format() for f in new)
    # and every baselined acceptance carries a real justification
    for key, entry in baseline.items():
        assert entry["justification"] and \
            not entry["justification"].startswith("TODO"), key


def test_cli_exit_codes(tmp_path):
    """0 on the clean repo; 1 with rule ID + file:line once a hazard is
    seeded (the ISSUE acceptance demo, via a copied mini-package)."""
    r = subprocess.run(
        [sys.executable, "-m", "t2omca_tpu.analysis"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    # seeded hazard in a scratch tree (repo-shaped so hot-path globs work)
    pkg = tmp_path / "t2omca_tpu"
    pkg.mkdir()
    hazard = pkg / "seeded.py"
    hazard.write_text(
        "import jax\n@jax.jit\ndef f(x):\n    if x > 0:\n"
        "        return x\n    return -x\n")
    r = subprocess.run(
        [sys.executable, "-m", "t2omca_tpu.analysis", "--root",
         str(tmp_path), "--no-baseline", str(pkg)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "GL101" in r.stdout and "t2omca_tpu/seeded.py:4" in r.stdout
    # a corrupt baseline is an internal error (2), never "new findings"
    bad = tmp_path / "bad_baseline.json"
    bad.write_text('{"version": 99, "findings": []}')
    r = subprocess.run(
        [sys.executable, "-m", "t2omca_tpu.analysis", "--baseline",
         str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 2 and "baseline" in r.stderr


def test_rule_catalog_documented():
    """Every rule ID is in docs/ANALYSIS.md and vice versa (the catalog
    is the user-facing contract)."""
    doc = (REPO / "docs" / "ANALYSIS.md").read_text()
    from t2omca_tpu.analysis.graftprog import GP_RULES
    for rule in list(RULES) + list(GP_RULES):
        assert rule in doc, f"{rule} missing from docs/ANALYSIS.md"


# ------------------------------------------------------- runtime guards

def test_compile_budget_counts_and_raises():
    import jax
    import jax.numpy as jnp

    def poly(x):
        return x * x + 3.0

    prog = jax.jit(poly)
    with compile_budget(1, match="poly") as log:
        for _ in range(4):
            prog(jnp.ones(3))            # one compile, then cache hits
    assert log.count == 1 and any("poly" in n for n in log.names)

    prog2 = jax.jit(lambda x: x - 1.0)
    with pytest.raises(CompileBudgetExceeded, match="retracing"):
        with compile_budget(1):
            prog2(jnp.ones(3))
            prog2(jnp.ones(4))           # shape change -> retrace


def test_compile_budget_match_filters_unrelated_compiles():
    import jax
    import jax.numpy as jnp

    def matched_fn(x):
        return x + 2.0

    prog = jax.jit(matched_fn)
    with compile_budget(1, match="matched_fn") as log:
        prog(jnp.ones(5))
        # unrelated op compiles (bare jnp ops are their own tiny
        # programs) must not count against the budget
        jnp.arange(7.0) * 3
    assert log.count == 1


def test_no_transfer_blocks_implicit_host_to_device():
    import jax
    import jax.numpy as jnp
    import numpy as np

    prog = jax.jit(lambda a, t: a * t)
    x = jnp.arange(3.0)
    t = jnp.asarray(2, jnp.int32)
    prog(x, t)                           # compile outside the guard
    with no_transfer():
        prog(x, t)                       # all-device dispatch: clean
    with pytest.raises(Exception, match="[Tt]ransfer"):
        with no_transfer():
            prog(x, 2)                   # python scalar sneaks into args
    with pytest.raises(Exception, match="[Tt]ransfer"):
        with no_transfer():
            prog(np.ones(3, np.float32), t)   # numpy arg -> implicit H2D
    # explicit transfers stay allowed: the cadence-boundary contract
    with no_transfer():
        jax.device_get(x)
