"""The tracing vocabulary (``obs/spans.py:KNOWN_SCOPES`` /
``KNOWN_PHASES``): every ``jax.named_scope`` of the package is in the
vocabulary and every member is opened; the four driver programs carry the
scopes they should and compute the same thing without them; the span
recorder's injected ``annotate`` nests LIFO; and a profiler trace of a
real ``run.run`` holds the driver's spans as host events."""

import ast
import contextlib
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from t2omca_tpu.config import from_dict, sanity_check
from t2omca_tpu.obs import spans
from t2omca_tpu.obs.spans import KNOWN_PHASES, KNOWN_SCOPES
from t2omca_tpu.utils import resilience
from t2omca_tpu.utils.logging import Logger

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PKG = os.path.join(REPO, "t2omca_tpu")

ROLLOUT = {"rollout.reset", "env.obs", "env.step", "env.normalizer",
           "act.forward", "act.select", "rollout.store", "agent.embed",
           "agent.attention", "agent.ff", "agent.head"}
TRAIN = {"replay.sample", "replay.priority", "learner.agent",
         "learner.mixer", "learner.target", "learner.loss",
         "learner.optimizer", "sight", "agent.embed", "agent.attention",
         "agent.ff", "agent.head"}
#: opened by a catalog trunk's layers alone (models/trunk.py), which in
#: turn have no ``agent.ff``: the first two by every trunk, the next two
#: where the layers have a shared expert / a dense feed-forward, the last
#: where attention is latent
ROUTED = {"agent.router", "agent.experts"}
SHARED = ROUTED | {"agent.shared", "agent.dense"}
TRUNK_ONLY = SHARED | {"agent.latent"}
#: what each family of ``TRUNKS`` opens of them
OPENS = {"smallthinker": ROUTED, "afmoe": SHARED, "deepseek_v3": TRUNK_ONLY}
CARRIES = {"_rollout": ROLLOUT, "_insert": {"replay.insert"},
           "_train_iter": TRAIN,
           "_superstep": set(KNOWN_SCOPES) - TRUNK_ONLY}


def tiny(**kw):
    """Entity tables, compact storage, PER, remat and sight on: every
    scope of the vocabulary has work to name."""
    d = {"batch_size_run": 4, "batch_size": 4, "superstep": 2,
         "t_max": 96, "test_interval": 48, "test_nepisode": 4,
         "log_interval": 24, "runner_log_interval": 24,
         "save_model": False,
         "env_args": {"agv_num": 3, "mec_num": 2, "num_channels": 2,
                      "episode_limit": 6},
         "model": {"emb": 8, "heads": 2, "depth": 2, "mixer_emb": 8,
                   "mixer_heads": 2, "mixer_depth": 2,
                   "standard_heads": True, "remat": True},
         "replay": {"buffer_size": 8, "store_dtype": "bfloat16"},
         "obs": {"enabled": True, "pulse_port": 0,
                 "sight": {"enabled": True}}}
    d.update(kw)
    return sanity_check(from_dict(d))


TRUNKS = {
    "smallthinker": {"hidden_size": 16, "head_dim": 4,
                     "num_attention_heads": 4, "num_key_value_heads": 2,
                     "num_hidden_layers": 2, "moe_ffn_hidden_size": 8,
                     "moe_num_primary_experts": 4,
                     "moe_num_active_primary_experts": 2,
                     "rope_layout": [0, 1],
                     "sliding_window_layout": [0, 1],
                     "sliding_window_size": 2, "experts_held": 2,
                     "heads_held": 2},
    # published layers 1-2 of 3: a dense and a routed layer
    "afmoe": {"model_type": "afmoe", "hidden_size": 16, "head_dim": 4,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "num_dense_layers": 2,
              "intermediate_size": 12, "moe_intermediate_size": 8,
              "num_experts": 4, "num_experts_per_tok": 2,
              "layer_types": ["sliding_attention", "full_attention",
                              "sliding_attention"],
              "sliding_window": 2, "experts_held": 2, "heads_held": 2,
              "first_layer": 1},
    # published layers 0-1 of 2: a dense and a routed layer
    "deepseek_v3": {"model_type": "deepseek_v3", "hidden_size": 16,
                    "head_dim": 2, "num_attention_heads": 4,
                    "num_key_value_heads": 4, "num_hidden_layers": 2,
                    "intermediate_size": 12, "moe_intermediate_size": 8,
                    "n_routed_experts": 4, "num_experts_per_tok": 2,
                    "kv_lora_rank": 6, "qk_nope_head_dim": 4,
                    "qk_rope_head_dim": 2, "qk_head_dim": 6,
                    "v_head_dim": 4, "experts_held": 2, "heads_held": 2}}


def tiny_trunk(family: str = "smallthinker"):
    """``tiny`` with a catalog trunk as the agent's stack."""
    model = {"emb": 16, "depth": 2, "mixer_emb": 16, "mixer_heads": 2,
             "mixer_depth": 2, "standard_heads": True, "remat": True,
             "trunk": TRUNKS[family]}
    return tiny(model=model)


def token(scope: str):
    """A scope as a token of a name stack: inside ``vmap(...)``,
    ``transpose(jvp(...))`` and the like, never as part of a longer
    dotted name."""
    return re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")


# ------------------------------------------------------------ (a) sources

def _named_scope_calls():
    """→ [(file, line, argument node)] of every ``jax.named_scope(...)``
    in the package."""
    out = []
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope"):
                out.append((os.path.relpath(path, REPO), node.lineno,
                            node.args[0] if node.args else None))
    return out


def test_every_named_scope_is_a_literal_of_the_vocabulary():
    calls = _named_scope_calls()
    assert calls
    for path, line, arg in calls:
        # no scope name is built from a runtime value
        assert isinstance(arg, ast.Constant) and isinstance(arg.value, str), \
            f"{path}:{line}: named_scope takes a literal"
        assert arg.value in KNOWN_SCOPES, \
            f"{path}:{line}: {arg.value!r} is not in KNOWN_SCOPES"


def test_every_member_of_the_vocabulary_is_opened_somewhere():
    opened = {arg.value for _, _, arg in _named_scope_calls()
              if isinstance(arg, ast.Constant)}
    assert KNOWN_SCOPES - opened == set()
    assert not (KNOWN_SCOPES & KNOWN_PHASES)


# ---------------------------------------------------- (b), (c) programs

def _lowered_texts(cfg=None):
    """The four driver programs of the tiny configuration, lowered from
    shapes → {program: (text with debug info, text without)}."""
    from t2omca_tpu.run import Experiment
    cfg = cfg or tiny()
    exp = Experiment.build(cfg)
    ts = jax.eval_shape(lambda: exp.init_train_state(0))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    k = cfg.superstep
    keys = jax.ShapeDtypeStruct((k,) + key.shape, key.dtype)
    t_env = jnp.asarray(0)
    rollout, insert, train_iter = exp.jitted_programs()
    params, rs = ts.learner.params["agent"], ts.runner
    _, batch, _ = jax.eval_shape(
        lambda p, r: rollout(p, r, test_mode=False), params, rs)
    lowered = {
        "_rollout": rollout.lower(params, rs, test_mode=False),
        "_insert": insert.lower(ts.buffer, batch),
        "_train_iter": train_iter.lower(ts, key, t_env),
        "_superstep": exp.superstep_program(k).lower(ts, keys, t_env),
    }
    return {name: (lo.as_text(debug_info=True), lo.as_text())
            for name, lo in lowered.items()}


@pytest.fixture(scope="module")
def texts():
    return _lowered_texts()


@pytest.fixture(scope="module")
def texts_without_scopes():
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "named_scope", contextlib.nullcontext)
    try:
        return _lowered_texts()
    finally:
        mp.undo()


@pytest.mark.parametrize("program", sorted(CARRIES))
def test_program_carries_its_scopes(texts, program):
    debug, _ = texts[program]
    missing = {s for s in CARRIES[program] if not token(s).search(debug)}
    assert missing == set()
    if program in ("_train_iter", "_superstep"):
        # the wrappers split the learner into forward and backward
        assert re.search(r"transpose\(jvp\(learner\.agent\)\)", debug)
        assert re.search(r"(?<!transpose\()jvp\(learner\.agent\)", debug)
        assert "checkpoint" in debug or "remat" in debug
    if program in ("_rollout", "_insert"):
        assert not token("learner.optimizer").search(debug)
    # the T2OMCA stack opens none of a catalog trunk's scopes
    assert not any(token(s).search(debug) for s in TRUNK_ONLY)


@pytest.mark.parametrize("family", sorted(TRUNKS))
@pytest.mark.parametrize("program,scopes", [
    ("_rollout", ROLLOUT - {"agent.ff"}),
    ("_train_iter", TRAIN),
    ("_superstep", set(KNOWN_SCOPES) - TRUNK_ONLY)],
    ids=["_rollout", "_train_iter", "_superstep"])
def test_trunk_program_carries_the_trunk_scopes(program, scopes, family):
    """A ``model.trunk`` configuration opens ``agent.router`` and
    ``agent.experts`` beside the other ``agent.*`` — under ``act.forward``
    and under the learner's scopes (``checkpoint`` bodies, forward and
    backward) — and no ``agent.ff`` outside the mixer's blocks (the
    rollout has no mixer); ``agent.shared`` and ``agent.dense`` exactly
    where the layers have a shared expert and a dense feed-forward,
    ``agent.latent`` — inside ``agent.attention`` — where attention is
    latent."""
    debug, _ = _trunk_texts(family)[program]
    mine = OPENS[family]
    assert {s for s in scopes | mine if not token(s).search(debug)} == set()
    assert not any(token(s).search(debug) for s in TRUNK_ONLY - mine)
    if program == "_rollout":
        assert not token("agent.ff").search(debug)
    else:
        assert re.search(r"transpose\(jvp\(learner\.agent\)\)", debug)
        assert re.search(r"rematted_computation/agent\.experts", debug)
    if "agent.latent" in mine:
        assert re.search(r"agent\.attention/agent\.latent/", debug)


_TRUNK_TEXTS = {}


def _trunk_texts(family):
    if family not in _TRUNK_TEXTS:
        _TRUNK_TEXTS[family] = _lowered_texts(tiny_trunk(family))
    return _TRUNK_TEXTS[family]


@pytest.mark.parametrize("program", sorted(CARRIES))
def test_scopes_change_no_computation(texts, texts_without_scopes, program):
    debug_off, plain_off = texts_without_scopes[program]
    assert not any(token(s).search(debug_off) for s in KNOWN_SCOPES)
    assert texts[program][1] == plain_off


# -------------------------------------------------------- (d) the recorder

class _Annotation:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.log.append(("enter", self.name))

    def __exit__(self, exc_type, exc, tb):
        _Annotation.log.append(("exit", self.name, exc_type))


def test_injected_annotate_nests_lifo_and_survives_an_exception(tmp_path):
    _Annotation.log = []
    path = str(tmp_path / "spans.jsonl")
    rec = spans.SpanRecorder(jsonl_path=path, flush_every=1,
                             annotate=_Annotation)
    with rec.span("dispatch.superstep"):
        with rec.span("fetch.train_stats"):
            pass
        with pytest.raises(KeyError):
            with rec.span("dispatch.wait"):
                raise KeyError("x")
    rec.close()
    assert _Annotation.log == [
        ("enter", "dispatch.superstep"),
        ("enter", "fetch.train_stats"), ("exit", "fetch.train_stats", None),
        ("enter", "dispatch.wait"), ("exit", "dispatch.wait", KeyError),
        ("exit", "dispatch.superstep", None)]
    assert rec._open_ann == {} and rec._open == {}
    import json
    with open(path) as f:
        events = [json.loads(line) for line in f]
    assert [e["phase"] for e in events] == [
        "fetch.train_stats", "dispatch.wait", "dispatch.superstep"]
    assert events[1]["outcome"] == "error:KeyError"
    # t0 is the wall clock as read, not rounded to the millisecond
    assert any(e["t0"] != round(e["t0"], 3) for e in events)
    # without an annotate the recorder opens none
    plain = spans.SpanRecorder()
    with plain.span("dispatch.wait"):
        pass
    assert plain._open_ann == {}


def test_spans_module_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, t2omca_tpu.obs.spans as s; "
         "assert 'jax' not in sys.modules, 'spans imports jax'; "
         "assert s.KNOWN_SCOPES and s.KNOWN_PHASES"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]


# ------------------------------------------- (e) the profiler's host plane

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One ``run.run`` of the tiny configuration with the repo's own
    trace window over two fused dispatches, and a hook that keeps what
    the driver hands it."""
    from t2omca_tpu.run import run
    root = tmp_path_factory.mktemp("traced_run")
    trace_dir = str(root / "trace")
    cfg = tiny(local_results_path=str(root / "results"),
               profile_dir=trace_dir, profile_start=48,
               profile_iterations=2)
    seen = {"driver.iteration": [], "fetch.train_infos": []}
    resilience.clear_faults()
    for point in seen:
        resilience.register_fault(
            point, lambda _p=point, **kw: seen[_p].append(kw))
    import logging
    console = logging.Logger("t2omca.traced_run")
    seen["log"] = []
    handler = logging.Handler()
    handler.emit = lambda record: seen["log"].append(record.getMessage())
    console.addHandler(handler)
    try:
        run(cfg, Logger(console))
    finally:
        resilience.clear_faults()
    return cfg, trace_dir, seen


def test_profiler_trace_holds_the_drivers_spans_as_host_events(traced_run):
    from jax.profiler import ProfileData
    _, trace_dir, _ = traced_run
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths
    names = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in KNOWN_PHASES:
                    names[ev.name] = names.get(ev.name, 0) + 1
    assert names.get("dispatch.superstep", 0) >= 2, names
    assert names.get("driver.prepare", 0) >= 1, names
    assert names.get("driver.account", 0) >= 1, names


def test_hooks_hand_over_state_key_and_info_rows(traced_run):
    cfg, _, seen = traced_run
    boundaries = seen["driver.iteration"]
    assert len(boundaries) >= 3
    for kw in boundaries:
        assert {"t_env", "guard", "ts", "key", "train_infos"} <= set(kw)
        assert hasattr(kw["ts"], "learner") and hasattr(kw["ts"], "buffer")
        assert kw["key"].shape == jax.random.PRNGKey(0).shape
        assert isinstance(kw["train_infos"], list)
    fetched = seen["fetch.train_infos"]
    assert fetched
    for kw in fetched:
        assert kw["train_infos"] and "all_finite" in kw["train_infos"][-1]


def test_start_up_log_names_the_acting_forward_and_its_attention(traced_run):
    """Beside the fused-dispatch line: which forward ``act`` compiled and
    which attention (``BasicMAC.describe_acting``) — on the CPU the XLA
    form, whatever the shapes."""
    _, _, seen = traced_run
    assert any("fused superstep:" in line for line in seen["log"])
    assert "acting forward: entity tables, attention: xla" in seen["log"]
