"""graftserve: the AOT policy-serving subsystem (docs/SERVING.md).

Two tiers, matching the tier-1 budget reality (the 870s gate is nearly
full): the host-side batching logic — bucket pick, mask-correct
padding, session carry, meta round-trip, CLI usage errors — runs
in-gate with no jit; everything that compiles (export → load → serve
round-trips, the CLI, the DP sharded resume) is ``slow``-marked.
The serve PROGRAM itself is still statically gated on every t1 run:
the graftprog prelude lowers+compiles ``serve_step`` and ratchets its
FLOPs/bytes/fingerprint (analysis/programs.json).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")

# ---------------------------------------------------------------------------
# host-side batching logic (in-gate: no jit, no Experiment build)
# ---------------------------------------------------------------------------


def test_pick_bucket_boundaries():
    from t2omca_tpu.serve.frontend import pick_bucket
    buckets = [1, 2, 4, 8]
    assert pick_bucket(1, buckets) == 1
    assert pick_bucket(2, buckets) == 2
    assert pick_bucket(3, buckets) == 4          # boundary + 1 pads up
    assert pick_bucket(4, buckets) == 4          # exact bucket, no pad
    assert pick_bucket(5, buckets) == 8
    assert pick_bucket(8, buckets) == 8
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        pick_bucket(9, buckets)
    with pytest.raises(ValueError, match=">= 1"):
        pick_bucket(0, buckets)


def test_pad_request_mask_correct():
    from t2omca_tpu.serve.frontend import pad_request
    rng = np.random.default_rng(0)
    a, d, na = 3, 5, 4
    obs = rng.standard_normal((3, a, d)).astype(np.float32)
    avail = rng.random((3, a, na)) < 0.5
    hidden = rng.standard_normal((3, a, 2)).astype(np.float32)
    po, pa, ph = pad_request(obs, avail, hidden, 8)
    assert po.shape == (8, a, d) and pa.shape == (8, a, na)
    assert ph.shape == (8, a, 2)
    # real rows untouched
    assert np.array_equal(po[:3], obs)
    assert np.array_equal(pa[:3], avail)
    assert np.array_equal(ph[:3], hidden)
    # pad rows: zero obs/hidden, avail legalizes ONLY action 0 (never
    # an all-unavailable row — masked argmax stays well-defined)
    assert not po[3:].any() and not ph[3:].any()
    assert pa.dtype == np.bool_
    assert pa[3:, :, 0].all() and not pa[3:, :, 1:].any()
    # exact-bucket batches pass through without a copy
    o2, a2, h2 = pad_request(obs, avail.astype(np.bool_), hidden, 3)
    assert o2 is obs and h2 is hidden


def test_session_store_carries_and_evicts():
    from t2omca_tpu.serve.frontend import SessionStore

    class _FakeHub:
        def __init__(self):
            self.counts = {}

        def inc(self, name, delta=1.0, **labels):
            self.counts[name] = self.counts.get(name, 0) + delta

        def set(self, name, value, **labels):
            pass

    class _FakeFrontend:
        n_agents, emb = 2, 4

        def __init__(self):
            self.seen_hidden = []
            self._hub = _FakeHub()

        def select(self, obs, avail, hidden=None):
            self.seen_hidden.append(np.array(hidden))
            n = np.asarray(obs).shape[0]
            # new hidden = old + 1 so carry is observable
            return (np.zeros((n, 2), np.int32), hidden + 1.0)

    fe = _FakeFrontend()
    store = SessionStore(fe, max_sessions=2)
    obs1 = np.zeros((2, 2, 3), np.float32)
    avail1 = np.ones((2, 2, 5), np.bool_)
    _, fresh = store.select(["a", "b"], obs1, avail1)
    assert not fe.seen_hidden[0].any()           # fresh sessions: zeros
    assert fresh.dtype == np.bool_ and fresh.all()
    _, fresh = store.select(["a", "b"], obs1, avail1)
    assert (fe.seen_hidden[1] == 1.0).all()      # carried hidden
    assert not fresh.any()                       # both carries live
    # LRU eviction at max_sessions=2: "a"/"b" touched, "c" pushes out
    # the least recently used ("a" after "b" re-touch below)
    store.select(["b"], obs1[:1], avail1[:1])
    store.select(["c"], obs1[:1], avail1[:1])
    assert len(store) == 2
    assert store.evicted == 1                    # "a" silently dropped...
    assert fe._hub.counts["serve_session_evicted"] == 1   # ...NOT silently
    # the eviction sentinel: "a" believes it is live, fresh=True says
    # its carry is gone and it restarted from zeros mid-conversation
    _, fresh = store.select(["a"], obs1[:1], avail1[:1])
    assert not fe.seen_hidden[-1].any()
    assert fresh.all()
    assert store.evicted == 2                    # re-adding "a" evicted "b"
    assert fe._hub.counts["serve_session_evicted"] == 2
    store.end("c")
    assert len(store) == 1                       # just the re-added "a"
    with pytest.raises(ValueError, match="session ids"):
        store.select(["a"], obs1, avail1)


def _stub_frontend(buckets=(1, 2, 4), a=3, d=5, na=4, emb=8):
    """A ServeFrontend over fake compiled steps: real host logic
    (validate → chunk → pad → dispatch → unpad), zero jit."""
    from t2omca_tpu.obs.spans import NULL_RECORDER
    from t2omca_tpu.serve.frontend import ServeFrontend
    meta = {"buckets": list(buckets), "n_agents": a, "obs_dim": d,
            "n_actions": na, "emb": emb}
    fe = ServeFrontend("/nonexistent", meta, mac=None, params=None,
                       dtype="float32", use_exported=False,
                       rec=NULL_RECORDER)
    dispatched = []

    def fake_step(params, obs, avail, hidden):
        n = obs.shape[0]
        dispatched.append(n)
        # actions: lowest legal action; hidden: +1 so carry/stitching
        # mistakes are observable per row
        acts = np.argmax(avail, axis=-1).astype(np.int32)
        return acts, hidden + 1.0

    fe._steps = {b: fake_step for b in buckets}
    return fe, dispatched


def test_frontend_validate_rejects_malformed_requests():
    fe, dispatched = _stub_frontend()
    good_obs = np.zeros((2, 3, 5), np.float32)
    good_avail = np.ones((2, 3, 4), np.bool_)
    with pytest.raises(ValueError, match="obs must be"):
        fe.select(np.zeros((2, 3), np.float32), good_avail)   # ndim
    with pytest.raises(ValueError, match="obs must be"):
        fe.select(np.zeros((2, 3, 6), np.float32), good_avail)  # obs_dim
    with pytest.raises(ValueError, match="avail must be"):
        fe.select(good_obs, np.ones((2, 3, 5), np.bool_))     # n_actions
    with pytest.raises(ValueError, match="avail must be"):
        fe.select(good_obs, np.ones((3, 3, 4), np.bool_))     # row count
    with pytest.raises(ValueError, match="hidden must be"):
        fe.select(good_obs, good_avail,
                  np.zeros((2, 3, 7), np.float32))            # emb
    with pytest.raises(ValueError, match="hidden must be"):
        fe.select(good_obs, good_avail,
                  np.zeros((1, 3, 8), np.float32))            # row count
    # a rejected request dispatched NOTHING (validation precedes pad)
    assert dispatched == []


def test_frontend_chunks_ragged_bursts_past_max_bucket():
    """Ragged burst schedule straddling the max bucket: every dispatch
    lands on a compiled bucket shape (never above bmax), and the
    stitched outputs keep per-row order and carried hidden across the
    chunk seams."""
    from t2omca_tpu.serve.frontend import pick_bucket
    fe, dispatched = _stub_frontend(buckets=(1, 2, 4))
    rng = np.random.default_rng(9)
    for n in (7, 4, 9, 1, 5, 13, 3):         # ragged, mostly > bmax=4
        obs = rng.standard_normal((n, 3, 5)).astype(np.float32)
        avail = rng.random((n, 3, 4)) < 0.5
        avail[..., 0] = True
        del dispatched[:]
        # per-row-distinct hidden: a chunk-seam row swap would show
        hidden_in = rng.standard_normal((n, 3, 8)).astype(np.float32)
        actions, hidden = fe.select(obs, avail, hidden_in)
        # every dispatch is a compiled bucket, none above the max
        assert all(b in (1, 2, 4) for b in dispatched), dispatched
        # chunk cover: full chunks of bmax + one bucketed remainder
        want = [4] * (n // 4)
        if n % 4:
            want.append(pick_bucket(n % 4, [1, 2, 4]))
        assert dispatched == want, (n, dispatched)
        # stitched per-row: action = first legal action of that row
        np.testing.assert_array_equal(
            actions, np.argmax(avail, axis=-1).astype(np.int32),
            err_msg=f"n={n}")
        np.testing.assert_array_equal(hidden, hidden_in + 1.0,
                                      err_msg=f"n={n}")


# ---------------------------------------------------------------------------
# atomic artifact writes (satellite: torn-write safety for binary blobs)
# ---------------------------------------------------------------------------


def test_write_bytes_atomic_survives_torn_write(tmp_path, monkeypatch):
    from t2omca_tpu.utils.ioutil import write_bytes_atomic
    target = tmp_path / "params.msgpack"
    write_bytes_atomic(str(target), b"v1-good")
    assert target.read_bytes() == b"v1-good"
    # a crash between tmp write and publish must leave the OLD blob
    # intact and no tmp litter for the next export to trip on
    real_replace = os.replace

    def torn(src, dst):
        raise OSError("injected: crash before publish")

    monkeypatch.setattr(os, "replace", torn)
    with pytest.raises(OSError, match="crash before publish"):
        write_bytes_atomic(str(target), b"v2-half-written")
    monkeypatch.setattr(os, "replace", real_replace)
    assert target.read_bytes() == b"v1-good"     # old blob untouched
    assert os.listdir(tmp_path) == ["params.msgpack"]   # no tmp leftovers
    # and a clean retry publishes
    write_bytes_atomic(str(target), b"v2-good")
    assert target.read_bytes() == b"v2-good"


def test_export_writes_no_raw_binary_handles():
    """Source pin for the atomic-write satellite: serve/export.py must
    route EVERY write through the atomic helpers (tmp + fsync + rename)
    — a raw ``open(..., "wb")`` write would reintroduce the torn-blob
    window the sha256 check can only detect, not prevent."""
    src_path = os.path.join(REPO, "t2omca_tpu", "serve", "export.py")
    with open(src_path) as f:
        src = f.read()
    assert '"wb"' not in src and "'wb'" not in src
    assert "write_bytes_atomic" in src and "write_json_atomic" in src


def test_train_config_dict_roundtrip():
    from t2omca_tpu.config import EnvConfig, ModelConfig, TrainConfig, \
        from_dict, sanity_check
    cfg = sanity_check(TrainConfig(
        batch_size_run=4, superstep=2,
        env_args=EnvConfig(agv_num=5, episode_limit=9),
        model=ModelConfig(emb=16, heads=2, mixer_emb=16, dtype="bfloat16")))
    back = from_dict(dataclasses.asdict(cfg))
    assert back == cfg


def test_serve_phases_registered_and_spanned():
    """GL110 contract for the serving boundaries: every literal phase
    the serve modules record is in KNOWN_PHASES, and the front-end's
    three request stages are all present (an unregistered phase would
    be a serving boundary with no flight/report coverage)."""
    from t2omca_tpu.obs.spans import KNOWN_PHASES
    from test_obs import _literal_phases
    phases = set()
    for mod in ("frontend.py", "export.py"):
        phases |= _literal_phases(
            os.path.join(REPO, "t2omca_tpu", "serve", mod),
            fn_names=("_watched",))
    assert {"serve.load", "serve.pad", "serve.dispatch",
            "serve.unpad", "serve.export"} <= phases
    assert phases <= KNOWN_PHASES, phases - KNOWN_PHASES
    # the report CLI maps the dispatch span onto the ratcheted program
    from t2omca_tpu.obs.report import PHASE_PROGRAMS
    assert PHASE_PROGRAMS["serve.dispatch"] == "serve_step"


def test_serve_cli_usage_errors(tmp_path, capsys):
    from t2omca_tpu.serve.__main__ import main
    # export against an empty checkpoint dir: clean exit 2, no artifact
    out = tmp_path / "art"
    rc = main(["export", str(tmp_path / "nothing"), "--out", str(out)])
    assert rc == 2
    assert "no valid checkpoint" in capsys.readouterr().err
    assert not out.exists()
    # info on a non-artifact dir
    rc = main(["info", str(tmp_path)])
    assert rc == 2
    assert "unreadable artifact" in capsys.readouterr().err
    # stray non-override positional
    with pytest.raises(SystemExit):
        main(["export", "ckpt", "not-an-override"])
    # overrides only make sense for export
    with pytest.raises(SystemExit):
        main(["info", str(tmp_path), "a=b"])


# ---------------------------------------------------------------------------
# export → load → serve round-trip (slow: Experiment build + compiles)
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                                   TrainConfig, sanity_check)
    return sanity_check(TrainConfig(
        batch_size_run=4, batch_size=4,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8)))


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    """One smoke checkpoint + exported artifact shared by the slow
    round-trip tests (the export compiles 2 dtypes × 3 buckets)."""
    from t2omca_tpu.run import Experiment
    from t2omca_tpu.serve.export import export_artifact
    from t2omca_tpu.utils.checkpoint import save_checkpoint
    root = tmp_path_factory.mktemp("serve")
    cfg = _tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    ck = os.path.join(root, "models")
    save_checkpoint(ck, 128, ts)
    art = os.path.join(root, "art")
    meta = export_artifact(cfg, ck, art, buckets=(1, 2, 4))
    return cfg, exp, ts, art, meta


@pytest.mark.slow
def test_export_artifact_layout_and_provenance(exported):
    cfg, exp, ts, art, meta = exported
    assert meta["format"] == 1
    assert meta["checkpoint"]["t_env"] == 128
    assert meta["checkpoint"]["state_sha256"]      # provenance chain
    assert meta["buckets"] == [1, 2, 4]
    assert meta["folded"] == exp.mac.use_qslice
    for dt in ("float32", "bfloat16"):
        p = meta["params"][dt]
        assert os.path.isfile(os.path.join(art, p["file"]))
        for b in (1, 2, 4):
            entry = meta["programs"][dt][str(b)]
            assert entry["fingerprint"]
            assert os.path.isfile(os.path.join(art, entry["file"]))
    # bf16 variant actually halves the big leaves
    assert (meta["params"]["bfloat16"]["bytes"]
            < 0.75 * meta["params"]["float32"]["bytes"])
    # the artifact carries no compile cache of its own
    # (utils/compile_cache.py places the process's)
    assert not os.path.exists(os.path.join(art, "compile_cache"))
    assert "compile_cache" not in meta


@pytest.mark.slow
def test_serve_bit_parity_with_training_select_actions(exported):
    """The K=1-parity convention for serving: f32 greedy actions from
    the exported artifact bit-match the training path's
    ``select_actions(test_mode=True)``, with the recurrent hidden
    carried across requests, at ragged sizes incl. batch=1, a
    bucket-boundary size, and a beyond-max-bucket batch (chunking)."""
    import jax
    import jax.numpy as jnp
    from t2omca_tpu.serve.frontend import ServeFrontend
    cfg, exp, ts, art, meta = exported
    fe = ServeFrontend.load(art, dtype="float32")
    mac = exp.mac
    env_info = exp.env.get_env_info()
    a, d, na = mac.n_agents, env_info["obs_shape"], env_info["n_actions"]
    params = jax.device_put(
        mac.prepare_acting_params(ts.learner.params["agent"]))
    sel = jax.jit(lambda p, o, av, h, k: mac.select_actions(
        p, o, av, h, k, jnp.asarray(10_000), test_mode=True))
    rng = np.random.default_rng(7)
    for n in (1, 3, 4, 7):       # batch=1, boundary+1, exact, > max bucket
        h_ref = np.zeros((n, a, mac.emb), np.float32)
        h_fe = None
        for step in range(3):    # hidden carried across request steps
            obs = rng.standard_normal((n, a, d)).astype(np.float32)
            avail = rng.random((n, a, na)) < 0.7
            avail[..., 0] = True
            a_ref, h2, _ = sel(params, obs, avail.astype(np.int32),
                               h_ref, jax.random.PRNGKey(step))
            a_fe, h_fe = fe.select(obs, avail, h_fe)
            np.testing.assert_array_equal(np.asarray(a_ref), a_fe,
                                          err_msg=f"n={n} step={step}")
            np.testing.assert_array_equal(
                np.asarray(h2, dtype=np.float32), h_fe,
                err_msg=f"hidden n={n} step={step}")
            h_ref = np.asarray(h2)


@pytest.mark.slow
def test_serve_bf16_variant_within_tolerance(exported):
    """The bf16 param variant tracks the f32 serve outputs within the
    established bf16 tolerance (tests/test_bf16.py convention) on the
    carried hidden; actions may flip on near-ties, so the pin is the
    representation, not the argmax."""
    from t2omca_tpu.serve.frontend import ServeFrontend
    cfg, exp, ts, art, meta = exported
    fe32 = ServeFrontend.load(art, dtype="float32")
    fe16 = ServeFrontend.load(art, dtype="bfloat16")
    a, d = fe32.n_agents, fe32.obs_dim
    rng = np.random.default_rng(3)
    obs = rng.standard_normal((4, a, d)).astype(np.float32)
    avail = np.ones((4, a, fe32.n_actions), np.bool_)
    _, h32 = fe32.select(obs, avail)
    _, h16 = fe16.select(obs, avail)
    np.testing.assert_allclose(h16, h32, atol=0.15, rtol=0.15)


@pytest.mark.slow
def test_serve_warm_dispatch_never_retraces(exported):
    """Warm-path pin (compile_budget): after warm-up, repeated serving
    at any bucket — including ragged sizes padding into it and carried
    hidden fed back — compiles NOTHING. The aval-stability contract
    that makes AOT serving AOT."""
    from t2omca_tpu.analysis.guards import compile_budget
    from t2omca_tpu.serve.frontend import ServeFrontend
    cfg, exp, ts, art, meta = exported
    fe = ServeFrontend.load(art, dtype="float32")
    fe.warmup()
    a, d, na = fe.n_agents, fe.obs_dim, fe.n_actions
    rng = np.random.default_rng(1)
    hidden = None
    with compile_budget(0):
        for n in (1, 2, 3, 4, 4):
            obs = rng.standard_normal((n, a, d)).astype(np.float32)
            avail = np.ones((n, a, na), np.bool_)
            _, h = fe.select(obs, avail, hidden)
            hidden = h if n == 4 else None


@pytest.mark.slow
def test_serve_compile_cache_warms_fresh_process(exported, tmp_path):
    """Cache semantics (docs/SERVING.md): the compile cache is the
    PROCESS's, placed from outside. A first serving process started
    with ``JAX_COMPILATION_CACHE_DIR`` fills it; a second adds no new
    entries (a cold miss would write one) and answers identically.
    The one-second floor is dropped by the test — these programs are
    tiny."""
    cfg, exp, ts, art, meta = exported
    from t2omca_tpu.serve.frontend import ServeFrontend
    fe = ServeFrontend.load(art, dtype="float32")
    a, d, na = fe.n_agents, fe.obs_dim, fe.n_actions
    rng = np.random.default_rng(5)
    obs = rng.standard_normal((2, a, d)).astype(np.float32)
    avail = np.ones((2, a, na), np.bool_)
    ours, _ = fe.select(obs, avail)
    cache = str(tmp_path / "placed_cache")
    code = (
        "import numpy as np, json, sys\n"
        "from t2omca_tpu.utils.compile_cache import enable_compile_cache\n"
        "from t2omca_tpu.serve.frontend import ServeFrontend\n"
        "enable_compile_cache()\n"
        f"fe = ServeFrontend.load({art!r}, dtype='float32')\n"
        f"rng = np.random.default_rng(5)\n"
        f"obs = rng.standard_normal((2, {a}, {d})).astype(np.float32)\n"
        f"avail = np.ones((2, {a}, {na}), bool)\n"
        "actions, _ = fe.select(obs, avail)\n"
        "print(json.dumps(actions.tolist()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")

    def serve_once():
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              env=env, capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        theirs = np.asarray(
            json.loads(proc.stdout.strip().splitlines()[-1]))
        np.testing.assert_array_equal(ours, theirs)
        return {f for f in os.listdir(cache) if f.endswith("-cache")}

    first = serve_once()
    assert first, "the placed cache directory stayed empty"
    assert serve_once() == first, "second process cold-compiled"


# ---------------------------------------------------------------------------
# DP sharded resume (the serve exporter shares the host-restore path)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_dp_resume_restores_sharded_without_single_device_copy(tmp_path):
    """``load_checkpoint_sharded`` (ADVICE r5): restoring into the
    sharded abstract template is bit-identical to the classic
    load-then-shard sequence, leaf for leaf, sharding for sharding —
    and the restored state dispatches."""
    import jax
    from t2omca_tpu.parallel import DataParallel, make_mesh
    from t2omca_tpu.run import Experiment
    from t2omca_tpu.utils.checkpoint import (load_checkpoint,
                                             load_checkpoint_sharded,
                                             save_checkpoint)
    cfg = _tiny_cfg().replace(dp_devices=2)
    exp = Experiment.build(cfg)
    dp = DataParallel(exp, make_mesh(2))
    ts = exp.init_train_state(0)
    save_checkpoint(str(tmp_path), 64, ts)
    d = os.path.join(str(tmp_path), "64")

    classic = dp.shard(load_checkpoint(d, exp.init_train_state(1)))
    shapes = jax.eval_shape(lambda: exp.init_train_state(1))
    sharded = load_checkpoint_sharded(d, shapes,
                                      dp.state_shardings(shapes))
    flat_c = jax.tree_util.tree_leaves_with_path(classic)
    flat_s = jax.tree_util.tree_leaves_with_path(sharded)
    assert len(flat_c) == len(flat_s)
    for (kp, lc), (_, ls) in zip(flat_c, flat_s):
        key = jax.tree_util.keystr(kp)
        assert lc.sharding == ls.sharding, key
        np.testing.assert_array_equal(np.asarray(jax.device_get(lc)),
                                      np.asarray(jax.device_get(ls)),
                                      err_msg=key)
    rollout, _, _ = dp.jitted_programs()
    _, batch, _ = rollout(sharded.learner.params["agent"],
                          sharded.runner, test_mode=False)
    assert len(jax.tree.leaves(batch.obs)[0].sharding.device_set) == 2


# ---------------------------------------------------------------------------
# CLI e2e (slow: subprocesses)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_export_cli_and_info(exported, tmp_path):
    """The CLI surface end-to-end: export a second artifact from the
    shared checkpoint with overrides, then ``info`` summarizes it."""
    cfg, exp, ts, art, meta = exported
    ck = os.path.join(os.path.dirname(art), "models")
    out = str(tmp_path / "art2")
    overrides = [
        "batch_size_run=4", "batch_size=4",
        "env_args.agv_num=3", "env_args.mec_num=2",
        "env_args.num_channels=2", "env_args.episode_limit=6",
        "model.emb=8", "model.heads=2", "model.depth=1",
        "model.mixer_emb=8", "model.mixer_heads=2",
        "model.mixer_depth=1", "replay.buffer_size=8"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "t2omca_tpu.serve", "export", ck,
         "--out", out, "--buckets", "1,2", "--dtypes", "float32",
         "--no-blobs", *overrides],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "artifact written" in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "t2omca_tpu.serve", "info", out],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert "buckets: [1, 2]" in proc.stdout
    assert "params[float32]" in proc.stdout
    # --no-blobs artifacts still serve (config-rebuild fallback)
    from t2omca_tpu.serve.frontend import ServeFrontend
    fe = ServeFrontend.load(out, dtype="float32")
    a_out, _ = fe.select(
        np.zeros((2, fe.n_agents, fe.obs_dim), np.float32),
        np.ones((2, fe.n_agents, fe.n_actions), np.bool_))
    assert a_out.shape == (2, fe.n_agents)
