"""graftfleet: fault-tolerant multi-engine serving (docs/SERVING.md §fleet).

Two tiers, like tests/test_serve.py. The fleet's supervision, admission,
hedging, ladder and refresh logic is pure host code — the in-gate tests
drive a real :class:`~t2omca_tpu.serve.fleet.ServeFleet` (real threads,
real watchdogs, real supervisor) over stub frontends injected via
``frontend_factory``, so no jit and no Experiment build ever runs in the
tier-1 budget. Everything artifact-backed (refresh bit-parity, the
fingerprint gate against real lowered programs, the chaos acceptance
run) is ``slow``-marked; the chaos acceptance run additionally carries
the ``chaos`` marker so ``scripts/chaos.sh`` can select it into the soak
battery.
"""

import os
import threading
import time

import numpy as np
import pytest

from t2omca_tpu.utils import resilience

REPO = os.path.join(os.path.dirname(__file__), "..")

A, D, NA, EMB = 2, 3, 4, 2      # stub model surface


@pytest.fixture(autouse=True)
def _no_fault_leaks():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


# ---------------------------------------------------------------------------
# stub engines (in-gate: no jit, no artifact)
# ---------------------------------------------------------------------------


class _StubFrontend:
    """Duck-typed ServeFrontend: instant selects, params-observable
    actions (``actions == int(params['w']) % n_actions`` — a hot refresh
    is visible in the output), dispatch batch sizes recorded so ladder
    bucket caps are assertable."""

    def __init__(self, dtype="float32", buckets=(1, 2, 4)):
        self.dtype = dtype
        self.buckets = list(buckets)
        self.n_agents, self.obs_dim = A, D
        self.n_actions, self.emb = NA, EMB
        self._params = {"w": np.float32(1.0)}
        self.sizes = []                     # per-dispatch batch sizes
        self.calls = 0

    def select(self, obs, avail, hidden=None):
        self.calls += 1
        n = np.asarray(obs).shape[0]
        self.sizes.append(n)
        if hidden is None:
            hidden = np.zeros((n, self.n_agents, self.emb), np.float32)
        act = int(np.asarray(self._params["w"])) % self.n_actions
        return (np.full((n, self.n_agents), act, np.int32),
                np.asarray(hidden, np.float32) + 1.0)

    def warmup(self):
        pass


def _cfg(**kw):
    from t2omca_tpu.serve.fleet import FleetConfig
    base = dict(poll_s=0.005, deadline_s=3.0, dispatch_timeout_s=0.6,
                request_retries=1, retry_backoff_s=0.005,
                restart_backoff_s=0.02, restart_backoff_max_s=0.1,
                hedge_min_s=0.02, ladder_cooldown_s=0.05)
    base.update(kw)
    return FleetConfig(**base)


def _mk_fleet(n=2, cfg=None, factory=None, hub=None, artifact_dir=None,
              meta=None):
    from t2omca_tpu.serve.fleet import ServeFleet
    fleet = ServeFleet(artifact_dir, n_engines=n, cfg=cfg or _cfg(),
                       hub=hub,
                       frontend_factory=factory
                       or (lambda dtype: _StubFrontend(dtype)))
    if meta is not None:
        fleet.meta = meta
    return fleet


def _req(n=2):
    return (np.zeros((n, A, D), np.float32), np.ones((n, A, NA), np.bool_))


def _until(pred, timeout=5.0, poll=0.005):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(poll)
    return False


# ---------------------------------------------------------------------------
# GL110: fleet phases are registered serving boundaries
# ---------------------------------------------------------------------------


def test_fleet_phases_registered():
    from t2omca_tpu.obs.spans import KNOWN_PHASES
    from test_obs import _literal_phases
    phases = _literal_phases(
        os.path.join(REPO, "t2omca_tpu", "serve", "fleet.py"),
        fn_names=("_watched",))
    assert {"fleet.load", "fleet.dispatch", "fleet.selfcheck",
            "fleet.restart", "fleet.refresh"} <= phases
    assert phases <= KNOWN_PHASES, phases - KNOWN_PHASES


# ---------------------------------------------------------------------------
# the pressure ladder (unit)
# ---------------------------------------------------------------------------


def test_fleet_ladder_rungs_and_hysteresis():
    from t2omca_tpu.serve.fleet import FleetLadder
    lad = FleetLadder([1, 2, 4], "float32", "bfloat16",
                      high=0.75, low=0.25, cooldown_s=0.0,
                      max_bucket_steps=2)
    # rung order: full → bucket caps (descending) → dtype fallback
    assert lad.rungs == [(None, "float32"), (2, "float32"),
                         (1, "float32"), (1, "bfloat16")]
    assert lad.current() == (None, "float32")
    for want in ((2, "float32"), (1, "float32"), (1, "bfloat16")):
        assert lad.update(0.9, time.monotonic()) == "degrade"
        assert lad.current() == want
    assert lad.update(1.0, time.monotonic()) is None     # floor
    # hysteresis band: mid fill moves nothing
    assert lad.update(0.5, time.monotonic()) is None
    for _ in range(3):
        assert lad.update(0.1, time.monotonic()) == "restore"
    assert lad.current() == (None, "float32")
    assert lad.update(0.0, time.monotonic()) is None     # ceiling
    assert lad.degrades == 3 and lad.restores == 3
    # dwell: a second move inside the cooldown is suppressed
    lad2 = FleetLadder([1, 2], "float32", None, 0.75, 0.25,
                       cooldown_s=100.0)
    assert lad2.rungs[-1] == (1, "float32")      # no alt → no dtype rung
    assert lad2.update(1.0, now=0.0) == "degrade"
    assert lad2.update(1.0, now=1.0) is None


# ---------------------------------------------------------------------------
# admission, deadlines, retries (in-gate, stub engines)
# ---------------------------------------------------------------------------


def test_fleet_select_ok_and_hidden_carry():
    with _mk_fleet(n=2) as fleet:
        assert fleet.serving_engines() == 2
        r = fleet.select(*_req(3))
        assert r.ok and r.status == "ok"
        assert r.actions.shape == (3, A) and (r.actions == 1).all()
        assert r.hidden.shape == (3, A, EMB)
        r2 = fleet.select(*_req(3), hidden=r.hidden)
        assert (r2.hidden == r.hidden + 1.0).all()
        st = fleet.stats()
        assert st["serving"] == 2
        assert st["fleet_requests_total"] == 2


def test_fleet_sheds_past_queue_bound_never_blocks():
    with _mk_fleet(n=2, cfg=_cfg(queue_depth=2)) as fleet:
        for e in fleet.engines:
            e.pause_ev.set()
        admitted = [fleet.submit(*_req()) for _ in range(2)]
        t0 = time.monotonic()
        shed = fleet.submit(*_req())
        assert time.monotonic() - t0 < 0.5       # shed is immediate
        assert shed.done
        assert shed.result.status == "shed"
        assert "queue full" in shed.result.error
        for e in fleet.engines:
            e.pause_ev.clear()
        assert all(r.wait(5.0).ok for r in admitted)
        assert fleet.stats()["fleet_shed_total"] == 1


def test_fleet_deadline_resolves_even_with_all_engines_paused():
    with _mk_fleet(n=1) as fleet:
        fleet.engines[0].pause_ev.set()            # nothing will dispatch
        t0 = time.monotonic()
        r = fleet.select(*_req(), deadline_s=0.3)
        assert r.status == "deadline"
        assert time.monotonic() - t0 < 2.0       # bounded, not hung
        assert fleet.stats()["fleet_deadline_total"] >= 1


def test_fleet_transient_fault_retried_in_place():
    attempts = []

    def flaky(engine, attempt, rid, **kw):
        attempts.append((rid, attempt))
        if attempt == 1:
            raise RuntimeError("chaos: connection reset by peer")

    resilience.register_fault("fleet.dispatch", flaky)
    with _mk_fleet(n=1) as fleet:
        r = fleet.select(*_req())
        assert r.ok                              # retried on the SAME engine
        st = fleet.stats()
        assert st.get("fleet_restarts_total", 0) == 0   # no quarantine
        assert fleet.engines[0].restarts == 0
    # both attempts fired for the request (attempt 2 succeeded)
    rids = {rid for rid, _ in attempts}
    assert any((rid, 1) in attempts and (rid, 2) in attempts
               for rid in rids)


def test_fleet_crash_quarantines_bounces_and_rejoins():
    killed = []

    def killer(engine, attempt, rid, **kw):
        if engine == 0 and not killed:
            killed.append(rid)
            raise RuntimeError("chaos: engine killed (injected)")

    resilience.register_fault("fleet.dispatch", killer)
    with _mk_fleet(n=2) as fleet:
        fleet.engines[1].pause_ev.set()    # engine 0 must take the request
        r = fleet.select(*_req(), deadline_s=5.0)
        # the request survived the crash: bounced, re-served after the
        # backoff restart of the only unpaused engine
        assert r.ok and r.engine == 0
        assert killed
        assert _until(lambda: fleet.engines[0].state == "serving")
        assert fleet.engines[0].restarts == 1
        assert fleet.recoveries                 # quarantine→rejoin timed
        st = fleet.stats()
        assert st["fleet_engine_failures_total"] == 1
        assert st["fleet_restarts_total"] == 1


def test_fleet_stall_is_hedged_and_stalled_engine_restarts():
    hung = []

    def hanger(engine, attempt, rid, **kw):
        if engine == 0 and not hung:
            hung.append(rid)
            time.sleep(1.2)                     # >> dispatch_timeout_s

    resilience.register_fault("fleet.dispatch", hanger)
    with _mk_fleet(n=2, cfg=_cfg(dispatch_timeout_s=0.3,
                                 deadline_s=5.0)) as fleet:
        fleet.engines[1].pause_ev.set()
        req = fleet.submit(*_req())
        assert _until(lambda: hung, timeout=2.0)
        fleet.engines[1].pause_ev.clear()          # the hedge target
        r = req.wait(6.0)
        # the hedge won on the healthy peer LONG before the wedged
        # dispatch would have returned
        assert r.ok and r.engine == 1
        assert r.hedged
        assert _until(lambda: fleet.stats().get("fleet_stalls_total",
                                                0) >= 1)
        assert fleet.stats()["fleet_hedges_total"] >= 1
        # the stalled engine was quarantined and rejoined
        assert _until(lambda: fleet.engines[0].state == "serving"
                      and fleet.engines[0].restarts == 1)


def test_fleet_bounce_cap_resolves_error_not_hang():
    def always_fail(engine, attempt, rid, **kw):
        raise RuntimeError("chaos: engine killed (injected)")

    resilience.register_fault("fleet.dispatch", always_fail)
    with _mk_fleet(n=2, cfg=_cfg(max_bounces=2, deadline_s=6.0)) as fleet:
        r = fleet.select(*_req())
        assert r.status == "error"
        assert "failed on 3 engines" in r.error
        assert "chaos: engine killed" in r.error
        assert fleet.stats()["fleet_engine_failures_total"] == 3


def test_fleet_permanent_eject_after_restart_cap():
    def always_fail(engine, attempt, rid, **kw):
        raise RuntimeError("chaos: engine killed (injected)")

    resilience.register_fault("fleet.dispatch", always_fail)
    with _mk_fleet(n=1, cfg=_cfg(max_restarts=1, max_bounces=5,
                                 deadline_s=1.0)) as fleet:
        r = fleet.select(*_req())
        # the lone engine burns its restart budget and is ejected; the
        # request resolves (deadline) instead of hanging
        assert r.status in ("deadline", "error")
        assert _until(lambda: fleet.engines[0].state == "ejected")
        assert fleet.stats()["fleet_ejected_total"] == 1
        ok, detail = fleet._fleet_health()
        assert not ok and "0/1" in detail
        # with every engine ejected, admission errors out immediately
        r2 = fleet.submit(*_req())
        assert r2.done and r2.result.status == "error"
        assert "all ejected" in r2.result.error


def test_fleet_ladder_caps_dispatch_and_falls_back_to_bf16():
    made = {}

    def factory(dtype):
        fe = _StubFrontend(dtype=dtype)
        made.setdefault(dtype, []).append(fe)
        return fe

    meta = {"buckets": [1, 2, 4],
            "params": {"float32": {}, "bfloat16": {}}}
    with _mk_fleet(n=1, factory=factory, meta=meta) as fleet:
        lad = fleet._ladder
        assert lad.rungs == [(None, "float32"), (2, "float32"),
                             (1, "float32"), (1, "bfloat16")]
        fe = made["float32"][0]
        fe.sizes.clear()                        # drop the selfcheck batch
        lad.level = 1                           # cap buckets at 2
        r = fleet.select(*_req(6))
        assert r.ok and r.actions.shape == (6, A)
        assert fe.sizes and max(fe.sizes) <= 2  # chunked under the cap
        lad.level = 3                           # bf16 rung, cap 1
        r = fleet.select(*_req(3))
        assert r.ok
        assert "bfloat16" in made               # alt variant lazily loaded
        alt = made["bfloat16"][0]
        assert alt.sizes and max(alt.sizes) <= 1


# ---------------------------------------------------------------------------
# hot refresh (in-gate: fold check stubbed; the real fold is slow-tier)
# ---------------------------------------------------------------------------


def test_fleet_refresh_rolls_all_engines_and_swaps_params():
    with _mk_fleet(n=2) as fleet:
        new = {"w": np.float32(2.0)}
        fleet._fold_check = lambda ck: (new, {"t_env": 7,
                                              "buckets_checked": 0})
        out = fleet.refresh("ckpt")
        assert out["status"] == "ok"
        assert out["engines"] == 2 and out["t_env"] == 7
        assert all(e.fe._params is new for e in fleet.engines)
        assert fleet._live_params is new
        assert fleet.serving_engines() == 2
        r = fleet.select(*_req())
        assert r.ok and (r.actions == 2).all()  # traffic sees new params
        assert fleet.stats()["fleet_refresh_total"] == 1


def test_fleet_refresh_rolled_back_when_selfcheck_trips():
    def tripper(engine, stage, **kw):
        if stage == "refresh":
            raise RuntimeError("chaos: poisoned selfcheck (injected)")

    resilience.register_fault("fleet.selfcheck", tripper)
    with _mk_fleet(n=2) as fleet:
        old = [e.fe._params for e in fleet.engines]
        fleet._fold_check = lambda ck: ({"w": np.float32(3.0)},
                                        {"t_env": 9, "buckets_checked": 0})
        out = fleet.refresh("ckpt")
        assert out["status"] == "rolled_back"
        assert "poisoned selfcheck" in out["reason"]
        # every engine kept (or got back) the params it had
        assert [e.fe._params for e in fleet.engines] == old
        assert fleet.serving_engines() == 2     # never stopped serving
        assert fleet.select(*_req()).ok
        assert fleet.stats()["fleet_refresh_rollback_total"] == 1


def test_fleet_refresh_refused_keeps_serving():
    from t2omca_tpu.serve.fleet import RefreshRefused
    with _mk_fleet(n=2) as fleet:
        old = [e.fe._params for e in fleet.engines]

        def refuse(ck):
            raise RefreshRefused("fingerprint drift")

        fleet._fold_check = refuse
        out = fleet.refresh("ckpt")
        assert out["status"] == "refused"
        assert "fingerprint drift" in out["reason"]
        assert [e.fe._params for e in fleet.engines] == old
        assert fleet.serving_engines() == 2
        assert fleet.select(*_req()).ok
        assert fleet.stats()["fleet_refresh_refused_total"] == 1


def test_fleet_refresh_aborts_below_n_minus_1_and_reports_busy():
    with _mk_fleet(n=2) as fleet:
        fleet._fold_check = lambda ck: ({"w": np.float32(4.0)},
                                        {"t_env": 1, "buckets_checked": 0})
        # concurrent refresh: second caller bounces off, no queueing
        assert fleet._refresh_lock.acquire(blocking=False)
        try:
            assert fleet.refresh("ckpt") == {"status": "busy"}
        finally:
            fleet._refresh_lock.release()
        # with a peer down, swapping the survivor would drop the fleet
        # below N-1 serving → abort, params untouched
        eng1 = fleet.engines[1]
        with eng1.lock:
            eng1.gen += 1                       # supersede its worker
            eng1.state = "quarantined"
            eng1.restart_at = time.monotonic() + 60.0
        old0 = fleet.engines[0].fe._params
        out = fleet.refresh("ckpt")
        assert out["status"] == "aborted"
        assert "N-1" in out["reason"]
        assert fleet.engines[0].fe._params is old0


def test_fleet_refresh_trigger_file_arms_refresh(tmp_path):
    from t2omca_tpu.serve.fleet import REFRESH_TRIGGER
    meta = {"buckets": [1], "params": {"float32": {}}}
    with _mk_fleet(n=1, artifact_dir=str(tmp_path), meta=meta) as fleet:
        seen = []

        def fold(ck):
            seen.append(ck)
            return {"w": np.float32(3.0)}, {"t_env": 5,
                                            "buckets_checked": 0}

        fleet._fold_check = fold
        trig = tmp_path / REFRESH_TRIGGER
        trig.write_text(str(tmp_path / "ck") + "\n")
        assert _until(lambda: fleet.stats().get("fleet_refresh_total",
                                                0) == 1)
        assert not trig.exists()                # consumed, not re-armed
        assert seen == [str(tmp_path / "ck")]


# ---------------------------------------------------------------------------
# lifecycle + pulse wiring
# ---------------------------------------------------------------------------


def test_fleet_stop_resolves_everything_outstanding():
    fleet = _mk_fleet(n=2).start()
    for e in fleet.engines:
        e.pause_ev.set()
    reqs = [fleet.submit(*_req()) for _ in range(5)]
    fleet.stop()
    for req in reqs:
        r = req.wait(1.0)
        assert r.status == "error" and "shutdown" in r.error
    late = fleet.submit(*_req())
    assert late.done and late.result.status == "error"
    assert "stopped" in late.result.error
    fleet.stop()                                # idempotent


def test_fleet_health_on_pulse_hub():
    from t2omca_tpu.obs.pulse import MetricsHub
    hub = MetricsHub()
    fleet = _mk_fleet(n=2, hub=hub).start()
    try:
        ok, payload = hub.healthz()
        checks = payload["checks"]
        assert checks["fleet"]["ok"]
        assert "2/2 engines serving" in checks["fleet"]["detail"]
        assert checks["fleet_engine0"]["ok"] and checks["fleet_engine1"]["ok"]
        # supervisor exports the gauges each tick
        assert _until(lambda: "t2omca_fleet_queue_depth"
                      in hub.render_prometheus())
        assert 't2omca_fleet_engine_state{engine="0"}' \
            in hub.render_prometheus()
        # one engine down: its check flips, the FLEET check holds at N-1
        eng1 = fleet.engines[1]
        with eng1.lock:
            eng1.gen += 1
            eng1.state = "quarantined"
            eng1.last_error = "injected"
            eng1.restart_at = time.monotonic() + 60.0
        ok, payload = hub.healthz()
        assert not payload["checks"]["fleet_engine1"]["ok"]
        assert payload["checks"]["fleet"]["ok"]
    finally:
        fleet.stop()
    ok, payload = hub.healthz()
    assert not ok and not payload["checks"]["fleet"]["ok"]


# ---------------------------------------------------------------------------
# artifact-backed refresh (slow: real fold + fingerprint gate)
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
    fleet tests (same shape as tests/test_serve.py's fixture)."""
    from t2omca_tpu.run import Experiment
    from t2omca_tpu.serve.export import export_artifact
    from t2omca_tpu.utils.checkpoint import save_checkpoint
    root = tmp_path_factory.mktemp("fleet")
    cfg = _tiny_cfg()
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    ck = os.path.join(root, "models")
    save_checkpoint(ck, 128, ts)
    art = os.path.join(root, "art")
    meta = export_artifact(cfg, ck, art, buckets=(1, 2, 4))
    return cfg, ck, art, meta


@pytest.mark.slow
def test_fleet_clean_refresh_is_bit_identical(exported):
    """The rolling-refresh parity pin: re-folding the SAME checkpoint
    through the hot-refresh path and rolling it across every engine
    changes nothing — responses before and after are bit-identical, and
    the fleet never dropped an engine doing it."""
    from t2omca_tpu.serve.fleet import FleetConfig, ServeFleet
    cfg, ck, art, meta = exported
    fleet = ServeFleet(art, n_engines=2, dtype="float32",
                       cfg=FleetConfig(poll_s=0.005)).start()
    try:
        assert fleet.serving_engines() == 2
        rng = np.random.default_rng(11)
        fe = fleet.engines[0].fe
        obs = rng.standard_normal(
            (3, fe.n_agents, fe.obs_dim)).astype(np.float32)
        avail = rng.random((3, fe.n_agents, fe.n_actions)) < 0.7
        avail[..., 0] = True
        before = fleet.select(obs, avail)
        assert before.ok
        out = fleet.refresh(ck)
        assert out["status"] == "ok", out
        assert out["engines"] == 2
        assert out["buckets_checked"] == 3      # every bucket fingerprinted
        assert fleet.serving_engines() == 2
        after = fleet.select(obs, avail)
        assert after.ok
        np.testing.assert_array_equal(before.actions, after.actions)
        np.testing.assert_array_equal(before.hidden, after.hidden)
        # a poisoned refresh against the same live fleet: refused, and
        # serving continues uninterrupted on the refreshed params
        bad = fleet.refresh(os.path.join(art, "_no_such_checkpoint"))
        assert bad["status"] == "refused"
        assert fleet.serving_engines() == 2
        assert fleet.select(obs, avail).ok
        assert fleet.stats()["fleet_refresh_refused_total"] == 1
    finally:
        fleet.stop()


@pytest.mark.slow
def test_check_refresh_dry_run_and_cli(exported, capsys):
    from t2omca_tpu.serve.__main__ import main
    from t2omca_tpu.serve.fleet import check_refresh
    cfg, ck, art, meta = exported
    out = check_refresh(art, ck)
    assert out["status"] == "compatible"
    assert out["buckets_checked"] == 3 and out["t_env"] == 128
    bad = check_refresh(art, os.path.join(art, "_no_such_checkpoint"))
    assert bad["status"] == "refused" and bad["reason"]
    # the CLI surface: exit 0 compatible, exit 2 refused / not an artifact
    assert main(["refresh", art, ck]) == 0
    assert "refresh compatible" in capsys.readouterr().out
    rc = main(["refresh", art, os.path.join(art, "_no_such_checkpoint")])
    assert rc == 2
    assert "REFUSED" in capsys.readouterr().err
    rc = main(["refresh", os.path.dirname(art), ck])
    assert rc == 2
    assert "not a serve artifact" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# chaos acceptance: the fleet under fire (slow + chaos battery)
# ---------------------------------------------------------------------------


def _chaos_traffic(art, n_eng=2, duration=8.0):
    """Drive a real fleet over ``art`` with bursty heavy-tailed
    open-loop traffic (Pareto-tailed request sizes; exponential arrivals
    whose rate steps up 5x inside burst windows; requests are submitted
    on the clock whether or not earlier ones completed — the only honest
    way to exercise shedding) while a fault schedule runs underneath:
    engine 0 killed mid-burst, one dispatch hang on a peer engine, one
    poisoned hot refresh. Returns every request's result, the fleet's
    final stats and the refresh outcome."""
    from t2omca_tpu.serve.fleet import FleetConfig, ServeFleet
    fcfg = FleetConfig(
        queue_depth=32,
        deadline_s=max(2.0, duration / 2.5),
        dispatch_timeout_s=max(0.75, min(2.0, duration / 6.0)),
        restart_backoff_s=0.05, restart_backoff_max_s=0.5,
        ladder_cooldown_s=0.25,
    )
    fleet = ServeFleet(art, n_engines=n_eng, dtype="float32",
                       cfg=fcfg).start()
    try:
        assert fleet.serving_engines() > 0, fleet.stats()["engines"]
        fleet.warmup()

        fe0 = fleet.engines[0].fe
        a, d, na = fe0.n_agents, fe0.obs_dim, fe0.n_actions
        bmax = fe0.buckets[-1]
        rng = np.random.default_rng(0)

        # request pool: heavy-tailed sizes (the Pareto tail past the max
        # bucket exercises the chunking path), one pre-built request per
        # distinct size so the open-loop submitter costs ~nothing
        sizes = np.minimum(1 + rng.pareto(1.1, 4096).astype(np.int64),
                           2 * bmax)
        pool = {}
        for n in np.unique(sizes):
            n = int(n)
            obs = rng.standard_normal((n, a, d)).astype(np.float32)
            avail = rng.random((n, a, na)) < 0.7
            avail[..., 0] = True
            pool[n] = (obs, avail)

        # fault schedule (one-shot each, on the fleet's own chaos hooks)
        kill_at = 0.25 * duration
        refresh_at = 0.40 * duration
        hang_at = 0.55 * duration
        hang_s = fcfg.dispatch_timeout_s + min(1.5, 0.2 * duration)
        hang_engine = 1 % n_eng
        t0 = time.monotonic()
        killed, hung = [], []

        def _fault_schedule(engine, attempt, rid, **kw):
            now = time.monotonic() - t0
            if engine == 0 and not killed and now >= kill_at:
                killed.append(now)
                raise RuntimeError("chaos: engine killed (injected)")
            if engine == hang_engine and not hung and now >= hang_at:
                hung.append(now)
                time.sleep(hang_s)

        resilience.register_fault("fleet.dispatch", _fault_schedule)

        refresh_out = {}

        def _poisoned_refresh():
            refresh_out.update(fleet.refresh(
                os.path.join(art, "_no_such_checkpoint")))

        poison = threading.Timer(refresh_at, _poisoned_refresh)
        poison.daemon = True
        poison.start()

        # base rate sized to the measured warm dispatch, so that the
        # bursts saturate the fleet on any host
        t_warm0 = time.perf_counter()
        fleet.select(*pool[min(pool)])
        warm_s = max(time.perf_counter() - t_warm0, 1e-4)
        base_rate = max(10.0, min(200.0, 1.5 * n_eng / warm_s))
        bursts = [(0.2 * duration, 0.3 * duration),
                  (0.5 * duration, 0.65 * duration),
                  (0.8 * duration, 0.9 * duration)]

        def rate_at(t):
            burst = any(lo <= t < hi for lo, hi in bursts)
            return base_rate * (5.0 if burst else 1.0)

        requests = []
        t = 0.0
        i = 0
        while t < duration:
            now = time.monotonic() - t0
            if now < t:
                time.sleep(min(t - now, 0.05))
                continue
            requests.append(fleet.submit(*pool[int(sizes[i % len(sizes)])]))
            i += 1
            t += rng.exponential(1.0 / rate_at(t))
        # drain: every admitted request must resolve (completion, SHED,
        # deadline or error) — the supervisor's deadline sweep bounds
        # this wait
        results = [r.wait(timeout=fcfg.deadline_s + 2.0)
                   for r in requests]
        poison.join(timeout=30.0)
        assert not poison.is_alive(), "poisoned refresh never returned"
    finally:
        resilience.clear_faults("fleet.dispatch")
        stats = fleet.stats()
        fleet.stop()
    return results, stats, refresh_out


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.faultinject
def test_fleet_chaos_acceptance(exported):
    """The fleet-under-fire acceptance run (scripts/chaos.sh serve
    scenario): bursty open-loop traffic with engine 0 killed mid-burst,
    a dispatch hang injected on a peer and a poisoned hot refresh —
    every admitted request must resolve explicitly (ZERO silent hangs),
    the quarantined engines must restart and rejoin, and the refresh
    must be refused while serving continues."""
    cfg, ck, art, meta = exported
    results, stats, refresh = _chaos_traffic(art, n_eng=2, duration=8.0)
    by = {}
    for r in results:
        by[r.status] = by.get(r.status, 0) + 1
    # zero silent hangs: every admitted request resolved to exactly one
    # explicit status, none via the unresolved-at-wait backstop
    assert not [r for r in results if r.status == "error"
                and "unresolved" in (r.error or "")]
    assert by.get("ok", 0) + by.get("shed", 0) + by.get("deadline", 0) \
        + by.get("error", 0) == len(results)
    assert by.get("ok", 0) > 0
    # the killed engine was quarantined, restarted and rejoined
    assert stats.get("fleet_restarts_total", 0) >= 1
    assert stats["recoveries_s"] and max(stats["recoveries_s"]) > 0
    assert stats.get("fleet_ejected_total", 0) == 0
    # the injected hang tripped the per-engine watchdog
    assert stats.get("fleet_stalls_total", 0) >= 1
    # the poisoned refresh was REFUSED, never applied
    assert refresh and refresh["status"] == "refused"
    # the fleet ended RESUMABLE: every engine back in serving state
    assert stats["serving"] == 2
