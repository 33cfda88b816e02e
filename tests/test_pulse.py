"""graftpulse live telemetry plane (``t2omca_tpu/obs/pulse.py``,
``memwatch.py``; docs/OBSERVABILITY.md §pulse):
MetricsHub rendering/probes/health, the HTTP endpoint routes, the
on-demand trace trigger, HBM memwatch high-water attribution, the
torn-tail/degraded-input contracts of the post-mortem readers, and —
slow-marked — the acceptance paths: a live CPU run scraped mid-flight (env-steps/s +
watchdog heartbeat-age gauges, /healthz flipping to degraded on a
chaos-injected hang)."""

import glob
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from t2omca_tpu.config import ObsConfig, TrainConfig, sanity_check
from t2omca_tpu.obs.memwatch import (MemWatch, NULL_MEMWATCH,
                                     make_memwatch)
from t2omca_tpu.obs.pulse import (MetricsHub, PulseServer,
                                  TraceController, make_pulse)
from t2omca_tpu.obs.spans import KNOWN_PHASES, SpanRecorder
from t2omca_tpu.utils.ioutil import read_jsonl_tolerant

pytestmark = pytest.mark.pulse

REPO = os.path.join(os.path.dirname(__file__), "..")


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# MetricsHub
# ---------------------------------------------------------------------------

def test_hub_gauges_counters_and_quantiles():
    hub = MetricsHub(window=64)
    hub.set("env_steps_per_sec", 123.5)
    hub.set("hbm_bytes_in_use", 10, device="0")
    hub.inc("serve_requests_total")
    hub.inc("serve_rows_total", 5, bucket=8)
    for v in (1.0, 2.0, 3.0, 100.0):
        hub.observe("serve_select_ms", v)
    out = hub.render_prometheus()
    assert "t2omca_env_steps_per_sec 123.5" in out
    assert 't2omca_hbm_bytes_in_use{device="0"} 10' in out
    assert "# TYPE t2omca_serve_requests_total counter" in out
    assert 't2omca_serve_rows_total{bucket="8"} 5' in out
    assert "t2omca_serve_select_ms_p50 3" in out
    assert "t2omca_serve_select_ms_p99 100" in out
    assert "t2omca_serve_select_ms_count 4" in out
    assert "t2omca_beat_age_seconds" in out
    # window is bounded: old samples evict
    for v in range(200):
        hub.observe("serve_select_ms", 50.0)
    assert "serve_select_ms_p99 50" in hub.render_prometheus()


def test_hub_probes_and_health():
    hub = MetricsHub()
    hub.probe(lambda: [("watchdog_armed_seconds",
                        {"phase": "dispatch.train"}, 2.5)])
    hub.probe(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    out = hub.render_prometheus()   # a raising probe never kills scrape
    assert ('t2omca_watchdog_armed_seconds{phase="dispatch.train"} 2.5'
            in out)
    hub.health("good", lambda: (True, "fine"))
    ok, payload = hub.healthz()
    assert ok and payload["status"] == "ok"
    hub.health("bad", lambda: (False, "stalled"))
    ok, payload = hub.healthz()
    assert not ok and payload["status"] == "degraded"
    assert payload["checks"]["bad"] == {"ok": False, "detail": "stalled"}
    # a RAISING health check reads as degraded, never as green
    hub2 = MetricsHub()
    hub2.health("dead", lambda: (_ for _ in ()).throw(ValueError("x")))
    ok2, payload2 = hub2.healthz()
    assert not ok2 and "check failed" in payload2["checks"]["dead"]["detail"]


def test_hub_one_type_line_per_family():
    """Prometheus text format: a second ``# TYPE`` line for the same
    metric name fails the WHOLE scrape — a multi-label family (two
    devices, actor+learner sides, two buckets) must render one TYPE
    line followed by all its samples."""
    hub = MetricsHub()
    hub.set("hbm_bytes_in_use", 10, device="0")
    hub.set("hbm_bytes_in_use", 20, device="1")
    hub.inc("serve_dispatches_total", bucket=2)
    hub.inc("serve_dispatches_total", bucket=4)
    hub.probe(lambda: [("watchdog_armed", {"side": "actor"}, 1.0),
                       ("watchdog_armed", {"side": "learner"}, 0.0)])
    out = hub.render_prometheus()
    for fam in ("t2omca_hbm_bytes_in_use",
                "t2omca_serve_dispatches_total", "t2omca_watchdog_armed"):
        type_lines = [l for l in out.splitlines()
                      if l.startswith(f"# TYPE {fam} ")]
        samples = [l for l in out.splitlines()
                   if l.startswith(fam + "{")]
        assert len(type_lines) == 1, (fam, type_lines)
        assert len(samples) == 2, (fam, samples)
    # samples immediately follow their family's TYPE line
    lines = out.splitlines()
    i = lines.index("# TYPE t2omca_hbm_bytes_in_use gauge")
    assert lines[i + 1].startswith("t2omca_hbm_bytes_in_use{")
    assert lines[i + 2].startswith("t2omca_hbm_bytes_in_use{")


def test_pulse_server_binds_loopback_by_default():
    """/trace is unauthenticated and state-changing: the default bind
    must be loopback; off-host exposure is an explicit pulse_host."""
    srv = PulseServer(MetricsHub(), 0)
    try:
        assert srv._srv.server_address[0] == "127.0.0.1"
    finally:
        srv.close()
    assert ObsConfig().pulse_host == "127.0.0.1"


def test_hub_trace_request_consumed_once():
    hub = MetricsHub()
    assert not hub.take_trace_request()
    hub.request_trace()
    assert hub.take_trace_request()
    assert not hub.take_trace_request()


# ---------------------------------------------------------------------------
# PulseServer routes
# ---------------------------------------------------------------------------

def test_pulse_server_routes(tmp_path):
    rec = SpanRecorder(ring_size=32,
                       jsonl_path=str(tmp_path / "spans.jsonl"),
                       flush_every=1)
    hub = MetricsHub()
    hub.set("t_env", 42)
    hub.health("always", lambda: (True, "fine"))
    srv = PulseServer(hub, 0, rec=rec).start()   # 0 = ephemeral (tests)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status, body = _get(base + "/metrics")
        assert status == 200 and "t2omca_t_env 42" in body
        status, body = _get(base + "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"
        status, body = _get(base + "/trace")
        assert status == 200 and json.loads(body)["armed"] is True
        assert hub.take_trace_request()
        hub.health("bad", lambda: (False, "watchdog fired"))
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["status"] == "degraded"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/nope")
        assert ei.value.code == 404
    finally:
        srv.close()
    # scrape spans stay OUT of the flight ring (a scrape cadence must
    # not evict the pre-stall phase history) but land in the JSONL
    # sink + phase aggregate; the rare trace-arm span IS ringed
    tail_phases = {e.get("phase") for e in rec.tail()}
    assert "pulse.scrape" not in tail_phases
    assert "trace.trigger" in tail_phases
    assert "pulse.scrape" in rec.summary()
    rec.close()
    events = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
    phases = {e.get("phase") for e in events}
    # scrapes and the endpoint trace-arm are spanned + registered
    assert "pulse.scrape" in phases and "trace.trigger" in phases
    assert not any("_ring" in e for e in events)    # internal flag only
    assert {"pulse.scrape", "trace.trigger"} <= KNOWN_PHASES


def test_memwatch_keeps_verdict_over_transient_device_failure():
    """A transient device-list failure after successful snapshots must
    not flip the report to 'unsupported' over its own populated rows."""
    devs = {"fn": lambda: [_FakeDev(0, 100)]}
    mw = MemWatch(_devices=lambda: devs["fn"]())
    mw.snapshot("startup")
    assert mw.supported is True

    def _boom():
        raise RuntimeError("backend teardown race")
    devs["fn"] = _boom
    assert mw.snapshot("shutdown") is None
    rep = mw.report()
    assert rep["supported"] is True and rep["devices"]


def test_make_pulse_off_state_and_bind_failure():
    assert make_pulse(ObsConfig()) is None          # default: no plane
    assert make_pulse(ObsConfig(pulse_port=0)) is None
    # a taken port degrades to None + warning, never a crash
    blocker = socket.socket()
    blocker.bind(("0.0.0.0", 0))
    port = blocker.getsockname()[1]

    class _Log:
        def __init__(self):
            self.warned = []

        def warning(self, msg):
            self.warned.append(msg)

        info = warning
    log = _Log()
    assert make_pulse(ObsConfig(pulse_port=port), log=log) is None
    assert any("could not bind" in w for w in log.warned)
    blocker.close()


def test_pulse_config_sanity():
    sanity_check(TrainConfig(obs=ObsConfig(pulse_port=8080)))
    with pytest.raises(ValueError):
        sanity_check(TrainConfig(obs=ObsConfig(pulse_port=70000)))
    with pytest.raises(ValueError):
        sanity_check(TrainConfig(obs=ObsConfig(pulse_port=-1)))
    with pytest.raises(ValueError):
        sanity_check(TrainConfig(obs=ObsConfig(pulse_window=4)))
    # memwatch without the master switch is a dead knob; with it, valid
    with pytest.raises(ValueError):
        sanity_check(TrainConfig(obs=ObsConfig(memwatch=True)))
    sanity_check(TrainConfig(obs=ObsConfig(enabled=True, memwatch=True)))


# ---------------------------------------------------------------------------
# TraceController (stubbed window — no profiler needed)
# ---------------------------------------------------------------------------

class _StubWindow:
    def __init__(self, trace_dir, n_iterations=3):
        self.trace_dir = trace_dir
        self.n_iterations = n_iterations
        self._active = None
        self._done = False
        self.ticks = 0

    def maybe_start(self, t_env):
        self._active = self.n_iterations

    def tick(self, logger=None, t_env=0):
        if self._active is None:
            return
        self.ticks += 1
        self._active -= 1
        if self._active <= 0:
            self._active = None
            self._done = True


def test_trace_controller_file_trigger(tmp_path):
    rec = SpanRecorder(ring_size=32)
    made = []

    def factory(trace_dir, n_iterations=3):
        w = _StubWindow(trace_dir, n_iterations)
        made.append(w)
        return w

    trc = TraceController(str(tmp_path), rec=rec, n_iterations=2,
                          window_factory=factory)
    trc.poll(0)
    assert not made                         # no trigger, no window
    trigger = tmp_path / "PULSE_TRACE"
    trigger.touch()
    trc.poll(12)
    assert len(made) == 1                   # armed at the boundary
    assert not trigger.exists()             # trigger consumed
    assert "pulse_trace_01_t12" in made[0].trace_dir
    trc.poll(12)                            # active: no re-arm
    assert len(made) == 1
    trc.tick(None, 12)
    trc.tick(None, 24)                      # bounded: closes after 2
    assert made[0]._done
    # a NEW trigger after close arms a fresh window
    trigger.touch()
    trc.poll(36)
    assert len(made) == 2 and trc.captures == 2
    # the arming is spanned with the registered phase
    tail = rec.tail()
    assert any(e.get("phase") == "trace.trigger" and e.get("source") ==
               "file" for e in tail)


def test_trace_controller_endpoint_trigger(tmp_path):
    hub = MetricsHub()
    made = []
    trc = TraceController(
        str(tmp_path), hub=hub, n_iterations=1,
        window_factory=lambda d, n_iterations=3:
            made.append(_StubWindow(d, n_iterations)) or made[-1])
    hub.request_trace()
    trc.poll(48)
    assert len(made) == 1
    assert not hub.take_trace_request()     # consumed by the controller


# ---------------------------------------------------------------------------
# memwatch
# ---------------------------------------------------------------------------

class _FakeDev:
    def __init__(self, i, bytes_in_use, peak=None, broken=False):
        self.id = i
        self._b = bytes_in_use
        self._p = peak if peak is not None else bytes_in_use
        self._broken = broken

    def memory_stats(self):
        if self._broken:
            raise RuntimeError("allocator says no")
        return {"bytes_in_use": self._b, "peak_bytes_in_use": self._p}


def test_memwatch_high_water_phase_attribution():
    rec = SpanRecorder(ring_size=32)
    devs = [[_FakeDev(0, 100, peak=120), _FakeDev(1, 50)]]
    mw = MemWatch(rec=rec, budgets={"superstep": 247866.0},
                  _devices=lambda: devs[0])
    snap = mw.snapshot("startup", t_env=0)
    assert snap["0"]["bytes_in_use"] == 100
    devs[0] = [_FakeDev(0, 900, peak=950), _FakeDev(1, 40)]
    mw.snapshot("dispatch.train", t_env=48)
    rep = mw.report()
    assert rep["supported"] is True and rep["snapshots"] == 2
    d0 = rep["devices"]["0"]
    assert d0["high_water_bytes"] == 950
    assert d0["high_water_phase"] == "dispatch.train"
    assert d0["high_water_t_env"] == 48
    # device 1 peaked at startup — attribution is per-device
    assert rep["devices"]["1"]["high_water_phase"] == "startup"
    assert rep["budgets_audit_peak_bytes"]["superstep"] == 247866.0
    # snapshots are spanned with the registered phase
    assert any(e.get("phase") == "memwatch.snapshot"
               for e in rec.tail())
    assert "memwatch.snapshot" in KNOWN_PHASES


def test_memwatch_degrades_without_allocator_stats():
    # the CPU-client shape: memory_stats raises (or returns None) on
    # every device — report states unsupported, nothing crashes
    mw = MemWatch(_devices=lambda: [_FakeDev(0, 0, broken=True)])
    assert mw.snapshot("startup") is None
    rep = mw.report()
    assert rep["supported"] is False and rep["devices"] == {}
    # a device-list failure degrades the same way
    def _boom():
        raise RuntimeError("no backend")
    mw2 = MemWatch(_devices=_boom)
    assert mw2.snapshot("startup") is None
    assert mw2.supported is False


def test_make_memwatch_gating():
    assert make_memwatch(ObsConfig()) is NULL_MEMWATCH
    assert make_memwatch(ObsConfig(enabled=True)) is NULL_MEMWATCH
    assert make_memwatch(ObsConfig(memwatch=True)) is NULL_MEMWATCH
    mw = make_memwatch(ObsConfig(enabled=True, memwatch=True))
    assert mw.enabled and isinstance(mw, MemWatch)
    # the GP303 budgets rode along from programs.json (jax-free read)
    assert mw._budgets.get("superstep")
    assert NULL_MEMWATCH.snapshot("x") is None
    assert NULL_MEMWATCH.report() == {}


def test_watchdog_heartbeat_snapshot():
    from t2omca_tpu.utils.watchdog import Watchdog
    wd = Watchdog(timeout_s=60.0)
    hb = wd.heartbeat()
    assert hb["armed_phase"] is None and hb["stall_count"] == 0
    wd.stamp("dispatch.train", t_env=48)
    time.sleep(0.02)
    hb = wd.heartbeat()
    assert hb["armed_phase"] == "dispatch.train"
    assert hb["armed_s"] >= 0.02
    assert hb["beat_age_s"] >= 0.02
    wd.clear()
    hb = wd.heartbeat()
    assert hb["armed_phase"] is None and hb["beat_age_s"] < 0.02


# ---------------------------------------------------------------------------
# torn-tail tolerance + report degraded inputs (satellites)
# ---------------------------------------------------------------------------

def test_read_jsonl_tolerant_torn_tail(tmp_path):
    p = tmp_path / "spans.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"event": "mark", "kind": "run"}) + "\n")
        f.write(json.dumps({"event": "span", "phase": "x"}) + "\n")
        f.write('{"event": "span", "phase": "dispatch.trai')  # torn tail
    bad = []
    out = read_jsonl_tolerant(str(p),
                              on_bad=lambda ln, last: bad.append((ln,
                                                                  last)))
    assert len(out) == 2
    assert bad == [(3, True)]               # final line, flagged as such
    # mid-file corruption is flagged distinctly
    with open(p, "w") as f:
        f.write("{broken\n")
        f.write(json.dumps({"ok": 1}) + "\n")
    bad.clear()
    assert read_jsonl_tolerant(str(p), on_bad=lambda ln, last:
                               bad.append((ln, last))) == [{"ok": 1}]
    assert bad == [(1, False)]


def _seed_spans(run_dir, torn=False):
    run_dir.mkdir(parents=True, exist_ok=True)
    events = [
        {"event": "mark", "kind": "run", "seq": 1, "t0": 0.0,
         "backend": "cpu", "batch_size_run": 2, "episode_limit": 6,
         "batch_size": 4, "superstep": 1},
        {"event": "span", "seq": 2, "t0": 0.0, "phase":
         "dispatch.rollout", "t_env": 0, "depth": 0, "wall_ms": 5000.0,
         "outcome": "ok", "first": True},
        {"event": "span", "seq": 3, "t0": 0.0, "phase":
         "dispatch.rollout", "t_env": 12, "depth": 0, "wall_ms": 80.0,
         "outcome": "ok"},
    ]
    with open(run_dir / "spans.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
        if torn:
            f.write('{"event": "span", "phase": "dispatch.tr')
    return events


def test_report_skips_torn_tail_with_warning(tmp_path, capsys):
    """Satellite: the exact artifact a killed run leaves — a truncated
    final spans.jsonl line — must be skipped with a warning, and the
    report must still render the intact prefix."""
    from t2omca_tpu.obs.__main__ import main
    run_dir = tmp_path / "run"
    _seed_spans(run_dir, torn=True)
    rc = main(["report", str(run_dir)])
    cap = capsys.readouterr()
    assert rc == 0
    assert "dispatch.rollout" in cap.out    # intact prefix rendered
    assert "torn final line" in cap.err     # warned, not raised


def test_report_flight_recorder_only_run_dir(tmp_path, capsys):
    """Degraded input: a run dir with ONLY a flight_recorder.json (the
    crash artifact) still reports — from the bounded tail, stated."""
    from t2omca_tpu.obs.__main__ import main
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    events = _seed_spans(tmp_path / "donor")     # same event schema
    with open(run_dir / "flight_recorder.json", "w") as f:
        json.dump({"version": 1, "events": events,
                   "memwatch": {"supported": False}}, f)
    rc = main(["report", str(run_dir)])
    cap = capsys.readouterr()
    assert rc == 0
    assert "dispatch.rollout" in cap.out
    assert "flight-recorder tail" in cap.err
    # an empty dir (neither artifact) is still the usage error
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["report", str(empty)]) == 2


def test_report_spans_with_empty_or_torn_metrics(tmp_path, capsys):
    """A run dir with spans and metrics only is all the report reads.
    Degraded input: an EMPTY metrics.jsonl — the per-slice table must
    state 'no data', not crash (PR 11's table reads this file)."""
    from t2omca_tpu.obs.__main__ import main
    run_dir = tmp_path / "run"
    _seed_spans(run_dir)
    (run_dir / "metrics.jsonl").write_text("")
    rc = main(["report", str(run_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "scenario slices: no data" in out
    # a metrics.jsonl with ONLY a torn line: tolerated the same way
    (run_dir / "metrics.jsonl").write_text('{"key": "slice0_retu')
    assert main(["report", str(run_dir)]) == 0


# ---------------------------------------------------------------------------
# serve front-end hub wiring (host logic only — stubbed programs)
# ---------------------------------------------------------------------------

def test_serve_frontend_hub_metrics():
    import numpy as np
    from t2omca_tpu.obs.spans import NULL_RECORDER
    from t2omca_tpu.serve.frontend import ServeFrontend, SessionStore

    hub = MetricsHub()
    meta = {"buckets": [2, 4], "n_agents": 3, "obs_dim": 5,
            "n_actions": 4, "emb": 8}
    fe = ServeFrontend("/nonexistent", meta, mac=None, params=None,
                       dtype="float32", use_exported=False,
                       rec=NULL_RECORDER, hub=hub)

    def fake_program(params, obs, avail, hidden):
        n = obs.shape[0]
        return (np.zeros((n, 3), np.int32),
                np.zeros((n, 3, 8), np.float32))

    fe._steps = {2: fake_program, 4: fake_program}
    obs = np.zeros((3, 3, 5), np.float32)
    avail = np.ones((3, 3, 4), bool)
    fe.select(obs, avail)                   # one chunk, bucket 4
    out = hub.render_prometheus()
    assert 't2omca_serve_dispatches_total{bucket="4"} 1' in out
    assert 't2omca_serve_rows_total{bucket="4"} 3' in out
    assert "t2omca_serve_requests_total 1" in out
    assert "t2omca_serve_select_ms_p50" in out
    # SessionStore LRU fill gauge
    store = SessionStore(fe, max_sessions=4)
    store.select(["a", "b"], obs[:2], avail[:2])
    out = hub.render_prometheus()
    assert "t2omca_serve_sessions 2" in out
    assert "t2omca_serve_session_lru_fill 0.5" in out


# ---------------------------------------------------------------------------
# driver integration (slow: full run() legs on tiny CPU configs)
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _tiny_cfg(tmp_path, port, **kw):
    from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                                   ResilienceConfig)
    res_kw = kw.pop("res_kw", {})
    obs_kw = kw.pop("obs_kw", {})
    defaults = dict(
        t_max=120, batch_size_run=2, batch_size=4,
        test_interval=1_000_000, test_nepisode=2, log_interval=12,
        runner_log_interval=12, save_model=False,
        local_results_path=str(tmp_path), use_tensorboard=False,
        epsilon_anneal_time=50,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8),
        resilience=ResilienceConfig(stall_grace_s=0.0, **res_kw),
        obs=ObsConfig(enabled=True, flush_every=1, pulse_port=port,
                      memwatch=True, **obs_kw),
    )
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


class _Poller(threading.Thread):
    """Scrapes /metrics + /healthz concurrently with a live run and
    keeps what it saw — the run's exit tears the server down, so the
    assertions read the poller's captures."""

    def __init__(self, port):
        super().__init__(daemon=True)
        self.base = f"http://127.0.0.1:{port}"
        self.metrics = []
        self.health = []
        self.stop = threading.Event()

    def run(self):
        while not self.stop.is_set():
            try:
                self.metrics.append(_get(self.base + "/metrics",
                                         timeout=1)[1])
            except Exception:
                pass
            try:
                self.health.append(_get(self.base + "/healthz",
                                        timeout=1))
            except urllib.error.HTTPError as e:
                self.health.append((e.code, e.read().decode()))
            except Exception:
                pass
            time.sleep(0.05)


@pytest.mark.slow
def test_pulse_live_scrape_during_run(tmp_path):
    """Acceptance: during a CPU smoke run with obs.pulse_port set,
    /metrics returns env-steps/s + watchdog heartbeat-age gauges and
    /healthz reports ok."""
    from t2omca_tpu.run import run
    from t2omca_tpu.utils.logging import Logger

    port = _free_port()
    cfg = _tiny_cfg(tmp_path, port,
                    res_kw=dict(dispatch_timeout=30.0))
    poller = _Poller(port)
    poller.start()
    try:
        run(cfg, Logger())
    finally:
        poller.stop.set()
        poller.join(timeout=5)
    assert poller.metrics, "endpoint never answered during the run"
    joined = "\n".join(poller.metrics)
    assert "t2omca_env_steps_per_sec" in joined
    assert "t2omca_watchdog_heartbeat_age_seconds" in joined
    assert "t2omca_t_env" in joined
    assert any(code == 200 and json.loads(body)["status"] == "ok"
               for code, body in poller.health)
    # the scrape spans landed in the run's own span stream
    run_dir = [d for d in glob.glob(os.path.join(str(tmp_path), "*"))
               if os.path.isdir(d)
               and os.path.basename(d) != "models"][0]
    events = [json.loads(l)
              for l in open(os.path.join(run_dir, "spans.jsonl"))
              if l.strip()]
    phases = {e.get("phase") for e in events if e["event"] == "span"}
    assert "pulse.scrape" in phases
    assert "memwatch.snapshot" in phases
    assert phases <= KNOWN_PHASES, phases - KNOWN_PHASES


@pytest.mark.slow
@pytest.mark.faultinject
def test_healthz_degrades_on_injected_hang(tmp_path):
    """Acceptance: a chaos-injected hang trips the watchdog and the
    LIVE /healthz flips to degraded while the run is still wedged."""
    from t2omca_tpu.run import run
    from t2omca_tpu.utils import resilience
    from t2omca_tpu.utils.logging import Logger

    resilience.clear_faults()
    port = _free_port()
    cfg = _tiny_cfg(tmp_path, port,
                    res_kw=dict(dispatch_timeout=0.75))
    hung = []

    def _hang(t_env, **kw):
        if t_env >= 24 and not hung:
            hung.append(t_env)
            time.sleep(2.5)

    resilience.register_fault("dispatch.rollout", _hang)
    poller = _Poller(port)
    poller.start()
    try:
        run(cfg, Logger())
    finally:
        poller.stop.set()
        poller.join(timeout=5)
        resilience.clear_faults()
    assert hung == [24]
    degraded = [(c, b) for c, b in poller.health if c == 503]
    assert degraded, "healthz never flipped to degraded during the hang"
    payload = json.loads(degraded[-1][1])
    assert payload["status"] == "degraded"
    # the watchdog check is the one that flipped it
    assert any(not chk["ok"] and "stalls=" in chk["detail"]
               for name, chk in payload["checks"].items()
               if name.startswith("watchdog"))


@pytest.mark.slow
def test_trace_trigger_on_live_run(tmp_path):
    """On-demand capture: touching <run_dir>/PULSE_TRACE mid-run arms a
    bounded TraceWindow without a restart; the capture directory lands
    in the run dir."""
    from t2omca_tpu.run import run
    from t2omca_tpu.utils import resilience
    from t2omca_tpu.utils.logging import Logger

    resilience.clear_faults()
    armed = []

    def _touch(t_env, **kw):
        if t_env >= 24 and not armed:
            dirs = [d for d in glob.glob(os.path.join(str(tmp_path),
                                                      "*"))
                    if os.path.isdir(d)
                    and os.path.basename(d) != "models"]
            if dirs:
                open(os.path.join(dirs[0], "PULSE_TRACE"), "w").close()
                armed.append(t_env)

    resilience.register_fault("driver.iteration", _touch)
    cfg = _tiny_cfg(tmp_path, 0)        # plane off: file trigger alone
    try:
        run(cfg, Logger())
    finally:
        resilience.clear_faults()
    assert armed, "trigger never planted"
    run_dir = [d for d in glob.glob(os.path.join(str(tmp_path), "*"))
               if os.path.isdir(d)
               and os.path.basename(d) != "models"][0]
    captures = glob.glob(os.path.join(run_dir, "pulse_trace_*"))
    assert captures, "no pulse trace capture directory"
    assert not os.path.exists(os.path.join(run_dir, "PULSE_TRACE"))
    events = [json.loads(l)
              for l in open(os.path.join(run_dir, "spans.jsonl"))
              if l.strip()]
    assert any(e.get("phase") == "trace.trigger" for e in events)
