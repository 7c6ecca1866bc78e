"""paddle_tpu.watch: detector math, alert fan-out, SLO burn rates,
registry subscription hooks, runlog rotation, exporter hardening,
straggler parity, and the trainer+serving end-to-end anomaly-alert path."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import watch
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import runlog
from paddle_tpu.observability.exporter import MetricsServer, parse_text_exposition
from paddle_tpu.observability.metrics import MetricRegistry, histogram_quantile
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.circuit import CircuitBreaker
from paddle_tpu.watch import alerts as alerts_mod
from paddle_tpu.watch import slo as slo_mod
from paddle_tpu.watch.detectors import (
    EwmaDetector,
    RollingQuantileDetector,
    SkewDetector,
)



@pytest.fixture(autouse=True)
def _fresh_hub():
    """Every test sees an empty default alert hub (and leaves one behind)."""
    alerts_mod.default_hub().clear()
    yield
    alerts_mod.default_hub().clear()


def test_every_name_the_package_exports_resolves():
    """``from paddle_tpu.watch import *`` must not fail on a name whose
    module went."""
    assert watch.__all__ and not [n for n in watch.__all__ if not hasattr(watch, n)]


# ---- detectors ------------------------------------------------------------


def test_ewma_flags_spike_not_steady_state():
    d = EwmaDetector(alpha=0.3, z_threshold=4.0, min_samples=5)
    results = [d.observe("step", 0.1 + 0.001 * (i % 3)) for i in range(30)]
    flagged = [r for r in results if r is not None and r.flagged]
    assert not flagged  # steady series never alerts
    spike = d.observe("step", 1.5)
    assert spike is not None and spike.flagged and spike.mode == "ewma_z"
    assert spike.score > 4.0


def test_ewma_spike_not_absorbed_into_baseline():
    d = EwmaDetector(alpha=0.5, z_threshold=4.0, min_samples=4)
    for _ in range(10):
        d.observe("k", 1.0)
    assert d.observe("k", 100.0).flagged
    # one spike must not teach the detector that spikes are normal
    assert d.snapshot()["k"]["mean"] < 2.0
    assert d.observe("k", 100.0).flagged  # still anomalous on repeat


def test_ewma_poison_after_relearns_level_shift():
    d = EwmaDetector(alpha=0.5, z_threshold=4.0, min_samples=4, poison_after=3)
    for _ in range(10):
        d.observe("k", 1.0)
    # a persistent shift: after poison_after consecutive flags the new
    # level is absorbed and flagging stops
    for _ in range(20):
        r = d.observe("k", 10.0)
    assert r is not None and not r.flagged


def test_ewma_warmup_and_nonfinite_return_none():
    d = EwmaDetector(min_samples=5)
    assert d.observe("k", float("nan")) is None
    for i in range(5):
        assert d.observe("k", 1.0) is None  # warming up
    assert d.observe("k", 1.0) is not None


def test_rolling_quantile_flags_ratio_exceed():
    d = RollingQuantileDetector(window=16, q=0.5, ratio=2.0, min_samples=4)
    for i in range(10):
        r = d.observe("lat", 10.0 + (i % 2))
    assert r is not None and not r.flagged
    spike = d.observe("lat", 50.0)
    assert spike.flagged and spike.mode == "rolling_quantile"
    assert spike.baseline == pytest.approx(10.5, abs=1.0)


def test_detector_param_validation():
    with pytest.raises(EnforceError):
        EwmaDetector(alpha=0.0)
    with pytest.raises(EnforceError):
        RollingQuantileDetector(ratio=1.0)
    with pytest.raises(EnforceError):
        SkewDetector(ratio=0.5)


def test_skew_detector_spatial_and_temporal_modes():
    d = SkewDetector(ratio=2.0, window=16, min_samples=4)
    # temporal first: single key, steady then spike
    for _ in range(6):
        d.record("step", 0.1)
    r = d.record("step", 0.5)
    assert r.flagged and r.mode == "temporal" and r.score == pytest.approx(5.0)
    d.reset()
    # spatial: two healthy peers + one slow key
    for _ in range(6):
        d.record("r0", 0.010)
        d.record("r1", 0.011)
        r = d.record("r2", 0.042)
    assert r.flagged and r.mode == "spatial" and r.score > 2.0


def test_straggler_shell_delegates_to_shared_core():
    """Parity: the straggler shell and a bare SkewDetector with the same
    params flag the exact same observations on the test_tracing fixture
    stream (spatial slow-replica shape)."""
    from paddle_tpu.tracing.straggler import StragglerDetector

    shell = StragglerDetector("parity", ratio=2.0, window=16, min_samples=5)
    core = SkewDetector(ratio=2.0, window=16, min_samples=5)
    rng = np.random.RandomState(7)
    shell_flags, core_flags = [], []
    for i in range(40):
        for key, base in (("replica0", 0.010), ("replica1", 0.011),
                          ("replica2", 0.042 if i >= 8 else 0.012)):
            v = base * (1.0 + 0.01 * rng.rand())
            shell_flags.append((i, key, shell.record(key, v)))
            r = core.record(key, v)
            core_flags.append((i, key, r is not None and r.flagged))
    assert shell_flags == core_flags
    assert any(f for _, k, f in shell_flags if k == "replica2")
    assert not any(f for _, k, f in shell_flags if k != "replica2")


# ---- histogram quantile ---------------------------------------------------


def test_histogram_quantile_linear_interpolation():
    # 100 observations uniform in (0, 1] into buckets (0.25, 0.5, 0.75, 1.0)
    edges = [0.25, 0.5, 0.75, 1.0]
    cumulative = [25, 50, 75, 100]
    assert histogram_quantile(edges, cumulative, 100, 0.5) == pytest.approx(0.5)
    assert histogram_quantile(edges, cumulative, 100, 0.9) == pytest.approx(0.9)
    assert histogram_quantile(edges, cumulative, 100, 0.125) == pytest.approx(0.125)


def test_histogram_quantile_overflow_clamps_to_last_edge():
    # half the mass beyond the last finite edge: high quantiles clamp
    assert histogram_quantile([1.0], [5], 10, 0.99) == 1.0


def test_registry_quantile_readout():
    r = MetricRegistry()
    r.histogram("lat", buckets=(0.1, 1.0, 10.0))
    assert r.quantile("lat", 0.5) is None  # empty child -> None, not 0.0
    for v in (0.05, 0.2, 0.4, 0.9, 2.0):
        r.observe("lat", v)
    q50 = r.quantile("lat", 0.5)
    assert 0.1 < q50 <= 1.0
    with pytest.raises(EnforceError):
        histogram_quantile([1.0], [1], 1, 1.5)


def test_serving_metrics_latency_quantile_matches_histogram():
    from paddle_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(engine_label="qtest")
    assert m.latency_quantile(0.5) is None
    for v in (0.001, 0.002, 0.002, 0.004, 0.050):
        m.record_response(v)
    q = m.latency_quantile(0.99)
    assert q is not None and 0.004 < q <= 0.1


# ---- registry subscription hooks ------------------------------------------


def test_registry_subscribe_sees_every_write_kind():
    r = MetricRegistry()
    r.histogram("h", buckets=(1.0, 2.0))
    seen = []
    r.subscribe(lambda name, kind, value, labels: seen.append(
        (name, kind, value, labels)))
    r.inc("c", 2.0, labels={"a": "b"})
    r.set("g", 7.0)
    r.observe("h", 1.5)
    assert ("c", "counter", 2.0, {"a": "b"}) in seen
    assert ("g", "gauge", 7.0, None) in seen
    assert ("h", "histogram", 1.5, None) in seen


def test_registry_unsubscribe_and_exception_isolation():
    r = MetricRegistry()
    calls = []

    def bad(*a):
        calls.append(a)
        raise RuntimeError("subscriber bug")

    r.subscribe(bad)
    r.inc("c")  # must not raise
    assert len(calls) == 1
    r.unsubscribe(bad)
    r.inc("c")
    assert len(calls) == 1
    # subscriptions survive reset (reset drops data, not consumers)
    r.subscribe(bad)
    r.reset()
    r.inc("c")
    assert len(calls) == 2


# ---- alerts ---------------------------------------------------------------


def test_alert_hub_fans_out_store_metrics_runlog(tmp_path):
    path = str(tmp_path / "run.jsonl")
    prev = runlog.set_runlog(runlog.RunLog(path))
    hub = alerts_mod.AlertHub()
    before = prof.counters().get("watch.alert.events_total", 0.0)
    try:
        hub.emit(alerts_mod.Alert(
            "watch.test", "replica1", "latency anomalous", value=0.5,
            baseline=0.1, score=5.0, labels={"engine": "serving0"}))
    finally:
        got = runlog.set_runlog(prev)
        got.close()
    assert len(hub.alerts()) == 1
    assert prof.counters()["watch.alert.events_total"] - before == 1.0
    events = runlog.read_runlog(path)
    al = [e for e in events if e["kind"] == "alert"]
    assert len(al) == 1
    assert al[0]["source"] == "watch.test" and al[0]["key"] == "replica1"
    assert al[0]["severity"] == "warning" and al[0]["engine"] == "serving0"


def test_alert_actions_run_and_errors_are_counted():
    hub = alerts_mod.AlertHub()
    fired = []
    hub.register_action(fired.append)
    hub.register_action(lambda a: 1 / 0)
    before = prof.counters().get("watch.alert.action_errors_total", 0.0)
    hub.emit(alerts_mod.Alert("s", "k", "m"))
    assert len(fired) == 1
    assert prof.counters()["watch.alert.action_errors_total"] - before == 1.0
    hub.unregister_action(fired.append)
    hub.emit(alerts_mod.Alert("s", "k2", "m"))
    assert len(fired) == 1


def test_alert_hub_bounded_and_source_filter():
    hub = alerts_mod.AlertHub(capacity=4)
    for i in range(10):
        hub.emit(alerts_mod.Alert("a" if i % 2 else "b", f"k{i}", "m"))
    assert len(hub.alerts()) == 4
    assert all(a.source == "a" for a in hub.alerts(source="a"))
    assert hub.emitted_total == 10


# ---- SLO engine -----------------------------------------------------------


def _fake_clock(start=1000.0):
    state = {"t": start}

    def clock():
        return state["t"]

    def advance(dt):
        state["t"] += dt

    return clock, advance


def test_slo_latency_breach_emits_edge_triggered_alert():
    r = MetricRegistry()
    r.histogram("serving.request_latency_seconds",
                buckets=tuple(obs_metrics.exponential_buckets(0.001, 2.0, 12)))
    hub = alerts_mod.AlertHub()
    clock, advance = _fake_clock()
    eng = slo_mod.SloEngine(registry=r, hub=hub, clock=clock,
                            min_interval_s=0.0)
    eng.add(slo_mod.SLO("p99_lat", "latency",
                        "serving.request_latency_seconds", objective=0.010,
                        window_s=60.0, quantile=0.9, burn_alert=1.5))
    for _ in range(20):
        r.observe("serving.request_latency_seconds", 0.002)
        advance(1.0)
        eng.tick(force=True)
    assert hub.emitted_total == 0
    status = eng.status()[0]
    assert status["compliant"] and not status["breached"]
    # latency degrades 20x past the objective: breach + exactly one alert
    for _ in range(30):
        r.observe("serving.request_latency_seconds", 0.2)
        advance(1.0)
        eng.tick(force=True)
    status = eng.status()[0]
    assert status["breached"] and status["burn_rate"] > 1.5
    assert hub.emitted_total == 1  # edge-triggered, not one per tick
    assert hub.alerts()[0].source == "slo.p99_lat"


def test_slo_error_rate_budget_accounting():
    r = MetricRegistry()
    hub = alerts_mod.AlertHub()
    clock, advance = _fake_clock()
    eng = slo_mod.SloEngine(registry=r, hub=hub, clock=clock,
                            min_interval_s=0.0)
    eng.add(slo_mod.SLO("err", "error_rate", "serving.errors_total",
                        objective=0.05, total_metric="serving.responses_total",
                        window_s=100.0))
    for i in range(50):
        r.inc("serving.responses_total", 10)
        if i >= 25:
            r.inc("serving.errors_total", 5)  # 50% errors in second half
        advance(1.0)
        eng.tick(force=True)
    status = eng.status()[0]
    assert not status["compliant"]
    assert status["value"] > 0.05
    assert 0.0 < status["budget_spent_frac"] <= 1.0
    assert hub.emitted_total >= 1


def test_slo_gauge_bound_and_window_value():
    r = MetricRegistry()
    clock, advance = _fake_clock()
    eng = slo_mod.SloEngine(registry=r, hub=alerts_mod.AlertHub(),
                            clock=clock, min_interval_s=0.0)
    eng.add(slo_mod.SLO("goodput", "gauge_bound", "trainer.goodput_frac",
                        objective=0.9, bound="min", window_s=50.0))
    for _ in range(10):
        r.set("trainer.goodput_frac", 0.97)
        advance(1.0)
        eng.tick(force=True)
    assert eng.status()[0]["compliant"]
    r.set("trainer.goodput_frac", 0.5)
    advance(1.0)
    eng.tick(force=True)
    status = eng.status()[0]
    assert not status["compliant"] and status["breached"]


def test_slo_gauge_bound_ignores_never_written_gauge():
    """Warmup: ticks before the gauge's first write must sample "no data",
    not a phantom 0.0 violating a min-bound (seen live: a goodput-floor
    SLO alerting during trainer compile)."""
    r = MetricRegistry()
    hub = alerts_mod.AlertHub()
    clock, advance = _fake_clock()
    eng = slo_mod.SloEngine(registry=r, hub=hub, clock=clock,
                            min_interval_s=0.0)
    eng.add(slo_mod.SLO("goodput", "gauge_bound", "trainer.goodput_frac",
                        objective=0.5, bound="min", window_s=600.0))
    for _ in range(5):  # e.g. during compile, gauge not yet set
        advance(1.0)
        eng.tick(force=True)
    status = eng.status()[0]
    assert status["compliant"] and not status["breached"]
    assert status["value"] is None and hub.emitted_total == 0
    r.set("trainer.goodput_frac", 0.97)
    advance(1.0)
    eng.tick(force=True)
    status = eng.status()[0]
    assert status["compliant"] and status["value"] == 0.0  # no violations
    assert hub.emitted_total == 0


def test_slo_validation_and_install_registry():
    with pytest.raises(EnforceError):
        slo_mod.SLO("x", "latency", "m", objective=0.0)
    with pytest.raises(EnforceError):
        slo_mod.SLO("x", "error_rate", "m", objective=0.5)  # no total_metric
    with pytest.raises(EnforceError):
        slo_mod.SLO("x", "nope", "m", objective=1.0)
    eng = slo_mod.SloEngine(registry=MetricRegistry())
    eng.add(slo_mod.SLO("a", "gauge_bound", "g", objective=1.0))
    with pytest.raises(EnforceError):
        eng.add(slo_mod.SLO("a", "gauge_bound", "g", objective=1.0))
    slo_mod.install(eng)
    try:
        assert eng in slo_mod.installed_engines()
    finally:
        slo_mod.uninstall(eng)
    assert eng not in slo_mod.installed_engines()


# ---- watcher --------------------------------------------------------------


def test_metric_watcher_feeds_detector_and_alerts():
    r = MetricRegistry()
    r.histogram("trainer.step_seconds",
                buckets=tuple(obs_metrics.exponential_buckets(0.001, 2.0, 14)))
    hub = alerts_mod.AlertHub()
    rule = watch.WatchRule(
        "trainer.step_seconds",
        EwmaDetector(alpha=0.3, z_threshold=4.0, min_samples=4))
    w = watch.MetricWatcher(registry=r, hub=hub, rules=[rule]).start()
    try:
        for _ in range(12):
            r.observe("trainer.step_seconds", 0.1)
        assert hub.emitted_total == 0
        r.observe("trainer.step_seconds", 2.0)
        assert hub.emitted_total == 1
        a = hub.alerts()[0]
        assert a.source == "watch.trainer.step_seconds"
        assert a.value == pytest.approx(2.0)
    finally:
        w.close()
    r.observe("trainer.step_seconds", 50.0)  # after close: no more alerts
    assert hub.emitted_total == 1


def test_metric_watcher_no_reentrant_feedback_loop():
    """The alert emission writes watch.alert.* counters into the DEFAULT
    registry; a watcher on the default registry must not recurse on its
    own output."""
    r = obs_metrics.default_registry()
    hub = alerts_mod.AlertHub()
    rule = watch.WatchRule(
        "watchtest.series",
        EwmaDetector(alpha=0.3, z_threshold=4.0, min_samples=4))
    w = watch.MetricWatcher(registry=r, hub=hub, rules=[rule]).start()
    try:
        for _ in range(10):
            r.set("watchtest.series", 1.0)
        r.set("watchtest.series", 99.0)
        assert hub.emitted_total == 1
    finally:
        w.close()
    # refusing to watch watch.* families entirely
    w2 = watch.MetricWatcher(registry=MetricRegistry(), hub=hub)
    w2.add_rule(watch.WatchRule("watch.alert.events_total", EwmaDetector()))
    assert not w2.rules


def test_watch_rule_invert_catches_drops():
    r = MetricRegistry()
    hub = alerts_mod.AlertHub()
    rule = watch.WatchRule(
        "trainer.mfu", EwmaDetector(alpha=0.3, z_threshold=4.0, min_samples=4),
        invert=True)
    w = watch.MetricWatcher(registry=r, hub=hub, rules=[rule]).start()
    try:
        for _ in range(10):
            r.set("trainer.mfu", 0.40)
        r.set("trainer.mfu", 0.05)  # MFU collapse = anomaly despite being LOW
        assert hub.emitted_total == 1
        assert hub.alerts()[0].value == pytest.approx(0.05)
    finally:
        w.close()


def test_watch_build_from_config_and_default_rules():
    assert watch.build(watch.WatchConfig(enabled=False)) is None
    cfg = watch.WatchConfig(enabled=True, hub=alerts_mod.AlertHub(),
                            slos=[slo_mod.SLO("g", "gauge_bound",
                                              "trainer.goodput_frac",
                                              objective=0.5)])
    w = watch.build(cfg, registry=MetricRegistry())
    try:
        assert w is not None and w.slo_engine is not None
        assert w.slo_engine in slo_mod.installed_engines()
        metrics_watched = {r.metric for r in w.rules}
        assert "trainer.step_seconds" in metrics_watched
        assert "serving.replica_exec_seconds" in metrics_watched
    finally:
        slo_mod.uninstall(w.slo_engine)
        w.close()


# ---- runlog rotation ------------------------------------------------------


def test_runlog_rotation_and_cross_segment_read(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = runlog.RunLog(path, max_bytes=600, keep=3)
    for i in range(60):
        log.emit("step", step=i, idx=i)
    log.close()
    assert log.rotations >= 2
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 600
    # read stitches segments oldest-first into one continuous stream
    events = runlog.read_runlog(path)
    kept_idx = [e["idx"] for e in events]
    assert kept_idx == sorted(kept_idx)
    assert kept_idx[-1] == 59
    # every segment parses standalone (no torn lines at boundaries)
    for seg in runlog.rotated_paths(path):
        assert runlog.read_runlog(seg, include_rotated=False)


def test_runlog_rotation_drops_oldest_beyond_keep(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = runlog.RunLog(path, max_bytes=300, keep=2)
    for i in range(200):
        log.emit("step", step=i)
    log.close()
    assert not os.path.exists(path + ".3")  # keep=2: at most .1 and .2
    assert os.path.exists(path + ".2")
    events = runlog.read_runlog(path)
    steps = [e["step"] for e in events]
    assert steps == sorted(steps) and steps[-1] == 199


def test_runlog_no_rotation_by_default(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = runlog.RunLog(path)
    for i in range(500):
        log.emit("step", step=i)
    log.close()
    assert log.rotations == 0 and not os.path.exists(path + ".1")
    assert len(runlog.read_runlog(path)) == 500


def test_runlog_tail_endpoint_correct_across_rotation(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = runlog.RunLog(path, max_bytes=500, keep=4)
    prev = runlog.set_runlog(log)
    server = MetricsServer(registry=MetricRegistry()).start()
    try:
        for i in range(50):
            runlog.emit("step", step=i)
        assert log.rotations >= 1  # the tail below spans a boundary
        with urllib.request.urlopen(server.url + "/runlog/tail?n=40") as resp:
            assert resp.headers["Content-Type"].endswith("charset=utf-8")
            events = json.loads(resp.read())
        assert [e["step"] for e in events] == list(range(10, 50))
    finally:
        server.close()
        runlog.set_runlog(prev)
        log.close()


def test_runlog_flags_config_roundtrip(monkeypatch):
    from paddle_tpu.core.config import Flags

    monkeypatch.setenv("PADDLE_TPU_RUNLOG_MAX_BYTES", "1024")
    monkeypatch.setenv("PADDLE_TPU_RUNLOG_KEEP", "5")
    f = Flags().load_env()
    assert f.runlog_max_bytes == 1024 and f.runlog_keep == 5
    # from_flags reads the process-global flags; patch them briefly
    from paddle_tpu.core import config as core_config

    prev = (core_config.flags().runlog_max_bytes,
            core_config.flags().runlog_keep)
    core_config.set_flags(runlog_max_bytes=1024, runlog_keep=5)
    try:
        cfg = pt.ObservabilityConfig.from_flags()
        assert cfg.runlog_max_bytes == 1024 and cfg.runlog_keep == 5
    finally:
        core_config.set_flags(runlog_max_bytes=prev[0], runlog_keep=prev[1])


# ---- exporter hardening ---------------------------------------------------


def test_metrics_scrape_concurrent_with_mutation_never_torn():
    r = MetricRegistry()
    r.histogram("h", buckets=tuple(obs_metrics.exponential_buckets(0.001, 2.0, 10)))
    server = MetricsServer(registry=r).start()
    stop = threading.Event()
    errors = []

    def mutate():
        i = 0
        while not stop.is_set():
            r.inc("c", labels={"shard": str(i % 4)})
            r.set("g", i)
            r.observe("h", 0.001 * (1 + i % 100))
            i += 1

    def scrape():
        try:
            while not stop.is_set():
                with urllib.request.urlopen(server.url + "/metrics") as resp:
                    assert resp.headers["Content-Type"].endswith("charset=utf-8")
                    text = resp.read().decode()
                # strict parse: torn exposition (histogram missing +Inf,
                # cumulative counts decreasing, sample without TYPE) raises
                parse_text_exposition(text)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=mutate) for _ in range(2)]
    threads += [threading.Thread(target=scrape) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        server.close()
    assert not errors, f"torn/failed scrape under mutation: {errors[0]}"


def test_alerts_and_slo_endpoints():
    r = MetricRegistry()
    server = MetricsServer(registry=r).start()
    eng = slo_mod.SloEngine(registry=r, min_interval_s=0.0)
    eng.add(slo_mod.SLO("g", "gauge_bound", "trainer.goodput_frac",
                        objective=0.5))
    slo_mod.install(eng)
    try:
        alerts_mod.default_hub().emit(alerts_mod.Alert(
            "watch.test", "k", "msg", value=1.0))
        with urllib.request.urlopen(server.url + "/alerts?n=10") as resp:
            assert resp.headers["Content-Type"] == "application/json; charset=utf-8"
            payload = json.loads(resp.read())
        assert payload and payload[-1]["source"] == "watch.test"
        with urllib.request.urlopen(
                server.url + "/alerts?source=nope") as resp:
            assert json.loads(resp.read()) == []
        r.set("trainer.goodput_frac", 0.9)
        eng.tick(force=True)
        with urllib.request.urlopen(server.url + "/slo") as resp:
            slos = json.loads(resp.read())
        assert slos and slos[0]["name"] == "g" and slos[0]["compliant"]
        with urllib.request.urlopen(server.url + "/alerts?n=bad") as resp:
            pass
    except urllib.error.HTTPError as e:
        assert e.code == 400
    finally:
        slo_mod.uninstall(eng)
        server.close()


# ---- circuit breaker trip() ----------------------------------------------


def test_breaker_trip_forces_open_with_backoff():
    clock = {"t": 0.0}
    b = CircuitBreaker(failure_threshold=3, cooldown_s=1.0, jitter=0.0,
                       clock=lambda: clock["t"])
    assert b.state == "closed"
    assert b.trip() is True
    assert b.state == "open" and b.trips_total == 1
    assert b.trip() is False  # already open
    assert not b.allow()
    clock["t"] = 2.0
    assert b.allow()  # half-open probe after cooldown
    assert b.record_success() is True
    assert b.state == "closed" and b.recoveries_total == 1


# ---- end-to-end: trainer + serving with injected latency spike ------------


def _linreg_model():
    import jax.numpy as jnp

    def net(x, y):
        pred = pt.layers.fc(x, size=1)
        return jnp.mean(pt.ops.nn.square_error_cost(pred, y))

    return net


def _reader(n_batches=8, bs=8, seed=0):
    def reader():
        rng = np.random.RandomState(seed)
        w = np.array([[2.0], [-1.0], [0.5], [3.0]], np.float32)
        for _ in range(n_batches):
            x = rng.randn(bs, 4).astype(np.float32)
            yield x, x @ w + 0.1

    return reader


def test_watch_end_to_end_trainer_serving_alert(tmp_path):
    """The acceptance path: drive a trainer and a serving engine with the
    watch layer attached, inject a latency spike into one serving replica
    (a SERVING_DISPATCH stall inside the timed execute section), and
    assert the full alert trail: runlog ``alert`` event, ``watch.alert.*``
    counter increment, and the alert visible at ``/alerts``."""
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    runlog_path = str(tmp_path / "run.jsonl")
    hub = alerts_mod.default_hub()
    alerts_before = prof.counters().get("watch.alert.events_total", 0.0)

    # -- trainer with the watch layer attached (its steady steps must not
    # false-positive while the serving spike below must alert)
    tr = pt.Trainer(
        _linreg_model, lambda: pt.optimizer.SGD(learning_rate=0.1),
        observability=pt.ObservabilityConfig(runlog_path=runlog_path),
        watch=watch.WatchConfig(enabled=True, hub=hub),
    )
    server = MetricsServer(registry=obs_metrics.default_registry()).start()
    engine = None
    try:
        tr.train(reader=_reader(n_batches=6), num_epochs=1)
        assert tr._watcher is not None

        # -- serving with a fast per-replica latency rule; replica 0 gets a
        # 0.25s stall injected INSIDE the timed execute section
        rule = watch.WatchRule(
            "serving.replica_exec_seconds",
            RollingQuantileDetector(window=32, q=0.5, ratio=5.0,
                                    min_samples=6))
        model = pt.build(lambda x: pt.layers.fc(x, size=2))
        variables = model.init(0, np.zeros((2, 4), np.float32))
        with faults.injected(faults.FaultSpec(
                faults.SERVING_DISPATCH, "stall", after=12, times=1,
                stall_s=0.25, match={"replica": 0})):
            engine = ServingEngine(
                model, variables, [FeedSpec("x", (4,), "float32")],
                ServingConfig(
                    max_batch_size=4, num_replicas=1, max_queue_delay_s=0.0,
                    engine_label="watch_e2e",
                    watch=watch.WatchConfig(enabled=True, rules=[rule],
                                            use_default_rules=False,
                                            hub=hub)),
            )
            x = np.ones((1, 4), np.float32)
            for _ in range(30):
                engine.infer({"x": x})
        assert hub.emitted_total >= 1
        spike = [a for a in hub.alerts()
                 if a.source == "watch.serving.replica_exec_seconds"]
        assert spike, f"no replica-latency alert in {hub.alerts()}"
        assert spike[0].labels.get("engine") == "watch_e2e"
        assert spike[0].value >= 0.25  # the injected stall, not noise

        # counter incremented
        assert (prof.counters()["watch.alert.events_total"]
                - alerts_before >= 1.0)
        # runlog carries the structured alert event
        events = runlog.read_runlog(runlog_path)
        alert_events = [e for e in events if e["kind"] == "alert"]
        assert alert_events
        assert alert_events[0]["source"] == "watch.serving.replica_exec_seconds"
        assert alert_events[0]["value"] >= 0.25
        # alert visible at the exporter's /alerts endpoint
        with urllib.request.urlopen(server.url + "/alerts?n=50") as resp:
            served = json.loads(resp.read())
        assert any(a["source"] == "watch.serving.replica_exec_seconds"
                   for a in served)
    finally:
        if engine is not None:
            engine.close(timeout=30)
        if tr._watcher is not None:
            tr._watcher.close()
        server.close()
        pt.observability.shutdown()


def test_anomaly_eject_trips_replica_breaker():
    """anomaly_eject=True: a latency-anomaly alert ejects the flagged
    replica through the same breaker path consecutive failures use —
    unless it is the last healthy one."""
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    hub = alerts_mod.AlertHub()
    rule = watch.WatchRule(
        "serving.replica_exec_seconds",
        RollingQuantileDetector(window=32, q=0.5, ratio=5.0, min_samples=6))
    model = pt.build(lambda x: pt.layers.fc(x, size=2))
    variables = model.init(0, np.zeros((2, 4), np.float32))
    with faults.injected(faults.FaultSpec(
            faults.SERVING_DISPATCH, "stall", after=16, times=2,
            stall_s=0.25, match={"replica": 0})):
        engine = ServingEngine(
            model, variables, [FeedSpec("x", (4,), "float32")],
            ServingConfig(
                max_batch_size=4, num_replicas=2, max_queue_delay_s=0.0,
                engine_label="eject_e2e", anomaly_eject=True,
                watch=watch.WatchConfig(enabled=True, rules=[rule],
                                        use_default_rules=False, hub=hub)),
        )
        try:
            x = np.ones((1, 4), np.float32)
            for _ in range(60):
                engine.infer({"x": x})
            if engine.num_replicas < 2:
                pytest.skip("engine built with a single replica")
            spikes = [a for a in hub.alerts()
                      if a.source == "watch.serving.replica_exec_seconds"
                      and a.labels.get("replica") == "0"]
            assert spikes
            health = engine.replica_health()
            assert any(h["index"] == 0 and h["trips_total"] >= 1
                       for h in health), health
            # requests keep completing on the surviving replica
            assert engine.infer({"x": x}) is not None
        finally:
            engine.close(timeout=30)
