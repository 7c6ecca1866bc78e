"""paddle_tpu.observability: registry semantics, Prometheus exposition
golden-parse, runlog JSONL round-trip, MFU/goodput units, and the trainer/
serving integration hooks."""

import json
import math
import os
import urllib.request

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.observability import exporter, metrics, mfu, runlog
from paddle_tpu.observability.exporter import (
    ExpositionError,
    MetricsServer,
    parse_text_exposition,
    render_text,
)
from paddle_tpu.observability.metrics import MetricRegistry
from paddle_tpu.resilience import ResilienceConfig, faults


# ---- registry -------------------------------------------------------------


def test_registry_counter_gauge_basics():
    r = MetricRegistry()
    r.inc("trainer.steps_total")
    r.inc("trainer.steps_total", 2)
    r.set("trainer.loss", 0.5)
    r.set("trainer.loss", 0.25)
    assert r.get("trainer.steps_total") == 3.0
    assert r.get("trainer.loss") == 0.25
    assert r.flat_counters() == {"trainer.steps_total": 3.0}
    assert r.flat_gauges() == {"trainer.loss": 0.25}


def test_registry_labels_sum_and_last_write():
    r = MetricRegistry()
    r.inc("serving.responses_total", 3, labels={"engine": "serving0"})
    r.inc("serving.responses_total", 4, labels={"engine": "serving1"})
    r.set("serving.queue_depth", 7, labels={"engine": "serving0"})
    r.set("serving.queue_depth", 9, labels={"engine": "serving1"})
    # per-child reads
    assert r.get("serving.responses_total", {"engine": "serving0"}) == 3.0
    assert r.get("serving.responses_total", {"engine": "serving1"}) == 4.0
    # legacy flat views: counters sum children, gauges keep the last write
    assert r.flat_counters()["serving.responses_total"] == 7.0
    assert r.flat_gauges()["serving.queue_depth"] == 9.0


def test_registry_kind_conflict_raises():
    r = MetricRegistry()
    r.inc("trainer.steps_total")
    with pytest.raises(EnforceError):
        r.set("trainer.steps_total", 1.0)
    with pytest.raises(EnforceError):
        r.observe("trainer.steps_total", 0.1)


def test_registry_label_schema_enforced():
    r = MetricRegistry()
    r.inc("serving.responses_total", labels={"engine": "serving0"})
    with pytest.raises(EnforceError):
        r.inc("serving.responses_total", labels={"replica": "0"})


def test_histogram_observe_and_snapshot():
    r = MetricRegistry()
    r.histogram("trainer.step_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        r.observe("trainer.step_seconds", v)
    snap = r.histogram_snapshot("trainer.step_seconds")
    assert snap["edges"] == [0.1, 1.0, 10.0]
    assert snap["cumulative"] == [1, 3, 4]  # 50.0 overflows past the last edge
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(56.05)


def test_histogram_bad_buckets_rejected():
    r = MetricRegistry()
    with pytest.raises(EnforceError):
        r.histogram("x.bad", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(EnforceError):
        r.histogram("x.bad2", buckets=(2.0, 1.0))


def test_bucket_helpers():
    assert metrics.exponential_buckets(1.0, 2.0, 4) == (1.0, 2.0, 4.0, 8.0)
    assert metrics.linear_buckets(0.0, 0.5, 3) == (0.0, 0.5, 1.0)
    with pytest.raises(EnforceError):
        metrics.exponential_buckets(0.0, 2.0, 4)


# ---- exposition golden parse ---------------------------------------------


def _golden_registry():
    r = MetricRegistry()
    r.counter("serving.responses_total", help="responses sent")
    r.inc("serving.responses_total", 5, labels={"engine": "serving0"})
    r.inc("serving.responses_total", 7, labels={"engine": "serving1"})
    r.set("trainer.loss", 0.125)
    r.histogram("trainer.step_seconds", help="per-step wall time",
                buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        r.observe("trainer.step_seconds", v)
    return r


def test_render_golden_families():
    text = render_text(_golden_registry())
    fams = parse_text_exposition(text)
    assert fams["serving_responses_total"]["type"] == "counter"
    assert fams["serving_responses_total"]["help"] == "responses sent"
    assert fams["trainer_loss"]["type"] == "gauge"
    assert fams["trainer_step_seconds"]["type"] == "histogram"
    # counter samples keep their labels
    samples = {
        (s[0], tuple(sorted(s[1].items()))): s[2]
        for s in fams["serving_responses_total"]["samples"]
    }
    assert samples[("serving_responses_total", (("engine", "serving0"),))] == 5
    assert samples[("serving_responses_total", (("engine", "serving1"),))] == 7


def test_render_histogram_series_shape():
    text = render_text(_golden_registry())
    lines = [l for l in text.splitlines()
             if l.startswith("trainer_step_seconds")]
    # buckets are cumulative, le edges monotone, +Inf terminal
    les, cums = [], []
    for l in lines:
        if l.startswith("trainer_step_seconds_bucket"):
            le = l.split('le="')[1].split('"')[0]
            les.append(math.inf if le == "+Inf" else float(le))
            cums.append(float(l.rsplit(" ", 1)[1]))
    assert les == [0.01, 0.1, 1.0, math.inf]
    assert cums == [1, 2, 3, 4]
    count = [l for l in lines if l.startswith("trainer_step_seconds_count")]
    total = [l for l in lines if l.startswith("trainer_step_seconds_sum")]
    assert float(count[0].rsplit(" ", 1)[1]) == 4
    assert float(total[0].rsplit(" ", 1)[1]) == pytest.approx(5.555)


def test_parser_rejects_malformed_exposition():
    with pytest.raises(ExpositionError):
        parse_text_exposition("no_type_declared 1\n")
    with pytest.raises(ExpositionError):
        parse_text_exposition(
            "# TYPE x histogram\n"
            'x_bucket{le="1"} 1\n'  # no +Inf terminal bucket
            "x_sum 1\nx_count 1\n")
    with pytest.raises(ExpositionError):
        parse_text_exposition(
            "# TYPE x histogram\n"
            'x_bucket{le="1"} 5\n'
            'x_bucket{le="+Inf"} 3\n'  # cumulative counts decrease
            "x_sum 1\nx_count 3\n")
    with pytest.raises(ExpositionError):
        parse_text_exposition(
            "# TYPE x histogram\n"
            'x_bucket{le="1"} 1\n'
            'x_bucket{le="+Inf"} 2\n'
            "x_sum 1\nx_count 99\n")  # _count != +Inf bucket


def test_dotted_names_sanitized():
    r = MetricRegistry()
    r.inc("serving.responses_total")
    text = render_text(r)
    assert "serving_responses_total 1" in text
    # only the HELP text may mention the dotted registry name
    for line in text.splitlines():
        if not line.startswith("#"):
            assert "serving.responses_total" not in line


def test_metrics_server_http():
    r = _golden_registry()
    srv = MetricsServer(registry=r, port=0).start()
    try:
        body = urllib.request.urlopen(srv.url + "/metrics", timeout=10).read()
        fams = parse_text_exposition(body.decode("utf-8"))
        assert "trainer_step_seconds" in fams
        health = json.loads(
            urllib.request.urlopen(srv.url + "/healthz", timeout=10).read())
        assert health == {"status": "ok"}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
    finally:
        srv.close()


# ---- runlog ---------------------------------------------------------------


def test_runlog_round_trip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = runlog.RunLog(path)
    log.emit("step", step=1, loss=0.5, examples_per_sec=100.0)
    log.emit("compile", target="train_step", seconds=1.25)
    log.emit("checkpoint_save", step=1, path="/tmp/ckpt_0")
    log.emit("custom", step=None, value=np.float32(2.5))  # numpy coerces
    log.close()
    events = runlog.read_runlog(path)
    assert [e["kind"] for e in events] == [
        "step", "compile", "checkpoint_save", "custom"]
    for e in events:
        assert "ts" in e and "kind" in e and "step" in e
    assert events[0]["loss"] == 0.5
    assert events[3]["value"] == 2.5  # not a repr string


def test_runlog_module_emit_requires_install(tmp_path):
    assert runlog.get_runlog() is None or True  # no crash either way
    prev = runlog.set_runlog(None)
    try:
        runlog.emit("ignored")  # no sink installed: silent no-op
        path = str(tmp_path / "run2.jsonl")
        log = runlog.RunLog(path)
        runlog.set_runlog(log)
        runlog.emit("hello", step=3)
        runlog.set_runlog(None)
        log.close()
        events = runlog.read_runlog(path)
        assert len(events) == 1 and events[0]["kind"] == "hello"
    finally:
        runlog.set_runlog(prev)


def test_runlog_torn_line_raises(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with open(path, "w") as f:
        f.write('{"ts": 1, "kind": "step", "step": 0}\n')
        f.write('{"ts": 2, "kind": "st')  # crashed writer
    with pytest.raises(ValueError, match="torn.jsonl:2"):
        runlog.read_runlog(path)


# ---- mfu / goodput --------------------------------------------------------


def test_peak_flops_resolution_order():
    assert mfu.peak_flops_for_kind("TPU v4") == 275e12
    assert mfu.peak_flops_for_kind("TPU v5p") == 459e12  # v5p before v5
    assert mfu.peak_flops_for_kind("cpu") == 5e10
    assert mfu.peak_flops_for_kind("quantum") is None
    mfu.set_peak_flops(123.0)
    try:
        assert mfu.peak_flops_for_kind("TPU v4") == 123.0
    finally:
        mfu.set_peak_flops(None)


def test_lowered_flops_and_mfu():
    import jax

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((64, 64), jnp.float32)
    flops = mfu.lowered_flops(f, x, x)
    # one 64^3 matmul = 2*64^3 FLOPs give or take the cost model's rounding
    assert flops > 0
    util = mfu.mfu(flops, step_time_s=0.01, device_count=1,
                   peak_per_device=1e12)
    assert util == pytest.approx(flops / (0.01 * 1e12))
    assert mfu.mfu(0.0, 0.01) is None
    assert mfu.mfu(flops, 0.0) is None
    assert mfu.mfu(flops, 0.01, peak_per_device=0.0) is None


def test_goodput_tracker():
    g = mfu.GoodputTracker()
    assert g.goodput_frac() == 1.0  # untroubled/empty run
    g.record_good(9.0)
    g.record_bad(0.5, "nan_skip")
    g.record_bad(0.5, "rollback")
    assert g.goodput_frac() == pytest.approx(0.9)
    assert g.badput_by_category() == {"nan_skip": 0.5, "rollback": 0.5}
    snap = g.snapshot()
    assert snap["good_seconds"] == 9.0
    assert snap["bad_seconds.rollback"] == 0.5


# ---- framework integration ------------------------------------------------


def _linreg_model():
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


def test_trainer_telemetry_end_to_end(tmp_path):
    runlog_path = str(tmp_path / "run.jsonl")
    ckpt_root = str(tmp_path / "ckpt")
    steps_before = prof.counters().get("trainer.steps_total", 0.0)
    hist_before = (metrics.default_registry()
                   .histogram_snapshot("trainer.step_seconds") or {"count": 0})
    with faults.injected(
        faults.FaultSpec(faults.TRAINER_STEP, "nan", after=3, times=1)
    ):
        tr = pt.Trainer(
            _linreg_model, lambda: pt.optimizer.SGD(learning_rate=0.1),
            checkpoint_config=pt.CheckpointConfig(ckpt_root, step_interval=5),
            resilience=ResilienceConfig(nan_policy="skip_step"),
            observability=pt.ObservabilityConfig(runlog_path=runlog_path),
        )
        tr.train(reader=_reader(), num_epochs=1)
    pt.observability.shutdown()

    events = runlog.read_runlog(runlog_path)
    kinds = {e["kind"] for e in events}
    assert {"step", "checkpoint_save", "nan_skip", "fault_injected"} <= kinds
    for e in events:
        assert "ts" in e and "kind" in e and "step" in e
    step_ev = next(e for e in events if e["kind"] == "step")
    assert {"loss", "step_time_s", "examples_per_sec",
            "ema_examples_per_sec"} <= set(step_ev)

    c, g = prof.counters(), prof.gauges()
    assert c["trainer.steps_total"] - steps_before == 7  # 8 batches - 1 nan
    hist = metrics.default_registry().histogram_snapshot("trainer.step_seconds")
    assert hist["count"] - hist_before["count"] == 7
    # MFU from cost_analysis flops: finite and positive even on CPU
    assert g["trainer.mfu"] > 0 and np.isfinite(g["trainer.mfu"])
    assert 0.0 < g["trainer.goodput_frac"] <= 1.0


def test_trainer_runlog_has_compile_events(tmp_path):
    runlog_path = str(tmp_path / "compile.jsonl")
    tr = pt.Trainer(
        _linreg_model, lambda: pt.optimizer.SGD(learning_rate=0.1),
        observability=pt.ObservabilityConfig(runlog_path=runlog_path),
    )
    tr.train(reader=_reader(n_batches=2), num_epochs=1)
    pt.observability.shutdown()
    events = runlog.read_runlog(runlog_path)
    compiles = [e for e in events if e["kind"] == "compile"]
    assert compiles and all(e["seconds"] > 0 for e in compiles)


def test_serving_engines_get_distinct_labels():
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = pt.build(lambda x: pt.layers.fc(x, size=2))
    variables = model.init(0, np.zeros((2, 4), np.float32))
    specs = [FeedSpec("x", (4,), "float32")]
    cfg = ServingConfig(max_batch_size=8, num_replicas=1)
    eng1 = ServingEngine(model, variables, specs, cfg)
    eng2 = ServingEngine(model, variables, specs, cfg)
    try:
        assert eng1.metrics.engine_label != eng2.metrics.engine_label
        x = np.ones((1, 4), np.float32)
        for _ in range(3):
            eng1.submit({"x": x}).result(timeout=30)
            eng2.submit({"x": x}).result(timeout=30)
        reg = metrics.default_registry()
        for eng in (eng1, eng2):
            lat = reg.histogram_snapshot(
                "serving.request_latency_seconds",
                {"engine": eng.metrics.engine_label})
            assert lat is not None and lat["count"] >= 3
        assert eng1.metrics.snapshot()["engine"] == eng1.metrics.engine_label
    finally:
        eng1.close(timeout=30)
        eng2.close(timeout=30)


def test_explicit_engine_label_respected():
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = pt.build(lambda x: pt.layers.fc(x, size=2))
    variables = model.init(0, np.zeros((2, 4), np.float32))
    eng = ServingEngine(
        model, variables, [FeedSpec("x", (4,), "float32")],
        ServingConfig(max_batch_size=4, num_replicas=1,
                      engine_label="ranker"))
    try:
        assert eng.metrics.engine_label == "ranker"
    finally:
        eng.close(timeout=30)




# ---- remembered child keys, the name check at a child's making (ISSUE 37) --


def _write_all(r, labels_of):
    r.histogram("unit.seconds", help="a histogram", buckets=(0.01, 0.1, 1.0))
    for i in range(40):
        r.inc("unit.calls_total", 1 + i % 3, labels=labels_of("decode0", "batch"))
        r.inc("unit.calls_total", 2, labels=labels_of("decode1", "interactive"))
        r.set("unit.level", i, labels=labels_of("decode0", "batch"))
        r.observe("unit.seconds", 0.004 * (i + 1), labels=labels_of("decode0", "batch"))
    r.observe_many("unit.seconds", [0.5, 5.0, 0.05], labels=labels_of("decode1", "batch"))
    r.inc("unit.bare_total")


def test_a_remembered_child_key_gives_the_same_families_buckets_and_text():
    """A caller that hands one dict call after call (the registry remembers
    its child key on the dict's items) against one that builds a new dict,
    in the other order, every call: the same registry to the last digit."""
    kept = {}

    def same_dict(engine, cls):
        return kept.setdefault((engine, cls), {"engine": engine, "cls": cls})

    remembered, fresh = MetricRegistry(), MetricRegistry()
    _write_all(remembered, same_dict)
    _write_all(fresh, lambda engine, cls: {"cls": cls, "engine": engine})
    assert remembered._keys and render_text(remembered) == render_text(fresh)
    for a, b in zip(remembered.collect(), fresh.collect()):
        assert (a.name, a.kind, a.help, a.buckets, a.samples) == \
               (b.name, b.kind, b.help, b.buckets, b.samples)
    snap = remembered.histogram_snapshot("unit.seconds", {"cls": "batch", "engine": "decode1"})
    assert snap["count"] == 3 and snap["cumulative"] == [0, 1, 2] and snap["sum"] == 5.55
    assert remembered.get("unit.calls_total", {"engine": "decode1", "cls": "interactive"}) == 80
    assert remembered.flat_gauges() == {"unit.level": 39.0}


def test_a_labels_dict_that_changes_between_writes_goes_to_the_child_it_names():
    r = MetricRegistry()
    labels = {"engine": "a"}
    r.inc("unit.calls_total", labels=labels)
    labels["engine"] = "b"  # remembered on the items, never on the dict's id
    r.inc("unit.calls_total", 5, labels=labels)
    r.inc("unit.calls_total", labels={"engine": 7})  # a value that is no str is not remembered
    r.inc("unit.calls_total", labels={"engine": "7"})
    assert {k: v for k, v in r.collect()[0].samples} == {
        (("engine", "a"),): 1.0, (("engine", "b"),): 5.0, (("engine", "7"),): 2.0}
    # the family's label names are held to a child when it is first made
    with pytest.raises(EnforceError, match="inconsistent label names"):
        r.inc("unit.calls_total", labels={"replica": "a"})
    with pytest.raises(EnforceError, match="already registered as counter"):
        r.set("unit.calls_total", 1.0, labels={"engine": "a"})


def test_a_subscriber_added_after_a_write_hears_the_next_and_many_is_each():
    r = MetricRegistry()
    labels = {"engine": "decode0"}
    r.inc("unit.calls_total", labels=labels)
    heard = []
    r.subscribe(lambda name, kind, value, lab: heard.append((name, kind, value, lab)))
    r.inc("unit.calls_total", 2, labels=labels)
    r.set("unit.level", 3, labels=labels)
    r.observe_many("unit.seconds", [0.25, 0.5], labels=labels)
    assert heard == [("unit.calls_total", "counter", 2, labels),
                     ("unit.level", "gauge", 3.0, labels),
                     ("unit.seconds", "histogram", 0.25, labels),
                     ("unit.seconds", "histogram", 0.5, labels)]
    one_by_one = MetricRegistry()
    for v in (0.25, 0.5):
        one_by_one.observe("unit.seconds", v, labels=labels)
    assert one_by_one.histogram_snapshot("unit.seconds", labels) == \
        r.histogram_snapshot("unit.seconds", labels)
