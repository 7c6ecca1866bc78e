"""paddle_tpu.tracing: SpanContext round-trips, span propagation through a
real ServingEngine request and a real Trainer step, straggler detection on
seeded skew, device-memory telemetry, and merged Chrome-trace export schema
validation."""

import json
import threading
import urllib.error
import urllib.request
from collections import deque

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import tracing
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.observability import runlog
from paddle_tpu.tracing import context as trace_ctx
from paddle_tpu.tracing.straggler import StragglerDetector


@pytest.fixture(autouse=True)
def _fresh_trace_store():
    tracing.reset_tracing()
    yield
    tracing.reset_tracing()


def _counter(name):
    return prof.counters().get(name, 0.0)


# ---- SpanContext ----------------------------------------------------------


def test_traceparent_round_trip():
    ctx = tracing.SpanContext.new_trace()
    header = ctx.to_traceparent()
    assert header == f"00-{ctx.trace_id}-{ctx.span_id}-01"
    back = tracing.SpanContext.from_traceparent(header)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id


def test_traceparent_malformed_rejected():
    good = tracing.SpanContext.new_trace().to_traceparent()
    for bad in (
        "not-a-traceparent",
        good.replace("-", "_"),
        "ff-" + good[3:],                       # forbidden version
        f"00-{'0' * 32}-{'a' * 16}-01",         # all-zero trace id
        f"00-{'a' * 32}-{'0' * 16}-01",         # all-zero span id
        good[:-2] + "zz",                       # non-hex flags
        good + "-extra",
    ):
        with pytest.raises(EnforceError):
            tracing.SpanContext.from_traceparent(bad)


def test_child_lineage():
    root = tracing.SpanContext.new_trace()
    child = root.child()
    grandchild = child.child()
    assert root.parent_id is None
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert grandchild.trace_id == root.trace_id
    assert grandchild.parent_id == child.span_id
    assert child.span_id != root.span_id


def test_span_context_rejects_bad_ids():
    with pytest.raises(EnforceError):
        tracing.SpanContext("short", "a" * 16)
    with pytest.raises(EnforceError):
        tracing.SpanContext("A" * 32, "a" * 16)  # uppercase
    with pytest.raises(EnforceError):
        tracing.SpanContext("a" * 32, "a" * 15)


# ---- span scopes and the store --------------------------------------------


def test_start_span_nesting_and_current_context():
    assert tracing.current_context() is None
    with tracing.start_trace("unit.root") as root:
        assert tracing.current_context() is root.context
        with tracing.start_span("unit.inner") as inner:
            assert inner.context.trace_id == root.context.trace_id
            assert inner.context.parent_id == root.context.span_id
            assert tracing.current_context() is inner.context
        assert tracing.current_context() is root.context
    assert tracing.current_context() is None
    tree = tracing.spans_for_trace(root.context.trace_id)
    assert [s.name for s in tree] == ["unit.root", "unit.inner"]
    assert tracing.validate_trace(tree) == []


def test_start_trace_is_root_even_when_nested():
    with tracing.start_trace("unit.outer") as outer:
        with tracing.start_trace("unit.fresh") as fresh:
            assert fresh.context.trace_id != outer.context.trace_id
            assert fresh.context.parent_id is None


def test_record_span_explicit_context_and_parent():
    ctx = tracing.SpanContext.new_trace()
    got = tracing.record_span("unit.root_like", 1.0, 2.0, context=ctx, rows=4)
    assert got is ctx
    child_ctx = tracing.record_span("unit.child_like", 1.2, 1.8, parent=ctx)
    assert child_ctx.trace_id == ctx.trace_id
    assert child_ctx.parent_id == ctx.span_id
    tree = tracing.spans_for_trace(ctx.trace_id)
    assert tracing.validate_trace(tree) == []
    assert tree[0].attrs == {"rows": 4}
    with pytest.raises(EnforceError):
        tracing.record_span("unit.backwards", 2.0, 1.0)


def test_span_exception_sets_error_status():
    with pytest.raises(RuntimeError):
        with tracing.start_trace("unit.boom") as sp:
            raise RuntimeError("x")
    stored = [s for s in tracing.spans() if s.name == "unit.boom"]
    assert stored and stored[0].attrs["status"] == "error"
    assert stored[0].attrs["exception"] == "RuntimeError"
    assert sp.t1_us is not None


def test_span_cancel_discards():
    with tracing.start_trace("unit.discarded") as sp:
        sp.cancel()
    assert not [s for s in tracing.spans() if s.name == "unit.discarded"]


def test_disable_tracing_suppresses_spans():
    tracing.disable_tracing()
    try:
        assert tracing.record_span("unit.off", 0.0, 1.0) is None
        with tracing.start_trace("unit.off_scope"):
            pass
        assert tracing.spans() == []
    finally:
        tracing.enable_tracing()


def test_store_eviction_is_counted(monkeypatch):
    monkeypatch.setattr(trace_ctx, "_store", deque(maxlen=3))
    before = _counter("tracing.spans_evicted")
    for i in range(5):
        tracing.record_span("unit.evict", float(i), float(i) + 0.5)
    assert len(tracing.spans()) == 3
    assert _counter("tracing.spans_evicted") - before == 2
    # oldest evicted first
    assert [s.t0_us for s in tracing.spans()] == [2e6, 3e6, 4e6]


def test_phase_totals():
    tracing.record_span("unit.phase_a", 0.0, 1.5)
    tracing.record_span("unit.phase_a", 2.0, 2.5)
    tracing.record_span("unit.phase_b", 0.0, 0.25)
    totals = tracing.phase_totals(("unit.phase_a", "unit.phase_b", "unit.absent"))
    assert totals["unit.phase_a"] == pytest.approx(2.0)
    assert totals["unit.phase_b"] == pytest.approx(0.25)
    assert totals["unit.absent"] == 0.0


def test_validate_trace_detects_problems():
    assert tracing.validate_trace([]) == ["trace has no spans"]
    ctx = tracing.SpanContext.new_trace()
    root = trace_ctx.Span("unit.root", ctx, 0.0)
    root.t1_us = 100.0
    open_child = trace_ctx.Span("unit.open", ctx.child(), 10.0)
    dangling = trace_ctx.Span(
        "unit.dangling",
        tracing.SpanContext(ctx.trace_id, "b" * 16, "c" * 16), 10.0)
    dangling.t1_us = 20.0
    escapee = trace_ctx.Span("unit.escapee", ctx.child(), 50.0)
    escapee.t1_us = 9e9  # far past the parent's end
    problems = tracing.validate_trace([root, open_child, dangling, escapee])
    assert any("never closed" in p for p in problems)
    assert any("unresolved parent" in p for p in problems)
    assert any("escapes parent" in p for p in problems)
    second_root = trace_ctx.Span("unit.root2", tracing.SpanContext(
        ctx.trace_id, "d" * 16), 0.0)
    second_root.t1_us = 1.0
    problems = tracing.validate_trace([root, second_root])
    assert any("exactly 1 root" in p for p in problems)


def test_active_spans_visible_across_threads():
    release = threading.Event()
    opened = threading.Event()

    def hold():
        with tracing.start_trace("unit.held"):
            opened.set()
            release.wait(timeout=10)

    t = threading.Thread(target=hold, name="holder")
    t.start()
    try:
        assert opened.wait(timeout=10)
        names = [s.name for s in tracing.active_spans()]
        assert "unit.held" in names
    finally:
        release.set()
        t.join(timeout=10)
    assert "unit.held" not in [s.name for s in tracing.active_spans()]


# ---- straggler detection --------------------------------------------------


def _drain(detector, key, values):
    flags = [detector.record(key, v) for v in values]
    return flags


def test_straggler_spatial_flags_slow_replica(tmp_path):
    path = str(tmp_path / "run.jsonl")
    prev = runlog.set_runlog(runlog.RunLog(path))
    try:
        det = StragglerDetector("unit.exec", ratio=2.0, min_samples=5)
        before = _counter("tracing.straggler.flags_total")
        # two healthy replicas, one 4x slower
        flagged = False
        for _ in range(8):
            det.record("replica0", 0.010)
            det.record("replica1", 0.011)
            flagged |= det.record("replica2", 0.042)
        assert flagged
        assert det.flagged.get("replica2", 0) >= 1
        assert not det.flagged.get("replica0")
        assert _counter("tracing.straggler.flags_total") > before
        snap = det.snapshot()
        assert snap["replica2"]["flags"] >= 1
        assert snap["replica0"]["count"] == 8
    finally:
        log = runlog.set_runlog(prev)
        log.close()
    events = [e for e in runlog.read_runlog(path) if e["kind"] == "straggler"]
    assert events and events[0]["key"] == "replica2"
    assert events[0]["mode"] == "spatial"
    assert events[0]["skew_ratio"] > 2.0


def test_straggler_temporal_flags_spike():
    det = StragglerDetector("unit.step", ratio=2.0, min_samples=5)
    assert not any(_drain(det, "step", [0.1] * 10))
    assert det.record("step", 0.5)  # 5x the rolling median
    assert det.snapshot()["step"]["flags"] == 1


def test_straggler_needs_min_samples():
    det = StragglerDetector("unit.warm", ratio=1.5, min_samples=5)
    # wild skew, but below min_samples: never flagged
    assert not any(_drain(det, "a", [0.001, 1.0, 0.001, 5.0]))
    assert det.snapshot()["a"]["flags"] == 0
    with pytest.raises(EnforceError):
        StragglerDetector("unit.bad", ratio=0.5)
    with pytest.raises(EnforceError):
        StragglerDetector("unit.bad", window=1)


# ---- device memory telemetry ----------------------------------------------


def test_sample_device_memory_cpu_fallback():
    import jax

    tracing.reset_memory_telemetry()
    keep = jax.device_put(np.ones((64, 64), np.float32))  # noqa: F841
    devices = [jax.local_devices()[0]]
    samples = tracing.sample_device_memory(devices)
    assert len(samples) == 1
    s = samples[0]
    assert s["device"] == tracing.device_label(devices[0])
    assert s["bytes_in_use"] > 0
    assert s["peak_bytes_in_use"] >= s["bytes_in_use"]
    assert s["source"] in ("memory_stats", "live_arrays")
    g = prof.gauges()
    assert g.get("device.hbm.bytes_in_use", 0) > 0
    assert g.get("device.hbm.peak_bytes_in_use", 0) > 0
    hist = tracing.memory_history()
    assert hist and hist[-1][1] == s["device"]


def test_record_executable_memory():
    import jax

    def f(x):
        return (x @ x.T).sum()

    compiled = jax.jit(f).lower(np.ones((8, 8), np.float32)).compile()
    got = tracing.record_executable_memory(compiled, "unit.test_exe")
    if got is None:  # backend exposes no memory_analysis: nothing to check
        pytest.skip("no memory_analysis on this backend")
    assert got["peak_bytes"] > 0
    assert prof.gauges().get("device.hbm.executable_peak_bytes", 0) > 0


# ---- end-to-end propagation -----------------------------------------------


def test_serving_request_trace_end_to_end():
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    def net(x):
        return pt.layers.fc(x, size=3)

    rng = np.random.RandomState(0)
    model = pt.build(net)
    variables = model.init(0, rng.randn(2, 5).astype(np.float32))
    engine = ServingEngine(
        model, variables, [FeedSpec("x", (5,), "float32")],
        config=ServingConfig(max_batch_size=4, max_queue_delay_s=0.002),
    )
    try:
        pending = engine.submit({"x": rng.randn(1, 5).astype(np.float32)})
        out = pending.result()
        assert np.asarray(out).shape == (1, 3)
        assert pending.trace is not None
        tree = tracing.spans_for_trace(pending.trace.trace_id)
        assert tracing.validate_trace(tree) == []
        names = {s.name for s in tree}
        assert {"serving.request", "serving.enqueue", "serving.queue_wait",
                "serving.dispatch", "serving.execute",
                "serving.reply"} <= names
        root = next(s for s in tree if s.name == "serving.request")
        assert root.context.span_id == pending.trace.span_id
        assert root.attrs["status"] == "ok"
        by_name = {s.name: s for s in tree}
        assert (by_name["serving.enqueue"].t0_us
                <= by_name["serving.execute"].t0_us
                <= by_name["serving.reply"].t0_us)
    finally:
        assert not engine.close(timeout=30)


def test_serving_deadline_trace_marks_expiry():
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import DeadlineExceeded, ServingConfig, ServingEngine

    def net(x):
        return pt.layers.fc(x, size=2)

    rng = np.random.RandomState(1)
    model = pt.build(net)
    variables = model.init(0, rng.randn(2, 4).astype(np.float32))
    engine = ServingEngine(
        model, variables, [FeedSpec("x", (4,), "float32")],
        config=ServingConfig(max_batch_size=4, max_queue_delay_s=0.05),
    )
    try:
        pending = engine.submit(
            {"x": rng.randn(1, 4).astype(np.float32)}, deadline_s=1e-9)
        with pytest.raises(DeadlineExceeded):
            pending.result()
        tree = tracing.spans_for_trace(pending.trace.trace_id)
        root = next(s for s in tree if s.name == "serving.request")
        assert root.attrs["status"] == "deadline_exceeded"
    finally:
        engine.close(timeout=30)


def test_trainer_step_trace_end_to_end():
    def net(x, y):
        pred = pt.layers.fc(x, size=1)
        return pt.layers.mean((pred - y) ** 2)

    def reader():
        rng = np.random.RandomState(0)
        for _ in range(3):
            x = rng.randn(8, 4).astype(np.float32)
            yield x, x.sum(axis=1, keepdims=True)

    trainer = pt.Trainer(lambda: net, lambda: pt.optimizer.SGD(learning_rate=0.1))
    trainer.train(num_epochs=1, reader=reader)
    roots = [s for s in tracing.spans() if s.name == "trainer.step"]
    assert len(roots) == 3
    for root in roots:
        tree = tracing.spans_for_trace(root.context.trace_id)
        assert tracing.validate_trace(tree) == []
        names = {s.name for s in tree}
        assert {"trainer.data_wait", "trainer.h2d",
                "trainer.step_compute"} <= names
    assert roots[0].attrs["step"] == 0  # stamped before the step's update
    # compile happened under some step's trace, parented to it
    compiles = [s for s in tracing.spans() if s.name == "executor.compile"]
    assert compiles
    assert compiles[0].context.trace_id in {
        r.context.trace_id for r in roots}


def test_runlog_events_gain_trace_ids(tmp_path):
    path = str(tmp_path / "run.jsonl")
    prev = runlog.set_runlog(runlog.RunLog(path))
    try:
        runlog.emit("outside_any_span")
        with tracing.start_trace("unit.correlated") as sp:
            runlog.emit("inside_span", detail=1)
            runlog.emit("explicit_wins", trace_id="f" * 32)
    finally:
        log = runlog.set_runlog(prev)
        log.close()
    events = {e["kind"]: e for e in runlog.read_runlog(path)}
    assert "trace_id" not in events["outside_any_span"]
    assert events["inside_span"]["trace_id"] == sp.context.trace_id
    assert events["inside_span"]["span_id"] == sp.context.span_id
    assert events["explicit_wins"]["trace_id"] == "f" * 32


# ---- merged export --------------------------------------------------------


def test_merged_export_schema_and_round_trip(tmp_path):
    import jax

    path = str(tmp_path / "run.jsonl")
    prev = runlog.set_runlog(runlog.RunLog(path))
    try:
        with tracing.start_trace("unit.work", kind="test"):
            runlog.emit("work_happened", step=1)
        tracing.sample_device_memory([jax.local_devices()[0]])
    finally:
        log = runlog.set_runlog(prev)
        log.close()
    out = str(tmp_path / "trace.json")
    tracing.export_chrome_trace(out, runlog_path=path)
    with open(out) as f:
        doc = json.load(f)
    counts = tracing.validate_chrome_trace(doc)
    assert counts["X"] >= 1 and counts["i"] >= 1
    assert counts["C"] >= 1 and counts["M"] >= 3
    span_ev = next(ev for ev in doc["traceEvents"]
                   if ev.get("cat") == "tracing" and ev["name"] == "unit.work")
    assert len(span_ev["args"]["trace_id"]) == 32
    assert span_ev["args"]["kind"] == "test"
    inst = next(ev for ev in doc["traceEvents"]
                if ev.get("cat") == "runlog" and ev["name"] == "work_happened")
    # runlog instant converted onto the span timebase: inside the span
    # (generous slack — the epoch<->perf_counter offset carries ms jitter)
    assert (span_ev["ts"] - 5e4 <= inst["ts"]
            <= span_ev["ts"] + span_ev["dur"] + 5e5)
    assert inst["args"]["trace_id"] == span_ev["args"]["trace_id"]
    # validator accepts the string form too
    assert tracing.validate_chrome_trace(json.dumps(doc)) == counts


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        tracing.validate_chrome_trace({"not": "a trace"})
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": -5},
        {"name": "y", "ph": "Z", "pid": 1, "tid": 1},
        {"name": "", "ph": "i", "pid": 1, "tid": 1, "ts": 0.0, "s": "q"},
        {"name": "c", "ph": "C", "pid": 1, "tid": 1, "ts": 0.0,
         "args": {"dev": "not-a-number"}},
    ]}
    with pytest.raises(ValueError) as ei:
        tracing.validate_chrome_trace(bad)
    msg = str(ei.value)
    for frag in ("dur", "unknown phase", "scope", "numeric 'args'"):
        assert frag in msg


# ---- exporter debug endpoints ---------------------------------------------


def test_exporter_debug_endpoints(tmp_path):
    from paddle_tpu.observability.exporter import MetricsServer

    path = str(tmp_path / "run.jsonl")
    prev = runlog.set_runlog(runlog.RunLog(path))
    srv = MetricsServer(port=0).start()
    try:
        for i in range(4):
            runlog.emit("tick", step=i)
        with tracing.start_trace("unit.http_visible"):
            pass

        tail = json.loads(urllib.request.urlopen(
            srv.url + "/runlog/tail?n=2", timeout=10).read().decode("utf-8"))
        assert [e["step"] for e in tail] == [2, 3]
        everything = json.loads(urllib.request.urlopen(
            srv.url + "/runlog/tail", timeout=10).read().decode("utf-8"))
        assert len(everything) == 4

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/runlog/tail?n=bogus", timeout=10)
        assert ei.value.code == 400

        doc = json.loads(urllib.request.urlopen(
            srv.url + "/trace", timeout=10).read().decode("utf-8"))
        tracing.validate_chrome_trace(doc)
        assert any(ev.get("name") == "unit.http_visible"
                   for ev in doc["traceEvents"])
    finally:
        srv.close()
        log = runlog.set_runlog(prev)
        log.close()

    # with no runlog installed the tail endpoint answers 404, not 500
    prev2 = runlog.set_runlog(None)
    srv2 = MetricsServer(port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv2.url + "/runlog/tail", timeout=10)
        assert ei.value.code == 404
    finally:
        srv2.close()
        runlog.set_runlog(prev2)


# ---- watchdog integration -------------------------------------------------


def test_watchdog_summarizes_open_spans():
    from paddle_tpu.resilience.watchdog import StepWatchdog

    with tracing.start_trace("unit.wedged"):
        summary = StepWatchdog._active_span_summary()
    assert any(s.startswith("unit.wedged@") for s in summary)


# ---- ids, the thread's state and the store without a lock (ISSUE 37) -------


def _on_threads(n, fn):
    out, errors = [None] * n, []

    def run(i):
        try:
            out[i] = fn(i)
        except BaseException as e:  # surface in the test's own thread
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,), name=f"minter-{i}") for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    return out


def test_a_hundred_thousand_ids_on_four_threads_are_unique_well_formed_and_round_trip():
    """One ``os.urandom`` draw a thread and a counter on it: every trace id
    is 32 and every span id 16 lowercase hex digits, none repeats within a
    thread or across threads, and each context survives ``traceparent``."""
    def mint(_):
        got = []
        for _ in range(6250):
            root = tracing.SpanContext.new_trace()
            kids = [root.child(), root.child(), root.child()]
            assert all(k.trace_id == root.trace_id and k.parent_id == root.span_id for k in kids)
            got += [root] + kids
        return got

    minted = [ctx for got in _on_threads(4, mint) for ctx in got]
    assert len(minted) == 100_000
    assert len({c.span_id for c in minted}) == 100_000
    assert len({c.trace_id for c in minted}) == 25_000
    hexdigits = set("0123456789abcdef")
    for c in minted:
        assert len(c.trace_id) == 32 and len(c.span_id) == 16
        assert set(c.trace_id) <= hexdigits and set(c.span_id) <= hexdigits
        back = tracing.SpanContext.from_traceparent(c.to_traceparent())
        assert (back.trace_id, back.span_id) == (c.trace_id, c.span_id)


def test_a_thread_draws_from_urandom_at_most_once(monkeypatch):
    draws = []
    real = trace_ctx.os.urandom
    monkeypatch.setattr(trace_ctx.os, "urandom", lambda n: draws.append(n) or real(n))

    def spans(_):
        before = len(draws)
        with tracing.start_trace("unit.root") as root:
            for _ in range(50):
                with tracing.start_span("unit.child"):
                    pass
        tracing.record_span("unit.after", 1.0, 2.0, parent=root)
        with tracing.start_trace("unit.second_root"):
            pass
        return len(draws) - before

    assert _on_threads(2, spans) == [1, 1]
    assert spans(0) <= 1  # this thread may have drawn in an earlier test


def test_a_span_carries_the_name_and_id_of_the_thread_that_opened_it():
    def one(i):
        with tracing.start_trace("unit.named") as sp:
            pass
        return sp.thread_name, sp.tid == threading.get_ident()

    assert _on_threads(3, one) == [(f"minter-{i}", True) for i in range(3)]


def test_the_store_is_read_while_other_threads_commit_to_it():
    """No lock around the store: ``spans``, ``spans_for_trace`` and
    ``phase_totals`` work on a ``list()`` of it, so a deque that grows under
    them cannot raise, and every committed span is there at the end."""
    stop = threading.Event()
    roots = []

    def commit(i):
        with tracing.start_trace("unit.writer") as root:
            roots.append(root)
            n = 0
            while not stop.is_set() and n < 20000:
                with tracing.start_span("unit.written"):
                    n += 1
        return n

    def read(_):
        seen = 0
        while not stop.is_set():
            seen = max(seen, len(tracing.spans()))
            for root in list(roots):
                tracing.spans_for_trace(root.context.trace_id)
            tracing.phase_totals(["unit.written"])
            tracing.active_spans()
            if seen > 2000:
                stop.set()
        return seen

    out = _on_threads(3, lambda i: read(i) if i == 2 else commit(i))
    assert out[2] > 2000
    written = [s for s in tracing.spans() if s.name == "unit.written"]
    assert len(written) == out[0] + out[1]
    for root in roots:
        assert tracing.validate_trace(tracing.spans_for_trace(root.context.trace_id)) == []
    assert tracing.active_spans() == []
