"""One span system on the profiler's clock.

A scoped ``tracing`` span holds a ``jax.profiler.TraceAnnotation``, so it
lands on the host plane of the profiler's trace; disabled, the scopes cost
nothing; the Trainer step and the DecodeEngine iteration are covered from
inside by named children; ``core.profiler.record_event`` is built on the
same spans; and the device work carries ``jax.named_scope`` names.
"""

import functools
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models, tracing
from paddle_tpu.core import profiler as prof
from paddle_tpu.serving import DecodeConfig, DecodeEngine

TRAINER_CHILDREN = [
    "trainer.data_wait", "trainer.begin_event", "trainer.h2d",
    "trainer.step_compute", "trainer.fetch", "trainer.commit",
    "trainer.record_step", "trainer.end_event", "trainer.checkpoint",
]


@pytest.fixture(autouse=True)
def _clean_store():
    tracing.enable_tracing()
    tracing.reset_tracing()
    yield
    tracing.enable_tracing()
    tracing.reset_tracing()


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    return {ev.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


# ---- spans share the profiler's clock --------------------------------------


def test_scoped_span_lands_on_the_profilers_host_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.start_trace("unit.on_the_profilers_clock"):
            with tracing.start_span("unit.child_of_it"):
                jnp.ones((8,)).block_until_ready()
        with prof.record_event("unit.record_event_too"):
            pass
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert {"unit.on_the_profilers_clock", "unit.child_of_it",
            "unit.record_event_too"} <= names


# ---- off costs nothing -----------------------------------------------------


def test_disabled_tracing_hands_out_one_shared_noop_scope():
    tracing.disable_tracing()
    scope = tracing.start_span("unit.off")
    assert scope is tracing.start_trace("unit.off_root")
    assert scope is tracing.start_span("unit.off", parent=tracing.SpanContext.new_trace(), k=1)
    with scope as sp:
        assert sp.set(anything=1) is sp  # the call sites' uses still work
        sp.cancel()
        assert tracing.current_context() is None
    with prof.record_event("unit.off_event"):
        pass
    assert tracing.spans() == [] and tracing.active_spans() == []


def test_a_trainer_runs_with_tracing_disabled_and_records_nothing():
    tracing.disable_tracing()
    losses = _train_tiny(steps=2)
    assert len(losses) == 2 and all(np.isfinite(losses))
    assert tracing.spans() == []


# ---- the Trainer step, covered from inside ---------------------------------


def _train_tiny(steps=3):
    def net(x, y):
        pred = pt.layers.fc(x, size=1)
        return pt.layers.mean((pred - y) ** 2)

    def reader():
        rng = np.random.RandomState(0)
        for _ in range(steps):
            x = rng.randn(8, 4).astype(np.float32)
            yield x, x.sum(axis=1, keepdims=True)

    losses = []

    def on_event(ev):
        if isinstance(ev, pt.EndStepEvent):
            losses.append(ev.metrics)

    trainer = pt.Trainer(lambda: net, lambda: pt.optimizer.SGD(learning_rate=0.1))
    trainer.train(num_epochs=1, reader=reader, event_handler=on_event)
    return losses


def test_trainer_step_holds_its_children_in_order_without_gaps():
    _train_tiny(steps=3)
    roots = [s for s in tracing.spans() if s.name == "trainer.step"]
    assert len(roots) == 3  # the end-of-epoch wait opened a scope and cancelled it
    for root in roots:
        tree = tracing.spans_for_trace(root.context.trace_id)
        assert tracing.validate_trace(tree) == []
        children = [s for s in tree if s.context.parent_id == root.context.span_id
                    and s.name != "executor.compile"]
        assert [s.name for s in children] == TRAINER_CHILDREN
        # in order and one after the other: no child starts before the last ended
        for a, b in zip(children, children[1:]):
            assert a.t1_us <= b.t0_us
        assert root.t0_us <= children[0].t0_us and children[-1].t1_us <= root.t1_us
    assert [r.attrs["step"] for r in roots] == [0, 1, 2]
    # the compiling call is still found afterwards, inside the step's enqueue
    compile_span = next(s for s in tracing.spans() if s.name == "executor.compile")
    by_id = {s.context.span_id: s for s in tracing.spans()}
    assert by_id[compile_span.context.parent_id].name == "trainer.step_compute"


# ---- the engine iteration, covered from inside -----------------------------


@pytest.fixture(scope="module")
def lm():
    spec = models.get_model("transformer_lm", seq_len=64, vocab=97,
                            d_model=32, d_inner=64, num_heads=4, n_layers=2)
    variables = spec.model.init(0, *spec.synth_batch(2, np.random.RandomState(1)))
    return spec.extra["cfg"], variables


def _engine(lm, **kw):
    cfg, variables = lm
    conf = dict(max_slots=3, page_size=4, max_context=40, prefill_chunk=8)
    conf.update(kw)
    return DecodeEngine(variables, cfg, decode=DecodeConfig(**conf))


def _children(spans, parent, prefix=""):
    return [s for s in sorted(spans, key=lambda s: s.t0_us)
            if s.context.parent_id == parent.context.span_id and s.name.startswith(prefix)]


def test_engine_iteration_spans_and_the_seconds_the_metrics_were_handed(lm):
    engine = _engine(lm)
    handed = []
    record_step = engine.metrics.record_step

    def tapped(active, max_slots, seconds, new_tokens):
        handed.append((active, max_slots, seconds, new_tokens))
        return record_step(active, max_slots, seconds, new_tokens)

    engine.metrics.record_step = tapped
    try:
        prompts = [np.arange(1, 12, dtype=np.int32), np.arange(3, 8, dtype=np.int32)]
        outs = [h.result(timeout=300) for h in [engine.submit(p, 6) for p in prompts]]
        assert all(len(o.tokens) == 6 for o in outs)
    finally:
        engine.close()
    loop = tracing.spans_for_trace(engine._loop_trace.trace_id)
    names = {s.name for s in loop}
    assert {"serving.decode.admit", "serving.decode.step", "serving.decode.publish",
            "serving.decode.prefill", "serving.decode.prefill.wait",
            "serving.decode.model_step", "serving.decode.idle"} <= names
    # the passes of the loop hang under the loop's trace, one after the other
    passes = [s for s in loop if s.context.parent_id == engine._loop_trace.span_id]
    assert {s.name for s in passes} == {"serving.decode.admit", "serving.decode.step",
                                        "serving.decode.publish", "serving.decode.idle"}
    for a, b in zip(passes, passes[1:]):
        assert a.t1_us <= b.t0_us
    # a model step holds pack, dispatch, wait and land, in that order, and
    # carries the counts at the boundary and the very float the metrics got.
    # Between dispatch and wait, with the step enqueued, it enqueues the
    # iteration's chunk behind the step and then reads the first token of a
    # last chunk that an earlier iteration enqueued
    steps = [s for s in loop if s.name == "serving.decode.model_step"]
    assert [(s.attrs["active"], s.attrs["max_slots"], s.attrs["seconds"], s.attrs["new_tokens"])
            for s in steps] == handed
    by_id = {s.context.span_id: s for s in loop}
    chunk, first_token = "serving.decode.prefill", "serving.decode.prefill.wait"
    part = "serving.decode.model_step.%s"
    for s in steps:
        assert by_id[s.context.parent_id].name == "serving.decode.step"
        names = [c.name for c in _children(loop, s)]
        assert names[:2] == [part % "pack", part % "dispatch"]
        assert names[-2:] == [part % "wait", part % "land"]
        behind = names[2:-2]
        assert set(behind) <= {chunk, first_token}
        assert behind == sorted(behind, key=[chunk, first_token].index)
    # a chunk is enqueued behind its iteration's step, or under the pass where
    # no step went out; it never waits. Every prompt's last chunk, and only
    # that one, has its token waited for: once, in a later step, or at the end
    # of a pass that enqueued no step
    chunks = [s for s in loop if s.name == chunk]
    assert sum(bool(s.attrs["last_chunk"]) for s in chunks) == len(prompts)
    waits = [s for s in loop if s.name == first_token]
    assert len(waits) == len(prompts)
    for s in chunks + waits:
        assert by_id[s.context.parent_id].name in ("serving.decode.model_step", "serving.decode.step")
    assert not any(w.context.parent_id == s.context.span_id for w in waits for s in chunks)
    for w in waits:
        over = by_id[w.context.parent_id]
        last = max((s for s in chunks if s.attrs["last_chunk"] and s.t1_us <= w.t0_us),
                   key=lambda s: s.t1_us)
        # under a step, the chunk is an earlier step's or pass's; under the pass, its own
        assert (last.context.parent_id == over.context.span_id) == (over.name == "serving.decode.step")
    # a request's own tree keeps its chunks, as README documents it
    request_chunks = [s for s in tracing.spans() if s.name == "serving.decode.prefill"
                      and s.context.trace_id != engine._loop_trace.trace_id]
    assert len(request_chunks) == len(chunks)


def _turns(loop):
    """[(the step span before, this turn's step span, its model step)] of the
    loop's turns that held a model step and have a turn before them."""
    steps = sorted((s for s in loop if s.name == "serving.decode.step"), key=lambda s: s.t1_us)
    model = {s.context.parent_id: s for s in loop if s.name == "serving.decode.model_step"}
    return [(a, b, model[b.context.span_id]) for a, b in zip(steps, steps[1:])
            if b.context.span_id in model]


def test_a_turns_step_span_carries_its_cpu_and_its_bookings_seconds(lm):
    """The span that ends a turn says what the turn cost the loop thread:
    ``cpu_seconds`` (its CPU clock since the step span before closed) and
    ``telemetry_seconds`` (the stretches the engine's bookings are gathered
    into). Both lie inside the turn; the bookings inside its host part."""
    engine = _engine(lm)
    handed = []
    record_step = engine.metrics.record_step

    def tapped(active, max_slots, seconds, new_tokens):
        handed.append(seconds)
        return record_step(active, max_slots, seconds, new_tokens)

    engine.metrics.record_step = tapped
    try:
        prompts = [np.arange(1, 12, dtype=np.int32), np.arange(3, 8, dtype=np.int32)]
        for h in [engine.submit(p, 8) for p in prompts]:
            h.result(timeout=300)
    finally:
        engine.close()
    loop = tracing.spans_for_trace(engine._loop_trace.trace_id)
    turns = _turns(loop)
    assert len(turns) >= 6
    # the tree under a turn is what it was: the very float the metrics got
    assert [m.attrs["seconds"] for _, _, m in turns] == handed[-len(turns):]
    waits = [s for s in loop if s.name.endswith(".wait")]
    for before, step, model in turns:
        cpu, booked = step.attrs["cpu_seconds"], step.attrs["telemetry_seconds"]
        turn = (step.t1_us - before.t1_us) / 1e6
        waited = sum(w.t1_us - w.t0_us for w in waits
                     if before.t1_us <= w.t0_us and w.t1_us <= step.t1_us) / 1e6
        assert 0.0 < booked <= turn - waited
        assert 0.0 <= cpu <= turn + 0.005  # two clocks, read a line apart
        assert [c.name.rsplit(".", 1)[1] for c in _children(loop, model)
                if c.name.startswith("serving.decode.model_step.")] == [
            "pack", "dispatch", "wait", "land"]
    # every committed step span after the engine's first has the account, a
    # pass that only enqueued a chunk too; the first has no turn before it
    steps = sorted((s for s in loop if s.name == "serving.decode.step"), key=lambda s: s.t1_us)
    assert "cpu_seconds" not in steps[0].attrs
    assert all({"cpu_seconds", "telemetry_seconds"} <= set(s.attrs) for s in steps[1:])


def test_with_tracing_disabled_the_turn_reads_neither_clock(lm, monkeypatch):
    from paddle_tpu.serving import decode as decode_mod

    reads = []
    real = decode_mod.time.thread_time
    monkeypatch.setattr(decode_mod.time, "thread_time", lambda: reads.append(1) or real())
    tracing.disable_tracing()
    engine = _engine(lm)
    try:
        out = engine.submit(np.arange(1, 12, dtype=np.int32), 6).result(timeout=300)
        assert len(out.tokens) == 6
        assert engine._clock() is None
    finally:
        engine.close()
    assert reads == [] and engine._turn_telemetry == 0.0 and engine._turn_cpu0 is None
    assert tracing.spans() == []
    # switched on in mid-run, the first turn only sets the clock: no account
    # is ever read against a stretch that was not timed
    tracing.enable_tracing()
    engine = _engine(lm)
    try:
        engine.submit(np.arange(1, 12, dtype=np.int32), 6).result(timeout=300)
    finally:
        engine.close()
    assert reads and _turns(tracing.spans_for_trace(engine._loop_trace.trace_id))


def test_a_paged_step_carries_the_pages_its_slots_hold_and_the_table_it_was_handed(lm):
    """What the gather reads and what is live, on the step's span: the sum
    over the decoding slots of ``pos // page_size + 1``, the ``S * P`` pages
    of the tables, and whether the compiled step attends through the
    ``paged_attend_step`` kernel (never on a CPU)."""
    engine = _engine(lm)
    handed, step = [], engine._step

    def tapped(params, tokens, positions, *rest):
        handed.append(np.asarray(positions))
        return step(params, tokens, positions, *rest)

    engine._step = tapped
    try:
        prompts = [np.arange(1, 12, dtype=np.int32), np.arange(3, 8, dtype=np.int32)]
        for h in [engine.submit(p, 6) for p in prompts]:
            h.result(timeout=300)
    finally:
        engine.close()
    steps = [s for s in tracing.spans_for_trace(engine._loop_trace.trace_id)
             if s.name == "serving.decode.model_step"]
    assert len(steps) == len(handed) >= 6
    # a decoding slot's position is past its prompt, an idle slot's is 0
    assert [s.attrs["attend_live_pages"] for s in steps] == [
        int((pos[pos > 0] // 4 + 1).sum()) for pos in handed]
    assert max(s.attrs["attend_live_pages"] for s in steps) >= 2 + 4
    assert {s.attrs["attend_table_pages"] for s in steps} == {3 * 10}
    assert {s.attrs["attend_kernel"] for s in steps} == {0}
    # one page over every plane and both arrays: what a live page costs a step
    per_token = sum(c.shape[0] * c.shape[3] * c.dtype.itemsize for c in engine._cache)
    assert {s.attrs["attend_page_bytes"] for s in steps} == {4 * per_token}


def test_a_hybrid_step_and_chunk_carry_the_states_they_moved_beside_the_pages():
    """A model with pages and states (``models/hybrid_ssm_lm.py``): the step's
    span has the attention layers' ``attend_*`` as a paged step has, and from
    the program's extras the slots whose SSM states it updated, the Mamba-2
    layers and the bytes of state that crossed HBM for them; a chunk's span
    has the same for its one slot. Gauges, once: the states' bytes beside the
    pages'."""
    from paddle_tpu import models
    from paddle_tpu.observability import metrics as obs_metrics

    spec = models.get_model(
        "hybrid_ssm_lm", seq_len=16, vocab=97, d_model=32, d_inner=64, num_heads=2,
        num_kv_heads=1, head_dim=16, ssm_heads=2, ssm_head_dim=16, ssm_state=8, ssm_chunk=4,
        layer_types=("mamba", "attention", "mamba"), param_dtype="float32",
        compute_dtype="float32")
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    engine = _engine((spec.extra["cfg"], spec.model.init(0, ids, labels)))
    try:
        for h in [engine.submit(np.arange(1, 12, dtype=np.int32), 6),
                  engine.submit(np.arange(3, 8, dtype=np.int32), 6)]:
            h.result(timeout=300)
    finally:
        engine.close()
    spans = tracing.spans_for_trace(engine._loop_trace.trace_id)
    steps = [s for s in spans if s.name == "serving.decode.model_step"]
    chunks = [s for s in spans if s.name == "serving.decode.prefill"]
    assert len(steps) >= 6 and len(chunks) >= 3
    a_slot_layer = 4 * 8 * 32  # a state [N, d_ssm] of float32
    for s in steps:
        assert s.attrs["ssm_layers"] == 2 and 1 <= s.attrs["ssm_active_slots"] == s.attrs["active"]
        assert s.attrs["ssm_state_bytes_moved"] == 2 * s.attrs["active"] * 2 * a_slot_layer
        assert s.attrs["attend_live_pages"] >= s.attrs["active"] and s.attrs["attend_kernel"] == 0
    for s in chunks:
        assert (s.attrs["ssm_active_slots"], s.attrs["ssm_layers"]) == (1, 2)
        assert s.attrs["ssm_state_bytes_moved"] == 2 * 2 * a_slot_layer
    reg, label = obs_metrics.default_registry(), {"engine": engine.metrics.engine_label}
    get = lambda name: reg.get(f"serving.decode.{name}", label, default=None)
    assert get("state_bytes") == 2 * 3 * (a_slot_layer + 3 * 48 * 4)  # states and tails
    assert get("cache_bytes_per_token") == 2 * 16 * 4 and get("pages_free") is not None
    assert get("ssm.layers") == 2 and get("ssm.state_bytes_a_slot") == 2 * a_slot_layer


def test_a_hybrid_expert_step_and_chunk_carry_the_ssms_and_the_experts_counts_together():
    """A model with pages, states and expert layers (``models/hybrid_moe_lm.py``):
    both programs return two extras, ``active`` and ``expert_load``, and the
    span of a call carries the SSM's counts and the expert layers' side by
    side, beside the attention layer's ``attend_*``; the four gauges, once;
    the lowered programs sit under the named scopes the trace is read by."""
    from paddle_tpu import models
    from paddle_tpu.models import hybrid_moe_lm as hmm
    from paddle_tpu.observability import metrics as obs_metrics

    spec = models.get_model(
        "hybrid_moe_lm", seq_len=16, vocab=97, d_model=32, pattern="M*EME", num_heads=2,
        num_kv_heads=1, head_dim=16, ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
        ssm_chunk=4, num_experts=8, experts_per_token=2, experts_held=(2, 4), moe_latent=16,
        moe_d_inner=24, shared_d_inner=48, param_dtype="float32", compute_dtype="float32")
    cfg = spec.extra["cfg"]
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    engine = _engine((cfg, variables))
    try:
        for h in [engine.submit(np.arange(1, 12, dtype=np.int32), 6),
                  engine.submit(np.arange(3, 8, dtype=np.int32), 6)]:
            h.result(timeout=300)
    finally:
        engine.close()
    spans = tracing.spans_for_trace(engine._loop_trace.trace_id)
    steps = [s for s in spans if s.name == "serving.decode.model_step"]
    chunks = [s for s in spans if s.name == "serving.decode.prefill"]
    assert len(steps) >= 6 and len(chunks) >= 3
    a_slot_layer = 4 * 8 * 64  # a state [N, d_ssm] of float32
    for s in steps:
        assert s.attrs["ssm_layers"] == 2 and 1 <= s.attrs["ssm_active_slots"] == s.attrs["active"]
        assert s.attrs["ssm_state_bytes_moved"] == 2 * s.attrs["active"] * 2 * a_slot_layer
        assert s.attrs["attend_live_pages"] >= s.attrs["active"]
        # 2 expert layers, 2 of 8 experts a token, 4 held: at most every pair lands here
        assert 0 <= s.attrs["moe_pairs"] <= 2 * 2 * s.attrs["active"]
        assert s.attrs["moe_experts_hit"] <= min(s.attrs["moe_pairs"], 2 * 4)
        assert s.attrs["moe_max_load"] <= s.attrs["active"]
    assert sum(s.attrs["moe_pairs"] for s in steps) > 0
    for s in chunks:
        assert (s.attrs["ssm_active_slots"], s.attrs["ssm_layers"]) == (1, 2)
        assert 0 < s.attrs["moe_pairs"] <= 2 * 2 * 8 and s.attrs["moe_max_load"] <= 8
    reg, label = obs_metrics.default_registry(), {"engine": engine.metrics.engine_label}
    get = lambda name: reg.get(f"serving.decode.{name}", label, default=None)
    assert get("ssm.layers") == 2 and get("ssm.state_bytes_a_slot") == 2 * a_slot_layer
    assert get("moe.experts_held") == 4 and get("moe.router_width") == 8
    slots, page, per_slot = 3, 4, 10
    specs = hmm.serving_programs().cache_specs(cfg, max_slots=slots, num_pages=1 + slots * per_slot,
                                               page_size=page, dtype=jnp.float32)
    cache = [jnp.zeros(sp.shape, sp.dtype) for sp in specs]
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    for fn, args in ((hmm.hybrid_moe_decode_step,
                      (i32(slots), i32(slots), (i32(slots, per_slot), i32(slots)))),
                     (hmm.hybrid_moe_prefill_chunk, (i32(8), i32(), i32(), (i32(per_slot), i32())))):
        assert _scopes_missing(
            functools.partial(fn, cfg=cfg, page_size=page), (variables.params, *args, *cache),
            ("embed", "mamba", "attention", "router", "latent_down", "moe_experts", "latent_up",
             "shared_expert", "head", "sampling")) == []


def test_an_idle_engine_adds_nothing_to_the_store(lm):
    engine = _engine(lm, idle_poll_s=0.005)
    try:
        time.sleep(0.2)  # dozens of empty passes
        assert tracing.spans_for_trace(engine._loop_trace.trace_id) == []
    finally:
        engine.close()


def test_verify_step_holds_the_same_four_children(lm):
    cfg, variables = lm
    dspec = models.get_model("transformer_lm", seq_len=64, vocab=97,
                             d_model=16, d_inner=32, num_heads=2, n_layers=1)
    draft = dspec.model.init(1, *dspec.synth_batch(2, np.random.RandomState(9)))
    engine = DecodeEngine(
        variables, cfg,
        decode=DecodeConfig(max_slots=3, page_size=4, max_context=40,
                            prefill_chunk=8, spec_tokens=3),
        draft_variables=draft, draft_cfg=dspec.extra["cfg"])
    try:
        engine.infer(np.arange(1, 9, dtype=np.int32), 8)
    finally:
        engine.close()
    loop = tracing.spans_for_trace(engine._loop_trace.trace_id)
    verifies = [s for s in loop if s.name == "serving.decode.verify"]
    assert verifies and all(s.attrs["seconds"] > 0 for s in verifies)
    for s in verifies:
        assert [c.name.rsplit(".", 1)[1] for c in _children(loop, s)] == [
            "pack", "dispatch", "wait", "dispatch", "wait", "land"]


# ---- one emitter of annotations --------------------------------------------


def test_record_event_is_a_tracing_span_and_a_row_of_the_table(tmp_path):
    prof.enable_profiler()
    try:
        with tracing.start_trace("unit.outer") as outer:
            with prof.record_event("unit.window_one"):
                pass
        table = prof.disable_profiler()
    finally:
        prof.disable_profiler()
    assert table["unit.window_one"]["calls"] == 1
    (span,) = [s for s in tracing.spans() if s.name == "unit.window_one"]
    assert span.context.parent_id == outer.context.span_id
    # the one exporter writes it, with a named thread track for Perfetto
    with open(tracing.export_chrome_trace(str(tmp_path / "t.json"))) as f:
        doc = json.load(f)
    tracing.validate_chrome_trace(doc)
    ev = next(e for e in doc["traceEvents"] if e.get("name") == "unit.window_one")
    assert ev["ph"] == "X" and ev["cat"] == "tracing"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M" and e["tid"] == ev["tid"]]
    assert meta and meta[0]["args"]["name"]


def test_the_table_is_per_window_and_off_between_windows():
    with prof.record_event("unit.before"):
        pass
    prof.enable_profiler()
    with prof.record_event("unit.kept"):
        pass
    prof.reset_profiler()  # drops the window's rows; the spans are tracing's
    with prof.record_event("unit.after_reset"):
        pass
    table = prof.disable_profiler()
    assert set(table) == {"unit.after_reset"}
    assert prof.disable_profiler() == {}  # a closed window starts the next one empty
    assert {s.name for s in tracing.spans()} == {"unit.before", "unit.kept", "unit.after_reset"}
    tracing.reset_tracing()
    assert tracing.spans() == []


def test_core_profiler_keeps_no_span_list_of_its_own():
    for gone in ("_spans", "_thread_names", "_MAX_SPANS", "spans", "thread_names",
                 "export_chrome_trace"):
        assert not hasattr(prof, gone), gone


# ---- device work has names -------------------------------------------------


def _scopes_missing(fn, args, scopes):
    """The scopes no op of the lowered program sits under; under ``grad`` a
    scope reads ``jvp(name)`` and ``transpose(jvp(name))``."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return [s for s in scopes if not re.search(rf'[/(]{s}[/)"]', text)]


def test_lm_train_step_carries_its_scope_names():
    spec = models.get_model("transformer_lm", seq_len=16, vocab=61,
                            d_model=32, d_inner=64, num_heads=4, n_layers=1)
    batch = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, *batch)
    opt = spec.optimizer()
    step = opt.minimize(spec.model)
    assert _scopes_missing(
        step, (variables, opt.create_state(variables.params), *batch),
        ("embed", "attention", "ffn", "head", "loss", "optimizer_update")) == []


def test_nmt_forward_carries_its_scope_names():
    spec = models.get_model("transformer", src_vocab=53, trg_vocab=61, d_model=32,
                            d_inner=64, num_heads=4, n_layers=1, max_len=16, seq_len=16)
    batch = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, *batch)
    assert _scopes_missing(lambda v, *b: spec.model.apply(v, *b)[0], (variables, *batch),
                           ("embed", "attention", "ffn", "head", "loss")) == []


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk", "verify_step"])
def test_paged_steps_carry_their_scope_names(lm, which):
    from paddle_tpu.models import transformer_lm as tlm

    cfg, variables = lm
    slots, page, per_slot = 3, 4, 10
    pages = jnp.zeros(tlm.paged_cache_shape(cfg, 1 + slots * per_slot, page), jnp.float32)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    if which == "decode_step":
        fn, args = tlm.paged_decode_step, (i32(slots), i32(slots), i32(slots, per_slot))
    elif which == "prefill_chunk":
        fn, args = tlm.paged_prefill_chunk, (i32(8), i32(), i32(), i32(per_slot))
    else:
        fn, args = tlm.paged_verify_step, (i32(slots, 4), i32(slots), i32(slots, per_slot))
    assert _scopes_missing(
        functools.partial(fn, cfg=cfg, page_size=page), (variables.params, *args, pages, pages),
        ("embed", "attention", "page_write", "ffn", "head", "sampling")) == []


# ---- the tool that shares idle gaps out to the program's spans -------------


def _span_report():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "tools", "span_report.py")
    spec = importlib.util.spec_from_file_location("span_report", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_loop_turns_reads_a_turn_and_its_host_part_from_the_spans(lm):
    """The tool's untraced twin of ``loop_iteration_ms`` and ``loop_host_ms``:
    a turn that held a model step, from the end of the turn before, and that
    less the ``.wait`` spans inside it."""
    engine = _engine(lm)
    try:
        engine.submit(np.arange(1, 12, dtype=np.int32), 8).result(timeout=300)
    finally:
        engine.close()
    spans = tracing.spans_for_trace(engine._loop_trace.trace_id)
    steps = [s for s in spans if s.name == "serving.decode.model_step"]
    loop = _span_report().loop_turns(spans)
    assert len(steps) - 1 <= loop["turns"] <= len(steps)  # the first has no turn before it
    assert 0 < loop["host_ms_p50"] < loop["turn_ms_p50"] <= loop["turn_ms_p95"]
    assert _span_report().loop_turns([s for s in spans if s.name != "serving.decode.step"]) == {}


def test_loop_turns_shares_a_turn_out_to_its_phases_and_reads_its_account(lm):
    """``loop:``'s phases are the turn's moments under the innermost span, so
    they add up to the turn; ``dispatch_ms`` is the step's dispatch and the
    chunk's enqueue; CPU, off-CPU and bookings come from the step span."""
    engine = _engine(lm)
    try:
        for h in [engine.submit(np.arange(1, 1 + n, dtype=np.int32), 8) for n in (11, 5)]:
            h.result(timeout=300)
    finally:
        engine.close()
    tool = _span_report()
    spans = tracing.spans_for_trace(engine._loop_trace.trace_id)
    loop = tool.loop_turns(spans)
    assert set(loop["phase_ms"]) == {name for name, _ in tool.LOOP_PHASES}
    assert all(0.0 <= p50 <= p95 for p50, p95 in loop["phase_ms"].values())
    one = tool.loop_turns([s for s in spans if s.t1_us <= sorted(
        (t for t in spans if t.name == "serving.decode.step"), key=lambda t: t.t1_us)[2].t1_us])
    assert one["turns"] == 1  # the engine's first turn has none before it, the second no account
    assert sum(p50 for p50, _ in one["phase_ms"].values()) == pytest.approx(one["turn_ms_p50"])
    assert one["dispatch_ms_p50"] == pytest.approx(
        one["phase_ms"]["dispatch"][0] + one["phase_ms"]["chunk_enqueue"][0])
    assert 0.0 < loop["telemetry_ms_p50"] <= loop["host_ms_p95"]
    assert 0.0 <= loop["offcpu_ms"] <= loop["host_ms_p50"] and loop["spans_p50"] >= 8
    assert loop["cpu_ms"] > 0.0 and loop["cpu_share"] > 0.0
    assert loop["offcpu_ms"] == pytest.approx(
        loop["host_ms_p50"] * max(0.0, 1.0 - loop["cpu_share"]))
    # a program from before the account: the phases still read, the rest is absent
    for s in spans:
        s.attrs.pop("cpu_seconds", None)
    assert "cpu_ms" not in tool.loop_turns(spans) and "phase_ms" in tool.loop_turns(spans)


def test_span_cost_times_a_span_on_and_off_and_the_three_writes():
    """What ``--span-cost`` prints (the costs are the chip runs' to report:
    no number is held to anything here), and that it leaves tracing as it
    found it."""
    cost = _span_report().span_cost(calls=200, rounds=2)
    assert set(cost) == {"calls", "rounds", "span_on_us", "span_off_us", "counter_us",
                         "gauge_us", "histogram_us"}
    assert all(v > 0 for v in cost.values())
    assert tracing.tracing_enabled() and tracing.spans() == []


def test_the_report_shares_out_idle_gaps_as_the_harness_does(tmp_path, capsys):
    """``tools/span_report.py::report`` holds no reduction of its own: on
    device operations with gaps of every size and spans that nest, overlap a
    gap's edge, span several gaps or touch none, what it writes is
    ``trace_reduce.idle_gaps`` over the program's spans cut to their
    innermost pieces, and over the benchmark's annotations as they are."""
    from benchmarks import trace_reduce

    tool = _span_report()
    rng = np.random.RandomState(3)
    at, device = 0, []
    for i in range(400):
        at += int(rng.choice([0, 1, 3, 50, 4000]))
        device.append((f"op{i % 7}", at, int(rng.randint(1, 300))))
        at += device[-1][2]
    names = ["trainer.h0", "serving.h1", "executor.h2", "serving.h3", "bench.h4"]
    host = [(names[i % 5], int(rng.randint(0, at)), int(rng.choice([1, 40, 900, 60000])))
            for i in range(300)]

    def load(path, host_prefix="bench."):
        return {"devices": {"/device:TPU:0": device},
                "host": [e for e in host if e[0].startswith(host_prefix)]}

    trace = tmp_path / "t.xplane.pb"
    trace.write_bytes(b"")  # no plane: the device's names are read off the file itself
    tool.report(str(trace), "a.cell", str(tmp_path / "out"), load)
    capsys.readouterr()
    with open(tmp_path / "out" / "a.cell.json") as f:
        doc = json.load(f)
    spans = [e for prefix in tool.PROGRAM_PREFIXES for e in host if e[0].startswith(prefix)]
    want = trace_reduce.idle_gaps(device, tool.innermost(spans), top=40)
    assert doc["idle_gaps_by_program_span"] == want
    assert {n.replace(" (self)", "") for n, _ in want} == set(names[:4]) | {"unattributed"}
    assert doc["idle_gaps_by_bench_annotation"] == trace_reduce.idle_gaps(
        device, [e for e in host if e[0] == "bench.h4"], top=10)
    assert doc["busy_s"] == trace_reduce.busy_ns(device) / 1e9
    assert not hasattr(tool, "idle_gaps")


def test_a_run_of_the_tool_rebinds_the_harness_s_trace_load_and_nothing_else(monkeypatch, capsys):
    """While the harness runs, ``trace_reduce.load_xplane`` is the tool's
    (it reads the trace before the harness deletes it) and the reduction is
    the harness's own; afterwards the module is as it was."""
    from benchmarks import harness, trace_reduce

    tool = _span_report()
    before = dict(vars(trace_reduce))
    during = []

    def run(argv, t_start):
        during.append({k for k, v in vars(trace_reduce).items() if before.get(k) is not v})
        return 0

    monkeypatch.setattr(harness, "main", run)
    assert tool.main(["--workload", "a.cell", "--seed", "1", "--seconds", "1"]) == 0
    capsys.readouterr()
    assert during == [{"load_xplane"}]
    assert all(before[k] is v for k, v in vars(trace_reduce).items())


def test_innermost_gives_every_moment_to_one_span():
    tool = _span_report()
    events = [("step", 0, 100), ("wait", 10, 20), ("model", 40, 50), ("model.pack", 40, 10),
              ("model.wait", 55, 30), ("idle", 120, 5)]
    pieces = sorted(tool.innermost(events), key=lambda e: e[1])
    assert pieces == [
        ("step (self)", 0, 10), ("wait", 10, 20), ("step (self)", 30, 10),
        ("model.pack", 40, 10), ("model (self)", 50, 5), ("model.wait", 55, 30),
        ("model (self)", 85, 5), ("step (self)", 90, 10), ("idle", 120, 5)]
    assert sum(d for _, _, d in pieces) == 105  # the union, each moment once
    table = {r["name"]: r for r in tool.span_table(events, pieces)}
    assert table["step"]["own_s"] == pytest.approx(30e-9)
    assert table["model"]["total_s"] == pytest.approx(50e-9)
    assert table["model"]["own_s"] == pytest.approx(10e-9)
