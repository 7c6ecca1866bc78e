"""The three per-layer metrics that read the serving turn's account
(``loop_dispatch_ms``, ``loop_telemetry_ms``, ``loop_offcpu_ms``) and the one
helper that finds the window's turns for them (``benchmarks/loop_spans.py``):
on hand-made span stores, on a tiny engine's own spans beside
``loop_iteration_ms.window_iterations`` turn for turn, and in the line of a
traced tiny run. No number from here is a device metric, and none is a time
held to anything."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, loop_spans, tiny  # noqa: E402
from paddle_tpu import models, tracing  # noqa: E402
from paddle_tpu.serving import DecodeConfig, DecodeEngine  # noqa: E402

READERS = ("loop_dispatch_ms", "loop_telemetry_ms", "loop_offcpu_ms")
SERVE_CELLS = ["lm_big.serve_long", "brumby_14b.serve_docs16", "sarvam_105b.serve_docs32",
               "ouro_2_6b.serve_reason8"]


@pytest.fixture(autouse=True)
def _clean_store():
    tracing.enable_tracing()
    tracing.reset_tracing()
    yield
    tracing.reset_tracing()


def read(name, **counters):
    return harness.load_reader(name).read({"counters": counters})


# -- hand-made stores --------------------------------------------------------


def put_turn(loop, t0, k, wait_ms, account=None, chunk_ms=2.0, first_token_ms=0.0):
    """One pass of the loop from ``t0``: 0.5 ms of the last turn's publishing,
    1 ms of admission, then a step span holding a model step (1 ms pack, 3 ms
    dispatch, ``chunk_ms`` of the chunk's enqueue, a first token's wait, the
    step's wait, 1 ms land). ``account`` is (cpu, telemetry) seconds for the
    step span. Returns (end of the step span, the seconds handed on)."""
    t = t0
    for name, ms in (("serving.decode.publish", 0.5), ("serving.decode.admit", 1.0)):
        tracing.record_span(name, t, t + ms / 1e3, parent=loop)
        t += ms / 1e3
    step, s0 = loop.child(), t
    model, m0 = step.child(), t
    parts = [("model_step.pack", 1.0), ("model_step.dispatch", 3.0), ("prefill", chunk_ms),
             ("prefill.wait", first_token_ms), ("model_step.wait", wait_ms), ("model_step.land", 1.0)]
    for part, ms in parts:
        if ms:
            tracing.record_span("serving.decode." + part, t, t + ms / 1e3, parent=model)
        t += ms / 1e3
    seconds = (4.0 + wait_ms) / 1e3 + k * 1e-9  # no two alike
    tracing.record_span("serving.decode.model_step", m0, t, context=model, active=2,
                        max_slots=3, new_tokens=2, seconds=seconds)
    attrs = {} if account is None else {"cpu_seconds": account[0], "telemetry_seconds": account[1]}
    tracing.record_span("serving.decode.step", s0, t, context=step, active=2, **attrs)
    return t, seconds


def put_run(accounts=((0.0100, 0.0009), (0.0000, 0.0004), (0.0100, 0.0006)), attrs=True):
    """Ramp, a window of three turns and the drain. A turn's host part is
    0.5 + 1 + 1 + 3 + 2 + 1 = 8.5 ms, whatever it waited."""
    loop = tracing.SpanContext.new_trace()
    t, _ = put_turn(loop, 5.0, 1, 700.0, (0.5, 0.5) if attrs else None)
    window = []
    for k, (wait, first) in enumerate([(40.0, 0.0), (50.0, 5.0), (60.0, 0.0)]):
        t, seconds = put_turn(loop, t, 10 + k, wait, accounts[k] if attrs else None,
                              chunk_ms=2.0 + k, first_token_ms=first)
        window.append(seconds)
    for k in range(3):  # the engine keeps turning while the harness reads its trace
        t, _ = put_turn(loop, t, 20 + k, 5.0, (0.3, 0.3) if attrs else None)
    return window


def test_the_three_readers_take_the_median_over_the_windows_turns():
    window = put_run()
    # dispatch 3 ms and the chunk's enqueue 2, 3, 4 ms; the waits are not in it
    assert read("loop_dispatch_ms", step_seconds=window) == pytest.approx(6.0, rel=1e-6)
    assert read("loop_telemetry_ms", step_seconds=window) == pytest.approx(0.6, rel=1e-6)
    # a clock that ticks every 10 ms: the turns read 10, 0, 10 ms of CPU in host
    # parts of 8.5, 9.5, 10.5; over the window the thread ran 20 of 28.5 ms, and
    # the median host part is shared out by that
    assert read("loop_offcpu_ms", step_seconds=window) == pytest.approx(
        9.5 * (1 - 20 / 28.5), rel=1e-6)
    turns = loop_spans.window_turns({"counters": {"step_seconds": window}})
    assert [round(1e3 * t.host_seconds, 6) for t in turns] == [8.5, 9.5, 10.5]
    assert [round(1e3 * t.wait_seconds, 6) for t in turns] == [40.0, 55.0, 60.0]
    assert [t.model_step.attrs["seconds"] for t in turns] == window
    # the ramp's and the drain's accounts (0.5, 0.3) are not the window's
    assert all(t.step.attrs["cpu_seconds"] not in (0.5, 0.3) for t in turns)
    tracing.reset_tracing()  # more CPU than host time (waits that spin): floored at 0
    window = put_run(accounts=((0.02, 0.001),) * 3)
    assert read("loop_offcpu_ms", step_seconds=window) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_window_that_cannot_be_found_reads_none(name):
    window = put_run()
    assert read(name, step_seconds=window[:2] + [window[2] + 1e-9]) is None
    assert read(name, step_seconds=[window[1], window[0]]) is None  # out of order
    assert read(name, step_seconds=[]) is None
    assert read(name) is None
    put_run()  # a second engine's loop with the same seconds: which one?
    assert read(name, step_seconds=window) is None
    tracing.reset_tracing()
    loop = tracing.SpanContext.new_trace()
    _, seconds = put_turn(loop, 5.0, 1, 40.0, (0.1, 0.1))
    assert read(name, step_seconds=[seconds]) is None  # no turn before it to measure from


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_account_reads_none_and_does_not_raise(name):
    """The parent commit's engine: the same spans, no ``cpu_seconds`` and no
    ``telemetry_seconds`` on ``serving.decode.step``."""
    window = put_run(attrs=False)
    if name == "loop_dispatch_ms":  # its two spans are older than this PR
        assert read(name, step_seconds=window) == pytest.approx(6.0, rel=1e-6)
    else:
        assert read(name, step_seconds=window) is None


# -- a tiny engine's own spans -----------------------------------------------


@pytest.fixture(scope="module")
def lm():
    spec = models.get_model("transformer_lm", seq_len=64, vocab=97,
                            d_model=32, d_inner=64, num_heads=4, n_layers=2)
    variables = spec.model.init(0, *spec.synth_batch(2, np.random.RandomState(1)))
    return spec.extra["cfg"], variables


def test_loop_spans_finds_the_window_turn_for_turn_as_loop_iteration_ms_does(lm):
    cfg, variables = lm
    engine = DecodeEngine(variables, cfg, decode=DecodeConfig(
        max_slots=3, page_size=4, max_context=40, prefill_chunk=8))
    handed = []
    record_step = engine.metrics.record_step

    def tapped(active, max_slots, seconds, new_tokens):
        handed.append(seconds)
        return record_step(active, max_slots, seconds, new_tokens)

    engine.metrics.record_step = tapped
    try:
        prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (11, 5, 19)]
        for h in [engine.submit(p, 9) for p in prompts]:
            h.result(timeout=300)
    finally:
        engine.close()
    view = {"counters": {"step_seconds": handed[2:-2]}}  # a window inside the run
    old = harness.load_reader("loop_iteration_ms").window_iterations(view)
    turns = loop_spans.window_turns(view)
    assert len(turns) == len(old) == len(handed) - 4 >= 5
    for turn, (seconds, blocked) in zip(turns, old):
        assert turn.seconds == pytest.approx(seconds, abs=1e-12)
        assert turn.wait_seconds == pytest.approx(blocked, abs=1e-12)
    # dispatch and bookings lie inside the turn's host part, in every turn
    # (they are not summed: the chunk's span holds one booking, and a thread
    # can lose the CPU inside any phase); the off-CPU reading is a share of
    # the median host part, so it cannot pass ``loop_host_ms``
    for turn in turns:
        host = turn.host_seconds
        assert 0.0 < turn.seconds_in("serving.decode.model_step.dispatch",
                                     "serving.decode.prefill") <= host
        assert 0.0 < turn.step.attrs["telemetry_seconds"] <= host
        assert 0.0 <= turn.step.attrs["cpu_seconds"] <= turn.seconds + 0.005
        assert turn.model_step.context.parent_id == turn.step.context.span_id
        assert all(turn.t0_us <= s.t0_us and s.t1_us <= turn.step.t1_us for s in turn.inside)
    for name in READERS:
        assert read(name, step_seconds=handed[2:-2]) >= 0.0
        assert read(name, step_seconds=handed[2:-2]) <= read("loop_host_ms",
                                                             step_seconds=handed[2:-2])


# -- the manifest, and the line of a traced tiny run --------------------------


def test_each_manifest_entry_has_its_file_and_its_four_cells():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    # by name and relative order: a later PR appends its readers after these
    assert [m["name"] for m in manifest["per_layer"] if m["name"] in READERS] == list(READERS)
    host = entries["loop_host_ms"]
    for name in READERS:
        m = entries[name]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            "unit": "ms", "better": "lower", "source": "program_span",
            "layer": host["layer"], "moves": "tpot_p95_ms"}
        # the four serve cells of PR 37's benchmark (lm_big's is serve_long since PR 41),
        # and whatever serve cell a later PR adds, as the host part's entry has them
        assert m["workloads"] == host["workloads"] and m["workloads"][:4] == SERVE_CELLS
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))


def test_a_traced_tiny_serve_run_prints_the_three_beside_the_host_part(tmp_path):
    root = tiny.make_root(str(tmp_path))
    loaded = harness.load_cell("lm_tiny.serve_closed", root)
    line = harness.execute(loaded, jax.devices()[:1], 2**31 + 31, 0.5, True, time.perf_counter())
    assert line["correct"] is True
    got = line["metrics"]
    for name in READERS:
        assert got[name]["unit"] == "ms"
        assert 0.0 <= got[name]["value"] <= got["loop_host_ms"]["value"]
    assert got["loop_dispatch_ms"]["value"] > 0 and got["loop_telemetry_ms"]["value"] > 0
