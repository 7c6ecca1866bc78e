"""The five per-layer metrics that read the program's own spans: each reader on
hand-made span stores (the window found without the harness's help, and None
where it cannot be found), and traced tiny runs whose lines hold all five, the
CPU rehearsal of the chip run. No number from here is a device metric."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, tiny  # noqa: E402
from paddle_tpu import tracing  # noqa: E402

TRAIN_READERS = ("trainer_host_gap_ms", "trainer_dispatch_ms", "trainer_telemetry_ms")
SERVE_READERS = ("loop_iteration_ms", "loop_host_ms")


@pytest.fixture(autouse=True)
def _clean_store():
    tracing.enable_tracing()
    tracing.reset_tracing()
    yield
    tracing.reset_tracing()


def read(name, **counters):
    return harness.load_reader(name).read({"counters": counters})


# -- hand-made stores --------------------------------------------------------

CHILDREN = ("trainer.data_wait", "trainer.begin_event", "trainer.h2d", "trainer.step_compute",
            "trainer.fetch", "trainer.commit", "trainer.record_step", "trainer.end_event",
            "trainer.checkpoint")


def put_step(t0, ms, leave_out=()):
    """One ``trainer.step`` trace whose children last ``ms`` milliseconds each,
    one after the other, then 0.25 ms of the step that no child covers."""
    root = tracing.SpanContext.new_trace()
    t = t0
    for name, dur in zip(CHILDREN, ms):
        if name not in leave_out:
            tracing.record_span(name, t, t + dur / 1e3, parent=root)
        t += dur / 1e3
    t += 0.25e-3
    tracing.record_span("trainer.step", t0, t, context=root)
    return t


#          wait begin h2d enqueue fetch commit record end checkpoint
WARM = (50.0, 9.0, 9.0, 9.0, 900.0, 9.0, 9.0, 9.0, 9.0)
WINDOW = [(0.5, 0.25, 2.0, 6.0, 100.0, 2.0, 3.0, 1.0, 0.25),
          (0.5, 0.25, 4.0, 8.0, 90.0, 2.0, 5.0, 1.0, 0.25),
          (0.5, 0.25, 3.0, 7.0, 95.0, 2.0, 4.0, 1.0, 0.25)]


def put_training_run(window=WINDOW, **kw):
    t = 10.0
    for ms in [WARM, WARM] + list(window):
        t = put_step(t, ms, **kw) + 1e-4


@pytest.mark.parametrize("name, expected", [
    # the step less enqueue..fetch: wait + begin + h2d + commit + record + end + checkpoint + 0.25
    ("trainer_host_gap_ms", 0.5 + 0.25 + 3.0 + 2.0 + 4.0 + 1.0 + 0.25 + 0.25),
    ("trainer_dispatch_ms", 3.0 + 7.0),
    ("trainer_telemetry_ms", 4.0),
])
def test_train_readers_take_the_median_over_the_windows_steps(name, expected):
    put_training_run()
    assert read(name, steps=3) == pytest.approx(expected, rel=1e-6)
    # the warm steps are not the window's: counting them in moves the median
    assert read(name, steps=5) != pytest.approx(expected, rel=1e-6)


@pytest.mark.parametrize("name", TRAIN_READERS)
def test_a_store_shorter_than_the_window_reads_none(name):
    put_training_run()
    assert read(name, steps=6) is None
    assert read(name) is None  # no count of steps to go by
    tracing.reset_tracing()
    assert read(name, steps=1) is None


@pytest.mark.parametrize("name", TRAIN_READERS)
def test_a_program_without_the_new_spans_reads_none_and_does_not_raise(name):
    """The parent commit's Trainer: ``trainer.step`` roots that hold no
    ``trainer.fetch`` and no ``trainer.record_step``."""
    put_training_run(leave_out=("trainer.begin_event", "trainer.fetch", "trainer.commit",
                                "trainer.record_step", "trainer.end_event"))
    if name == "trainer_dispatch_ms":  # h2d and step_compute are older than this PR
        assert read(name, steps=3) == pytest.approx(10.0, rel=1e-6)
    else:
        assert read(name, steps=3) is None


def put_iteration(loop, t0, step_ms, wait_ms, chunk_wait_ms=0.0, model_step=True):
    """One pass of the engine's loop from ``t0``: 1 ms of admission, a step
    span holding a prefill chunk (2 ms, then its wait) and a model step
    (1 ms pack, 2 ms dispatch, the wait, 1 ms land), 0.5 ms of publishing.
    Returns (end of the pass, end of its step span, the seconds handed on)."""
    t = t0
    tracing.record_span("serving.decode.admit", t, t + 1e-3, parent=loop)
    t += 1e-3
    step = loop.child()
    s0 = t
    chunk = step.child()
    tracing.record_span("serving.decode.prefill.wait", t + 2e-3, t + 2e-3 + chunk_wait_ms / 1e3,
                        parent=chunk)
    tracing.record_span("serving.decode.prefill", t, t + 2e-3 + chunk_wait_ms / 1e3,
                        context=chunk, last_chunk=bool(chunk_wait_ms))
    t += 2e-3 + chunk_wait_ms / 1e3
    seconds = None
    if model_step:
        ms = step.child()
        m0 = t
        for part, dur in (("pack", 1.0), ("dispatch", 2.0), ("wait", wait_ms), ("land", 1.0)):
            tracing.record_span("serving.decode.model_step." + part, t, t + dur / 1e3, parent=ms)
            t += dur / 1e3
        seconds = (2.0 + wait_ms) / 1e3 + step_ms * 1e-9  # no two alike
        tracing.record_span("serving.decode.model_step", m0, t, context=ms, active=2,
                            max_slots=3, new_tokens=2, seconds=seconds)
    tracing.record_span("serving.decode.step", s0, t, context=step, active=2)
    s1 = t
    tracing.record_span("serving.decode.publish", t, t + 0.5e-3, parent=loop)
    return t + 0.5e-3, s1, seconds


def put_serving_run():
    """Ramp, window and drain: returns the window's ``step_seconds`` and the
    (turn, blocked) milliseconds a reader should find for each."""
    loop = tracing.SpanContext.new_trace()
    t, s_prev, _ = put_iteration(loop, 5.0, 1, 700.0)  # the ramp
    t, s_prev, _ = put_iteration(loop, t, 2, 700.0, model_step=False)
    window, expected = [], []
    for k, (wait, chunk_wait) in enumerate([(40.0, 0.0), (50.0, 5.0), (60.0, 0.0)]):
        t, s1, seconds = put_iteration(loop, t, 10 + k, wait, chunk_wait)
        window.append(seconds)
        expected.append((1e3 * (s1 - s_prev), wait + chunk_wait))
        s_prev = s1
    for k in range(4):  # the engine keeps turning while the harness reads its trace
        t, _, _ = put_iteration(loop, t, 20 + k, 5.0)
    return window, expected


def test_serve_readers_find_the_window_by_its_seconds_not_by_counting_from_the_end():
    window, expected = put_serving_run()
    turns = sorted(t for t, _ in expected)
    hosts = sorted(t - w for t, w in expected)
    assert read("loop_iteration_ms", step_seconds=window) == pytest.approx(turns[1], rel=1e-6)
    assert read("loop_host_ms", step_seconds=window) == pytest.approx(hosts[1], rel=1e-6)
    # a turn is admission + chunk + model step + the publishing of the turn before
    assert turns[1] == pytest.approx(0.5 + 1.0 + 2.0 + 1.0 + 2.0 + 50.0 + 5.0 + 1.0, rel=1e-6)
    assert hosts[1] == pytest.approx(7.5, rel=1e-6)


@pytest.mark.parametrize("name", SERVE_READERS)
def test_seconds_that_match_nowhere_or_twice_read_none(name):
    window, _ = put_serving_run()
    assert read(name, step_seconds=window[:2] + [window[2] + 1e-9]) is None
    assert read(name, step_seconds=[window[1], window[0]]) is None  # out of order
    assert read(name, step_seconds=[]) is None
    assert read(name) is None
    put_serving_run()  # a second engine's loop with the same seconds: which one?
    assert read(name, step_seconds=window) is None


@pytest.mark.parametrize("name", SERVE_READERS)
def test_a_window_with_no_turn_before_it_reads_none(name):
    loop = tracing.SpanContext.new_trace()
    _, _, seconds = put_iteration(loop, 5.0, 1, 40.0)
    assert read(name, step_seconds=[seconds]) is None


@pytest.mark.parametrize("name", SERVE_READERS)
def test_an_engine_without_model_step_spans_reads_none_and_does_not_raise(name):
    """The parent commit's engine records ``serving.decode.step`` after the
    fact and no ``serving.decode.model_step``."""
    loop = tracing.SpanContext.new_trace()
    for k in range(3):
        tracing.record_span("serving.decode.step", 5.0 + k, 5.5 + k, parent=loop, active=2)
    assert read(name, step_seconds=[0.4, 0.4, 0.4]) is None


# -- traced tiny runs: the rehearsal of the chip run -------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("span_metrics_root")))


@pytest.mark.parametrize("cell, readers", [
    ("lm_tiny.train_rows", TRAIN_READERS),
    ("nmt_tiny.train_pairs", TRAIN_READERS),
    ("lm_tiny.serve_closed", SERVE_READERS),
])
def test_a_traced_tiny_run_prints_the_span_metrics_of_its_kind(root, cell, readers):
    loaded = harness.load_cell(cell, root)
    listed = {m["name"] for m in loaded["manifest"]["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert set(readers) <= listed and not listed & (set(TRAIN_READERS + SERVE_READERS) - set(readers))
    line = harness.execute(loaded, jax.devices()[:1], 2**31 + 29, 0.5, True, time.perf_counter())
    assert line["correct"] is True
    got = line["metrics"]
    assert set(readers) <= set(got)
    assert all(got[r]["unit"] == "ms" and got[r]["value"] > 0 for r in readers)
    if readers is SERVE_READERS:
        # a turn holds the model step, and the host's part of it is a part
        assert got["loop_iteration_ms"]["value"] >= got["decode_step_ms"]["value"]
        assert got["loop_host_ms"]["value"] < got["loop_iteration_ms"]["value"]
    else:
        assert got["trainer_telemetry_ms"]["value"] < got["trainer_host_gap_ms"]["value"]


def test_the_manifest_lists_the_five_readers_last_and_each_has_its_file():
    """By name and in this order among themselves: later PRs append their own
    readers after these (the test's name is PR 25's, when they were last)."""
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(set(names)) == len(names)
    five = [m for m in manifest["per_layer"] if m["name"] in TRAIN_READERS + SERVE_READERS]
    assert [m["name"] for m in five] == list(TRAIN_READERS + SERVE_READERS)
    for m in five:
        assert m["source"] == "program_span" and m["unit"] == "ms" and m["better"] == "lower"
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
