"""Self-tests of the hybrid Mamba-2 / attention cell at a tiny size on the CPU,
through the same harness, driver, reference and comparison as a run on the
chip: the cell runs and is correct, every fault ``tools/calibrate_hybrid.py``
plants in the mechanism reads ``correct`` false, ``ssm_bytes.py`` counts what
the arrays hold, and the new readers read a recorded trace summary by name.
Rehearsals: no number from them is a device metric."""

import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, ssm_bytes, tiny_hybrid  # noqa: E402
from benchmarks.tools import calibrate_hybrid  # noqa: E402

CELL = tiny_hybrid.CELL


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_hybrid.make_root(str(tmp_path_factory.mktemp("hybrid_root")))


def drive(root, cell=CELL, seed=2**31 + 43, seconds=0.5, trace=False):
    return harness.execute(harness.load_cell(cell, root), jax.devices()[:1], seed, seconds,
                           trace, time.perf_counter())


def test_the_tiny_hybrid_cell_runs_and_is_correct(root, capsys):
    line = drive(root)
    assert line["correct"] is True
    assert {"tpot_p95_ms", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "check leaked_pages_or_slots: 0.0" in capsys.readouterr().out


def test_a_traced_run_reports_what_the_spans_say_and_leaves_out_what_needs_a_chip(root):
    line = drive(root, trace=True)
    assert line["correct"] is True
    assert {"decode_step_ms", "decode_occupancy", "loop_iteration_ms", "loop_host_ms",
            "compiles_in_window.serve", "state_bytes_share"} <= set(line["metrics"])
    # a tiny model's weights are nothing beside its states: most of a step's bytes
    assert 0 < line["metrics"]["state_bytes_share"]["value"] < 100
    # no TPU plane on the CPU: the kernels' readers find no op to read and say nothing
    assert not {"ssm_step_roofline", "ssm_time_share", "paged_attend_roofline"} & set(
        line["metrics"])


@pytest.mark.parametrize("fault", calibrate_hybrid.FAULTS)
def test_a_fault_planted_in_the_mechanism_is_not_correct(root, fault, capsys):
    with calibrate_hybrid.planted(fault):
        line = drive(root, seconds=0.3)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "check served_gap_sigmas" in out and "FAILED" in out
    assert line["failed"] == 0  # every request ran to its budget: the numbers are wrong


def test_the_planted_faults_are_taken_out_again(root):
    from paddle_tpu.models import hybrid_ssm_lm as hm

    before = (hm.ssm_chunked, hm._via_chunk, hm._via_step, hm._score_scale)
    for fault in calibrate_hybrid.FAULTS:
        with calibrate_hybrid.planted(fault):
            pass
    assert before == (hm.ssm_chunked, hm._via_chunk, hm._via_step, hm._score_scale)
    with pytest.raises(ValueError, match="unknown fault"):
        with calibrate_hybrid.planted("nothing"):
            pass


def test_ssm_bytes_counts_what_the_arrays_hold():
    """The published shapes: the required bytes of a step's ``ssm_step`` calls
    are the states the engine allocates for the active slots, in and out, plus
    the token's operands; and the step's bytes are those, the weights, the
    tails and the live rows."""
    import json

    from benchmarks.families import hybrid_ssm_lm as family
    from paddle_tpu import models
    from paddle_tpu.models import hybrid_ssm_lm as hm

    with open(os.path.join(ROOT, "benchmarks", "configs", "granite_4_0_h_micro.json")) as f:
        config = json.load(f)
    calls = family.ssm_calls(config)
    assert calls == {"layers": 36, "heads": 64, "head_dim": 64, "state": 128, "conv": 4,
                     "conv_channels": 4352, "attention_layers": 4, "kv_row_bytes": 1024,
                     "weight_bytes": 2 * 3_191_396_096}
    cfg = dict(hm.BASE_CFG, **family.model_cfg(config))
    _, _, states, tails = models.serving_programs(cfg).cache_specs(
        cfg, max_slots=64, num_pages=1 + 64 * 192, page_size=16, dtype="bfloat16")
    nbytes = lambda s: int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
    assert nbytes(states) == 64 * 36 * ssm_bytes.state_bytes(calls) == 4_831_838_208
    assert nbytes(states) + nbytes(tails) == 64 * 77_377_536  # the issue's state a slot
    token = (3 * 4096 + 64 + 2 * 128) * 4
    assert ssm_bytes.ssm_step_bytes(calls, 64) == 2 * nbytes(states) + 64 * 36 * token
    assert ssm_bytes.ssm_step_bytes(calls, 0) == 0
    assert ssm_bytes.ssm_step_flops(calls, 1) == 36 * 5 * 64 * 64 * 128
    step = ssm_bytes.step_bytes(calls, 61, 80_000)
    want = (calls["weight_bytes"] + ssm_bytes.ssm_step_bytes(calls, 61)
            + 61 * 2 * nbytes(tails) // 64 + 4 * 2 * 1024 * (80_000 + 61))
    assert step == want
    # the issue's reckoning: 57 % of a step at 95 % occupancy is state
    assert 0.55 < 61 * 36 * 2 * ssm_bytes.state_bytes(calls) / step < 0.59


COUNTERS = {"ssm_calls": {"layers": 36, "heads": 64, "head_dim": 64, "state": 128, "conv": 4,
                          "conv_channels": 4352, "attention_layers": 4, "kv_row_bytes": 1024,
                          "weight_bytes": 6_382_792_192},
            "step_occupancy": [1.0, 0.5], "step_seconds": [0.03] * 10, "max_slots": 64,
            "page_size": 16}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


@pytest.mark.parametrize("name, device_ops, expect", [
    ("ssm_step_roofline", {"ssm_step(tpu_custom_call)": 0.15, "fusion": 0.85}, True),
    ("ssm_time_share", {"ssm_step(tpu_custom_call)": 0.25, "fusion": 0.75}, True),
    ("ssm_step_roofline", {"fusion": 1.0}, False),
    ("ssm_time_share", {"fusion": 1.0}, False),
])
def test_the_kernel_readers_read_the_op_by_name(name, device_ops, expect):
    view = {"counters": COUNTERS, "peaks": PEAKS,
            "trace": {"ops": device_ops, "busy_s": 1.0, "window_s": 1.0}}
    value = harness.load_reader(name).read(view)
    if not expect:
        assert value is None
    elif name == "ssm_time_share":
        assert value == pytest.approx(25.0)
    else:  # 48 slots' states in and out and their operands, 15 ms of kernels a step
        want = ssm_bytes.ssm_step_bytes(COUNTERS["ssm_calls"], 48) / 819e9 / 0.015 * 100
        assert value == pytest.approx(want, rel=1e-6) and 0 < value < 100
    assert harness.load_reader(name).read(dict(view, counters={})) is None
    assert harness.load_reader(name).read(dict(view, trace=None)) is None


def _spans(monkeypatch, attrs_of):
    """``tracing.spans()`` as a window of ten model steps would leave it."""
    from paddle_tpu import tracing

    ctx = types.SimpleNamespace(trace_id="loop", parent_id=None, span_id=None)
    spans = [types.SimpleNamespace(name="serving.decode.model_step", t0_us=1e3 * i,
                                   t1_us=1e3 * i + 900, context=ctx,
                                   attrs=dict(attrs_of(i), seconds=0.03)) for i in range(10)]
    monkeypatch.setattr(tracing, "spans", lambda: spans)


def test_state_bytes_share_reads_the_steps_spans(monkeypatch):
    calls = COUNTERS["ssm_calls"]
    moved = 2 * 60 * 36 * ssm_bytes.state_bytes(calls)
    _spans(monkeypatch, lambda i: {"ssm_active_slots": 60, "ssm_layers": 36,
                                   "ssm_state_bytes_moved": moved, "attend_live_pages": 5000})
    view = {"counters": COUNTERS, "peaks": PEAKS, "trace": None}
    want = 100.0 * moved / ssm_bytes.step_bytes(calls, 60, 5000 * 16)
    read = harness.load_reader("state_bytes_share").read
    assert read(view) == pytest.approx(want) and 55 < want < 59
    assert read(dict(view, counters={})) is None
    # a program from before the counts: the spans carry none, the reader says nothing
    _spans(monkeypatch, lambda i: {"attend_live_pages": 5000})
    assert read(view) is None


def test_paged_attend_roofline_reads_the_kernel_and_the_steps_pages(monkeypatch):
    page_bytes = 16 * 4 * 2 * 1024  # a page of 16 rows, a K and a V row in 4 planes
    _spans(monkeypatch, lambda i: {"attend_live_pages": 5000, "attend_page_bytes": page_bytes,
                                   "attend_table_pages": 64 * 192, "attend_kernel": 1})
    trace = {"ops": {"paged_attend_step(tpu_custom_call)": 0.02, "fusion": 0.5}, "busy_s": 1.0}
    view = {"counters": COUNTERS, "peaks": PEAKS, "trace": trace}
    read = harness.load_reader("paged_attend_roofline").read
    want = 100.0 * (10 * 5000 * page_bytes / 819e9) / 0.02
    assert read(view) == pytest.approx(want) and 0 < want < 100
    assert read(dict(view, trace=dict(trace, ops={"fusion": 1.0}))) is None  # the gather
    assert read(dict(view, trace=None)) is None
    # the parent's program: the step's span has no ``attend_page_bytes``
    _spans(monkeypatch, lambda i: {"attend_live_pages": 5000, "attend_kernel": 1})
    assert read(view) is None


def test_the_manifest_has_the_cell_the_configuration_and_the_four_readers():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = "granite_4_0_h_micro.serve_chat64"
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "granite_4_0_h_micro", "serve_chat64", 1)
    config = next(c for c in manifest["configs"] if c["name"] == "granite_4_0_h_micro")
    assert config["reduced"] == [] and config["file"].endswith("granite_4_0_h_micro.json")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("ssm_step_roofline", "ssm_time_share", "state_bytes_share"):
        assert by_name[name]["workloads"] == [cell] and by_name[name]["moves"] == "tpot_p95_ms"
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert by_name["paged_attend_roofline"]["workloads"] == [
        "lm_big.serve_long", "ouro_2_6b.serve_reason8", cell]
    tpot = next(m for m in manifest["end_to_end"] if m["name"] == "tpot_p95_ms")
    assert tpot["workloads"][-1] == cell and tpot["bound"] == 0.03
    # every serving reader that finds something to read in the cell lists it
    for name in ("decode_step_ms", "decode_occupancy", "loop_iteration_ms", "loop_host_ms",
                 "loop_dispatch_ms", "device_idle_share.serve", "compiles_in_window.serve"):
        assert cell in by_name[name]["workloads"], name
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) == 8
