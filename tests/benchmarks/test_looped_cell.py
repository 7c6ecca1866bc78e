"""Self-tests of the looped-decoder cell and of the two readers PR 35 adds,
at a tiny size on the CPU, through the same harness, driver, reference and
comparison as a run on the chip. Rehearsals: no number from them is a device
metric."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, loop_bytes, tiny_looped, trace_reduce  # noqa: E402
from benchmarks.drivers import serve_closed_looped  # noqa: E402
from benchmarks.families import looped_lm as family  # noqa: E402
from benchmarks.tools import calibrate_looped  # noqa: E402

CELL = tiny_looped.CELL
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_looped.make_root(str(tmp_path_factory.mktemp("looped_root")))


def drive(root, cell=CELL, seed=2**31 + 35, seconds=0.5, trace=False):
    return harness.execute(harness.load_cell(cell, root), jax.devices()[:1], seed, seconds,
                           trace, time.perf_counter())


def test_the_tiny_looped_cell_runs_and_is_correct(root, capsys):
    line = drive(root)
    assert line["correct"] is True
    said = capsys.readouterr().out
    # both numbers decide it; float32 against float32 no token is off at all
    assert "check served_gap_sigmas:" in said and "check served_far_share: 0.0 " in said
    assert {"tpot_p95_ms", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0


def test_a_traced_run_reports_the_serving_readers_and_leaves_out_what_it_cannot_read(root):
    line = drive(root, trace=True)
    assert line["correct"] is True
    assert {"decode_step_ms", "decode_occupancy", "loop_iteration_ms", "loop_host_ms",
            "compiles_in_window.serve"} <= set(line["metrics"])
    # no TPU plane on the CPU: nothing was busy, the roofline's reader says nothing
    assert not set(tiny_looped.NEW_METRICS) & set(line["metrics"])


@pytest.mark.parametrize("fault", ["passes", "planes"])
def test_a_fault_of_the_mechanism_is_not_correct(root, fault):
    """One pass fewer than the configuration says; a pass that writes and
    attends the planes of the pass before it."""
    with calibrate_looped.planted(family, fault):
        line = drive(root)
    assert line["correct"] is False and line["failed"] == 0


def test_the_calibration_holds_every_variant_to_the_cells_limits():
    limits = {"served_gap_sigmas": 1.2, "served_far_share": 0.37}
    sound = calibrate_looped.summary([0.0] * 90 + [0.2] * 9 + [0.6], limits)
    assert sound["correct"] is True and sound["served_far_share"] == pytest.approx(0.10)
    assert sound["served_gap_sigmas"] == 0.6 and sound["limits"] == limits
    # many tokens a little off: the share refuses what the largest gap lets through
    many = calibrate_looped.summary([0.0] * 50 + [0.3] * 50, limits)
    assert many["correct"] is False and many["served_gap_sigmas"] < 1.2
    one = calibrate_looped.summary([0.0] * 99 + [2.0], limits)
    assert one["correct"] is False and one["served_far_share"] < 0.37


def test_the_roofline_reads_the_windows_spans_and_cannot_pass_its_busy_time(root):
    run = harness.Run(harness.load_cell(CELL, root), jax.devices()[:1], 2**31 + 36, 0.5, False,
                      time.perf_counter())
    counters = serve_closed_looped.run(run)["counters"]
    calls = counters["loop_calls"]
    assert calls == family.loop_calls(run.config) and calls["passes"] * calls["layers"] == 9
    reader = harness.load_reader("looped_hbm_roofline")
    view = {"counters": counters, "peaks": V5E, "trace": {"busy_s": 1.0, "window_s": 1.0,
                                                          "ops": {}}}
    found = reader.window_calls(view)
    assert len(found["steps"]) == len(counters["step_seconds"]) > 0
    # the tap books a chunk by the clock, the reader by the first and last step's spans
    assert abs(len(found["chunks"]) - len(counters["chunk_seconds"])) <= 2 < len(found["chunks"])
    for active, rows in found["steps"]:
        assert 0 <= active <= 3 and rows >= 5 * active  # a decoding slot is past its prompt
    for pos0, rows in found["chunks"]:  # every prompt of the mix is one chunk of 24
        assert pos0 == 0 and 4 <= rows <= 24
    least = (sum(loop_bytes.step_least_seconds(calls, a, r, V5E) for a, r in found["steps"])
             + sum(loop_bytes.chunk_least_seconds(calls, p, r, V5E) for p, r in found["chunks"]))
    assert reader.read(view) == pytest.approx(100.0 * least)
    assert reader.read(dict(view, trace=dict(view["trace"], busy_s=least))) == pytest.approx(100.0)
    # nothing to read: no trace, no busy time, another family's counters
    assert reader.read(dict(view, trace=None)) is None
    assert reader.read(dict(view, trace={"busy_s": 0.0, "window_s": 0.0, "ops": {}})) is None
    assert reader.read(dict(view, counters={k: v for k, v in counters.items()
                                            if k != "loop_calls"})) is None


def test_a_step_at_the_published_shapes_streams_the_weights_once_a_pass():
    with open(os.path.join(ROOT, "benchmarks", "configs", "ouro_2_6b.json")) as f:
        calls = family.loop_calls(json.load(f))
    assert calls["layer_params"] == 51_388_416 and calls["head_params"] == 49152 * 2048
    assert calls["row_bytes"] == 16 * 128 * 2
    # no cache row: 4 x 48 layers' weights, the head, 8 embedding rows, 8 new K and V rows a plane
    byts = loop_bytes.call_bytes(calls, 8, 0)
    assert byts == 192 * 102_776_832 + 201_326_592 + 8 * 4096 + 192 * 2 * 4096 * 8
    assert loop_bytes.step_least_seconds(calls, 8, 0, V5E) == pytest.approx(byts / 819e9)
    assert 24.0e-3 < byts / 819e9 < 24.5e-3  # the 24 ms of weight traffic a step
    # every live row is read once a plane, K and V: 1 572 864 bytes a token
    assert loop_bytes.call_bytes(calls, 8, 1000) - byts == 1000 * 1_572_864
    # a chunk of 96 real positions is bound by its bytes too; one of 320 by its operations
    for new, bound in ((96, "bytes"), (320, "ops")):
        scored = new * (new + 1) / 2
        t_b = loop_bytes.call_bytes(calls, new, new) / 819e9
        t_o = loop_bytes.call_flops(calls, new, 1.0, scored) / 197e12
        assert (t_b > t_o) == (bound == "bytes")
        assert loop_bytes.chunk_least_seconds(calls, 0, new, V5E) == pytest.approx(max(t_b, t_o))


def test_collective_exposed_share_on_a_recorded_trace_of_four_chips():
    with open(os.path.join(DATA, "recorded_trace_dp4.json")) as f:
        raw = json.load(f)
    trace = {"devices": {k: [tuple(e) for e in v] for k, v in raw["devices"].items()},
             "host": []}
    s = trace_reduce.summarize(trace)
    assert s["n_devices"] == 4
    reader = harness.load_reader("collective_exposed_share")
    # a chip: all-reduce-start 10, all-reduce-done 90, the all-gather nested in the while 100,
    # all-reduce 200 + 10 k, reduce-scatter 50 ns; the transfer between start and done runs
    # beside the flash kernel and is on no line
    want_ns = sum(10 + 90 + 100 + 200 + 10 * k + 50 for k in range(4)) / 4
    assert reader.exposed_seconds(s) * 1e9 == pytest.approx(want_ns)
    assert reader.read({"trace": s}) == pytest.approx(100.0 * want_ns / (s["window_s"] * 1e9))
    assert reader.read({"trace": None}) is None
    one_chip = trace_reduce.summarize({"devices": {"/device:TPU:0": [("fusion", 0, 100)]},
                                       "host": []})
    assert reader.read({"trace": one_chip}) == 0.0  # no collective: nothing exposed


def test_the_manifest_has_the_two_cells_the_configuration_and_the_two_readers_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    # by name and relative order: later PRs append cells, configurations and readers
    cells = {w["name"]: w for w in m["workloads"]}
    order = [w["name"] for w in m["workloads"]]
    assert order.index("lm_big.train_2k_dp4") < order.index("ouro_2_6b.serve_reason8")
    assert cells["lm_big.train_2k_dp4"]["chips"] == 4
    assert cells["ouro_2_6b.serve_reason8"]["chips"] == 1
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    (ouro,) = [c for c in m["configs"] if c["name"] == "ouro_2_6b"]
    assert ouro["reduced"] == []
    readers = [p["name"] for p in m["per_layer"]]
    assert readers.index("collective_exposed_share") < readers.index("looped_hbm_roofline")
    serve = [p for p in m["per_layer"] if p["moves"] == "tpot_p95_ms"
             and "lm_big.serve_long" in p["workloads"]]
    assert len(serve) >= 9 and all("ouro_2_6b.serve_reason8" in p["workloads"] for p in serve)
    assert not any("serve_closed16" in w for p in m["per_layer"] + m["end_to_end"]
                   for w in p.get("workloads", []))
    with open(os.path.join(ROOT, "benchmarks", "configs", "ouro_2_6b.json")) as f:
        config = json.load(f)
    assert config["reduced"] == []
    assert {"deployment", "assumed", "departs", "precision", "bytes"} <= set(config)
    # every published key unchanged
    assert (config["num_hidden_layers"], config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["vocab_size"], config["total_ut_steps"], config["early_exit_threshold"],
            config["rope_theta"], config["max_position_embeddings"]) == (
        48, 2048, 5632, 16, 16, 128, 49152, 4, 1, 1000000, 65536)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the builder's sandbox holds the catalog
        with open(catalog) as f:
            published = next(c for c in map(json.loads, f) if c["name"] == "Ouro-2.6B")
        assert {k: config[k] for k in published["config"]} == published["config"]
        assert config["source"] == published["source_url"]
