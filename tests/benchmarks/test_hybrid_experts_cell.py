"""Self-tests of the single-mixer Mamba-2 / attention / latent-expert cell at a
tiny size on the CPU, through the same harness, driver, reference and
comparison as a run on the chip: the cell runs and is correct, every fault
``tools/calibrate_hybrid_experts.py`` plants in the mechanism reads ``correct``
false, ``latent_expert_bytes.py`` counts two calls a layer, and the new reader
reads a recorded trace summary by name. Rehearsals: no number from them is a
device metric."""

import functools
import json
import os
import sys
import time
from unittest import mock

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, latent_expert_bytes, moe_bytes, tiny_hybrid_experts  # noqa: E402
from benchmarks.tools import calibrate_hybrid_experts  # noqa: E402

CELL = tiny_hybrid_experts.CELL
REAL_CELL = "nemotron_3_super_120b_a12b.serve_chat64_moe"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_hybrid_experts.make_root(str(tmp_path_factory.mktemp("hybrid_experts_root")))


def drive(root, cell=CELL, seed=2**31 + 45, seconds=0.5, trace=False):
    # traces go under this file's own root: other files trace tiny cells too
    with mock.patch.object(harness, "Run", functools.partial(harness.Run, scratch=root)):
        return harness.execute(harness.load_cell(cell, root), jax.devices()[:1], seed, seconds,
                               trace, time.perf_counter())


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    line = drive(root)
    out = capsys.readouterr().out
    assert line["correct"] is True
    assert {"tpot_p95_ms", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "check leaked_pages_or_slots: 0.0" in out and "check served_far_share: 0.0" in out
    assert "of the iterations carry a chunk" in out


def test_a_traced_run_reports_what_the_spans_say_and_leaves_out_what_needs_a_chip(root):
    line = drive(root, trace=True)
    assert line["correct"] is True
    assert {"decode_step_ms", "decode_occupancy", "loop_iteration_ms", "loop_host_ms",
            "compiles_in_window.serve", "moe_load_max_over_mean"} <= set(line["metrics"])
    # 4 of 16 experts a token, 8 held: a step's fullest expert is above the mean
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 8 * 2
    # no TPU plane on the CPU: the kernels' readers find no op to read and say nothing
    assert not {"ssm_step_roofline", "ssm_time_share", "paged_attend_roofline", "moe_time_share",
                "latent_expert_gmm_roofline"} & set(line["metrics"])


@pytest.mark.parametrize("fault", calibrate_hybrid_experts.FAULTS)
def test_a_fault_planted_in_the_mechanism_is_not_correct(root, fault, capsys):
    with calibrate_hybrid_experts.planted(fault):
        line = drive(root, seconds=0.3)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "check served_gap_sigmas" in out and "FAILED" in out
    assert line["failed"] == 0  # every request ran to its budget: the numbers are wrong


def test_the_planted_faults_are_taken_out_again():
    from paddle_tpu.models import hybrid_ssm_lm as hm
    from paddle_tpu.ops import moe

    held = lambda: (hm.ssm_chunked, hm._via_chunk, hm._via_step, hm.group_rms_norm,
                    moe.topk_route, moe.expert_share_ffn, moe.BODIES["relu2"])
    before = held()
    for fault in calibrate_hybrid_experts.FAULTS:
        with calibrate_hybrid_experts.planted(fault):
            assert held() != before, fault
        assert held() == before, fault
    with pytest.raises(ValueError, match="unknown fault"):
        with calibrate_hybrid_experts.planted("nothing"):
            pass


def test_weights_made_a_leaf_at_a_time_are_the_benchmarks_own_bit_for_bit():
    """The driver's maker against ``weights.make_weights``: the same values
    for every kind of leaf, and one compiled maker a (kind, shape, type), not
    one program over all 1 368 leaves."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import weights
    from benchmarks.drivers import serve_closed_hybrid_experts as driver

    S = jax.ShapeDtypeStruct
    shapes = {f"layer_1/moe/experts/{e}/fc1/w": S((32, 48), jnp.bfloat16) for e in range(4, 9)}
    shapes.update({"layer_3/moe/router/b": S((16,), jnp.bfloat16),
                   "emb/word_emb": S((97, 64), jnp.float32),
                   "layer_0/norm/scale": S((64,), jnp.bfloat16),
                   "layer_2/mamba/a_log/bias": S((8,), jnp.bfloat16),
                   "layer_2/mamba/conv/w": S((4, 160), jnp.bfloat16)})
    driver._maker.cache_clear()
    for seed in (7, 2**31 + 45):
        whole, mine = weights.make_weights(shapes, seed), driver.make_weights(shapes, seed)
        assert set(whole) == set(mine)
        for name in whole:
            assert whole[name].dtype == mine[name].dtype
            assert np.array_equal(np.asarray(whole[name].astype(jnp.float32)),
                                  np.asarray(mine[name].astype(jnp.float32))), name
    assert driver._maker.cache_info().currsize == 6  # five experts share one
    real = weights.make_weights
    with driver.leaf_at_a_time():
        assert weights.make_weights is driver.make_weights
    assert weights.make_weights is real


def test_latent_expert_bytes_counts_two_calls_a_layer_in_the_latent_width():
    """The cell's shapes: a step's 352 pairs on 120 hit experts, a chunk's
    2816 pairs on all 128; each call is bound by reading the hit experts'
    weights once, and ``moe_bytes``' three-call count would read half again
    too high."""
    for hit, pairs in ((120, 352), (128, 2816)):
        want = (hit * 1024 * 2688 * 2 + pairs * (1024 * 2 + 2688 * 4)
                + hit * 2688 * 1024 * 2 + pairs * (2688 * 2 + 1024 * 4)) / 819e9
        got = latent_expert_bytes.layer_least_seconds(hit, pairs, 1024, 2688, 2, PEAKS)
        assert got == pytest.approx(want)
        three = moe_bytes.layer_least_seconds(hit, pairs, 1024, 2688, 2, PEAKS)
        assert 1.45 < three / got < 1.55
    # with many rows an expert the operations bound a call, not the bytes
    assert latent_expert_bytes.layer_least_seconds(128, 128 * 4096, 1024, 2688, 2, PEAKS) == (
        pytest.approx(2 * 2.0 * 128 * 4096 * 1024 * 2688 / 197e12))
    # the configuration's own counts: what families.hybrid_moe_lm hands the readers
    from benchmarks.families import hybrid_moe_lm as family

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron_3_super_120b_a12b.json")) as f:
        config = json.load(f)
    assert family.moe_calls(config) == {"layers": 5, "held": 128, "router_width": 512,
                                        "per_token": 22, "latent": 1024, "f": 2688,
                                        "itemsize": 2}
    assert family.ssm_calls(config) == {
        "layers": 5, "heads": 128, "head_dim": 64, "state": 128, "groups": 8, "conv": 4,
        "conv_channels": 10240, "attention_layers": 1, "kv_row_bytes": 512,
        "weight_bytes": 2 * 4_648_163_712}


@pytest.fixture
def recorded_window():
    """A recorded tiny trace: two decode steps and one prefill chunk on the
    engine's loop, with the counts their spans carry, and the kernel's ops."""
    from paddle_tpu import tracing

    tracing.enable_tracing()
    tracing.reset_tracing()
    with tracing.start_trace("serving.decode.loop") as loop:
        t = time.perf_counter()
        tracing.record_span("serving.decode.model_step", t, t + 0.01, parent=loop.context,
                            seconds=0.011, moe_pairs=352 * 5, moe_experts_hit=120 * 5,
                            moe_max_load=9)
        tracing.record_span("serving.decode.prefill", t + 0.002, t + 0.003,
                            parent=loop.context, chunk=0, moe_pairs=2816 * 5,
                            moe_experts_hit=128 * 5, moe_max_load=40)
        tracing.record_span("serving.decode.model_step", t + 0.02, t + 0.03,
                            parent=loop.context, seconds=0.012, moe_pairs=340 * 5,
                            moe_experts_hit=118 * 5, moe_max_load=8)
    calls = {"layers": 5, "held": 128, "router_width": 512, "per_token": 22, "latent": 1024,
             "f": 2688, "itemsize": 2}
    yield {"counters": {"moe_calls": calls, "step_seconds": [0.011, 0.012], "max_slots": 64},
           "peaks": PEAKS, "trace": {"ops": {"moe_gmm(tpu_custom_call)": 0.030, "fusion": 0.070},
                                     "busy_s": 0.1, "window_s": 0.1}}
    tracing.reset_tracing()


def test_the_reader_on_a_recorded_tiny_trace(recorded_window):
    view = recorded_window
    read = harness.load_reader("latent_expert_gmm_roofline").read
    least = sum(5 * latent_expert_bytes.layer_least_seconds(hit, pairs, 1024, 2688, 2, PEAKS)
                for hit, pairs in ((120, 352), (118, 340), (128, 2816)))
    got = read(view)
    assert got == pytest.approx(100 * least / 0.030, rel=1e-9) and 0 < got < 100
    # the SwiGLU layer's reader is not this cell's: a third call would pass 105 %
    assert harness.load_reader("moe_time_share").read(view) == pytest.approx(30.0)
    # nothing to read: no trace, no kernel in it, a SwiGLU cell's counts, no counts at all
    assert read(dict(view, trace=None)) is None
    assert read(dict(view, trace=dict(view["trace"], ops={"fusion": 1.0}))) is None
    swiglu = dict(view["counters"], moe_calls={"layers": 4, "held": 32, "d": 4096, "f": 2048,
                                               "itemsize": 2})
    assert read(dict(view, counters=swiglu)) is None
    assert read(dict(view, counters={})) is None


def test_the_manifest_has_the_configuration_the_cell_and_the_reader():
    """Written to hold after later PRs add cells of their own: membership,
    not equality."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron_3_super_120b_a12b", "serve_chat64_moe", 1)
    config = next(c for c in manifest["configs"] if c["name"] == "nemotron_3_super_120b_a12b")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    by_name = {m["name"]: m for m in manifest["per_layer"] + manifest["end_to_end"]}
    new = by_name["latent_expert_gmm_roofline"]
    assert new["workloads"] == [REAL_CELL] and new["moves"] == "tpot_p95_ms"
    assert new["layer"] == by_name["moe_gmm_roofline"]["layer"] == "expert kernel"
    for name in ("tpot_p95_ms", "compiles_in_window.serve", "decode_step_ms", "serve_host_share",
                 "decode_occupancy", "window_out_tok_s", "window_ttft_p50_ms",
                 "device_idle_share.serve", "loop_iteration_ms", "loop_host_ms",
                 "loop_dispatch_ms", "loop_telemetry_ms", "loop_offcpu_ms", "ssm_step_roofline",
                 "ssm_time_share", "moe_time_share", "moe_load_max_over_mean",
                 "paged_attend_roofline"):
        assert REAL_CELL in by_name[name]["workloads"], name
    # its three calls a layer and its weights-read-once count do not hold here
    for name in ("moe_gmm_roofline", "state_bytes_share"):
        assert REAL_CELL not in by_name[name]["workloads"], name
    with open(os.path.join(ROOT, "benchmarks", "traffic", "serve_chat64_moe.json")) as f:
        mix = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "serve_chat64.json")) as f:
        chat = json.load(f)
    same = ("clients", "rounds", "deal_seed", "prompt_len", "output_len", "engine",
            "check_requests", "trace_seconds")
    assert {k: mix[k] for k in same} == {k: chat[k] for k in same}
    assert mix["driver"] == "serve_closed_hybrid_experts"


def test_the_configuration_holds_the_published_widths_and_the_cut():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron_3_super_120b_a12b.json")) as f:
        config = json.load(f)
    from benchmarks.families import hybrid_moe_lm as family

    cfg = family.model_cfg(config)
    want = dict(d_model=4096, pattern="*EMEMEMEMEM", num_heads=32, num_kv_heads=2, head_dim=128,
                ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_conv=4,
                ssm_chunk=128, num_experts=512, experts_per_token=22, experts_held=(0, 128),
                moe_latent=1024, moe_d_inner=2688, shared_d_inner=5376, routed_scaling=5,
                vocab=32768, max_len=3072)
    assert {k: cfg[k] for k in want} == want
    assert config["published"]["hybrid_override_pattern"][25:36] == cfg["pattern"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert "multi-token-prediction" in " ".join(config["departs"])
