"""Self-tests of the latent-attention, sparse-expert cell at a tiny size on the
CPU, through the same harness, driver, reference and comparison as a run on
the chip. Rehearsals: no number from them is a device metric."""

import functools
import json
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, moe_bytes, tiny_experts  # noqa: E402
from benchmarks.drivers import (serve_closed, serve_closed_experts,  # noqa: E402
                                serve_closed_layerwise)

CELL = tiny_experts.CELL
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_experts.make_root(str(tmp_path_factory.mktemp("experts_root")))


def drive(root, cell=CELL, seed=2**31 + 31, seconds=0.5, trace=False):
    # traces go under this file's own root: other files trace the lm_tiny cells
    # too, on other workers, and a run empties its trace directory first
    with mock.patch.object(harness, "Run", functools.partial(harness.Run, scratch=root)):
        return harness.execute(harness.load_cell(cell, root), jax.devices()[:1], seed, seconds,
                               trace, time.perf_counter())


def test_the_tiny_experts_cell_runs_and_is_correct(root):
    line = drive(root)
    assert line["correct"] is True
    assert {"tpot_p95_ms", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0


def test_a_traced_run_reports_the_serving_readers_and_the_routing_and_no_kernel(root):
    line = drive(root, trace=True)
    assert line["correct"] is True
    assert {"decode_step_ms", "decode_occupancy", "loop_iteration_ms", "loop_host_ms",
            "compiles_in_window.serve", "moe_load_max_over_mean"} <= set(line["metrics"])
    # 2 of 8 experts a token, 4 held: a step's fullest expert is above the mean
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 4 * 2
    # no TPU plane on the CPU (and the XLA form there): the kernel's readers say nothing
    assert not {"moe_gmm_roofline", "moe_time_share"} & set(line["metrics"])


def test_the_readers_say_nothing_of_a_run_without_an_expert_layer(root):
    line = drive(root, cell="lm_tiny.serve_closed", trace=True)
    assert line["correct"] is True
    assert not set(tiny_experts.NEW_METRICS) & set(line["metrics"])


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
                            seconds=0.011, moe_pairs=64 * 4, moe_experts_hit=27 * 4,
                            moe_max_load=6)
        tracing.record_span("serving.decode.prefill", t + 0.002, t + 0.003,
                            parent=loop.context, chunk=0, moe_pairs=1024 * 4,
                            moe_experts_hit=32 * 4, moe_max_load=50)
        tracing.record_span("serving.decode.model_step", t + 0.02, t + 0.03,
                            parent=loop.context, seconds=0.012, moe_pairs=60 * 4,
                            moe_experts_hit=25 * 4, moe_max_load=5)
        # outside the window: a drained step and a chunk after it
        tracing.record_span("serving.decode.model_step", t + 0.04, t + 0.05,
                            parent=loop.context, seconds=0.5, moe_pairs=8, moe_experts_hit=8,
                            moe_max_load=1)
        tracing.record_span("serving.decode.prefill", t + 0.06, t + 0.07, parent=loop.context,
                            chunk=1, moe_pairs=9, moe_experts_hit=9, moe_max_load=1)
    calls = {"layers": 4, "held": 32, "router_width": 128, "per_token": 8, "d": 4096,
             "f": 2048, "itemsize": 2}
    yield {"counters": {"moe_calls": calls, "step_seconds": [0.011, 0.012], "max_slots": 32},
           "peaks": PEAKS, "trace": {"ops": {"moe_gmm(tpu_custom_call)": 0.040, "fusion": 0.060},
                                     "busy_s": 0.1, "window_s": 0.1}}
    tracing.reset_tracing()


def test_the_three_readers_on_a_recorded_tiny_trace(recorded_window):
    view = recorded_window
    assert harness.load_reader("moe_time_share").read(view) == pytest.approx(40.0)
    want = np.mean([6 * 32 * 4 / (64 * 4), 5 * 32 * 4 / (60 * 4)])
    assert harness.load_reader("moe_load_max_over_mean").read(view) == pytest.approx(want)
    # a layer's three calls at a call's mean counts; bytes bound at 2 and at 32 rows an expert
    least = 0.0
    for hit, pairs in ((27, 64), (25, 60), (32, 1024)):
        per_layer = (2 * (hit * 4096 * 2048 * 2 + pairs * (4096 * 2 + 2048 * 4))
                     + hit * 2048 * 4096 * 2 + pairs * (2048 * 2 + 4096 * 4)) / 819e9
        assert per_layer == pytest.approx(moe_bytes.layer_least_seconds(
            hit, pairs, 4096, 2048, 2, PEAKS))
        least += 4 * per_layer
    got = harness.load_reader("moe_gmm_roofline").read(view)
    assert got == pytest.approx(100 * least / 0.040, rel=1e-9) and 0 < got < 100
    # with many rows an expert the operations bound a call, not the bytes
    assert moe_bytes.layer_least_seconds(32, 32 * 4096, 4096, 2048, 2, PEAKS) == pytest.approx(
        3 * 2.0 * 32 * 4096 * 4096 * 2048 / 197e12)


@pytest.mark.parametrize("name", tiny_experts.NEW_METRICS)
def test_a_reader_that_finds_nothing_to_read_returns_none(recorded_window, name):
    read = harness.load_reader(name).read
    view = recorded_window
    assert read(dict(view, counters=dict(view["counters"], moe_calls=None))) is None
    assert read(dict(view, counters=dict(view["counters"], step_seconds=[0.7]))) is None or \
        name == "moe_time_share"
    if name != "moe_load_max_over_mean":
        assert read(dict(view, trace=None)) is None
        assert read(dict(view, trace=dict(view["trace"], ops={"fusion": 1.0}))) is None


def test_a_served_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    from paddle_tpu.serving import decode

    real = decode.DecodeHandle.result

    def altered(self, timeout=None):
        out = real(self, timeout)
        out.tokens = np.asarray(out.tokens).copy()
        out.tokens[-1] = (out.tokens[-1] + 1) % 97
        return out

    monkeypatch.setattr(decode.DecodeHandle, "result", altered)
    assert drive(root)["correct"] is False


def test_an_expert_layer_that_computes_an_expert_it_does_not_hold_is_not_correct(
        root, monkeypatch):
    """The program takes its share to start one expert early: the selected
    experts' outputs come from the wrong weights."""
    from paddle_tpu.ops import moe

    real = moe.expert_share_ffn
    monkeypatch.setattr(moe, "expert_share_ffn", lambda x, route, experts, held, **kw: real(
        x, route, experts, (held[0] - 1, held[1]), **kw))
    line = drive(root)
    assert line["correct"] is False and line["failed"] == 0


def test_a_step_that_attends_without_the_rotary_key_is_not_correct(root, monkeypatch):
    """The absorbed form forgets the shared rotary key's part of the score."""
    from paddle_tpu.models import latent_moe_lm

    real = latent_moe_lm.CORES["absorbed"]

    def no_rope(q, rows, live, w_kb, w_vb, **kw):
        return real(q.at[..., w_kb.shape[-1]:].set(0.0), rows, live, w_kb, w_vb, **kw)

    monkeypatch.setitem(latent_moe_lm.CORES, "absorbed", no_rope)
    line = drive(root)
    assert line["correct"] is False and line["failed"] == 0


def test_the_fp8_control_puts_other_tokens_first(root):
    loaded = harness.load_cell(CELL, root)
    run = harness.Run(loaded, jax.devices()[:1], 17, 0.0, False, time.perf_counter())
    with serve_closed_experts.checkpoint_weights():
        family, _, shapes = serve_closed.prepare(run)
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(1, 97, 40, dtype=np.int32),
               "tokens": rng.integers(1, 97, 24, dtype=np.int32)} for _ in range(4)]
    gaps = serve_closed_layerwise.served_gaps(run, family, shapes, sample, ("f32", "fp8"))
    assert max(gaps["fp8"]) > run.limits["served_gap_sigmas"]
    assert serve_closed_experts.far_share(gaps["fp8"]) > run.limits["served_far_share"]
    assert min(gaps["f32"]) >= 0 and len(gaps["f32"]) == 96


@pytest.mark.parametrize("gaps, share", [([0.0, 0.02, 0.3, 0.0], 0.25), ([0.0, 0.1, 0.05], 0.0),
                                         ([0.11], 1.0), ([], float("inf"))])
def test_the_share_of_tokens_far_off_the_references_best(gaps, share):
    assert serve_closed_experts.far_share(gaps) == share


def test_the_driver_leaves_serve_closed_as_it_found_it(root):
    prepare, gaps = serve_closed.prepare, serve_closed.served_gaps
    assert drive(root)["correct"] is True
    assert serve_closed.prepare is prepare and serve_closed.served_gaps is gaps


def test_the_layerwise_walk_is_the_whole_models_forward_pass(root):
    """One layer's weights at a time (a dense layer, then two expert layers)
    gives what all of them at once give."""
    from benchmarks import weights
    from benchmarks.references import common as refc

    loaded = harness.load_cell(CELL, root)
    run = harness.Run(loaded, jax.devices()[:1], 5, 0.0, False, time.perf_counter())
    assert serve_closed.prepare(run)[2]["layer_1/moe/experts/fc2/w"].shape == (4, 32, 64)
    with serve_closed_experts.checkpoint_weights():
        family, _, shapes = serve_closed.prepare(run)
    # seeded as a checkpoint holds them: a matrix an expert, experts 2-5 of the router's 8
    assert "layer_1/moe/experts/fc2/w" not in shapes
    assert {n.split("/")[3] for n in shapes if n.startswith("layer_1/moe/experts/")} == set("2345")
    assert shapes["layer_1/moe/experts/2/gate/w"].shape == (64, 32)
    assert shapes["layer_1/moe/experts/5/fc2/w"].shape == (32, 64)
    rng = np.random.default_rng(1)
    sample = [{"prompt": rng.integers(1, 97, 30, dtype=np.int32),
               "tokens": rng.integers(1, 97, 10, dtype=np.int32)}]
    rows = serve_closed_layerwise.reference_rows(run, family, shapes, sample, refc.mm_f32)[0]
    params = weights.make_weights(weights.as_float32(shapes), run.seed)
    ids = np.concatenate([sample[0]["prompt"], sample[0]["tokens"]])[None]
    whole = family.reference_logits(run.config, refc.mm_f32)(params, ids)[0, 29:39]
    np.testing.assert_allclose(rows, np.asarray(whole), rtol=2e-4, atol=2e-4)


def test_the_configuration_holds_the_published_numbers_and_states_its_cut():
    """Every number of the catalog's entry under its key, but the three cut
    keys; the published counts beside them; the reference imports nothing of
    the program."""
    config = harness.load_json(ROOT, "benchmarks", "configs", "sarvam_105b.json")
    published = {
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_size": 4096,
        "intermediate_size": 16384, "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "moe_intermediate_size": 2048, "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_shared_experts": 1,
        "q_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "v_head_dim": 128, "vocab_size": 262144, "default_theta": 10000}
    cut = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 65536}
    assert sorted(config["reduced"]) == sorted(cut)
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
        if key in cut:
            assert config["published"][key] == value
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "deepseek_yarn"}
    for key in ("deployment", "assumed", "departs", "precision", "bytes", "source"):
        assert config[key]
    from benchmarks.families import latent_moe_lm as family

    cfg = family.model_cfg(config)
    assert cfg["experts_held"] == (0, 32) and cfg["num_experts"] == 128
    assert family.moe_calls(config) == {"layers": 4, "held": 32, "router_width": 128,
                                        "per_token": 8, "d": 4096, "f": 2048, "itemsize": 2}
    with open(os.path.join(ROOT, "benchmarks", "references", "latent_moe_lm.py")) as f:
        assert "paddle_tpu" not in f.read()
    mix = harness.load_json(ROOT, "benchmarks", "traffic", "serve_docs32.json")
    assert (mix["clients"], mix["rounds"], mix["check_requests"], mix["trace_seconds"]) == (
        32, 8, 4, 8)
    assert mix["engine"] == {"max_slots": 32, "page_size": 16, "max_context": 16384,
                             "prefill_chunk": 512, "cache_dtype": "bfloat16"}
    assert json.dumps(mix["prompt_len"], sort_keys=True) == json.dumps(
        {"hi": 12288, "lo": 1024, "median": 4096, "sigma": 0.7}, sort_keys=True)
