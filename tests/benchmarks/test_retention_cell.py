"""Self-tests of the power-retention cell at a tiny size on the CPU, through
the same harness, driver, reference and comparison as a run on the chip.
Rehearsals: no number from them is a device metric."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import harness, tiny_retention  # noqa: E402
from benchmarks.drivers import serve_closed, serve_closed_layerwise  # noqa: E402

CELL = tiny_retention.CELL


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_retention.make_root(str(tmp_path_factory.mktemp("retention_root")))


def drive(root, cell=CELL, seed=2**31 + 27, seconds=0.5, trace=False):
    return harness.execute(harness.load_cell(cell, root), jax.devices()[:1], seed, seconds,
                           trace, time.perf_counter())


def test_the_tiny_retention_cell_runs_and_is_correct(root):
    line = drive(root)
    assert line["correct"] is True
    assert {"tpot_p95_ms", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0


def test_a_traced_run_reports_the_serving_readers_and_leaves_out_what_it_cannot_read(root):
    line = drive(root, trace=True)
    assert line["correct"] is True
    assert {"decode_step_ms", "decode_occupancy", "loop_iteration_ms", "loop_host_ms",
            "compiles_in_window.serve"} <= set(line["metrics"])
    # no TPU plane on the CPU: the kernel's readers find no op to read and say nothing
    assert not set(tiny_retention.NEW_METRICS) & set(line["metrics"])


@pytest.mark.parametrize("name, device_ops, expect", [
    ("retention_step_roofline", {"retention_step(tpu_custom_call)": 0.5, "fusion": 0.5}, True),
    ("retention_time_share", {"retention_step(tpu_custom_call)": 0.25, "fusion": 0.75}, True),
    ("retention_step_roofline", {"fusion": 1.0}, False),
    ("retention_time_share", {"fusion": 1.0}, False),
])
def test_the_kernel_readers_read_the_op_by_name(name, device_ops, expect):
    counters = {"retention_calls": {"layers": 8, "kv_heads": 8, "q_heads": 40, "d": 9216,
                                    "value_width": 128},
                "step_occupancy": [1.0, 0.5], "step_seconds": [0.1] * 10, "max_slots": 16}
    view = {"counters": counters, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "trace": {"ops": device_ops, "busy_s": 1.0, "window_s": 1.0}}
    value = harness.load_reader(name).read(view)
    if not expect:
        assert value is None
    elif name == "retention_time_share":
        assert value == pytest.approx(25.0)
    else:  # 12 slots x 8 x 8 x (9216 x 129) x 4 x 2 bytes a step, 50 ms of kernels a step
        want = 12 * 8 * (8 * 9216 * 129 * 8 + 96 * 128 * 4) / 819e9 / 0.05 * 100
        assert value == pytest.approx(want, rel=1e-6) and 0 < value < 100
    assert harness.load_reader(name).read(dict(view, counters={})) is None


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


def test_a_state_update_left_out_for_one_layer_is_not_correct(root, monkeypatch):
    """The decode step forgets to add the new token to layer 1's state."""
    from paddle_tpu.models import retention_lm
    from paddle_tpu.ops.pallas import retention as kernel

    real = kernel.retention_step

    def forgetful(state, phi_q, phi_k, v_aug, g, *, layer):
        return real(state, phi_q, phi_k * (layer != 1), v_aug, g, layer=layer)

    monkeypatch.setattr(kernel, "retention_step", forgetful)
    line = drive(root)
    assert line["correct"] is False and line["failed"] == 0
    assert retention_lm.state_dim({"head_dim": 16, "ret_tile": 8}) == 192


def test_a_chunk_that_drops_the_state_it_was_handed_is_not_correct(root, monkeypatch):
    """Every prefill chunk starts from a zero state: with gates near 1 what
    the earlier chunks of a prompt held is missed in the served tokens."""
    from paddle_tpu.models import retention_lm

    real = retention_lm.retention_chunk

    def amnesiac(q, k, v_aug, log_g, s0, **kw):
        return real(q, k, v_aug, log_g, None if s0 is None else s0 * 0.0, **kw)

    monkeypatch.setattr(retention_lm, "retention_chunk", amnesiac)
    line = drive(root)
    assert line["correct"] is False and line["failed"] == 0


def test_a_slot_whose_state_is_not_started_over_is_not_correct(root, monkeypatch):
    """A chunk at position 0 keeps what the slot's last request left."""
    from paddle_tpu.models import retention_lm

    real = retention_lm._retain_chunk

    def stale(cfg, box, slot, pos0, valid):
        return real(cfg, box, slot, pos0 + 1, valid)

    monkeypatch.setattr(retention_lm, "_retain_chunk", stale)
    line = drive(root)
    assert line["correct"] is False and line["failed"] == 0


def test_the_fp8_control_puts_other_tokens_first(root):
    loaded = harness.load_cell(CELL, root)
    run = harness.Run(loaded, jax.devices()[:1], 17, 0.0, False, time.perf_counter())
    family, _, shapes = serve_closed.prepare(run)
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(1, 97, 40, dtype=np.int32),
               "tokens": rng.integers(1, 97, 24, dtype=np.int32)} for _ in range(4)]
    gaps = serve_closed_layerwise.served_gaps(run, family, shapes, sample, ("f32", "fp8"))
    assert max(gaps["fp8"]) > run.limits["served_gap_sigmas"]
    assert min(gaps["f32"]) >= 0 and len(gaps["f32"]) == 96


def test_the_layerwise_walk_is_the_whole_models_forward_pass(root):
    """One layer's weights at a time gives what all of them at once give."""
    from benchmarks import weights
    from benchmarks.references import common as refc

    loaded = harness.load_cell(CELL, root)
    run = harness.Run(loaded, jax.devices()[:1], 5, 0.0, False, time.perf_counter())
    family, _, shapes = serve_closed.prepare(run)
    rng = np.random.default_rng(1)
    sample = [{"prompt": rng.integers(1, 97, 30, dtype=np.int32),
               "tokens": rng.integers(1, 97, 10, dtype=np.int32)}]
    rows = serve_closed_layerwise.reference_rows(run, family, shapes, sample, refc.mm_f32)[0]
    params = weights.make_weights(weights.as_float32(shapes), run.seed)
    ids = np.concatenate([sample[0]["prompt"], sample[0]["tokens"]])[None]
    whole = family.reference_logits(run.config, refc.mm_f32)(params, ids)[0, 29:39]
    np.testing.assert_allclose(rows, np.asarray(whole), rtol=2e-4, atol=2e-4)
