"""``latent_attend_time_share`` on recorded trace summaries: a share where
the trace names the latent family's kernels (the step's, the chunk's, both),
None where it names none (the parent's program, which gathers the table; a
run that was not traced), and the manifest's entry for it."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

NAME = "latent_attend_time_share"
CELL = "sarvam_105b.serve_docs32"


@pytest.mark.parametrize("device_ops, want", [
    ({"latent_attend_step(tpu_custom_call)": 0.25, "fusion": 0.75}, 25.0),
    ({"latent_attend_step(tpu_custom_call)": 0.1, "latent_attend_chunk(tpu_custom_call)": 0.3,
      "moe_gmm(tpu_custom_call)": 0.2, "fusion": 0.4}, 40.0),
    # the K and V kernel is another layer's, and the parent's trace has neither
    ({"paged_attend_step(tpu_custom_call)": 0.5, "fusion": 0.5}, None),
    ({"fusion": 0.8, "moe_gmm(tpu_custom_call)": 0.2}, None),
])
def test_the_reader_reads_the_kernels_by_name(device_ops, want):
    view = {"counters": {}, "peaks": {},
            "trace": {"ops": device_ops, "busy_s": 1.0, "window_s": 1.25}}
    read = harness.load_reader(NAME).read
    assert read(view) == (None if want is None else pytest.approx(want))
    assert read(dict(view, trace=None)) is None
    assert read(dict(view, trace=dict(view["trace"], busy_s=0.0))) is None


def test_the_manifest_lists_it_last_for_the_one_cell_that_runs_the_kernels():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
                     "layer": "attention kernel", "moves": "tpot_p95_ms", "workloads": [CELL]}
    tpot = next(m for m in manifest["end_to_end"] if m["name"] == "tpot_p95_ms")
    assert CELL in tpot["workloads"]
    # the K and V kernel's roofline stays with the cells whose pages are K and V
    roofline = next(m for m in manifest["per_layer"] if m["name"] == "paged_attend_roofline")
    assert CELL not in roofline["workloads"]
