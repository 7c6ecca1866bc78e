"""Tiny fixture of the power-retention family for the CPU self-tests: the
root ``tiny.make_root`` makes, plus one configuration (widths 64, 4 query and
2 key-value heads of 16, 2 layers, vocabulary 97, float32), one mix of the
``serve_closed_layerwise`` driver and their cell, added to the temporary
manifest as the real ones are added to ``BENCHMARK.json``: appended, with the
cell on the lists of the serving metrics. Numbers from these runs are
rehearsals, never device metrics."""

from __future__ import annotations

import json
import os

from benchmarks import tiny

CELL = "brumby_tiny.serve_docs"
CONFIG = {
    "name": "brumby_tiny", "family": "retention_lm",
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 97,
    # gates near 1 (the shift is logit(0.999)), as in ``brumby_14b``: a state
    # outlives a chunk, so what one chunk hands the next is weighed
    "model": {"vocab": 97, "max_len": 64, "ret_tile": 8, "ret_eps": 1e-06,
              "ret_gate_shift": 6.906768,
              "param_dtype": "float32", "compute_dtype": "float32"},
    "serve": {}, "flags": {},
}
MIX = {"driver": "serve_closed_layerwise", "clients": 3, "rounds": 4,
       "prompt_len": {"median": 18, "sigma": 0.5, "lo": 6, "hi": 40},
       "output_len": {"median": 5, "sigma": 0.4, "lo": 3, "hi": 8},
       "engine": {"max_slots": 3, "page_size": 8, "max_context": 64, "prefill_chunk": 8},
       "check_requests": 4, "request_timeout_s": 60, "trace_seconds": 1}
NEW_METRICS = ("retention_step_roofline", "retention_time_share")


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s root with the retention cell added; returns it."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    for sub, name, data in (("configs", "brumby_tiny", CONFIG), ("traffic", "serve_docs", MIX),
                            ("workloads", CELL, {"name": CELL, "limits": tiny.F32_LIMITS})):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "brumby_tiny",
                                "file": "benchmarks/configs/brumby_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "brumby_tiny",
                                  "traffic": "serve_docs", "chips": 1})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "lm_tiny.serve_closed" in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
