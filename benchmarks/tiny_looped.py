"""Tiny fixture of the looped decoder family for the CPU self-tests: the root
``tiny.make_root`` makes, plus one configuration (width 64, 4 heads of 16, 3
layers run 3 passes, vocabulary 97, float32) under the published config's key
names, one mix of the ``serve_closed_looped`` driver and their cell, added to
the temporary manifest as the real ones are added to ``BENCHMARK.json``:
appended, with the cell on the lists of the serving metrics. Numbers from
these runs are rehearsals, never device metrics."""

from __future__ import annotations

import json
import os

from benchmarks import tiny

CELL = "ouro_tiny.serve_reason"
CONFIG = {
    "name": "ouro_tiny", "family": "looped_lm",
    "head_dim": 16, "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["full_attention"] * 3, "num_attention_heads": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 3, "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 97,
    "model": {"vocab": 97, "max_len": 64, "param_dtype": "float32", "compute_dtype": "float32"},
    "serve": {}, "flags": {},
}
MIX = {"driver": "serve_closed_looped", "clients": 3, "rounds": 4,
       "prompt_len": {"median": 10, "sigma": 0.5, "lo": 4, "hi": 24},
       "output_len": {"median": 6, "sigma": 0.3, "lo": 4, "hi": 9},
       # like the cell's, a chunk that does not divide the context
       "engine": {"max_slots": 3, "page_size": 8, "max_context": 64, "prefill_chunk": 24},
       "check_requests": 4, "request_timeout_s": 60, "trace_seconds": 1}
NEW_METRICS = ("looped_hbm_roofline",)


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s root with the looped cell added; returns it."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    for sub, name, data in (("configs", "ouro_tiny", CONFIG), ("traffic", "serve_reason", MIX),
                            ("workloads", CELL, {"name": CELL, "limits": dict(tiny.F32_LIMITS,
                                                                   served_far_share=0.0)})):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "ouro_tiny", "file": "benchmarks/configs/ouro_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "ouro_tiny",
                                  "traffic": "serve_reason", "chips": 1})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "lm_tiny.serve_closed" in m.get("workloads", ()) or m["name"] in NEW_METRICS:
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
