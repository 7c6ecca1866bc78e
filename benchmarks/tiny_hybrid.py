"""Tiny fixture of the hybrid Mamba-2 / attention family for the CPU
self-tests: the root ``tiny.make_root`` makes, plus one configuration (width
64, 4 query and 2 key-value heads of 16, 8 SSM heads of 16 with 8 state
numbers, blocks of 4, six layers of which two attend, vocabulary 97, float32)
under the published config's key names, one mix of the ``serve_closed_hybrid``
driver and their cell, added to the temporary manifest as the real ones are
added to ``BENCHMARK.json``: appended, with the cell on the lists of the
serving metrics. Numbers from these runs are rehearsals, never device
metrics."""

from __future__ import annotations

import json
import os

from benchmarks import tiny

CELL = "granite_tiny.serve_chat"
CONFIG = {
    "name": "granite_tiny", "family": "hybrid_ssm_lm",
    "attention_bias": False, "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "attention", "mamba"],
    "logits_scaling": 8, "mamba_chunk_size": 4, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_state": 8, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 8, "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 0, "num_hidden_layers": 6,
    "num_key_value_heads": 2, "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "shared_intermediate_size": 128, "tie_word_embeddings": True, "vocab_size": 97,
    # as in ``granite_4_0_h_micro``: a state outlives a chunk, the taps weigh
    # something, an attention layer is peaked and the layers' outputs outweigh
    # the token's own embedding under the tied head, so that what one chunk
    # hands the next, a slot's reset and the planes all move served tokens
    "model": {"vocab": 97, "max_len": 64, "ssm_dt_shift": -3.5, "ssm_conv_gain": 8.0,
              "attn_q_gain": 8.0, "branch_gain": 8.0, "param_dtype": "float32", "compute_dtype": "float32"},
    "serve": {}, "flags": {},
}
MIX = {"driver": "serve_closed_hybrid", "clients": 3, "rounds": 4, "deal_seed": 5,
       "prompt_len": {"median": 18, "sigma": 0.5, "lo": 6, "hi": 40},
       "output_len": {"median": 16, "sigma": 0.3, "lo": 12, "hi": 24},
       "engine": {"max_slots": 3, "page_size": 8, "max_context": 64, "prefill_chunk": 8},
       "check_requests": 8, "request_timeout_s": 60, "trace_seconds": 1}
NEW_METRICS = ("ssm_step_roofline", "ssm_time_share", "state_bytes_share",
               "paged_attend_roofline")


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s root with the hybrid cell added; returns it."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    for sub, name, data in (("configs", "granite_tiny", CONFIG), ("traffic", "serve_chat", MIX),
                            ("workloads", CELL, {"name": CELL, "limits": tiny.F32_LIMITS})):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "granite_tiny",
                                "file": "benchmarks/configs/granite_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "granite_tiny",
                                  "traffic": "serve_chat", "chips": 1})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "lm_tiny.serve_closed" in m.get("workloads", ()) or m["name"] in NEW_METRICS:
                m["workloads"] = list(m["workloads"]) + [CELL]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
