"""Tiny fixture of the single-mixer Mamba-2 / attention / latent-expert family
for the CPU self-tests: the root ``tiny.make_root`` makes, plus one
configuration (width 64, pattern ``*EMEM``, 4 query and 2 key-value heads of
16, 8 SSM heads of 16 in 2 groups with 8 state numbers, blocks of 4, a
16-wide router of which experts 4-11 are held, 4 a token, latent 32, experts
of 48, a shared expert of 96, vocabulary 97, float32) under the published
config's key names, one mix of the ``serve_closed_hybrid_experts`` driver and
their cell, added to the temporary manifest as the real ones are added to
``BENCHMARK.json``: appended, with the cell on the lists of the serving
metrics and of the readers the real cell is listed under. Numbers from these
runs are rehearsals, never device metrics."""

from __future__ import annotations

import json
import os

from benchmarks import tiny

CELL = "nemotron_tiny.serve_chat"
CONFIG = {
    "name": "nemotron_tiny", "family": "hybrid_moe_lm",
    "published": {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 97},
    "attention_bias": False, "chunk_size": 4, "conv_kernel": 4, "expand": 2, "head_dim": 16,
    "hidden_size": 64, "hybrid_override_pattern": "*EMEM", "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 16, "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_group": 1, "n_groups": 2,
    "n_routed_experts": 8, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 8, "tie_word_embeddings": False, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "vocab_size": 97,
    # as in ``nemotron_3_super_120b_a12b``: a state outlives a chunk, the taps
    # weigh something and the attention layer is peaked, so that what one chunk
    # hands the next and the groups move served tokens; experts 4-11 of the
    # router's 16 are held: neither the first nor the last
    "model": {"vocab": 97, "max_len": 64, "first_expert_held": 4, "ssm_dt_shift": -3.5,
              "ssm_conv_gain": 8.0, "attn_q_gain": 4.0, "param_dtype": "float32",
              "compute_dtype": "float32"},
    "serve": {}, "flags": {},
}
MIX = {"driver": "serve_closed_hybrid_experts", "clients": 3, "rounds": 4, "deal_seed": 5,
       "prompt_len": {"median": 18, "sigma": 0.5, "lo": 6, "hi": 40},
       "output_len": {"median": 16, "sigma": 0.3, "lo": 12, "hi": 24},
       "engine": {"max_slots": 3, "page_size": 8, "max_context": 64, "prefill_chunk": 8},
       "check_requests": 4, "request_timeout_s": 60, "trace_seconds": 1}
# the readers the real cell is listed under beside the serving metrics
NEW_METRICS = ("ssm_step_roofline", "ssm_time_share", "paged_attend_roofline", "moe_time_share",
               "moe_load_max_over_mean", "latent_expert_gmm_roofline")


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s root with the cell added; returns it."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    limits = dict(tiny.F32_LIMITS, served_far_share=0.0)
    for sub, name, data in (("configs", "nemotron_tiny", CONFIG), ("traffic", "serve_chat_moe", MIX),
                            ("workloads", CELL, {"name": CELL, "limits": limits})):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "nemotron_tiny",
                                "file": "benchmarks/configs/nemotron_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "nemotron_tiny",
                                  "traffic": "serve_chat_moe", "chips": 1})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "lm_tiny.serve_closed" in m.get("workloads", ()) or m["name"] in NEW_METRICS:
                m["workloads"] = list(m["workloads"]) + [CELL]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
