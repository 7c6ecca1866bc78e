"""Tiny fixture of the latent-attention, sparse-expert family for the CPU
self-tests: the root ``tiny.make_root`` makes, plus one configuration (hidden
64, 4 heads of 16 + 8, a latent of 32, three layers of which two are expert
layers of 8 experts, 4 of them held, 2 a token; vocabulary 97; float32), one
mix of the ``serve_closed_experts`` driver and their cell, added to the
temporary manifest as the real ones are added to ``BENCHMARK.json``: appended,
with the cell on the lists of the serving metrics and of the three expert
readers. Numbers from these runs are rehearsals, never device metrics."""

from __future__ import annotations

import json
import os

from benchmarks import tiny

CELL = "sarvam_tiny.serve_docs"
CONFIG = {
    "name": "sarvam_tiny", "family": "latent_moe_lm",
    "published": {"num_hidden_layers": 3, "num_experts": 8, "vocab_size": 97},
    "first_k_dense_replace": 1, "head_dim": 40, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 96, "kv_lora_rank": 32, "moe_intermediate_size": 32,
    "moe_router_enable_expert_bias": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3, "num_shared_experts": 1,
    "q_head_dim": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06,
    # the ramp and the scaled frequencies are exercised within the 64 positions
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
    "use_qk_norm": True, "v_head_dim": 16, "vocab_size": 97,
    # experts 2-5 of the router's 8 are held: neither the first nor the last
    "model": {"vocab": 97, "max_len": 64, "first_expert_held": 2,
              "param_dtype": "float32", "compute_dtype": "float32"},
    "serve": {}, "flags": {},
}
MIX = {"driver": "serve_closed_experts", "clients": 3, "rounds": 4,
       "prompt_len": {"median": 18, "sigma": 0.5, "lo": 6, "hi": 40},
       "output_len": {"median": 5, "sigma": 0.4, "lo": 3, "hi": 8},
       "engine": {"max_slots": 3, "page_size": 8, "max_context": 64, "prefill_chunk": 8},
       "check_requests": 4, "request_timeout_s": 60, "trace_seconds": 1}
NEW_METRICS = ("moe_gmm_roofline", "moe_time_share", "moe_load_max_over_mean")


def as_checkpoint(params: dict, held) -> dict:
    """The program's parameters as the checkpoint the reference reads and
    ``stack_experts`` loads: each stacked expert leaf ``<m>/experts/<which>/w``
    [count, a, b] as ``<m>/experts/<e>/<which>/w`` [a, b] for the ``held``
    (first, count) experts; what ``families.latent_moe_lm.checkpoint_shapes``
    does to shapes, for values."""
    first, count = held
    out = {}
    for name, w in params.items():
        if "/moe/experts/" in name or name.startswith("moe/experts/"):
            head, which, leaf = name.rsplit("/", 2)
            out.update({f"{head}/{first + j}/{which}/{leaf}": w[j] for j in range(count)})
        else:
            out[name] = w
    return out


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s root with the expert cell added; returns it."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    for sub, name, data in (("configs", "sarvam_tiny", CONFIG), ("traffic", "serve_docs", MIX),
                            ("workloads", CELL, {"name": CELL, "limits": dict(tiny.F32_LIMITS,
                                                                   served_far_share=0.0)})):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "sarvam_tiny",
                                "file": "benchmarks/configs/sarvam_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "sarvam_tiny",
                                  "traffic": "serve_docs", "chips": 1})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "lm_tiny.serve_closed" in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
