"""Tiny fixtures for the CPU self-tests: a temporary root holding a manifest,
tiny configurations and mixes, found by the harness exactly as the real
ones are. Numbers from these runs are rehearsals, never device metrics."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))

LM = {
    "name": "lm_tiny", "family": "decoder_lm",
    "model": {"vocab": 97, "d_model": 32, "d_inner": 64, "num_heads": 4, "n_layers": 1,
              "max_len": 64, "pos_encoding": "sinusoid", "ffn_activation": "relu",
              "attn_dropout": 0.0, "relu_dropout": 0.0, "residual_dropout": 0.0},
    "train": {"scan_layers": True}, "serve": {"scan_layers": False},
    "optimizer": {"name": "adam", "schedule": "constant", "learning_rate": 0.001,
                  "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-08},
    "flags": {"use_bf16_compute": False, "use_flash_attention": False},
}
NMT = {
    "name": "nmt_tiny", "family": "encdec_nmt",
    "model": {"src_vocab": 53, "trg_vocab": 61, "d_model": 32, "d_inner": 64, "num_heads": 4,
              "n_layers": 1, "max_len": 16, "label_smooth_eps": 0.1,
              "attn_dropout": 0.0, "relu_dropout": 0.0, "residual_dropout": 0.0},
    "train": {"scan_layers": True},
    "optimizer": {"name": "adam", "schedule": "noam", "learning_rate": 2.0, "warmup_steps": 8000,
                  "d_model": 32, "beta1": 0.9, "beta2": 0.98, "epsilon": 1e-09},
    "flags": {"use_bf16_compute": False, "use_flash_attention": False},
}
MIXES = {
    "train_rows": {"driver": "train_pool", "batch": 4, "seq_len": 16, "pool": 4,
                   "check_steps": 3, "warm_steps": 1, "ref_block_rows": 2, "trace_seconds": 1},
    "train_pairs": {"driver": "train_pool", "batch": 8, "pad_to": 16,
                    "len_lo": 8, "len_hi": 16, "pool": 4, "check_steps": 3, "warm_steps": 1,
                    "ref_block_rows": 4, "trace_seconds": 1},
    "serve_closed": {"driver": "serve_closed", "clients": 3, "rounds": 4,
                     "prompt_len": {"median": 10, "sigma": 0.5, "lo": 4, "hi": 24},
                     "output_len": {"median": 5, "sigma": 0.4, "lo": 3, "hi": 8},
                     "engine": {"max_slots": 3, "page_size": 8, "max_context": 64,
                                "prefill_chunk": 8},
                     "check_requests": 4, "request_timeout_s": 60, "trace_seconds": 1},
}
F32_LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3, "grad_diff_gap": 1e-3,
              "delta_norm_gap": 0.02,
              "served_gap_sigmas": 1e-3}


def make_root(tmp: str, chips: int = 1) -> str:
    """A root with three tiny cells; returns its path."""
    bench = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    shutil.copytree(os.path.join(HERE, "layer_metrics"), os.path.join(bench, "layer_metrics"))
    cells = [("lm_tiny.train_rows", "lm_tiny", "train_rows"),
             ("lm_tiny.serve_closed", "lm_tiny", "serve_closed"),
             ("nmt_tiny.train_pairs", "nmt_tiny", "train_pairs")]
    for cfg in (LM, NMT):
        with open(os.path.join(bench, "configs", cfg["name"] + ".json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for cell, _, _ in cells:
        with open(os.path.join(bench, "workloads", cell + ".json"), "w") as f:
            json.dump({"name": cell, "limits": F32_LIMITS}, f)
    real = json.load(open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")))
    manifest = dict(real)
    manifest["configs"] = [{"name": c["name"], "file": f"benchmarks/configs/{c['name']}.json"}
                           for c in (LM, NMT)]
    manifest["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": chips}
                             for n, c, t in cells]
    kinds = {".train": [n for n, _, _ in cells if ".train" in n],
             ".serve": [n for n, _, _ in cells if ".serve" in n]}
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [dict(m) for m in real[group]]
        for m in manifest[group]:
            if "workloads" in m:  # the tiny cells of the same kinds as the real ones
                m["workloads"] = sorted({c for w in m["workloads"] for k, cs in kinds.items()
                                         if k in w for c in cs})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return tmp
