"""Program side of the hybrid Mamba-2 / attention LM family:
``models.hybrid_ssm_lm`` through ``serving.DecodeEngine`` (and ``pt.Trainer``
at sizes that fit). The plain reference is ``references/hybrid_ssm_lm.py``.

A configuration of this family holds the published config's keys at its top
level, under their published names; the program's names for them are derived
here, so each number is written once. Its ``model`` group holds what the
published config does not give (``max_len``, the constants the seeded weights
need) and ``vocab``, which the serve drivers read."""

from __future__ import annotations

import functools

from benchmarks.families import _common

REFERENCE = "hybrid_ssm_lm"

PUBLISHED = {"d_model": "hidden_size", "d_inner": "shared_intermediate_size",
             "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
             "layer_types": "layer_types", "ssm_heads": "mamba_n_heads",
             "ssm_head_dim": "mamba_d_head", "ssm_state": "mamba_d_state",
             "ssm_groups": "mamba_n_groups", "ssm_conv": "mamba_d_conv",
             "ssm_chunk": "mamba_chunk_size", "embedding_multiplier": "embedding_multiplier",
             "residual_multiplier": "residual_multiplier",
             "attention_multiplier": "attention_multiplier", "logits_scaling": "logits_scaling",
             "rms_eps": "rms_norm_eps", "vocab": "vocab_size"}


def model_cfg(config: dict) -> dict:
    """The keys the program and the reference both read: the published
    numbers under the program's names, then the ``model`` group."""
    for key in ("attention_bias", "mamba_proj_bias", "rope_scaling", "num_local_experts",
                "num_experts_per_tok"):
        if config[key]:
            raise ValueError(f"family hybrid_ssm_lm has no {key}")
    for key, want in (("tie_word_embeddings", True), ("mamba_conv_bias", True),
                      ("hidden_act", "silu"), ("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm")):
        if config[key] != want:
            raise ValueError(f"family hybrid_ssm_lm has {key} {want!r} only, not {config[key]!r}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers differ")
    if config["mamba_expand"] * config["hidden_size"] != (config["mamba_n_heads"]
                                                         * config["mamba_d_head"]):
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x mamba_d_head")
    if config["shared_intermediate_size"] != config["intermediate_size"]:
        raise ValueError("a dense model's MLP is the shared one: the two widths differ")
    cfg = {ours: config[theirs] for ours, theirs in PUBLISHED.items()}
    cfg["layer_types"] = tuple(cfg["layer_types"])
    cfg["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    if config["model"]["vocab"] != cfg["vocab"]:
        raise ValueError("model.vocab and vocab_size differ")
    return dict(cfg, **config["model"])


def build_model(config: dict, seq_len: int, mode: str):
    """(model, program cfg) for ``mode`` ``train`` or ``serve``."""
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("hybrid_ssm_lm", seq_len=seq_len,
                            **model_cfg(config), **config[mode])
    return spec.model, spec.extra["cfg"]


def reference(config: dict, mm):
    """(embed, layer, logits_at) of the plain reference, each closed over
    the configuration and the matmul."""
    from benchmarks.references import hybrid_ssm_lm as ref

    cfg = model_cfg(config)
    return (functools.partial(ref.embed, cfg=cfg), functools.partial(ref.layer, cfg=cfg, mm=mm),
            functools.partial(ref.logits_at, cfg=cfg, mm=mm))


def reference_logits(config: dict, mm):
    from benchmarks.references import hybrid_ssm_lm as ref

    return functools.partial(ref.logits_fn, cfg=model_cfg(config), mm=mm)


def ssm_calls(config: dict) -> dict:
    """Shapes of the ``ssm_step`` kernel calls of one decode step, and the
    bytes a step has to move beside the states, for ``benchmarks/ssm_bytes.py``."""
    import math

    from paddle_tpu.models import hybrid_ssm_lm as hm

    cfg = dict(hm.BASE_CFG, **model_cfg(config))
    params = sum(math.prod(s) for s in hm.param_shapes(cfg).values())
    kv_heads = cfg["num_kv_heads"] or cfg["num_heads"]
    return {"layers": len(hm.layers_of(cfg, hm.MAMBA)), "heads": cfg["ssm_heads"],
            "head_dim": cfg["ssm_head_dim"], "state": cfg["ssm_state"],
            "conv": cfg["ssm_conv"], "conv_channels": hm.conv_width(cfg),
            "attention_layers": len(hm.layers_of(cfg, hm.ATTENTION)),
            "kv_row_bytes": 2 * kv_heads * cfg["head_dim"], "weight_bytes": 2 * params}


def make_engine(config: dict, weights: dict, engine_kwargs: dict):
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    _, cfg = build_model(config, config["model"]["max_len"], "serve")
    return DecodeEngine(_common.variables_from(weights), cfg,
                        decode=DecodeConfig(**engine_kwargs))
