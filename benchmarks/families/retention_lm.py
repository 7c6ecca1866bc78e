"""Program side of the power-retention LM family: ``models.retention_lm``
through ``serving.DecodeEngine`` (and ``pt.Trainer`` at sizes that fit).
The plain reference is ``references/retention_lm.py``.

A configuration of this family holds the published config's keys at its top
level, under their published names; the program's names for them are derived
here, so each number is written once. Its ``model`` group holds what the
published config does not give (``assumed``) and ``vocab``, which the serve
drivers read."""

from __future__ import annotations

import functools

from benchmarks.families import _common

REFERENCE = "retention_lm"

PUBLISHED = {"d_model": "hidden_size", "d_inner": "intermediate_size",
             "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
             "head_dim": "head_dim", "n_layers": "num_hidden_layers",
             "rope_theta": "rope_theta", "rms_eps": "rms_norm_eps", "vocab": "vocab_size"}


def model_cfg(config: dict) -> dict:
    """The keys the program and the reference both read: the published
    numbers under the program's names, then the ``model`` group."""
    for key in ("attention_bias", "tie_word_embeddings", "use_sliding_window"):
        if config[key]:
            raise ValueError(f"family retention_lm has no {key}")
    if config["hidden_act"] != "silu":
        raise ValueError(f"family retention_lm has no {config['hidden_act']!r} MLP")
    cfg = {ours: config[theirs] for ours, theirs in PUBLISHED.items()}
    if config["model"]["vocab"] != cfg["vocab"]:
        raise ValueError("model.vocab and vocab_size differ")
    return dict(cfg, **config["model"])


def build_model(config: dict, seq_len: int, mode: str):
    """(model, program cfg) for ``mode`` ``train`` or ``serve``."""
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("retention_lm", seq_len=seq_len,
                            **model_cfg(config), **config[mode])
    return spec.model, spec.extra["cfg"]


def reference(config: dict, mm):
    """(embed, layer, logits_at) of the plain reference, each closed over
    the configuration and the matmul."""
    from benchmarks.references import retention_lm as ref

    cfg = model_cfg(config)
    return (ref.embed, functools.partial(ref.layer, cfg=cfg, mm=mm),
            functools.partial(ref.logits_at, cfg=cfg, mm=mm))


def reference_logits(config: dict, mm):
    from benchmarks.references import retention_lm as ref

    return functools.partial(ref.logits_fn, cfg=model_cfg(config), mm=mm)


def retention_calls(config: dict) -> dict:
    """Shapes of the ``retention_step`` kernel calls of one decode step."""
    cfg = model_cfg(config)
    tiles = cfg["head_dim"] // cfg["ret_tile"]
    return {"layers": cfg["n_layers"], "kv_heads": cfg["num_kv_heads"],
            "q_heads": cfg["num_heads"], "value_width": cfg["head_dim"],
            "d": tiles * (tiles + 1) // 2 * cfg["ret_tile"] ** 2}


def make_engine(config: dict, weights: dict, engine_kwargs: dict):
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    _, cfg = build_model(config, config["model"]["max_len"], "serve")
    return DecodeEngine(_common.variables_from(weights), cfg,
                        decode=DecodeConfig(**engine_kwargs))
