"""Program side of the looped decoder LM family: ``models.looped_lm`` through
``serving.DecodeEngine`` (and ``pt.Trainer`` at sizes that fit). The plain
reference is ``references/looped_lm.py``.

A configuration of this family holds the published config's keys at its top
level, under their published names; the program's names for them are derived
here, so each number is written once. Its ``model`` group holds what the
published config does not give and ``vocab``, which the serve drivers read."""

from __future__ import annotations

import functools
import math

from benchmarks.families import _common

REFERENCE = "looped_lm"

PUBLISHED = {"d_model": "hidden_size", "d_inner": "intermediate_size",
             "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
             "head_dim": "head_dim", "n_layers": "num_hidden_layers",
             "total_ut_steps": "total_ut_steps", "early_exit_threshold": "early_exit_threshold",
             "rope_theta": "rope_theta", "rms_eps": "rms_norm_eps", "vocab": "vocab_size"}


def model_cfg(config: dict) -> dict:
    """The keys the program and the reference both read: the published
    numbers under the program's names, then the ``model`` group."""
    for key in ("tie_word_embeddings", "use_sliding_window", "rope_scaling", "sliding_window"):
        if config[key]:
            raise ValueError(f"family looped_lm has no {key}")
    if config["hidden_act"] != "silu":
        raise ValueError(f"family looped_lm has no {config['hidden_act']!r} MLP")
    if set(config["layer_types"]) != {"full_attention"}:
        raise ValueError("family looped_lm has full-attention layers only")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers differ")
    cfg = {ours: config[theirs] for ours, theirs in PUBLISHED.items()}
    if config["model"]["vocab"] != cfg["vocab"]:
        raise ValueError("model.vocab and vocab_size differ")
    return dict(cfg, **config["model"])


def build_model(config: dict, seq_len: int, mode: str):
    """(model, program cfg) for ``mode`` ``train`` or ``serve``."""
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("looped_lm", seq_len=seq_len, **model_cfg(config), **config[mode])
    return spec.model, spec.extra["cfg"]


def checkpoint_shapes(config: dict, shapes: dict) -> dict:
    """The program's parameter shapes with every stacked leaf
    ``layers/<suffix>`` [L, ...] as the leaves a published checkpoint holds,
    ``layer_<i>/<suffix>`` [...]. ``weights.py`` seeds a leaf by its own name
    and fans, so a layer's matrices get a layer's fans and not the stack's;
    the reference reads them as they are, the program through its loader
    (:func:`make_engine`)."""
    import jax

    out = {}
    for name, s in shapes.items():
        if not name.startswith("layers/"):
            out[name] = s
            continue
        for i in range(s.shape[0]):
            out[f"layer_{i}/{name[len('layers/'):]}"] = jax.ShapeDtypeStruct(s.shape[1:], s.dtype)
    return out


def reference(config: dict, mm):
    """(embed, layer, close_pass, logits_at) of the plain reference, each
    closed over the configuration and the matmul."""
    from benchmarks.references import looped_lm as ref

    cfg = model_cfg(config)
    return (ref.embed, functools.partial(ref.layer, cfg=cfg, mm=mm),
            functools.partial(ref.close_pass, cfg=cfg), functools.partial(ref.logits_at, mm=mm))


def reference_logits(config: dict, mm):
    from benchmarks.references import looped_lm as ref

    return functools.partial(ref.logits_fn, cfg=model_cfg(config), mm=mm)


def loop_calls(config: dict) -> dict:
    """Static shapes of one program call, for ``benchmarks/loop_bytes.py``:
    the passes and layers of the loop, a layer's weight bytes and
    parameters, the head's and an embedding row's bytes, the bytes of one
    cached K or V row (all heads of one position in one plane)."""
    from paddle_tpu.models import looped_lm

    cfg = model_cfg(config)
    shapes = looped_lm.param_shapes(dict(looped_lm.BASE_CFG, **cfg))
    layer = sum(math.prod(s[1:]) for n, s in shapes.items() if n.startswith("layers/"))
    head = math.prod(shapes["head/w"])
    kv_heads = cfg["num_kv_heads"] or cfg["num_heads"]
    return {"passes": cfg["total_ut_steps"], "layers": cfg["n_layers"],
            "layer_params": layer, "layer_bytes": 2 * layer,
            "head_params": head, "head_bytes": 2 * head,
            "embed_row_bytes": 2 * cfg["d_model"],
            "row_bytes": 2 * kv_heads * cfg["head_dim"],
            "q_width": cfg["num_heads"] * cfg["head_dim"]}


def make_engine(config: dict, weights: dict, engine_kwargs: dict):
    """The engine over ``weights`` in the checkpoint's form
    (:func:`checkpoint_shapes`), which the program's loader stacks and
    **empties**: the caller's dict is what still holds the per-layer arrays,
    and the chip has no room for them beside their stacks."""
    from paddle_tpu.models import looped_lm
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    _, cfg = build_model(config, config["model"]["max_len"], "serve")
    return DecodeEngine(_common.variables_from(looped_lm.stack_layers(weights, cfg)), cfg,
                        decode=DecodeConfig(**engine_kwargs))
