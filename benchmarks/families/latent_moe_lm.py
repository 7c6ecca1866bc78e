"""Program side of the latent-attention, sparse-expert LM family:
``models.latent_moe_lm`` through ``serving.DecodeEngine`` (and ``pt.Trainer``
at sizes that fit). The plain reference is ``references/latent_moe_lm.py``.

A configuration of this family holds the published config's keys at its top
level, under their published names; the program's names for them are derived
here, so each number is written once. ``num_experts`` there counts the experts
**held on this chip** (it is listed in ``reduced``); the router's width is the
published count, ``published.num_experts``. The ``model`` group holds what the
published config does not give (``assumed``), the first expert held, and
``vocab``, which the serve drivers read."""

from __future__ import annotations

import functools

from benchmarks.families import _common

REFERENCE = "latent_moe_lm"

PUBLISHED = {"d_model": "hidden_size", "d_inner": "intermediate_size",
             "moe_d_inner": "moe_intermediate_size", "num_heads": "num_attention_heads",
             "qk_nope_dim": "qk_nope_head_dim", "qk_rope_dim": "qk_rope_head_dim",
             "v_head_dim": "v_head_dim", "kv_lora_rank": "kv_lora_rank",
             "n_layers": "num_hidden_layers", "first_dense": "first_k_dense_replace",
             "experts_per_token": "num_experts_per_tok",
             "routed_scaling": "routed_scaling_factor", "rope_theta": "rope_theta",
             "rope_scaling": "rope_scaling", "rms_eps": "rms_norm_eps", "vocab": "vocab_size"}


def model_cfg(config: dict) -> dict:
    """The keys the program and the reference both read: the published
    numbers under the program's names, the experts held, then the ``model``
    group."""
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("family latent_moe_lm has an untied head and a SwiGLU only")
    if not (config["use_qk_norm"] and config["moe_router_enable_expert_bias"]
            and config["num_shared_experts"] == 1):
        raise ValueError("family latent_moe_lm has QK-norm, a selection bias and one "
                         "shared expert, always")
    cfg = {ours: config[theirs] for ours, theirs in PUBLISHED.items()}
    if (config["q_head_dim"] != cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
            or config["head_dim"] != cfg["kv_lora_rank"] + cfg["qk_rope_dim"]):
        raise ValueError("q_head_dim or head_dim is not the sum of its parts")
    if config["model"]["vocab"] != cfg["vocab"]:
        raise ValueError("model.vocab and vocab_size differ")
    model = dict(config["model"])
    cfg["num_experts"] = config["published"]["num_experts"]
    cfg["experts_held"] = (model.pop("first_expert_held"), config["num_experts"])
    return dict(cfg, **model)


def build_model(config: dict, seq_len: int, mode: str):
    """(model, program cfg) for ``mode`` ``train`` or ``serve``."""
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("latent_moe_lm", seq_len=seq_len,
                            **model_cfg(config), **config[mode])
    return spec.model, spec.extra["cfg"]


def checkpoint_shapes(config: dict, shapes: dict) -> dict:
    """The program's parameter shapes with every stacked expert leaf
    ``<m>/experts/<gate|fc1|fc2>/w`` [count, a, b] as the matrices a
    published checkpoint holds, ``<m>/experts/<e>/<gate|fc1|fc2>/w`` [a, b],
    ``e`` the expert's index in the router's width. ``weights.py`` seeds a
    leaf by its own name and fans, so a routed expert's matrices get what
    the shared expert's get; the reference reads them as they are, the
    program through its loader (:func:`make_engine`)."""
    import jax

    first, count = model_cfg(config)["experts_held"]
    out = {}
    for name, s in shapes.items():
        if "/moe/experts/" not in name:
            out[name] = s
            continue
        head, which, leaf = name.rsplit("/", 2)
        for j in range(count):
            out[f"{head}/{first + j}/{which}/{leaf}"] = jax.ShapeDtypeStruct(s.shape[1:], s.dtype)
    return out


def reference(config: dict, mm):
    """(embed, layer, logits_at) of the plain reference, each closed over
    the configuration and the matmul."""
    from benchmarks.references import latent_moe_lm as ref

    cfg = model_cfg(config)
    return (ref.embed, functools.partial(ref.layer, cfg=cfg, mm=mm),
            functools.partial(ref.logits_at, cfg=cfg, mm=mm))


def reference_logits(config: dict, mm):
    from benchmarks.references import latent_moe_lm as ref

    return functools.partial(ref.logits_fn, cfg=model_cfg(config), mm=mm)


def moe_calls(config: dict) -> dict:
    """Static shapes of the ``moe_gmm`` calls of one program call: per
    expert layer two products ``[pairs, d] x [d, f]`` and one ``[pairs, f] x
    [f, d]`` over the experts held; a step routes ``max_slots`` tokens, a
    chunk ``prefill_chunk``."""
    cfg = model_cfg(config)
    return {"layers": cfg["n_layers"] - cfg["first_dense"], "held": cfg["experts_held"][1],
            "router_width": cfg["num_experts"], "per_token": cfg["experts_per_token"],
            "d": cfg["d_model"], "f": cfg["moe_d_inner"], "itemsize": 2}


def make_engine(config: dict, weights: dict, engine_kwargs: dict):
    """The engine over ``weights`` in the checkpoint's form
    (:func:`checkpoint_shapes`), which the program's loader stacks and
    **empties**: the caller's dict is what still holds the per-expert
    arrays, and the chip has no room for them beside their stacks."""
    from paddle_tpu.models import latent_moe_lm
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    _, cfg = build_model(config, config["model"]["max_len"], "serve")
    return DecodeEngine(_common.variables_from(latent_moe_lm.stack_experts(weights, cfg)), cfg,
                        decode=DecodeConfig(**engine_kwargs))
