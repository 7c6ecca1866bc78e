"""Program side of the family whose layers are one mixer each, Mamba-2,
attention or a latent expert layer: ``models.hybrid_moe_lm`` through
``serving.DecodeEngine`` (and ``pt.Trainer`` at sizes that fit). The plain
reference is ``references/hybrid_moe_lm.py``.

A configuration of this family holds the published ``nemotron_h`` config's
keys at its top level, under their published names; the program's names for
them are derived here, so each number is written once. ``n_routed_experts``
there counts the experts **held on this chip** (it is listed in ``reduced``);
the router's width is the published count, ``published.n_routed_experts``.
The ``model`` group holds what the published config does not give
(``max_len``, the constants the seeded weights need), the first expert held,
and ``vocab``, which the serve drivers read."""

from __future__ import annotations

import functools

from benchmarks.families import _common

REFERENCE = "hybrid_moe_lm"

PUBLISHED = {"d_model": "hidden_size", "pattern": "hybrid_override_pattern",
             "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
             "head_dim": "head_dim", "ssm_heads": "mamba_num_heads",
             "ssm_head_dim": "mamba_head_dim", "ssm_state": "ssm_state_size",
             "ssm_groups": "n_groups", "ssm_conv": "conv_kernel", "ssm_chunk": "chunk_size",
             "experts_per_token": "num_experts_per_tok", "moe_latent": "moe_latent_size",
             "moe_d_inner": "moe_intermediate_size",
             "shared_d_inner": "moe_shared_expert_intermediate_size",
             "routed_scaling": "routed_scaling_factor", "rms_eps": "layer_norm_epsilon",
             "vocab": "vocab_size"}


def model_cfg(config: dict) -> dict:
    """The keys the program and the reference both read: the published
    numbers under the program's names, the experts held, then the ``model``
    group."""
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias",
                "tie_word_embeddings", "sliding_window"):
        if config[key]:
            raise ValueError(f"family hybrid_moe_lm has no {key}")
    for key, want in (("use_conv_bias", True), ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("n_shared_experts", 1)):
        if config[key] != want:
            raise ValueError(f"family hybrid_moe_lm has {key} {want!r} only, not {config[key]!r}")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers differ")
    if config["expand"] * config["hidden_size"] != (config["mamba_num_heads"]
                                                   * config["mamba_head_dim"]):
        raise ValueError("expand x hidden_size is not mamba_num_heads x mamba_head_dim")
    if config["norm_eps"] != config["layer_norm_epsilon"]:
        raise ValueError("norm_eps and layer_norm_epsilon differ: which one a norm takes is open")
    cfg = {ours: config[theirs] for ours, theirs in PUBLISHED.items()}
    if config["model"]["vocab"] != cfg["vocab"]:
        raise ValueError("model.vocab and vocab_size differ")
    model = dict(config["model"])
    cfg["num_experts"] = config["published"]["n_routed_experts"]
    cfg["experts_held"] = (model.pop("first_expert_held"), config["n_routed_experts"])
    return dict(cfg, **model)


def build_model(config: dict, seq_len: int, mode: str):
    """(model, program cfg) for ``mode`` ``train`` or ``serve``."""
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("hybrid_moe_lm", seq_len=seq_len,
                            **model_cfg(config), **config[mode])
    return spec.model, spec.extra["cfg"]


def checkpoint_shapes(config: dict, shapes: dict) -> dict:
    """The program's parameter shapes with every stacked expert leaf
    ``<m>/experts/<fc1|fc2>/w`` [count, a, b] as the matrices a published
    checkpoint holds, ``<m>/experts/<e>/<fc1|fc2>/w`` [a, b], ``e`` the
    expert's index in the router's width. ``weights.py`` seeds a leaf by its
    own name and fans, so a routed expert's matrices get their own Xavier
    scale; the reference reads them as they are, the program through its
    loader (:func:`make_engine`)."""
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
    from benchmarks.references import hybrid_moe_lm as ref

    cfg = model_cfg(config)
    return (ref.embed, functools.partial(ref.layer, cfg=cfg, mm=mm),
            functools.partial(ref.logits_at, cfg=cfg, mm=mm))


def reference_logits(config: dict, mm):
    from benchmarks.references import hybrid_moe_lm as ref

    return functools.partial(ref.logits_fn, cfg=model_cfg(config), mm=mm)


def _program_cfg(config: dict) -> dict:
    """The cfg the program itself runs with (``layer_types`` made from the pattern)."""
    return build_model(config, config["model"]["max_len"], "serve")[1]


def ssm_calls(config: dict) -> dict:
    """Shapes of the ``ssm_step`` kernel calls of one decode step, and the
    bytes a step has to move beside the states, for ``benchmarks/ssm_bytes.py``
    (which counts ``B`` and ``C`` of one group)."""
    import math

    from paddle_tpu.models import hybrid_moe_lm as hmm
    from paddle_tpu.models import hybrid_ssm_lm as hm

    cfg = _program_cfg(config)
    params = sum(math.prod(s) for s in hmm.param_shapes(cfg).values())
    kv_heads = cfg["num_kv_heads"] or cfg["num_heads"]
    return {"layers": len(hm.layers_of(cfg, hm.MAMBA)), "heads": cfg["ssm_heads"],
            "head_dim": cfg["ssm_head_dim"], "state": cfg["ssm_state"],
            "groups": cfg["ssm_groups"], "conv": cfg["ssm_conv"],
            "conv_channels": hm.conv_width(cfg),
            "attention_layers": len(hm.layers_of(cfg, hm.ATTENTION)),
            "kv_row_bytes": 2 * kv_heads * cfg["head_dim"], "weight_bytes": 2 * params}


def moe_calls(config: dict) -> dict:
    """Static shapes of the ``moe_gmm`` calls of one program call: per expert
    layer one product ``[pairs, latent] x [latent, f]`` and one ``[pairs, f]
    x [f, latent]`` over the experts held (``benchmarks/latent_expert_bytes.py``);
    a step routes ``max_slots`` tokens, a chunk ``prefill_chunk``."""
    cfg = model_cfg(config)
    return {"layers": cfg["pattern"].count("E"), "held": cfg["experts_held"][1],
            "router_width": cfg["num_experts"], "per_token": cfg["experts_per_token"],
            "latent": cfg["moe_latent"], "f": cfg["moe_d_inner"], "itemsize": 2}


def make_engine(config: dict, weights: dict, engine_kwargs: dict):
    """The engine over ``weights`` in the checkpoint's form
    (:func:`checkpoint_shapes`), which the program's loader stacks and
    **empties**: the chip has no room for the per-expert arrays beside their
    stacks."""
    from paddle_tpu.models import hybrid_moe_lm as hmm
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    _, cfg = build_model(config, config["model"]["max_len"], "serve")
    return DecodeEngine(_common.variables_from(hmm.stack_experts(weights, cfg)), cfg,
                        decode=DecodeConfig(**engine_kwargs))
