"""Program side of the decoder-only LM family: ``models.transformer_lm``
through ``pt.Trainer`` for training and ``serving.DecodeEngine`` for
serving. The plain reference is ``references/decoder_lm.py``."""

from __future__ import annotations

import functools

import numpy as np

from benchmarks import flops, traffic
from benchmarks.families import _common

REFERENCE = "decoder_lm"


def build_model(config: dict, seq_len: int, mode: str):
    """(model, program cfg) for ``mode`` ``train`` or ``serve``."""
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("transformer_lm", seq_len=seq_len,
                            **config["model"], **config[mode])
    return spec.model, spec.extra["cfg"]


def training_pool(mix: dict, config: dict, seed: int):
    """``pool`` batches of (ids, labels): rows of seq_len + 1 seeded tokens,
    the labels being the ids shifted by one."""
    rng = traffic.rng_of(seed, 1)
    out = []
    for _ in range(mix["pool"]):
        tok = traffic.token_ids(rng, config["model"]["vocab"], (mix["batch"], mix["seq_len"] + 1))
        out.append((np.ascontiguousarray(tok[:, :-1]), np.ascontiguousarray(tok[:, 1:])))
    return out


def row_length(mix: dict) -> int:
    return mix["seq_len"]


def real_target_tokens(batch) -> int:
    return int(batch[1].size)


def reference_loss(config: dict, mm):
    from benchmarks.references import decoder_lm as ref

    return functools.partial(ref.loss_sum, cfg=config["model"], mm=mm)


def reference_logits(config: dict, mm):
    from benchmarks.references import decoder_lm as ref

    return functools.partial(ref.logits_fn, cfg=config["model"], mm=mm)


def train_flops_per_step(config: dict, mix: dict, batch) -> float:
    return flops.decoder_lm_train_flops(config["model"], mix["batch"], mix["seq_len"])


def flash_calls(config: dict, mix: dict) -> dict:
    """Shapes of the flash kernel calls of one training step."""
    m = config["model"]
    return {"calls": m["n_layers"], "b": mix["batch"], "heads": m["num_heads"],
            "t": mix["seq_len"], "dh": m["d_model"] // m["num_heads"], "causal": True}


def make_engine(config: dict, weights: dict, engine_kwargs: dict):
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    _, cfg = build_model(config, config["model"]["max_len"], "serve")
    return DecodeEngine(_common.variables_from(weights), cfg,
                        decode=DecodeConfig(**engine_kwargs))
