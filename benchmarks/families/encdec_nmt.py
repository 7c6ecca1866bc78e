"""Program side of the encoder-decoder NMT family: ``models.transformer``
through ``pt.Trainer``. The plain reference is ``references/encdec_nmt.py``."""

from __future__ import annotations

import functools

import numpy as np

from benchmarks import flops, traffic
from benchmarks.families import _common

REFERENCE = "encdec_nmt"


def build_model(config: dict, seq_len: int, mode: str):
    from paddle_tpu import models

    _common.apply_flags(config)
    spec = models.get_model("transformer", seq_len=seq_len, **config["model"], **config[mode])
    return spec.model, spec.extra["cfg"]


def training_pool(mix: dict, config: dict, seed: int):
    """``pool`` batches of (src, src_pad, trg, trg_pad, labels, label_pad),
    padded to ``pad_to``. Each batch holds the same even multiset of lengths
    in an order the seed picks; source and target lengths are permuted apart."""
    rng = traffic.rng_of(seed, 2)
    b, t = mix["batch"], mix["pad_to"]
    src_vocab, trg_vocab = config["model"]["src_vocab"], config["model"]["trg_vocab"]
    base = traffic.even_lengths(b, mix["len_lo"], mix["len_hi"])
    pos = np.arange(t)[None, :]
    out = []
    for _ in range(mix["pool"]):
        s_len, t_len = rng.permutation(base), rng.permutation(base)
        src_pad, trg_pad = pos >= s_len[:, None], pos >= t_len[:, None]
        src = np.where(src_pad, 0, traffic.token_ids(rng, src_vocab, (b, t))).astype(np.int32)
        full = traffic.token_ids(rng, trg_vocab, (b, t + 1))
        trg = np.where(trg_pad, 0, full[:, :-1]).astype(np.int32)
        labels = np.where(trg_pad, 0, full[:, 1:]).astype(np.int32)
        out.append((src, src_pad, trg, trg_pad, labels, trg_pad.copy()))
    return out


def row_length(mix: dict) -> int:
    return mix["pad_to"]


def real_target_tokens(batch) -> int:
    return int((~batch[5]).sum())


def reference_loss(config: dict, mm):
    from benchmarks.references import encdec_nmt as ref

    return functools.partial(ref.loss_sum, cfg=config["model"], mm=mm)


def train_flops_per_step(config: dict, mix: dict, batch) -> float:
    src_lens = (~batch[1]).sum(1)
    trg_lens = (~batch[5]).sum(1)
    return flops.encdec_nmt_train_flops(config["model"], src_lens, trg_lens)


def flash_calls(config: dict, mix: dict):
    return None  # no claim: whichever attention path the trace shows is recorded
