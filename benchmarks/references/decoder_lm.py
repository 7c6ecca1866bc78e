"""Plain reference of the decoder-only LM (fairseq ``transformer_lm_big``
layout as the repo builds it): token embedding times sqrt(d) plus sinusoidal
positions, post-LayerNorm blocks of causal self-attention and a ReLU FFN,
a final LayerNorm (the repo's departure) and an untied output projection.
Parameters are looked up by the names the program gives its leaves."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references import common as C


def logits_fn(params, ids, cfg: dict, mm=C.mm_f32):
    """[B, T] token ids -> [B, T, vocab] float32 logits."""
    t, d, heads = ids.shape[1], cfg["d_model"], cfg["num_heads"]
    x = params["emb/embedding/word_emb"][ids] * d ** 0.5 + C.sinusoid(t, d)
    mask = C.causal_mask(t)

    def block(h, p):
        h = C.layer_norm(h + C.attention(mm, p, "self_attn", h, h, heads, mask),
                         p["layer_norm/scale"], p["layer_norm/bias"])
        return C.layer_norm(h + C.ffn(mm, p, "ffn", h),
                            p["layer_norm_1/scale"], p["layer_norm_1/bias"])

    x = C.scan_layers(block, x, C.stack_layers(params, "layer_{}", cfg["n_layers"]))
    x = C.layer_norm(x, params["layer_norm/scale"], params["layer_norm/bias"])
    return mm(x, params["project/logits/w"])


def loss_sum(params, ids, labels, cfg: dict, mm=C.mm_f32):
    """Summed next-token negative log-likelihood over every position."""
    logp = jax.nn.log_softmax(logits_fn(params, ids, cfg, mm), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def blocks(batch, rows: int):
    ids, labels = batch
    for i in range(0, ids.shape[0], rows):
        yield ids[i:i + rows], labels[i:i + rows]


def n_tokens(batch) -> int:
    return int(batch[1].size)
