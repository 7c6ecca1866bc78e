"""Plain reference of the encoder-decoder Transformer of Vaswani et al.
(2017), post-LayerNorm, as the repo builds it: separate source and target
embeddings (the paper shares them), no final LayerNorm, an untied output
projection, label-smoothed cross-entropy averaged over the real target
tokens. Padding is a suffix; padded keys are masked, padded queries never
reach the loss."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references import common as C


def _embed(params, name, ids, d):
    return params[f"{name}/embedding/word_emb"][ids] * d ** 0.5 + C.sinusoid(ids.shape[1], d)


def logits_fn(params, src, src_pad, trg, cfg: dict, mm=C.mm_f32):
    d, heads, n = cfg["d_model"], cfg["num_heads"], cfg["n_layers"]
    src_keys = C.key_mask(jnp.sum(1 - src_pad.astype(jnp.int32), 1), src.shape[1])
    causal = C.causal_mask(trg.shape[1])

    def enc_block(h, p):
        h = C.layer_norm(h + C.attention(mm, p, "self_attn", h, h, heads, src_keys),
                         p["layer_norm/scale"], p["layer_norm/bias"])
        return C.layer_norm(h + C.ffn(mm, p, "ffn", h),
                            p["layer_norm_1/scale"], p["layer_norm_1/bias"])

    enc = C.scan_layers(enc_block, _embed(params, "src_emb", src, d),
                        C.stack_layers(params, "enc_layer_{}", n))

    def dec_block(h, p):
        h = C.layer_norm(h + C.attention(mm, p, "self_attn", h, h, heads, causal),
                         p["layer_norm/scale"], p["layer_norm/bias"])
        h = C.layer_norm(h + C.attention(mm, p, "cross_attn", h, enc, heads, src_keys),
                         p["layer_norm_1/scale"], p["layer_norm_1/bias"])
        return C.layer_norm(h + C.ffn(mm, p, "ffn", h),
                            p["layer_norm_2/scale"], p["layer_norm_2/bias"])

    x = C.scan_layers(dec_block, _embed(params, "trg_emb", trg, d),
                      C.stack_layers(params, "dec_layer_{}", n))
    return mm(x, params["project/logits/w"])


def loss_sum(params, src, src_pad, trg, trg_pad, labels, label_pad, cfg: dict, mm=C.mm_f32):
    """Summed label-smoothed cross-entropy over the real target tokens."""
    logp = jax.nn.log_softmax(logits_fn(params, src, src_pad, trg, cfg, mm), axis=-1)
    eps, vocab = cfg["label_smooth_eps"], logp.shape[-1]
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    tok = -((1 - eps) * picked + eps / vocab * jnp.sum(logp, -1))
    return jnp.sum(tok * (1.0 - label_pad.astype(jnp.float32)))


def blocks(batch, rows: int):
    for i in range(0, batch[0].shape[0], rows):
        yield tuple(x[i:i + rows] for x in batch)


def n_tokens(batch) -> int:
    return int((~batch[5].astype(bool)).sum())
