"""Operations and bytes an algorithm requires, computed from shapes.
Recomputed operations do not count; a causal product counts the half of the
square that is needed. A multiply-add is two operations."""

from __future__ import annotations


def attention_flops(b: int, heads: int, tq: int, tk: int, dh: int, causal: bool) -> float:
    """QK^T and PV of one attention call, forward."""
    full = 2 * 2.0 * b * heads * tq * tk * dh
    return full / 2 if causal else full


def flash_fwd_flops(b, heads, t, dh, causal=True) -> float:
    return attention_flops(b, heads, t, t, dh, causal)


def flash_bwd_flops(b, heads, t, dh, causal=True) -> float:
    """Backward needs five products (S again, dV, dP, dQ, dK) to forward's two."""
    return 2.5 * attention_flops(b, heads, t, t, dh, causal)


def flash_fwd_bytes(b, heads, t, dh, itemsize=2) -> float:
    """Read q, k, v, write o, plus the float32 row statistics."""
    return 4.0 * b * heads * t * dh * itemsize + 4.0 * b * heads * t


def flash_bwd_bytes(b, heads, t, dh, itemsize=2) -> float:
    """Read q, k, v, o, do and the row statistics, write dq, dk, dv."""
    return 8.0 * b * heads * t * dh * itemsize + 2 * 4.0 * b * heads * t


def decoder_lm_fwd_flops_per_token(cfg: dict, t: int, causal_half: bool = True) -> float:
    d, f, layers, vocab = cfg["d_model"], cfg["d_inner"], cfg["n_layers"], cfg["vocab"]
    matmul = 2.0 * (layers * (4 * d * d + 2 * d * f) + d * vocab)
    attn = layers * 4.0 * t * d  # QK^T and PV per query token, full square
    return matmul + (attn / 2 if causal_half else attn)


def decoder_lm_train_flops(cfg: dict, batch: int, t: int, causal_half: bool = True) -> float:
    """Forward plus backward (twice the forward) of one step."""
    return 3.0 * batch * t * decoder_lm_fwd_flops_per_token(cfg, t, causal_half)


def encdec_nmt_train_flops(cfg: dict, src_lens, trg_lens) -> float:
    """One step over sentence pairs of the given real lengths; padding does
    no required work."""
    d, f, layers = cfg["d_model"], cfg["d_inner"], cfg["n_layers"]
    vocab = cfg["trg_vocab"]
    fwd = 0.0
    for s, t in zip(src_lens, trg_lens):
        fwd += s * 2.0 * layers * (4 * d * d + 2 * d * f)            # encoder matmuls
        fwd += t * 2.0 * (layers * (8 * d * d + 2 * d * f) + d * vocab)  # decoder + logits
        # k and v of the cross-attention are projected from the s source tokens
        fwd -= (t - s) * 2.0 * layers * 2 * d * d
        fwd += layers * 4.0 * d * (s * s + t * t / 2 + t * s)        # attention products
    return 3.0 * fwd
