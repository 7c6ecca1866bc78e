"""Transformer NMT — the flagship model.

Reference: the Transformer config used by ``benchmark/fluid`` /
``python/paddle/fluid/tests/unittests/dist_transformer.py`` (post-LN
encoder-decoder, d_model 512, 8 heads, ffn 2048, 6+6 layers, label smoothing
0.1, Adam + Noam warmup) — attention built from composed ops
(``python/paddle/fluid/nets.py:332``).

TPU-first design:
- one fused attention path (``ops.attention.scaled_dot_product_attention``,
  fp32 softmax, MXU-friendly [B,N,T,D] batched matmuls); a Pallas
  flash-attention kernel takes over for long sequences.
- every projection carries a logical sharding spec so the same program runs
  unsharded, data-parallel, or tensor-parallel under a mesh: column-parallel
  qkv/ffn-in (shard output dim on ``tp``), row-parallel out/ffn-out (shard
  input dim on ``tp``) — the Megatron layout expressed purely as pjit
  constraints; XLA inserts the psums (no hand-written collectives).
- static shapes: [B, T] padded + additive masks (the LoD replacement).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import ParamAttr, create_parameter, name_scope
from paddle_tpu.models import ModelSpec
from paddle_tpu.ops import attention as oattn

# canonical tensor-parallel mesh axis; absent from a mesh → replicated
from paddle_tpu.parallel.mesh import MODEL_AXIS as TP


def _proj(x, size, *, shard_out: bool, name: str, bias: bool = True):
    """Linear projection over the last axis of [B, T, D] with a tensor-
    parallel sharding annotation (column- or row-parallel)."""
    sharding = (None, TP) if shard_out else (TP, None)
    return layers.fc(
        x,
        size=size,
        num_flatten_dims=x.ndim - 1,
        param_attr=ParamAttr(sharding=sharding),
        bias_attr=None if bias else False,
        name=name,
    )


@jax.named_scope("attention")
def multi_head_attention(
    queries,
    keys,
    values,
    d_model: int,
    num_heads: int,
    mask=None,
    dropout_rate: float = 0.0,
    cache: Optional[dict] = None,
    name: str = "mha",
    causal: bool = False,
    core=None,
    kv_len=None,
    num_kv_heads: Optional[int] = None,
    window: Optional[int] = None,
):
    """Projected multi-head attention (q/k/v/out linear maps + fused core).

    ``cache`` (decode-time) holds accumulated k/v: {"k": [B,N,T,D], "v": ...};
    when given, new k/v are appended (static-size cache with a write index is
    used in the beam-search decoder). ``core`` overrides the attention core
    ``(qh, kh, vh) -> ctx`` — e.g. a ring-attention body for sequence-
    parallel long context. ``num_kv_heads`` < num_heads enables
    grouped-query attention (MQA at 1): k/v project to fewer heads, cutting
    KV projection FLOPs, cache size, and HBM traffic proportionally."""
    h_kv = num_kv_heads or num_heads
    if num_heads % h_kv:
        raise ValueError(f"num_heads {num_heads} not divisible by num_kv_heads {h_kv}")
    d_kv = d_model // num_heads * h_kv
    with name_scope(name):
        q = _proj(queries, d_model, shard_out=True, name="q")
        k = _proj(keys, d_kv, shard_out=True, name="k")
        v = _proj(values, d_kv, shard_out=True, name="v")
        qh = oattn.split_heads(q, num_heads)
        kh = oattn.split_heads(k, h_kv)
        vh = oattn.split_heads(v, h_kv)
        if cache is not None:
            kh = jnp.concatenate([cache["k"], kh], axis=2)
            vh = jnp.concatenate([cache["v"], vh], axis=2)
            cache["k"], cache["v"] = kh, vh
        if core is not None:
            from paddle_tpu.core.enforce import enforce

            enforce(
                mask is None
                and cache is None
                and (dropout_rate == 0.0 or not pt.framework.is_training()),
                "multi_head_attention: a custom attention core supports neither "
                "an additive mask, nor a decode-time k/v cache (the core "
                "assumes q and k share global sequence alignment), nor "
                "attention dropout — got "
                f"mask={'set' if mask is not None else None}, "
                f"cache={'set' if cache is not None else None}, "
                f"dropout_rate={dropout_rate}",
            )
            # kv_len DOES pass through: ring/ulysses cores mask global key
            # positions >= kv_len[b] (ragged batches under seq parallelism)
            ctx = core(qh, kh, vh, kv_len=kv_len) if kv_len is not None else core(qh, kh, vh)
        else:
            ctx = oattn.scaled_dot_product_attention(
                qh, kh, vh, mask=mask, dropout_rate=dropout_rate,
                is_test=not pt.framework.is_training(),
                dropout_key=pt.framework.next_rng_key() if (dropout_rate > 0 and pt.framework.is_training()) else None,
                causal=causal,
                kv_len=kv_len,
                window=window,
            )
        out = oattn.combine_heads(ctx)
        return _proj(out, d_model, shard_out=False, name="out")


@jax.named_scope("ffn")
def positionwise_ffn(x, d_inner: int, d_model: int, dropout_rate: float,
                     name: str = "ffn", activation: str = "relu"):
    """``activation='swiglu'`` gates the up-projection with a SiLU branch
    (modern LM FFN; two column-parallel matmuls instead of one)."""
    with name_scope(name):
        if activation == "swiglu":
            up = _proj(x, d_inner, shard_out=True, name="fc1")
            gate = _proj(x, d_inner, shard_out=True, name="gate")
            hidden = up * jax.nn.silu(gate)
        else:
            hidden = _proj(x, d_inner, shard_out=True, name="fc1")
            hidden = layers.relu(hidden)
        if dropout_rate:
            hidden = layers.dropout(hidden, dropout_rate)
        return _proj(hidden, d_model, shard_out=False, name="fc2")


def _post_process(prev, out, dropout_rate):
    """residual add + LayerNorm (post-LN, reference-era transformer)."""
    if dropout_rate:
        out = layers.dropout(out, dropout_rate)
    return layers.layer_norm(prev + out, begin_norm_axis=prev.ndim - 1)


def sinusoid_position_encoding(max_len: int, d_model: int, dtype=jnp.float32):
    pos = np.arange(max_len)[:, None].astype(np.float64)
    dim = np.arange(d_model // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2 * dim / d_model)
    enc = np.concatenate([np.sin(angle), np.cos(angle)], axis=1)
    return jnp.asarray(enc, dtype)


@jax.named_scope("embed")
def prepare_embedding(ids, vocab_size, d_model, max_len, dropout_rate, name,
                      pos_offset=0, add_position_encoding=True):
    """token embedding * sqrt(d) (+ fixed sinusoid position encoding unless
    ``add_position_encoding=False`` — RoPE models inject position at the
    attention rotation instead). ``pos_offset`` (int or traced scalar)
    shifts positions for incremental decode with a k/v cache."""
    with name_scope(name):
        emb = layers.embedding(
            ids,
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name="word_emb", sharding=(None, TP)),
        )
        emb = emb * (d_model ** 0.5)
        if add_position_encoding:
            t = ids.shape[-1]
            pe = sinusoid_position_encoding(max_len, d_model, emb.dtype)
            emb = emb + jax.lax.dynamic_slice_in_dim(pe, pos_offset, t, axis=0)
        if dropout_rate:
            emb = layers.dropout(emb, dropout_rate)
        return emb


def encoder_layer(x, self_mask, cfg, name, kv_len=None):
    with name_scope(name):
        attn = multi_head_attention(
            x, x, x, cfg["d_model"], cfg["num_heads"], mask=self_mask,
            dropout_rate=cfg["attn_dropout"], name="self_attn", kv_len=kv_len,
        )
        x = _post_process(x, attn, cfg["residual_dropout"])
        ffn = positionwise_ffn(x, cfg["d_inner"], cfg["d_model"], cfg["relu_dropout"])
        return _post_process(x, ffn, cfg["residual_dropout"])


def decoder_layer(x, enc_out, self_mask, cross_mask, cfg, name, cache=None,
                  self_causal=False, cross_kv_len=None):
    with name_scope(name):
        attn = multi_head_attention(
            x, x, x, cfg["d_model"], cfg["num_heads"], mask=self_mask,
            dropout_rate=cfg["attn_dropout"], cache=cache, name="self_attn",
            causal=self_causal,
        )
        x = _post_process(x, attn, cfg["residual_dropout"])
        cross = multi_head_attention(
            x, enc_out, enc_out, cfg["d_model"], cfg["num_heads"], mask=cross_mask,
            dropout_rate=cfg["attn_dropout"], name="cross_attn",
            kv_len=cross_kv_len,
        )
        x = _post_process(x, cross, cfg["residual_dropout"])
        ffn = positionwise_ffn(x, cfg["d_inner"], cfg["d_model"], cfg["relu_dropout"])
        return _post_process(x, ffn, cfg["residual_dropout"])


def _pad_mask(pad_flags):
    """[B, T] bool (True = padding) → additive [B, 1, 1, T]."""
    return jnp.where(pad_flags, -jnp.inf, 0.0).astype(jnp.float32)[:, None, None, :]


def _structural_masking() -> bool:
    """With the flash flag on, padding travels as per-row kv_len bounds and
    causality as the kernel's block structure — no additive [T, T] masks.
    Valid because padding is a SUFFIX (ragged FeedSpec layout) and the loss
    zero-weights pad positions: pad QUERIES may compute garbage that never
    reaches the loss, while pad KEYS are excluded for every valid query."""
    from paddle_tpu.core import config as _cfg

    return _cfg.flags().use_flash_attention


def _lens(pad_flags):
    return jnp.sum(1 - pad_flags.astype(jnp.int32), axis=1)


def encode(src_ids, src_pad, cfg):
    structural = _structural_masking()
    self_mask = None if structural else _pad_mask(src_pad)
    src_len = _lens(src_pad) if structural else None
    x = prepare_embedding(
        src_ids, cfg["src_vocab"], cfg["d_model"], cfg["max_len"],
        cfg["residual_dropout"], name="src_emb",
    )
    if cfg.get("scan_layers") and not pt.framework.is_initializing():
        # one lax.scan body over stacked params (framework.scan_layer_stack:
        # compile cost and program size O(1) in n_layers); init stays
        # unrolled for trace-time param creation
        return pt.framework.scan_layer_stack(
            x, cfg["n_layers"], lambda i: f"enc_layer_{i}", "enc_layer_tpl",
            lambda h, name: encoder_layer(h, self_mask, cfg, name, kv_len=src_len),
        )
    for i in range(cfg["n_layers"]):
        x = encoder_layer(x, self_mask, cfg, name=f"enc_layer_{i}", kv_len=src_len)
    return x


def decode(trg_ids, trg_pad, enc_out, src_pad, cfg, caches=None, pos_offset=0):
    t = trg_ids.shape[1]
    structural = _structural_masking() and caches is None
    if caches is not None:
        self_mask = None
    elif structural:
        # causal alone suffices for decoder self-attention: pad keys sit at
        # positions >= len, and every valid query q has q < len <= pad key
        # positions, so causality already excludes them
        self_mask = None
    else:
        self_mask = oattn.causal_mask(t, t)[None, None] + _pad_mask(trg_pad)
    cross_mask = None if structural else _pad_mask(src_pad)
    cross_len = _lens(src_pad) if structural else None
    x = prepare_embedding(
        trg_ids, cfg["trg_vocab"], cfg["d_model"], cfg["max_len"],
        cfg["residual_dropout"], name="trg_emb",
        pos_offset=pos_offset if caches is not None else 0,
    )
    if (
        cfg.get("scan_layers")
        and caches is None  # cached decode keeps its per-layer loop
        and not pt.framework.is_initializing()
    ):
        x = pt.framework.scan_layer_stack(
            x, cfg["n_layers"], lambda i: f"dec_layer_{i}", "dec_layer_tpl",
            lambda h, name: decoder_layer(
                h, enc_out, self_mask, cross_mask, cfg, name,
                self_causal=structural, cross_kv_len=cross_len,
            ),
        )
    else:
        for i in range(cfg["n_layers"]):
            cache = caches[i] if caches is not None else None
            x = decoder_layer(
                x, enc_out, self_mask, cross_mask, cfg, name=f"dec_layer_{i}",
                cache=cache, self_causal=structural, cross_kv_len=cross_len,
            )
    with jax.named_scope("head"), name_scope("project"):
        logits = _proj(x, cfg["trg_vocab"], shard_out=True, name="logits", bias=False)
    return logits


def transformer_forward(src_ids, src_pad, trg_ids, trg_pad, labels, label_pad, *, cfg):
    """Training forward: returns (avg_loss, token_count, logits).

    Loss = label-smoothed softmax CE, averaged over non-pad tokens
    (reference transformer label_smooth eps=0.1)."""
    enc_out = encode(src_ids, src_pad, cfg)
    logits = decode(trg_ids, trg_pad, enc_out, src_pad, cfg)
    vocab = cfg["trg_vocab"]
    eps = cfg["label_smooth_eps"]
    with jax.named_scope("loss"):
        onehot = jax.nn.one_hot(labels, vocab, dtype=jnp.float32)
        smooth = onehot * (1 - eps) + eps / vocab
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tok_loss = -jnp.sum(smooth * logp, axis=-1)  # [B, T]
        weight = 1.0 - label_pad.astype(jnp.float32)
        n_tok = jnp.maximum(jnp.sum(weight), 1.0)
        avg_loss = jnp.sum(tok_loss * weight) / n_tok
    return avg_loss, n_tok, logits


BASE_CFG = dict(
    src_vocab=10000,
    trg_vocab=10000,
    d_model=512,
    d_inner=2048,
    num_heads=8,
    n_layers=6,
    max_len=256,
    attn_dropout=0.1,
    relu_dropout=0.1,
    residual_dropout=0.1,
    label_smooth_eps=0.1,
    # run encoder/decoder stacks as one lax.scan body each over stacked
    # params (framework.scan_layer_stack); cached decode stays unrolled
    scan_layers=False,
)


def get_model(
    seq_len: int = 64,
    learning_rate: float = 2.0,
    warmup_steps: int = 8000,
    **overrides,
) -> ModelSpec:
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})

    model = pt.build(functools.partial(transformer_forward, cfg=cfg), name="transformer")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        src = rng.randint(1, cfg["src_vocab"], size=(batch_size, seq_len)).astype(np.int32)
        trg = rng.randint(1, cfg["trg_vocab"], size=(batch_size, seq_len)).astype(np.int32)
        labels = rng.randint(1, cfg["trg_vocab"], size=(batch_size, seq_len)).astype(np.int32)
        # ragged lengths → pad flags (the LoD replacement)
        lens = rng.randint(seq_len // 2, seq_len + 1, size=(batch_size,))
        pos = np.arange(seq_len)[None, :]
        src_pad = (pos >= lens[:, None])
        return src, src_pad, trg, src_pad.copy(), labels, src_pad.copy()

    def make_optimizer():
        return pt.optimizer.Adam(
            learning_rate=pt.lr_scheduler.NoamDecay(cfg["d_model"], warmup_steps, learning_rate),
            beta1=0.9,
            beta2=0.98,
            epsilon=1e-9,
        )

    return ModelSpec(
        name="transformer",
        model=model,
        synth_batch=synth_batch,
        optimizer=make_optimizer,
        unit="tokens/sec",
        examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len},
    )
