"""Plain reference of the latent-attention, sparse-expert LM (DeepSeek-V2's
block as ``sarvamai/sarvam-105b`` configures it; Liu et al. 2024,
"DeepSeek-V2", arXiv:2405.04434, sections 2.1 and 2.2). Written from the
equations, in the **expanded form** only: every position's per-head key and
value are made from its latent, no cache, no absorbed products, no kernel.
Float32, every product through ``mm``. Imports nothing of the program;
parameters are looked up by the names the program gives them.

Block, pre-norm, no bias: ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``; a final RMSNorm; an untied head.

``Attn(n)``: ``q = W_q n`` as ``H`` heads of ``nope + rope``, RMSNorm with a
learned scale over each head, RoPE (half-split pairing) on the last ``rope``
of each. ``W_kv_a n`` is ``rank + rope`` wide: ``c = RMSNorm(first rank)``
with a learned scale, ``k_rope = RoPE(last rope)``, shared by the heads.
``[k_nope_h ; v_h] = W_kv_b c`` per head, ``k_h = [k_nope_h ; k_rope]``,
``a = softmax_causal(q_h . k_h * scale)``, ``Attn = W_o concat_h(a v_h)``.
RoPE's inverse frequencies under ``rope_scaling`` are YaRN's as DeepSeek-V2
spells it (:func:`yarn_inv_freq`, a transcription of its
``find_correction_range`` and ``linear_ramp_mask``), and ``scale = (nope +
rope) ** -0.5 * (0.1 * mscale_all_dim * ln(factor) + 1) ** 2``.

``FFN``: a layer that holds ``ffn/*`` is a SwiGLU. A layer that holds
``moe/*``: ``s = sigmoid(W_r n)``, the ``experts_per_token`` largest ``s + b``
selected, weights ``routed_scaling * s_e / sum_selected s``, ``FFN(n) = sum_e
w_e E_e(n) + Shared(n)``, every ``E_e`` and ``Shared`` a SwiGLU. The sum runs
over the selected experts whose weights are **held**: ``experts_held``
(first, count) of the router's width; expert ``e``'s three matrices are
``moe/experts/<e>/{gate,fc1,fc2}/w``, a matrix an expert as a published
checkpoint holds them (the program stacks them at load). What the experts
held elsewhere would add is left out: the share of one chip of an
expert-parallel layer. A plain loop over the held experts, each applied to
every token and weighted (0 where not selected).

A model of the published size does not fit the chip in float32 at once, so
the walk is by layer: :func:`embed`, :func:`layer` with one layer's
parameters, :func:`logits_at`. Inside a layer the query rows go in blocks.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 256  # query rows of every head scored at once


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def yarn_inv_freq(dim: int, base: float, scaling) -> np.ndarray:
    """[dim / 2] inverse frequencies; ``scaling`` None is plain RoPE."""
    idx = np.arange(0, dim, 2, dtype=np.float64)
    freq_extra = 1.0 / base ** (idx / dim)
    if not scaling:
        return freq_extra.astype(np.float32)
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]
    freq_inter = 1.0 / (factor * base ** (idx / dim))

    def find_correction_dim(num_rotations):
        return dim * math.log(orig / (num_rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(find_correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return (freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs["factor"] > 1:
        scale *= (0.1 * rs.get("mscale_all_dim", 0.0) * math.log(rs["factor"]) + 1.0) ** 2
    return scale


def rope(x, cfg: dict):
    """[..., T, rope] at positions 0..T-1, half-split pairing."""
    t, half = x.shape[-2], x.shape[-1] // 2
    freqs = jnp.asarray(yarn_inv_freq(x.shape[-1], cfg["rope_theta"], cfg.get("rope_scaling")))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x1 * jnp.sin(angle) + x2 * jnp.cos(angle)], -1)


def attention(q, k, v, scale: float, mm):
    """Causal softmax attention, ``q`` and ``k`` [H, T, dk], ``v`` [H, T, dv],
    query rows in blocks. Returns [H, T, dv]."""
    h, t, dk = q.shape
    pad = -t % ROW_BLOCK
    rows = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(h, -1, ROW_BLOCK, dk)
    at = jnp.pad(jnp.arange(t), (0, pad)).reshape(-1, ROW_BLOCK)

    def block(x):
        q_blk, t_blk = x  # [H, R, dk], [R]
        s = mm(q_blk, jnp.swapaxes(k, -1, -2)) * scale
        seen = jnp.arange(t)[None, :] <= t_blk[:, None]
        return mm(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1), v)

    out = jax.lax.map(block, (rows.transpose(1, 0, 2, 3), at))  # [n, H, R, dv]
    return out.transpose(1, 0, 2, 3).reshape(h, -1, v.shape[-1])[:, :t]


def swiglu(n, lp: dict, pfx: str, mm):
    return mm(jax.nn.silu(mm(n, lp[pfx + "/gate/w"])) * mm(n, lp[pfx + "/fc1/w"]),
              lp[pfx + "/fc2/w"])


def route(n, lp: dict, cfg: dict, mm):
    """[T, E] weight of every expert for every token, 0 where not selected."""
    s = jax.nn.sigmoid(mm(n, lp["moe/router/w"]))
    _, picked = jax.lax.top_k(s + lp["moe/router/b"], cfg["experts_per_token"])
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], picked].set(1.0)
    return cfg["routed_scaling"] * s * chosen / jnp.sum(s * chosen, -1, keepdims=True)


def expert_share(n, lp: dict, cfg: dict, mm):
    """The held experts' terms of the routed sum."""
    first, count = cfg["experts_held"] or (0, cfg["num_experts"])
    w = route(n, lp, cfg, mm)
    out = jnp.zeros_like(n)
    for e in range(first, first + count):
        out = out + w[:, e, None] * swiglu(n, lp, f"moe/experts/{e}", mm)
    return out


def embed(word_emb, ids):
    """[T] token ids -> [T, d]."""
    return word_emb[ids]


def layer(x, lp: dict, cfg: dict, mm):
    """One block on one sequence: ``x`` [T, d]; ``lp`` holds the layer's
    parameters by their names under ``layer_<i>/``; which FFN it is shows in
    the names it holds."""
    t = x.shape[0]
    h, nope, rank = cfg["num_heads"], cfg["qk_nope_dim"], cfg["kv_lora_rank"]
    heads = lambda y: y.reshape(t, h, -1).transpose(1, 0, 2)  # [H, T, .]
    n = rms_norm(x, lp["attn_norm/scale"], cfg["rms_eps"])
    q = rms_norm(heads(mm(n, lp["attn/q/w"])), lp["attn/q_norm/scale"], cfg["rms_eps"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    kv = mm(n, lp["attn/kv_a/w"])
    c = rms_norm(kv[:, :rank], lp["attn/kv_norm/scale"], cfg["rms_eps"])
    k_rope = rope(kv[:, rank:], cfg)
    kv_b = heads(mm(c, lp["attn/kv_b/w"]))  # [H, T, nope + v]
    k = jnp.concatenate([kv_b[..., :nope], jnp.broadcast_to(k_rope, (h,) + k_rope.shape)], -1)
    o = attention(q, k, kv_b[..., nope:], softmax_scale(cfg), mm)
    x = x + mm(o.transpose(1, 0, 2).reshape(t, -1), lp["attn/out/w"])
    n = rms_norm(x, lp["ffn_norm/scale"], cfg["rms_eps"])
    if "ffn/fc1/w" in lp:
        return x + swiglu(n, lp, "ffn", mm)
    return x + expert_share(n, lp, cfg, mm) + swiglu(n, lp, "moe/shared/ffn", mm)


def logits_at(x_rows, final_scale, head_w, cfg: dict, mm):
    """[n, d] rows of the last block's output -> [n, vocab] logits."""
    return mm(rms_norm(x_rows, final_scale, cfg["rms_eps"]), head_w)


def logits_fn(params, ids, cfg: dict, mm):
    """Whole model at once, for sizes that fit: [B, T] ids -> [B, T, vocab]."""
    def one(row):
        x = embed(params["emb/word_emb"], row)
        for i in range(cfg["n_layers"]):
            head = f"layer_{i}/"
            x = layer(x, {k[len(head):]: p for k, p in params.items()
                          if k.startswith(head)}, cfg, mm)
        return logits_at(x, params["final_norm/scale"], params["head/w"], cfg, mm)

    return jax.vmap(one)(ids)


def loss_sum(params, ids, labels, cfg: dict, mm):
    """Summed next-token negative log-likelihood over every position."""
    logp = jax.nn.log_softmax(logits_fn(params, ids, cfg, mm), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))
