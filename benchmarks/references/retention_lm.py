"""Plain reference of the power-retention LM (Brumby-14B-Base: Qwen3-14B's
block with every attention layer replaced by power retention; Buckman,
Gelada, Zhang et al. 2025, "Scaling Context Requires Rethinking Attention").
Written from the equations, in the **attention form** only: no state, no
chunks, no cache. Float32, every product through ``mm``. Imports nothing of
the program; parameters are looked up by the names the program gives them.

Block, pre-norm: ``h = x + Ret(RMSNorm(x))``, ``y = h + W_down(silu(W_gate n)
* W_up n)``, ``n = RMSNorm(h)``; no bias; a final RMSNorm; an untied head.
``Ret``: ``H`` query heads over ``H_kv`` key-value heads (query head ``j``
reads key-value head ``j // (H / H_kv)``), RMSNorm with a learned scale over
each q and k head, RoPE (half-split pairing, base ``rope_theta``), one gate
per key-value head and token, ``log g_t = logsigmoid(W_g n_t + shift)`` with
the configuration's constant ``ret_gate_shift``, and per head, for ``s <= t``::

    a[t, s] = (q_t . k_s / sqrt(dh))^2 * prod_{r = s+1 .. t} g_r
    o_t     = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)

A model of the published size does not fit the chip in float32 beside
anything else, so the walk is by layer: :func:`embed`, then :func:`layer`
with one layer's parameters at a time, then :func:`logits_at` for the rows
that are wanted. Inside a layer the heads go one key-value group at a time
and the query rows in blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_BLOCK = 512  # query rows of one key-value group scored at once


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """[..., T, dh] at positions 0..T-1, half-split pairing."""
    t, dh = x.shape[-2], x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x1 * jnp.sin(angle) + x2 * jnp.cos(angle)], -1)


def retention(q, k, v, log_g, eps: float, mm):
    """One key-value head: ``q`` [G, T, dh], ``k`` and ``v`` [T, dh],
    ``log_g`` [T]. Returns [G, T, dh]."""
    g_heads, t, dh = q.shape
    b = jnp.cumsum(log_g)  # prod_{r = s+1 .. t} g_r = exp(b_t - b_s)
    pad = -t % ROW_BLOCK
    rows = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(g_heads, -1, ROW_BLOCK, dh)
    at = jnp.pad(jnp.arange(t), (0, pad)).reshape(-1, ROW_BLOCK)

    def block(x):
        q_blk, t_blk = x  # [G, R, dh], [R]
        score = mm(q_blk, k.T) / jnp.sqrt(jnp.float32(dh))
        seen = jnp.arange(t)[None, :] <= t_blk[:, None]
        decay = jnp.exp(jnp.where(seen, b[t_blk][:, None] - b[None, :], -jnp.inf))
        a = jnp.square(score) * decay[None]
        return mm(a, v) / (jnp.sum(a, -1, keepdims=True) + eps)

    out = jax.lax.map(block, (rows.transpose(1, 0, 2, 3), at))  # [n, G, R, dh]
    return out.transpose(1, 0, 2, 3).reshape(g_heads, -1, dh)[:, :t]


def embed(word_emb, ids):
    """[T] token ids -> [T, d]."""
    return word_emb[ids]


def layer(x, lp: dict, cfg: dict, mm):
    """One block on one sequence: ``x`` [T, d]; ``lp`` holds the layer's
    parameters by their names under ``layer_<i>/``."""
    t = x.shape[0]
    dh, h, h_kv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    heads = lambda y, n: y.reshape(t, n, dh).transpose(1, 0, 2)  # [n, T, dh]
    n = rms_norm(x, lp["attn_norm/scale"], cfg["rms_eps"])
    q = heads(mm(n, lp["attn/q/w"]), h)
    k = heads(mm(n, lp["attn/k/w"]), h_kv)
    v = heads(mm(n, lp["attn/v/w"]), h_kv)
    log_g = jax.nn.log_sigmoid(mm(n, lp["attn/gate/w"]) + cfg["ret_gate_shift"]).T  # [H_kv, T]
    q = rope(rms_norm(q, lp["attn/q_norm/scale"], cfg["rms_eps"]), cfg["rope_theta"])
    k = rope(rms_norm(k, lp["attn/k_norm/scale"], cfg["rms_eps"]), cfg["rope_theta"])
    one_group = lambda a: retention(*a, cfg["ret_eps"], mm)
    o = jax.lax.map(one_group, (q.reshape(h_kv, h // h_kv, t, dh), k, v, log_g))
    x = x + mm(o.reshape(h, t, dh).transpose(1, 0, 2).reshape(t, h * dh), lp["attn/out/w"])
    n = rms_norm(x, lp["ffn_norm/scale"], cfg["rms_eps"])
    return x + mm(jax.nn.silu(mm(n, lp["ffn/gate/w"])) * mm(n, lp["ffn/fc1/w"]),
                  lp["ffn/fc2/w"])


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
