"""Plain reference of the hybrid Mamba-2 / attention LM (the published
``granitemoehybrid`` configs, IBM Granite 4.0-H; the Mamba-2 layer of Dao &
Gu 2024, "Transformers are SSMs"). Written from the equations: the Mamba-2
layers as the **plain recurrence**, one token after another (``lax.scan``
over the sequence; no chunks, no carried cache, no kernel), the attention
layers as a full causal softmax. Float32, every matrix product through
``mm``. Imports nothing of the program; parameters are looked up by the names
the program gives them, numbers by the program's names for them.

Stream ``x_0 = embedding_multiplier * E[ids]``; layer, with ``r =
residual_multiplier * branch_gain`` (the second a constant of the
configuration for seeded weights, 1 for trained ones)::

    h = x + r * Mixer(RMSNorm(x))
    y = h + r * W_down(silu(W_gate n) * W_up n),   n = RMSNorm(h)

logits ``RMSNorm(x_L) E^T / logits_scaling`` (the head is the embedding).

Attention mixer: ``H`` query heads over ``H_kv`` key-value heads (query head
``j`` reads key-value head ``j // (H / H_kv)``), no position embedding of any
kind, causal softmax of ``attention_multiplier * (attn_q_gain * q) . k``.

Mamba-2 mixer: ``[z ; xBC ; dt] = W_in n``; ``xBC`` through a depthwise
causal convolution of ``K`` taps (tap ``j`` weighs the input ``K - 1 - j``
positions back; the taps are multiplied by ``ssm_conv_gain``) with a bias,
then silu; ``[x ; B ; C] = xBC``; per head ``dt = softplus(dt + dt_bias +
ssm_dt_shift)``, ``a = exp(dt * A)``, ``A = -exp(A_log)``; per channel of a
head ``H_t = a_t H_{t-1} + dt_t x_t B_t``, ``y_t = C_t . H_t + D x_t``; then
``W_o RMSNorm_w(y * silu(z))``, the norm over all channels.

A model of the published size does not sit in float32 beside anything, so
the walk is by layer: :func:`embed`, then :func:`layer` with one layer's
parameters at a time, then :func:`logits_at` for the rows that are wanted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def embed(word_emb, ids, cfg: dict):
    """[T] token ids -> [T, d]."""
    return word_emb[ids] * cfg["embedding_multiplier"]


def attention_mixer(n, lp: dict, cfg: dict, mm):
    """``n`` [T, d] -> [T, d]: full causal softmax, one key-value group at a
    time."""
    t = n.shape[0]
    dh, h, h_kv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"] or cfg["num_heads"]
    scale = cfg["attention_multiplier"]
    scale = dh ** -0.5 if scale is None else scale
    heads = lambda y, k: y.reshape(t, k, dh).transpose(1, 0, 2)  # [k, T, dh]
    q = heads(mm(n, lp["attn/q/w"]), h) * cfg["attn_q_gain"]
    k = heads(mm(n, lp["attn/k/w"]), h_kv)
    v = heads(mm(n, lp["attn/v/w"]), h_kv)
    seen = jnp.tril(jnp.ones((t, t), bool))

    def one_group(a):
        q_g, k_g, v_g = a  # [G, T, dh], [T, dh], [T, dh]
        s = jnp.where(seen, mm(q_g, k_g.T) * scale, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v_g)

    o = jax.lax.map(one_group, (q.reshape(h_kv, h // h_kv, t, dh), k, v))
    return mm(o.reshape(h, t, dh).transpose(1, 0, 2).reshape(t, h * dh), lp["attn/out/w"])


def mamba_mixer(n, lp: dict, cfg: dict, mm):
    """``n`` [T, d] -> [T, d]: the recurrence, a token at a time."""
    t = n.shape[0]
    heads, p, ns, taps = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_state"], cfg["ssm_conv"]
    d = heads * p
    zxbcdt = mm(n, lp["mamba/in/w"])
    z, xbc, dt = zxbcdt[:, :d], zxbcdt[:, d:d + d + 2 * ns], zxbcdt[:, d + d + 2 * ns:]
    past = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    w = lp["mamba/conv/w"] * cfg["ssm_conv_gain"]  # [K, channels]
    xbc = jax.nn.silu(lp["mamba/conv/b"] + sum(w[j] * past[j:j + t] for j in range(taps)))
    x, b, c = xbc[:, :d].reshape(t, heads, p), xbc[:, d:d + ns], xbc[:, d + ns:]
    dt = jax.nn.softplus(dt + lp["mamba/dt/b"] + cfg["ssm_dt_shift"])  # [T, heads]
    a = jnp.exp(dt * -jnp.exp(lp["mamba/a_log/bias"]))

    def token(state, tok):  # state [heads, p, N]
        x_t, b_t, c_t, dt_t, a_t = tok
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, ns), jnp.float32), (x, b, c, dt, a))
    y = (y + lp["mamba/d/scale"][None, :, None] * x).reshape(t, d)
    return mm(rms_norm(y * jax.nn.silu(z), lp["mamba/norm/scale"], cfg["rms_eps"]),
              lp["mamba/out/w"])


def layer(x, lp: dict, cfg: dict, mm):
    """One block on one sequence: ``x`` [T, d]; ``lp`` holds the layer's
    parameters by their names under ``layer_<i>/``, which also say what kind
    of layer it is."""
    r = cfg["residual_multiplier"] * cfg["branch_gain"]
    mixer = attention_mixer if "attn/q/w" in lp else mamba_mixer
    x = x + r * mixer(rms_norm(x, lp["mixer_norm/scale"], cfg["rms_eps"]), lp, cfg, mm)
    n = rms_norm(x, lp["ffn_norm/scale"], cfg["rms_eps"])
    return x + r * mm(jax.nn.silu(mm(n, lp["ffn/gate/w"])) * mm(n, lp["ffn/fc1/w"]),
                      lp["ffn/fc2/w"])


def logits_at(x_rows, final_scale, word_emb, cfg: dict, mm):
    """[n, d] rows of the last block's output -> [n, vocab] logits through
    the tied head."""
    return mm(rms_norm(x_rows, final_scale, cfg["rms_eps"]), word_emb.T) / cfg["logits_scaling"]


def logits_fn(params, ids, cfg: dict, mm):
    """Whole model at once, for sizes that fit: [B, T] ids -> [B, T, vocab]."""
    def one(row):
        x = embed(params["emb/word_emb"], row, cfg)
        for i in range(len(cfg["layer_types"])):
            head = f"layer_{i}/"
            x = layer(x, {k[len(head):]: p for k, p in params.items()
                          if k.startswith(head)}, cfg, mm)
        return logits_at(x, params["final_norm/scale"], params["emb/word_emb"], cfg, mm)

    return jax.vmap(one)(ids)


def loss_sum(params, ids, labels, cfg: dict, mm):
    """Summed next-token negative log-likelihood over every position."""
    logp = jax.nn.log_softmax(logits_fn(params, ids, cfg, mm), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))
