"""Plain reference of the LM whose layers are one mixer each, of three kinds
(NVIDIA's ``nemotron_h`` configs with a latent expert layer, Nemotron 3 Super
120B-A12B: ``hybrid_override_pattern`` says which layer is which; the Mamba-2
layer of Dao & Gu 2024, "Transformers are SSMs"). Written from the equations:
the Mamba-2 layers as the **plain recurrence**, one token after another
(``lax.scan`` over the sequence; no chunks, no carried cache, no kernel), with
``B`` and ``C`` by group; the attention layers as a full causal softmax; the
expert layers as a plain loop over the held experts, each applied to every
token and weighted (0 where not selected). Float32, every matrix product
through ``mm``. Imports nothing of the program; parameters are looked up by
the names the program gives them, numbers by the program's names for them.

Stream ``x_0 = E[ids]`` (not scaled); a layer is one mixer alone, ``x = x +
Mixer(RMSNorm(x))``; logits ``W_head RMSNorm(x_L)``, the head untied.

``M``, Mamba-2: ``[z ; xBC ; dt] = W_in n``; ``xBC`` through a depthwise
causal convolution of ``K`` taps (tap ``j`` weighs the input ``K - 1 - j``
positions back; the taps are multiplied by ``ssm_conv_gain``) with a bias,
then silu; ``[x ; B ; C] = xBC``, ``B`` and ``C`` each ``[G, N]``; head ``h``
reads group ``h // (heads / G)``; per head ``dt = softplus(dt + dt_bias +
ssm_dt_shift)``, ``a = exp(dt * A)``, ``A = -exp(A_log)``; per channel of a
head ``H_t = a_t H_{t-1} + dt_t x_t B_t[g]``, ``y_t = C_t[g] . H_t + D x_t``;
then ``W_o GroupRMSNorm_w(y * silu(z))``, the norm over each group's ``d_ssm /
G`` channels separately, gate before norm.

``*``, attention: ``H`` query heads over ``H_kv`` key-value heads (query head
``j`` reads key-value head ``j // (H / H_kv)``), no position embedding of any
kind, causal softmax of ``(attn_q_gain * q) . k / sqrt(head_dim)``.

``E``, experts: ``s = sigmoid(W_r n)`` over the router's full width, the
``experts_per_token`` largest ``s + b`` selected, weights ``routed_scaling *
s_e / sum_selected s``; ``l = W_down n``; ``E_e(l) = W2_e relu(W1_e l)^2``,
two matrices and no gate; ``out = W_up(sum_e w_e E_e(l)) + W2_s relu(W1_s
n)^2``, the shared expert at the model's width. The sum runs over the
selected experts whose weights are **held**: ``experts_held`` (first, count)
of the router's width; expert ``e``'s matrices are
``moe/experts/<e>/{fc1,fc2}/w``, a matrix an expert as a published checkpoint
holds them (the program stacks them at load). What the experts held elsewhere
would add is left out: the share of one chip of an expert-parallel layer.

Departures from the published description: the multi-token-prediction module
(``mtp_hybrid_override_pattern``) is not here, it takes no part in the main
model's forward; ``ssm_dt_shift``, ``ssm_conv_gain`` and ``attn_q_gain`` are
constants of a configuration for seeded weights, 0, 1 and 1 for trained ones.

A model of the published size does not sit in float32 beside anything, so
the walk is by layer: :func:`embed`, then :func:`layer` with one layer's
parameters at a time, then :func:`logits_at` for the rows that are wanted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_BLOCK = 256  # query rows of a key-value group scored at once


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def embed(word_emb, ids):
    """[T] token ids -> [T, d]."""
    return word_emb[ids]


def attention_mixer(n, lp: dict, cfg: dict, mm):
    """``n`` [T, d] -> [T, d]: full causal softmax, one key-value group and
    one block of query rows at a time."""
    t = n.shape[0]
    dh, h, h_kv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"] or cfg["num_heads"]
    heads = lambda y, k: y.reshape(t, k, dh).transpose(1, 0, 2)  # [k, T, dh]
    q = heads(mm(n, lp["attn/q/w"]), h) * cfg["attn_q_gain"]
    k = heads(mm(n, lp["attn/k/w"]), h_kv)
    v = heads(mm(n, lp["attn/v/w"]), h_kv)
    pad = -t % ROW_BLOCK
    at = jnp.pad(jnp.arange(t), (0, pad)).reshape(-1, ROW_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(h_kv, h // h_kv, -1, ROW_BLOCK, dh)

    def one_group(a):
        q_g, k_g, v_g = a  # [H / H_kv, blocks, R, dh], [T, dh], [T, dh]

        def block(b):
            q_b, t_b = b  # [H / H_kv, R, dh], [R]
            seen = jnp.arange(t)[None, :] <= t_b[:, None]
            s = jnp.where(seen[None], mm(q_b, k_g.T) * dh ** -0.5, -jnp.inf)
            return mm(jax.nn.softmax(s, axis=-1), v_g)

        return jax.lax.map(block, (q_g.transpose(1, 0, 2, 3), at))  # [blocks, H / H_kv, R, dh]

    o = jax.lax.map(one_group, (q, k, v))  # [H_kv, blocks, H / H_kv, R, dh]
    o = o.transpose(1, 3, 0, 2, 4).reshape(-1, h * dh)[:t]
    return mm(o, lp["attn/out/w"])


def mamba_mixer(n, lp: dict, cfg: dict, mm):
    """``n`` [T, d] -> [T, d]: the recurrence, a token at a time, ``B`` and
    ``C`` by group."""
    t = n.shape[0]
    heads, p, ns, taps = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_state"], cfg["ssm_conv"]
    g = cfg["ssm_groups"]
    d = heads * p
    zxbcdt = mm(n, lp["mamba/in/w"])
    z, xbc, dt = zxbcdt[:, :d], zxbcdt[:, d:d + d + 2 * g * ns], zxbcdt[:, d + d + 2 * g * ns:]
    past = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    w = lp["mamba/conv/w"] * cfg["ssm_conv_gain"]  # [K, channels]
    xbc = jax.nn.silu(lp["mamba/conv/b"] + sum(w[j] * past[j:j + t] for j in range(taps)))
    x = xbc[:, :d].reshape(t, heads, p)
    # a head's own B and C: its group's, [T, heads, N]
    of_head = lambda m: jnp.repeat(m.reshape(t, g, ns), heads // g, axis=1)
    b, c = of_head(xbc[:, d:d + g * ns]), of_head(xbc[:, d + g * ns:])
    dt = jax.nn.softplus(dt + lp["mamba/dt/b"] + cfg["ssm_dt_shift"])  # [T, heads]
    a = jnp.exp(dt * -jnp.exp(lp["mamba/a_log/bias"]))

    def token(state, tok):  # state [heads, p, N]
        x_t, b_t, c_t, dt_t, a_t = tok
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, ns), jnp.float32), (x, b, c, dt, a))
    y = (y + lp["mamba/d/scale"][None, :, None] * x).reshape(t, d)
    gated = (y * jax.nn.silu(z)).reshape(t, g, d // g)
    normed = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), -1, keepdims=True) + cfg["rms_eps"])
    return mm(normed.reshape(t, d) * lp["mamba/norm/scale"], lp["mamba/out/w"])


def route(n, lp: dict, cfg: dict, mm):
    """[T, E] weight of every expert for every token, 0 where not selected."""
    s = jax.nn.sigmoid(mm(n, lp["moe/router/w"]))
    _, picked = jax.lax.top_k(s + lp["moe/router/b"], cfg["experts_per_token"])
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], picked].set(1.0)
    return cfg["routed_scaling"] * s * chosen / jnp.sum(s * chosen, -1, keepdims=True)


def expert_share(n, lp: dict, cfg: dict, mm, held=None):
    """The held experts' terms of the routed sum, through the latent space
    and back: ``W_up(sum_{e held} w_e E_e(W_down n))``."""
    first, count = held or cfg["experts_held"] or (0, cfg["num_experts"])
    w = route(n, lp, cfg, mm)
    latent = mm(n, lp["moe/down/w"])
    out = jnp.zeros_like(latent)
    for e in range(first, first + count):
        out = out + w[:, e, None] * mm(relu2(mm(latent, lp[f"moe/experts/{e}/fc1/w"])),
                                       lp[f"moe/experts/{e}/fc2/w"])
    return mm(out, lp["moe/up/w"])


def shared_expert(n, lp: dict, mm):
    return mm(relu2(mm(n, lp["moe/shared/fc1/w"])), lp["moe/shared/fc2/w"])


def expert_mixer(n, lp: dict, cfg: dict, mm):
    return expert_share(n, lp, cfg, mm) + shared_expert(n, lp, mm)


def layer(x, lp: dict, cfg: dict, mm):
    """One layer on one sequence: ``x`` [T, d]; ``lp`` holds the layer's
    parameters by their names under ``layer_<i>/``, which also say what kind
    of layer it is."""
    mixer = (attention_mixer if "attn/q/w" in lp else
             mamba_mixer if "mamba/in/w" in lp else expert_mixer)
    return x + mixer(rms_norm(x, lp["norm/scale"], cfg["rms_eps"]), lp, cfg, mm)


def logits_at(x_rows, final_scale, head_w, cfg: dict, mm):
    """[n, d] rows of the last layer's output -> [n, vocab] logits."""
    return mm(rms_norm(x_rows, final_scale, cfg["rms_eps"]), head_w)


def logits_fn(params, ids, cfg: dict, mm):
    """Whole model at once, for sizes that fit: [B, T] ids -> [B, T, vocab]."""
    def one(row):
        x = embed(params["emb/word_emb"], row)
        for i in range(len(cfg["pattern"])):
            head = f"layer_{i}/"
            x = layer(x, {k[len(head):]: p for k, p in params.items()
                          if k.startswith(head)}, cfg, mm)
        return logits_at(x, params["final_norm/scale"], params["head/w"], cfg, mm)

    return jax.vmap(one)(ids)


def loss_sum(params, ids, labels, cfg: dict, mm):
    """Summed next-token negative log-likelihood over every position."""
    logp = jax.nn.log_softmax(logits_fn(params, ids, cfg, mm), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))
