"""Plain reference of the looped decoder LM (the published ``ouro`` configs;
Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language Models").
Written from the equations: the full forward pass over whole sequences, no
cache, no pages, every pass spelled as a Python loop. Float32, every product
through ``mm``. Imports nothing of the program; parameters are looked up by
the names the program gives them.

``x_0 = E[token]``, not scaled. Pass ``r`` of ``R = total_ut_steps`` applies
the whole stack of ``L`` layers with the same weights, then the final
RMSNorm, which closes *every* pass. A layer, sandwich norms::

    h = x + N2(Attn(N1(x)))        x = h + N4(MLP(N3(h)))

``Attn``: bias-free q, k, v, ``H`` query heads over ``H_kv`` key-value heads
of ``dh``, RoPE on q and k (half-split pairing, base ``rope_theta``), causal
softmax at ``dh^-0.5`` over the keys and values **this pass** made, ``W_o``.
``MLP(n) = W_down(silu(W_gate n) * W_up n)``. After each pass the exit gate
reads the normed stream, ``lambda_r = sigmoid(w_g . x + b_g)``; the exit
distribution is ``p_r = lambda_r prod_{s<r} (1 - lambda_s)``, the last pass
taking the remainder. The logits are the untied head on the stream after the
last pass.

A model of the published size does not fit the chip in float32 beside
anything else, so the walk is by layer: :func:`embed`, then for every pass
:func:`layer` with one layer's parameters at a time and :func:`close_pass`,
then :func:`logits_at` for the rows that are wanted."""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def embed(word_emb, ids):
    """[T] token ids -> [T, d]."""
    return word_emb[ids]


def attention(q, k, v, mm):
    """Causal softmax attention of one sequence: ``q`` [H, T, dh], ``k`` and
    ``v`` [H_kv, T, dh] -> [H, T, dh]; query head ``j`` reads key-value head
    ``j // (H / H_kv)``."""
    h, t, dh = q.shape
    g = h // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = mm(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(jnp.float32(dh))
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return mm(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def layer(x, lp: dict, cfg: dict, mm, keep: dict | None = None):
    """One block on one sequence: ``x`` [T, d]; ``lp`` holds the layer's
    parameters by their names under ``layer_<i>/``. ``keep``, where given,
    receives the rotated keys and the values this application made."""
    t = x.shape[0]
    dh, h = cfg["head_dim"], cfg["num_heads"]
    h_kv = cfg.get("num_kv_heads") or h
    eps = cfg["rms_eps"]
    heads = lambda y, n: y.reshape(t, n, dh).transpose(1, 0, 2)  # [n, T, dh]
    n = rms_norm(x, lp["attn_norm/scale"], eps)
    q = rope(heads(mm(n, lp["attn/q/w"]), h), cfg["rope_theta"])
    k = rope(heads(mm(n, lp["attn/k/w"]), h_kv), cfg["rope_theta"])
    v = heads(mm(n, lp["attn/v/w"]), h_kv)
    if keep is not None:
        keep.update(k=k, v=v)
    o = attention(q, k, v, mm).transpose(1, 0, 2).reshape(t, h * dh)
    x = x + rms_norm(mm(o, lp["attn/out/w"]), lp["attn_post_norm/scale"], eps)
    n = rms_norm(x, lp["ffn_norm/scale"], eps)
    y = mm(jax.nn.silu(mm(n, lp["ffn/gate/w"])) * mm(n, lp["ffn/fc1/w"]), lp["ffn/fc2/w"])
    return x + rms_norm(y, lp["ffn_post_norm/scale"], eps)


def close_pass(x, final_scale, gate_w, gate_b, cfg: dict):
    """What ends a pass: the final RMSNorm, and the exit gate on the normed
    stream. ``x`` [T, d] -> (normed [T, d], lambda [T])."""
    x = rms_norm(x, final_scale, cfg["rms_eps"])
    lam = jax.nn.sigmoid(jnp.matmul(x, gate_w, precision=jax.lax.Precision.HIGHEST)[..., 0]
                         + gate_b[0])
    return x, lam


def exit_distribution(lams):
    """[R, ...] gates -> [R, ...] ``p_r = lambda_r prod_{s<r} (1 - lambda_s)``,
    the last pass taking the remainder: it sums to 1 over the passes."""
    out, stay = [], jnp.ones_like(lams[0])
    for r in range(len(lams) - 1):
        out.append(lams[r] * stay)
        stay = stay * (1.0 - lams[r])
    return jnp.stack(out + [stay])


def logits_at(x_rows, head_w, mm):
    """[n, d] rows of the stream the last pass closed -> [n, vocab]."""
    return mm(x_rows, head_w)


def layer_params(params: dict, i: int) -> dict:
    head = f"layer_{i}/"
    return {k[len(head):]: p for k, p in params.items() if k.startswith(head)}


def forward(params, row, cfg: dict, mm, keep: dict | None = None):
    """One sequence through every pass: [T] ids -> (logits [T, vocab], exit
    gates [R, T]). ``keep``, where given, receives ``(r, i) -> {"k", "v"}``,
    the rotated keys and the values of pass ``r``, layer ``i``."""
    x = embed(params["emb/word_emb"], row)
    lams = []
    for r in range(cfg["total_ut_steps"]):
        for i in range(cfg["n_layers"]):
            kept = None if keep is None else keep.setdefault((r, i), {})
            x = layer(x, layer_params(params, i), cfg, mm, kept)
        x, lam = close_pass(x, params["final_norm/scale"], params["exit_gate/w"],
                            params["exit_gate/b"], cfg)
        lams.append(lam)
    return logits_at(x, params["head/w"], mm), jnp.stack(lams)


def logits_fn(params, ids, cfg: dict, mm):
    """Whole model at once, for sizes that fit: [B, T] ids -> [B, T, vocab]."""
    return jax.vmap(lambda row: forward(params, row, cfg, mm)[0])(ids)


def loss_sum(params, ids, labels, cfg: dict, mm):
    """Summed next-token negative log-likelihood over every position, on the
    last pass's logits."""
    logp = jax.nn.log_softmax(logits_fn(params, ids, cfg, mm), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))
