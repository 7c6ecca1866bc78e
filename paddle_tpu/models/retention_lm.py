"""Decoder-only LM whose attention layers are power-retention layers
(Buckman, Gelada, Zhang et al. 2025, "Scaling Context Requires Rethinking
Attention"; the layer of Manifest AI's Brumby-14B-Base, which is Qwen3-14B
with every attention layer replaced by power retention).

Block, pre-norm: ``h = x + Ret(RMSNorm(x))``, ``y = h + W_down(silu(W_gate n)
* W_up n)`` with ``n = RMSNorm(h)``; no bias anywhere, a final RMSNorm and an
untied head. ``Ret``: grouped query heads over fewer key-value heads, RMSNorm
over each q and k head, RoPE, one gate per key-value head and token
(``log g = logsigmoid(W_g n + ret_gate_shift)``, float32; the shift is a
constant of the configuration, not a parameter: 0 by default, and
``logit(0.999)`` puts untrained gates near 1, where a trained model's lie),
degree 2::

    a[t, s] = (q_t . k_s / sqrt(dh))^2 * prod_{r=s+1..t} g_r        (s <= t)
    o_t     = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)

Every weight is non-negative, so the normaliser is a plain sum. With ``phi``
the degree-2 power map (``phi(x) . phi(y) = (x . y)^2 / dh``) the same
numbers come from a recurrent state per key-value head::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

whose size does not depend on the context: that state, not a KV cache, is
what ``serving.DecodeEngine`` keeps per slot for this model.

The core is written in three forms that give the same numbers: the attention
form (one chunk, no state), the chunked form (inside a chunk the attention
form, across chunks the state, the cumulated log-gates joining the two:
training and the engine's prefill) and the recurrent form (one token: the
engine's decode step, the ``retention_step`` kernel of
``ops/pallas/retention.py`` and its einsum twin there). The block is written
once, :func:`block`; training, a prefill chunk and a decode step differ only
in the ``retain`` they hand it.

``phi`` is tiled: the ``dh x dh`` outer product is cut into ``tile x tile``
tiles, the upper-triangular ones are kept and the off-diagonal ones counted
twice (on the key side). At ``dh`` 128 and ``tile`` 16 that is 9216 features
against the exact 8256, the same mathematics in shapes the chip likes.

State layout, per layer, slot and key-value head: ``[R, D]`` float32 with
``R = dh + 8``: rows ``0..dh-1`` hold ``S^T``, row ``dh`` holds ``z`` (the
value "1" carried through the same recurrence), the rest pad to a multiple of
8 and stay zero. So one product with ``phi(q)`` gives numerator and
normaliser, and a token's update needs no transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.core.enforce import enforce
from paddle_tpu.models import ModelSpec, ServingPrograms
from paddle_tpu.models.transformer_lm import _decode_ffn_fn, sample_logits
from paddle_tpu.ops.attention import apply_rope, rope_tables

__all__ = [
    "BASE_CFG", "block", "get_model", "lm_forward", "param_shapes", "phi",
    "retention_chunk", "serving_programs",
    "state_decode_step", "state_dim", "state_prefill_chunk", "state_shape",
]

BASE_CFG = dict(
    family="retention_lm",
    vocab=32000,
    d_model=512,
    d_inner=1536,
    num_heads=8,
    num_kv_heads=2,
    head_dim=64,
    n_layers=4,
    max_len=2048,
    rope_theta=1e6,
    rms_eps=1e-6,
    ret_eps=1e-6,     # added to the normaliser
    ret_gate_shift=0.0,  # constant added to the gate's logit
    ret_tile=16,      # tile of the power map
    train_chunk=64,   # chunk of the chunked form under pt.Trainer
    # the published checkpoint is bfloat16; so are the held parameters and
    # the matmul operands. State, gates and normaliser are float32 always
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)

PAD_ROWS = 8  # the normaliser's row, padded to a sublane tile


# -- the power map ---------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _tile_pairs(dh: int, tile: int):
    enforce(dh % tile == 0, f"head_dim {dh} is not a multiple of ret_tile {tile}")
    a, b = np.triu_indices(dh // tile)
    return a, b, np.where(a == b, 1.0, 2.0).astype(np.float32)


def state_dim(cfg: dict) -> int:
    """Features of the tiled degree-2 map."""
    nt = cfg["head_dim"] // cfg["ret_tile"]
    return nt * (nt + 1) // 2 * cfg["ret_tile"] ** 2


def state_shape(cfg: dict, max_slots: int):
    """``[L, slots, H_kv, dh + 8, D]``: see the module docstring."""
    return (cfg["n_layers"], max_slots, cfg["num_kv_heads"],
            cfg["head_dim"] + PAD_ROWS, state_dim(cfg))


def phi(x, tile: int, key_side: bool = False):
    """[..., dh] -> [..., D] float32. The key side carries the tiles'
    weights and the 1/dh of the squared scale, so that
    ``phi(q) . phi(k, key_side=True) == (q . k)^2 / dh``."""
    dh = x.shape[-1]
    a, b, w = _tile_pairs(dh, tile)
    xt = x.astype(jnp.float32).reshape(x.shape[:-1] + (dh // tile, tile))
    out = xt[..., a, :, None] * xt[..., b, None, :]  # [..., pairs, tile, tile]
    if key_side:
        out = out * (w / dh)[:, None, None]
    return out.reshape(x.shape[:-1] + (-1,))


def _augment(v, valid):
    """[..., T, dh] values -> [..., T, dh + 8]: a column of ones (it carries
    the normaliser) and zero padding; rows of padded positions are zero, so
    they add nothing to any sum."""
    pad = jnp.zeros(v.shape[:-1] + (PAD_ROWS,), jnp.float32).at[..., 0].set(1.0)
    return jnp.where(valid[..., None] > 0,
                     jnp.concatenate([v.astype(jnp.float32), pad], -1), 0.0)


# -- the core, one key-value head ------------------------------------------

def retention_chunk(q, k, v_aug, log_g, s0, *, tile: int, cdt):
    """One chunk of one key-value head. ``q`` [G, C, dh], ``k`` [C, dh],
    ``v_aug`` [C, R] (:func:`_augment`), ``log_g`` [C] (0 at padded
    positions, which therefore decay nothing), ``s0`` [R, D] the state the
    chunk starts from, or None for the attention form. The operands of the
    products inside the chunk are cast to ``cdt``; sums, gates, the state
    and the products that read or write it are float32. Returns
    ``(out [G, C, R], s1)``: numerator and, in column ``dh``, normaliser."""
    dh = k.shape[-1]
    b = jnp.cumsum(log_g)  # b_t = sum_{r <= t} log g_r
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    sc = mm("gtd,sd->gts", q.astype(cdt), k.astype(cdt)) * dh ** -0.5
    causal = jnp.tril(jnp.ones((k.shape[0],) * 2, bool))
    decay = jnp.exp(jnp.where(causal, b[:, None] - b[None, :], -jnp.inf))
    out = mm("gts,sr->gtr", (sc * sc * decay).astype(cdt), v_aug.astype(cdt))
    if s0 is None:
        return out, None
    # phi(q) . phi(k) is a square built from thousands of signed terms that
    # cancel: bfloat16 operands lose it, so the two products that carry the
    # power map keep float32 operands (three bfloat16 passes on the chip)
    exact = functools.partial(mm, precision=jax.lax.Precision.HIGH)
    out = out + jnp.exp(b)[None, :, None] * exact("gtd,rd->gtr", phi(q, tile), s0)
    carried = v_aug * jnp.exp(b[-1] - b)[:, None]
    return out, jnp.exp(b[-1]) * s0 + exact("cr,cd->rd", carried, phi(k, tile, key_side=True))


def _normalise(out, dh: int, eps: float):
    return out[..., :dh] / (out[..., dh:dh + 1] + eps)


# -- the three ways the block reaches its state ----------------------------

def _retain_train(cfg):
    """No state to keep: every row starts from zero and walks its chunks."""
    tile, cdt = cfg["ret_tile"], jnp.dtype(cfg["compute_dtype"])
    dh, eps = cfg["head_dim"], cfg["ret_eps"]

    def retain(_i, q, k, v, log_g):
        B, Hkv, T, _ = k.shape
        G = q.shape[1] // Hkv
        C = cfg["train_chunk"] if T % cfg["train_chunk"] == 0 else T
        n = T // C
        qc = q.reshape(B, Hkv, G, n, C, dh)
        v_aug = _augment(v, jnp.ones(v.shape[:-1], jnp.float32))
        head = functools.partial(retention_chunk, tile=tile, cdt=cdt)
        if n == 1:  # the attention form
            over = jax.vmap(jax.vmap(lambda q_, k_, v_, g_: head(q_, k_, v_, g_, None)[0]))
            out = over(qc[:, :, :, 0], k, v_aug, log_g)
        else:
            over = jax.vmap(jax.vmap(head))

            def step(state, c):
                out, state = over(qc[:, :, :, c],
                                  *(x.reshape(B, Hkv, n, C, *x.shape[3:])[:, :, c]
                                    for x in (k, v_aug, log_g)), state)
                return state, out

            s0 = jnp.zeros((B, Hkv, dh + PAD_ROWS, state_dim(cfg)), jnp.float32)
            _, outs = jax.lax.scan(step, s0, jnp.arange(n))  # [n, B, Hkv, G, C, R]
            out = jnp.moveaxis(outs, 0, 3).reshape(B, Hkv, G, T, -1)
        return _normalise(out, dh, eps).reshape(B, Hkv * G, T, dh)

    return retain


def _retain_chunk(cfg, box, slot, pos0, valid):
    """A prefill chunk of one sequence against ``box[0]``, the engine's
    state array: slot ``slot``'s state is read (zero where the chunk opens
    the sequence, so an admission needs no reset call), carried through the
    chunk and written back. One key-value head at a time, so that only one
    head's ``phi(q)`` is alive."""
    tile, cdt = cfg["ret_tile"], jnp.dtype(cfg["compute_dtype"])
    dh, eps = cfg["head_dim"], cfg["ret_eps"]

    def retain(i, q, k, v, log_g):
        Hkv, C = k.shape[1], k.shape[2]
        state = box[0]
        at = (i, slot, 0, 0, 0)
        s0 = jax.lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])[0, 0]
        s0 = jnp.where(pos0 > 0, s0, 0.0)
        head = lambda x: retention_chunk(*x, tile=tile, cdt=cdt)
        out, s1 = jax.lax.map(head, (
            q[0].reshape(Hkv, -1, C, dh), k[0], _augment(v[0], valid),
            log_g[0] * valid, s0))
        box[0] = jax.lax.dynamic_update_slice(state, s1[None, None], at)
        return _normalise(out, dh, eps).reshape(1, -1, C, dh)

    return retain


def _retain_step(cfg, box, active):
    """One token of every slot against ``box[0]``: the ``retention_step``
    kernel. A slot that is idle or still prefilling (``active`` 0) has gate
    1 and update 0, so its state comes out as it went in."""
    from paddle_tpu.ops.pallas.retention import retention_step

    tile, dh, eps = cfg["ret_tile"], cfg["head_dim"], cfg["ret_eps"]

    def retain(i, q, k, v, log_g):
        S, Hkv = k.shape[0], k.shape[1]
        G = q.shape[1] // Hkv
        on = active.astype(jnp.float32)[:, None]  # [S, 1]
        pq = phi(q[:, :, 0].reshape(S, Hkv, G, dh), tile)
        pk = phi(k[:, :, 0], tile, key_side=True) * on[..., None]
        v_aug = _augment(v[:, :, 0], jnp.broadcast_to(on, (S, Hkv)))
        g = jnp.exp(log_g[:, :, 0] * on)
        acc, box[0] = retention_step(
            box[0], pq, pk[:, :, None, :], v_aug[..., None], g, layer=i)
        return _normalise(acc, dh, eps).reshape(S, Hkv * G, 1, dh)

    return retain


# -- the block, written once -----------------------------------------------

def _rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _ops(p, cfg):
    cdt = jnp.dtype(cfg["compute_dtype"])

    def proj(x, pfx, bias=False):
        return jnp.matmul(x.astype(cdt), p(pfx + "/w").astype(cdt),
                          preferred_element_type=jnp.float32)

    norm = lambda x, pfx: _rms_norm(x, p(pfx + "/scale"), cfg["rms_eps"])
    return proj, norm, _decode_ffn_fn(proj, swiglu=True)


def block(p, x, i: int, cfg: dict, rope, retain):
    """Layer ``i`` on the float32 residual stream ``x`` [N, T, d_model].
    ``p(name)`` yields a parameter; ``rope`` is the (cos, sin) of the
    tokens' positions, broadcastable to [N, heads, T, dh / 2];
    ``retain(i, q, k, v, log_g)`` (q [N, H, T, dh], k and v [N, H_kv, T,
    dh], log_g [N, H_kv, T]) returns the retention output [N, H, T, dh]
    by whichever form the caller's state calls for."""
    N, T, _ = x.shape
    dh = cfg["head_dim"]
    proj, norm, ffn = _ops(p, cfg)
    pfx = f"layer_{i}/attn"
    heads = lambda y: y.reshape(N, T, -1, dh).transpose(0, 2, 1, 3)
    with jax.named_scope("retention"):
        n = norm(x, f"layer_{i}/attn_norm")
        q, k, v = (heads(proj(n, f"{pfx}/{w}")) for w in "qkv")
        log_g = jax.nn.log_sigmoid(
            proj(n, f"{pfx}/gate") + cfg["ret_gate_shift"]).transpose(0, 2, 1)
        q = apply_rope(norm(q, f"{pfx}/q_norm"), *rope)
        k = apply_rope(norm(k, f"{pfx}/k_norm"), *rope)
        ctx = retain(i, q, k, v, log_g)
        x = x + proj(ctx.transpose(0, 2, 1, 3).reshape(N, T, -1), f"{pfx}/out")
    with jax.named_scope("ffn"):
        return x + ffn(norm(x, f"layer_{i}/ffn_norm"), i)


def _embed(p, ids):
    """Token ids -> the float32 residual stream; the embedding is not scaled."""
    with jax.named_scope("embed"):
        return jnp.take(p("emb/word_emb"), ids, axis=0).astype(jnp.float32)


def _hidden(p, ids, cfg, rope, retain):
    """[N, T] token ids -> [N, T, d_model] after the last block."""
    x = _embed(p, ids)
    for i in range(cfg["n_layers"]):
        x = block(p, x, i, cfg, rope, retain)
    return x


def _logits(p, x, cfg):
    proj, norm, _ = _ops(p, cfg)
    with jax.named_scope("head"):
        return proj(norm(x, "final_norm"), "head")


# -- parameters -------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """{name: shape} of every parameter; leaves are named ``w``, ``scale``
    and ``word_emb``."""
    d, f, dh = cfg["d_model"], cfg["d_inner"], cfg["head_dim"]
    H, Hkv = cfg["num_heads"], cfg["num_kv_heads"]
    out = {"emb/word_emb": (cfg["vocab"], d), "final_norm/scale": (d,),
           "head/w": (d, cfg["vocab"])}
    for i in range(cfg["n_layers"]):
        a = f"layer_{i}/attn"
        out.update({
            f"layer_{i}/attn_norm/scale": (d,), f"layer_{i}/ffn_norm/scale": (d,),
            f"{a}/q/w": (d, H * dh), f"{a}/k/w": (d, Hkv * dh), f"{a}/v/w": (d, Hkv * dh),
            f"{a}/gate/w": (d, Hkv), f"{a}/out/w": (H * dh, d),
            f"{a}/q_norm/scale": (dh,), f"{a}/k_norm/scale": (dh,),
            f"layer_{i}/ffn/fc1/w": (d, f), f"layer_{i}/ffn/gate/w": (d, f),
            f"layer_{i}/ffn/fc2/w": (f, d),
        })
    return out


def _frame_params(cfg, shapes=None, own=None):
    """``p(name)`` inside a ``pt.build`` frame: created at init, fetched at
    apply, by the full name. ``shapes`` {name: shape}: this model's own
    where not given. ``own`` {name: initializer} for the leaves that take
    neither their kind's nor the framework's."""
    from paddle_tpu import initializer as init

    shapes = shapes or param_shapes(cfg)
    rules = {"scale": init.Constant(1.0),
             "word_emb": init.Normal(0.0, cfg["d_model"] ** -0.5)}

    def p(name):
        return pt.framework.create_parameter(
            shapes[name], cfg["param_dtype"], name=name,
            default_initializer=(own or {}).get(name) or rules.get(name.rsplit("/", 1)[-1]))

    return p


def _dict_params(params):
    params = params.params if hasattr(params, "params") else params
    return params.__getitem__


# -- training ---------------------------------------------------------------

def _next_token_loss(logits, labels):
    """``(mean nll, token count, logits)``: what a training forward returns."""
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), float(np.prod(labels.shape)), logits


def lm_forward(ids, labels, *, cfg):
    """Next-token training forward through the chunked form, differentiated
    by XLA: ``(loss, token count, logits)`` like ``transformer_lm``'s."""
    p = _frame_params(cfg)
    rope = rope_tables(cfg["head_dim"], ids.shape[1], cfg["rope_theta"])
    return _next_token_loss(
        _logits(p, _hidden(p, ids, cfg, rope, _retain_train(cfg)), cfg), labels)


# -- serving: the engine's three programs ----------------------------------

def state_cache_specs(cfg: dict, *, max_slots: int, **_):
    """What the engine allocates and owns for this model: one float32 state
    array, no pages."""
    return (jax.ShapeDtypeStruct(state_shape(cfg, max_slots), jnp.float32),)


def _enforce_sampling(temperature, rng, what="retention decode"):
    enforce(temperature == 0.0 or rng is not None,
            f"{what}: sampling (temperature > 0) needs an explicit rng key")


def state_prefill_chunk(params, tokens, pos0, last_index, slot, state, rng=None,
                        *, cfg: dict, temperature: float = 0.0,
                        top_k: int | None = None, top_p: float | None = None):
    """Prefill ONE sequence's chunk into slot ``slot`` of ``state``:
    ``tokens`` [C] at positions ``[pos0, pos0 + C)``, of which those up to
    chunk index ``last_index`` are real (the last chunk is padded: a padded
    position adds nothing to the state and decays nothing). A chunk at
    ``pos0`` 0 starts from a zero state whatever the slot held. Returns
    ``(next_token, state)``; the token is sampled at ``last_index`` and
    means something on the final chunk only."""
    _enforce_sampling(temperature, rng)
    p = _dict_params(params)
    (C,) = tokens.shape
    valid = (jnp.arange(C) <= last_index).astype(jnp.float32)
    box = [state]
    rope = rope_tables(cfg["head_dim"], C, cfg["rope_theta"], pos0)
    x = _hidden(p, tokens[None], cfg, rope, _retain_chunk(cfg, box, slot, pos0, valid))
    x_last = jax.lax.dynamic_index_in_dim(x[0], jnp.minimum(last_index, C - 1), 0)
    with jax.named_scope("sampling"):
        tok = sample_logits(_logits(p, x_last, cfg)[0], rng, temperature, top_k, top_p)
    return tok, box[0]


def state_decode_step(params, tokens, positions, active, state, rng=None,
                      *, cfg: dict, temperature: float = 0.0,
                      top_k: int | None = None, top_p: float | None = None):
    """One decode iteration for ``S`` slots: ``tokens`` [S] at ``positions``
    [S]; ``active`` [S] is 1 for a decoding slot. The state of an idle or
    still-prefilling slot is not changed and its output is garbage the
    engine ignores. Returns ``(next_tokens [S], state)``."""
    _enforce_sampling(temperature, rng)
    p = _dict_params(params)
    cos, sin = jax.vmap(lambda at: rope_tables(
        cfg["head_dim"], 1, cfg["rope_theta"], at))(positions)
    box = [state]
    x = _hidden(p, tokens[:, None], cfg, (cos[:, None], sin[:, None]),
                _retain_step(cfg, box, active))
    with jax.named_scope("sampling"):
        nxt = sample_logits(_logits(p, x[:, 0], cfg), rng, temperature, top_k, top_p)
    return nxt, box[0]


def serving_programs() -> ServingPrograms:
    return ServingPrograms(
        cache="state", cache_args=("state",), cache_specs=state_cache_specs,
        prefill_chunk=state_prefill_chunk, decode_step=state_decode_step,
        verify_step=None,
        mechanism="power retention: a fixed recurrent state per slot, no KV pages")


# -- registry ---------------------------------------------------------------

def get_model(seq_len: int = 1024, learning_rate: float = 1e-3, **overrides) -> ModelSpec:
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})
    cfg["max_len"] = max(cfg["max_len"], seq_len)
    enforce(cfg["num_heads"] % cfg["num_kv_heads"] == 0,
            f"num_heads {cfg['num_heads']} is not a multiple of num_kv_heads "
            f"{cfg['num_kv_heads']}")
    model = pt.build(functools.partial(lm_forward, cfg=cfg), name="retention_lm")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        tok = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    return ModelSpec(
        name="retention_lm", model=model, synth_batch=synth_batch,
        optimizer=lambda: pt.optimizer.Adam(learning_rate=learning_rate),
        unit="tokens/sec", examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len})
