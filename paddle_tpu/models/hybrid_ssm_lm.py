"""Decoder-only LM whose layers are of two kinds, Mamba-2 layers that keep a
recurrent state and attention layers that keep keys and values (Dao & Gu
2024, "Transformers are SSMs", the Mamba-2 layer; the published
``granitemoehybrid`` configs, IBM Granite 4.0-H: ``layer_types`` says which
layer is which, most are Mamba-2, a few are attention).

Stream: ``x_0 = embedding_multiplier * E[token]``. Layer ``i``, with ``r =
residual_multiplier``::

    h = x + r * Mixer_i(RMSNorm(x))
    y = h + r * W_down(silu(W_gate n) * W_up n),   n = RMSNorm(h)

and ``logits = RMSNorm(x_L) E^T / logits_scaling``: the head is the
embedding. No bias in any projection, no position embedding and no rotary
(the Mamba-2 layers carry the order).

**Attention mixer**: ``num_heads`` query heads over ``num_kv_heads``
key-value heads of ``head_dim``, causal softmax of ``attention_multiplier *
q . k`` (the configuration's scale, not ``1 / sqrt(head_dim)``), ``W_o``.
The cached-attention code this family shares with ``transformer_lm`` scales
by ``1 / sqrt(head_dim)``, so the query is scaled by the ratio here.

**Mamba-2 mixer**: ``d_ssm = ssm_heads * ssm_head_dim`` channels in heads,
``ssm_state`` state numbers a channel, ``G = ssm_groups`` groups of ``B`` and
``C`` (head ``h`` reads group ``h // (heads / G)``; Granite has one, shared
by all heads, Nemotron-H eight), a depthwise causal convolution of
``ssm_conv`` taps::

    [z ; xBC ; dt] = W_in n                        d_ssm + (d_ssm + 2 G N) + heads
    xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t - (K-1) + j})
    [x ; B ; C] = xBC_t                                  B and C [G, N]
    dt_t = softplus(dt_t + dt_bias + ssm_dt_shift)       a head, float32
    a_t  = exp(dt_t * A),   A = -exp(A_log)              a head, float32
    H_t  = a_t H_{t-1} + B_t[g] (x) (dt_t * x_t)         [N, d_ssm], float32
    y_t  = C_t[g] . H_t + D * x_t
    out  = W_o RMSNorm_w(y_t * silu(z_t))                the norm by group: over
                                                         each group's d_ssm / G

What a sequence keeps of such a layer is ``H`` and the last ``K - 1`` inputs
of the convolution; of an attention layer, K and V rows in pages. **Both
kinds of cache live in one engine** (``ServingPrograms.cache``
``"pages+state"``): ``serving.DecodeEngine`` owns four arrays, the K and the
V pages ``[attention layers, pages, page_size, H_kv * dh]``, the SSM states
``[mamba layers, slots, N, d_ssm]`` and the convolution tails ``[mamba
layers, slots, (K - 1) * (d_ssm + 2 N)]`` (a slot's ``K - 1`` inputs side by
side, oldest first: a row of whole lane tiles, which the chip holds as
spelled; with the taps an axis of their own it padded 3 rows to 8 and the
chunk's program copied the whole array); a plane of each is a layer's
ordinal among the layers of its kind.

The SSM core is written in three forms that give the same numbers: the plain
recurrence (:func:`ssm_scan`, one token after another), the chunked form
(:func:`ssm_chunked`: inside a block of ``ssm_chunk`` tokens the
masked-decay attention-like form, across blocks the state: training and the
engine's prefill chunk) and the one-token step (the ``ssm_step`` kernel of
``ops/pallas/ssm.py`` on a TPU, its einsum twin elsewhere: the engine's
decode step). The block is written once, :func:`block`; training, a prefill
chunk and a decode step differ only in the three functions they hand it
(``via``: the convolution's window, the SSM core, the attention).

``ssm_dt_shift``, ``ssm_conv_gain``, ``attn_q_gain`` and ``branch_gain`` are
constants of a configuration, not parameters: 0, 1, 1 and 1 for trained
weights. A benchmark that seeds its weights from noise sets them so that a
state outlives a chunk, a convolution's taps weigh what a trained one's do,
an attention layer is peaked enough to matter and the layers' outputs, not
the token's own embedding read back through the tied head, decide the logits
(``benchmarks/configs/granite_4_0_h_micro.json`` says why); program and
reference read them alike.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.core.enforce import enforce
from paddle_tpu.models import ModelSpec, ServingPrograms
from paddle_tpu.models.retention_lm import (
    _enforce_sampling, _frame_params, _next_token_loss, _ops, _rms_norm,
)
from paddle_tpu.models.transformer_lm import (
    _attend_cached, _live_mask, _paged_attend, kv_attends_in_kernel, kv_heads, sample_logits,
)

__all__ = [
    "ATTENTION", "BASE_CFG", "MAMBA", "attention_mixer", "block", "check_mixers", "conv_width",
    "get_model",
    "group_rms_norm", "hybrid_cache_specs", "hybrid_decode_step", "hybrid_prefill_chunk",
    "layers_of", "lm_forward", "mamba_mixer", "mamba_param_shapes", "param_shapes",
    "serving_programs", "span_attrs", "ssm_chunked", "ssm_scan", "state_bytes_a_slot",
]

MAMBA, ATTENTION = "mamba", "attention"
ATTN_OR_MAMBA = {ATTENTION: "attn", MAMBA: "mamba"}  # a mixer's parameters' prefix

BASE_CFG = dict(
    family="hybrid_ssm_lm",
    vocab=32000,
    d_model=256,
    d_inner=512,            # the MLP's width
    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
    num_heads=4,
    num_kv_heads=None,      # < num_heads -> grouped-query attention
    head_dim=64,
    ssm_heads=8,
    ssm_head_dim=64,
    ssm_state=128,          # N: state numbers a channel
    ssm_groups=1,
    ssm_conv=4,             # K: taps of the causal convolution
    ssm_chunk=256,          # block of the chunked form
    embedding_multiplier=1.0,
    residual_multiplier=1.0,
    attention_multiplier=None,  # None: 1 / sqrt(head_dim)
    logits_scaling=1.0,
    rms_eps=1e-5,
    ssm_dt_shift=0.0,       # constant added to dt's logit
    ssm_conv_gain=1.0,      # constant the convolution's taps are multiplied by
    attn_q_gain=1.0,        # constant the queries are multiplied by
    branch_gain=1.0,        # constant both residual branches are multiplied by
    max_len=2048,
    # the published checkpoint is bfloat16; so are the held parameters and
    # the matmul operands. Residual stream, norms, dt, decay, SSM state and
    # the products that read or write it are float32
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)


def layers_of(cfg: dict, kind: str):
    """The model's layers of ``kind``, in order: a layer's place in this
    list is its plane in the cache arrays of its kind."""
    return [i for i, t in enumerate(cfg["layer_types"]) if t == kind]


def _dims(cfg: dict):
    """(d_ssm, N, heads, head size, conv channels) of a Mamba-2 layer."""
    H, P, N = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_state"]
    return H * P, N, H, P, H * P + 2 * cfg["ssm_groups"] * N


def conv_width(cfg: dict) -> int:
    """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
    return _dims(cfg)[4]


def state_bytes_a_slot(cfg: dict) -> int:
    """Bytes of SSM state one slot holds in one Mamba-2 layer."""
    D, N = _dims(cfg)[:2]
    return 4 * N * D


def _score_scale(cfg: dict) -> float:
    """What the queries are multiplied by so that the shared attention code's
    ``1 / sqrt(head_dim)`` comes out as the configuration's scale."""
    dh = cfg["head_dim"]
    scale = cfg["attention_multiplier"]
    return cfg["attn_q_gain"] * (1.0 if scale is None else scale * np.sqrt(dh))


# -- the SSM core, one sequence ----------------------------------------------
# x [T, D] (heads side by side), dt [T, H], a_neg [H] (= A, negative),
# b and c [T, G, N] (channel d reads group d // (D / G)), h [N, D];
# everything float32

def _by_channel(per_head, P: int):
    """[..., H] a head -> [..., H * P] a channel."""
    return jnp.repeat(per_head, P, axis=-1)


def ssm_scan(x, dt, a_neg, b, c, h0):
    """The plain recurrence, a token at a time. Returns ``(y [T, D], h)``."""
    D = x.shape[-1]
    P, Dg = D // dt.shape[-1], D // b.shape[-2]
    columns = lambda v: jnp.repeat(v.T, Dg, axis=-1)  # [G, N] -> [N, D], a channel its group's

    def step(h, tok):
        x_t, dt_t, b_t, c_t = tok
        h = (_by_channel(jnp.exp(dt_t * a_neg), P)[None, :] * h
             + columns(b_t) * (_by_channel(dt_t, P) * x_t)[None, :])
        return h, jnp.sum(columns(c_t) * h, axis=0)

    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h


def ssm_chunked(x, dt, a_neg, b, c, h0, *, chunk: int, cdt=jnp.float32):
    """The chunked form (SSD): blocks of ``chunk`` tokens (all of ``T`` where
    ``chunk`` does not divide it). Inside a block the outputs are an
    attention-like product, ``(C_t . B_s) * exp(l_t - l_s)`` for ``s <= t``
    with ``l`` the cumulated log-decay, whose operands are cast to ``cdt``;
    across blocks the state, read and written in float32 at full precision.
    A position whose ``dt`` is 0 adds nothing and decays nothing. Returns
    ``(y [T, D], h)``."""
    T, D = x.shape
    H, G, N = dt.shape[-1], b.shape[-2], b.shape[-1]
    P = D // H
    Q = chunk if T % chunk == 0 else T
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    exact = functools.partial(mm, precision=jax.lax.Precision.HIGHEST)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one_block(h, blk):
        xb, dtb, bb, cb = blk
        l = jnp.cumsum(dtb * a_neg, axis=0).T  # [H, Q]: log of the decay up to t
        cb_bs = mm("tgn,sgn->gts", cb.astype(cdt), bb.astype(cdt))
        decay = jnp.exp(jnp.where(causal, l[:, :, None] - l[:, None, :], -jnp.inf))
        xd = (xb.reshape(Q, H, P) * dtb[:, :, None]).transpose(1, 0, 2)  # [H, Q, P]
        # a head weighs its group's scores by its own decay
        scores = (cb_bs[:, None] * decay.reshape(G, H // G, Q, Q)).reshape(H, Q, Q)
        y = mm("hts,hsp->htp", scores.astype(cdt), xd.astype(cdt))
        from_h = exact("tgn,ngd->tgd", cb, h.reshape(N, G, D // G))
        y = y + jnp.exp(l)[:, :, None] * from_h.reshape(Q, H, P).transpose(1, 0, 2)
        to_end = jnp.exp(l[:, -1:] - l)  # [H, Q]
        carried = (xd * to_end[:, :, None]).transpose(1, 0, 2).reshape(Q, G, D // G)
        h = (_by_channel(jnp.exp(l[:, -1]), P)[None, :] * h
             + exact("sgn,sgd->ngd", bb, carried).reshape(N, D))
        return h, y.transpose(1, 0, 2).reshape(Q, D)

    split = lambda v: v.reshape((T // Q, Q) + v.shape[1:])
    h, y = jax.lax.scan(one_block, h0, tuple(split(v) for v in (x, dt, b, c)))
    return y.reshape(T, D), h


def _conv(window, w, bias, gain: float):
    """Depthwise causal convolution: ``window`` [..., T + K - 1, ch] holds
    the ``K - 1`` inputs before the first position, ``w`` [K, ch]. Returns
    the ``T`` outputs before the activation."""
    K = w.shape[0]
    T = window.shape[-2] - (K - 1)
    w = w.astype(jnp.float32) * gain
    out = bias.astype(jnp.float32)
    for j in range(K):
        out = out + w[j] * jax.lax.slice_in_dim(window, j, j + T, axis=-2)
    return out


# -- the three ways the block reaches its caches -------------------------------
# via.window(j, xbc) -> the convolution's window of the Mamba layer of plane j
# via.scan(j, x, dt, a_neg, b, c) -> y;   via.attend(j, q, k, v) -> context

def _via_train(cfg):
    """Nothing to keep: every row starts from zeros and is one causal pass."""
    D, N, _, _, _ = _dims(cfg)
    cdt = jnp.dtype(cfg["compute_dtype"])

    def window(_j, xbc):  # [B, T, ch]
        return jnp.pad(xbc, ((0, 0), (cfg["ssm_conv"] - 1, 0), (0, 0)))

    def scan(_j, x, dt, a_neg, b, c):
        core = functools.partial(ssm_chunked, chunk=cfg["ssm_chunk"], cdt=cdt)
        h0 = jnp.zeros((N, D), jnp.float32)
        return jax.vmap(lambda x_, dt_, b_, c_: core(x_, dt_, a_neg, b_, c_, h0)[0])(x, dt, b, c)

    def attend(_j, q, k, v):
        T = q.shape[2]
        return _attend_cached(q, k, v, _live_mask(jnp.arange(T), T, None)[None, None, None])

    return types.SimpleNamespace(window=window, scan=scan, attend=attend)


def _via_chunk(cfg, cache: dict, page_table, slot, pos0, last_index, C: int, page_size: int):
    """A prefill chunk of one sequence against the engine's arrays: slot
    ``slot``'s convolution tails and SSM states are read (zeros where the
    chunk opens the sequence, so an admission needs no reset call), carried
    through the chunk and written back; its K and V rows go to its pages."""
    D, N, _, _, ch = _dims(cfg)
    cdt = jnp.dtype(cfg["compute_dtype"])
    K = cfg["ssm_conv"]
    fresh = pos0 <= 0
    valid = (jnp.arange(C) <= last_index).astype(jnp.float32)
    paged = _paged_attend(cache["pages"], page_table, pos0 + jnp.arange(C, dtype=jnp.int32),
                          page_size, None)

    def window(j, xbc):  # [1, C, ch]
        tails = cache["conv_state"]
        at = (j, slot, 0)
        tail = jax.lax.dynamic_slice(tails, at, (1, 1, (K - 1) * ch)).reshape(K - 1, ch)
        win = jnp.concatenate([jnp.where(fresh, 0.0, tail), xbc[0]], axis=0)
        # the last K - 1 real inputs: a padded position is no input
        kept = jax.lax.dynamic_slice_in_dim(win, jnp.minimum(last_index, C - 1) + 1, K - 1, axis=0)
        cache["conv_state"] = jax.lax.dynamic_update_slice(tails, kept.reshape(1, 1, -1), at)
        return win[None]

    def scan(j, x, dt, a_neg, b, c):
        states = cache["ssm_state"]
        at = (j, slot, 0, 0)
        h0 = jax.lax.dynamic_slice(states, at, (1, 1, N, D))[0, 0]
        y, h1 = ssm_chunked(x[0], dt[0] * valid[:, None], a_neg, b[0], c[0],
                            jnp.where(fresh, 0.0, h0), chunk=cfg["ssm_chunk"], cdt=cdt)
        cache["ssm_state"] = jax.lax.dynamic_update_slice(states, h1[None, None], at)
        return y[None]

    return types.SimpleNamespace(window=window, scan=scan, attend=paged)


def _via_step(cfg, cache: dict, page_tables, positions, active, page_size: int):
    """One token of every slot against the engine's arrays. A slot that is
    idle or still prefilling (``active`` 0) keeps its tail and its state as
    they are, its K and V row lands on the scratch page."""
    from paddle_tpu.ops.pallas.ssm import ssm_step, ssm_step_xla

    P, K, ch = cfg["ssm_head_dim"], cfg["ssm_conv"], conv_width(cfg)
    on = (active != 0)
    paged = _paged_attend(cache["pages"], page_tables, positions, page_size, None)
    # the kernel on a TPU, its einsum twin elsewhere (``ops/moe.py``'s rule
    # for ``moe_gmm``; a Mosaic kernel is not partitioned over a mesh)
    mesh = jax.sharding.get_abstract_mesh()
    step = (ssm_step if jax.default_backend() == "tpu"
            and all(n == 1 for n in mesh.shape.values()) else ssm_step_xla)

    def window(j, xbc):  # [S, 1, ch]
        tails = cache["conv_state"]
        tail = tails[j]  # [S, (K - 1) * ch]: lane-aligned pieces, oldest first
        new = jnp.concatenate([tail[:, ch:], xbc[:, 0]], axis=-1)
        cache["conv_state"] = tails.at[j].set(jnp.where(on[:, None], new, tail))
        return jnp.stack([tail[:, k * ch:(k + 1) * ch] for k in range(K - 1)]
                         + [xbc[:, 0]], axis=1)  # [S, K, ch]

    def scan(j, x, dt, a_neg, b, c):
        dt_, x_ = dt[:, 0], x[:, 0]
        xdt = _by_channel(dt_, P) * x_
        decay = _by_channel(jnp.exp(dt_ * a_neg), P)
        with jax.named_scope("ssm_step"):
            y, cache["ssm_state"] = step(
                cache["ssm_state"], xdt, decay, b[:, 0], c[:, 0], active, layer=j)
        return y[:, None]

    def attend(j, q, k, v):  # [S, n, 1, dh]
        return paged(j, q[:, :, 0], k[:, :, 0], v[:, :, 0])[:, :, None]

    return types.SimpleNamespace(window=window, scan=scan, attend=attend)


# -- the mixers and the block, written once ------------------------------------
# ``models/hybrid_moe_lm.py`` stacks the same two mixers one a layer, beside
# an expert layer of its own: ``cfg`` holds this module's keys there too

def group_rms_norm(x, scale, eps: float, groups: int):
    """RMSNorm with a learned scale over each of ``groups`` equal runs of the
    last axis separately (one group: over all of it)."""
    if groups == 1:
        return _rms_norm(x, scale, eps)
    by_group = x.astype(jnp.float32).reshape(x.shape[:-1] + (groups, -1))
    normed = by_group * jax.lax.rsqrt(jnp.mean(by_group * by_group, -1, keepdims=True) + eps)
    return normed.reshape(x.shape) * scale.astype(jnp.float32)


def attention_mixer(p, n, a: str, j: int, cfg: dict, via):
    """The attention mixer named ``a`` on the normed stream ``n`` [N, T,
    d_model], its K and V in plane ``j``."""
    N_, T, _ = n.shape
    proj, dh = _ops(p, cfg)[0], cfg["head_dim"]
    heads = lambda y: y.reshape(N_, T, -1, dh).transpose(0, 2, 1, 3)
    with jax.named_scope("attention"):
        q, k, v = (heads(proj(n, f"{a}/{w}")) for w in "qkv")
        ctx = via.attend(j, q * _score_scale(cfg), k, v)
        return proj(ctx.transpose(0, 2, 1, 3).reshape(N_, T, -1), f"{a}/out")


def mamba_mixer(p, n, m: str, j: int, cfg: dict, via):
    """The Mamba-2 mixer named ``m`` on the normed stream ``n`` [N, T,
    d_model], its state and tail in plane ``j``."""
    D, N, H, P, ch = _dims(cfg)
    G = cfg["ssm_groups"]
    proj = _ops(p, cfg)[0]
    f32 = lambda name: p(f"{m}/{name}").astype(jnp.float32)
    with jax.named_scope("mamba"):
        zxbcdt = proj(n, f"{m}/in")
        z, xbc, dt = zxbcdt[..., :D], zxbcdt[..., D:D + ch], zxbcdt[..., D + ch:]
        xbc = jax.nn.silu(_conv(via.window(j, xbc), p(f"{m}/conv/w"), p(f"{m}/conv/b"),
                                cfg["ssm_conv_gain"]))
        by_group = lambda v: v.reshape(v.shape[:-1] + (G, N))
        xs, b, c = xbc[..., :D], by_group(xbc[..., D:D + G * N]), by_group(xbc[..., D + G * N:])
        dt = jax.nn.softplus(dt + f32("dt/b") + cfg["ssm_dt_shift"])
        y = via.scan(j, xs, dt, -jnp.exp(f32("a_log/bias")), b, c)
        y = y + _by_channel(f32("d/scale"), P) * xs
        gated = group_rms_norm(y * jax.nn.silu(z), p(f"{m}/norm/scale"), cfg["rms_eps"], G)
        return proj(gated, f"{m}/out")


def block(p, x, i: int, cfg: dict, via):
    """Layer ``i`` on the float32 residual stream ``x`` [N, T, d_model].
    ``p(name)`` yields a parameter; ``via`` reaches the layer's cache by
    whichever form the caller's arrays call for (see the ``_via_*``)."""
    r = cfg["residual_multiplier"] * cfg["branch_gain"]
    _, norm, ffn = _ops(p, cfg)
    kind = cfg["layer_types"][i]
    j = layers_of(cfg, kind).index(i)  # the layer's plane among its kind
    mixer = attention_mixer if kind == ATTENTION else mamba_mixer
    mixed = mixer(p, norm(x, f"layer_{i}/mixer_norm"), f"layer_{i}/{ATTN_OR_MAMBA[kind]}",
                  j, cfg, via)
    x = x + r * mixed
    with jax.named_scope("ffn"):
        return x + r * ffn(norm(x, f"layer_{i}/ffn_norm"), i)


def _hidden(p, ids, cfg, via):
    """[N, T] token ids -> [N, T, d_model] after the last block."""
    with jax.named_scope("embed"):
        x = (jnp.take(p("emb/word_emb"), ids, axis=0).astype(jnp.float32)
             * cfg["embedding_multiplier"])
    for i in range(len(cfg["layer_types"])):
        x = block(p, x, i, cfg, via)
    return x


def _logits(p, x, cfg):
    """The tied head: the final norm, the embedding's rows as columns."""
    _, norm, _ = _ops(p, cfg)
    cdt = jnp.dtype(cfg["compute_dtype"])
    with jax.named_scope("head"):
        n = norm(x, "final_norm").astype(cdt)
        emb = p("emb/word_emb").astype(cdt)
        out = jax.lax.dot_general(n, emb, (((n.ndim - 1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return out / cfg["logits_scaling"]


# -- parameters -------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """{name: shape} of every parameter; leaves are named ``w``, ``b``,
    ``scale``, ``bias`` and ``word_emb`` (a Mamba-2 layer's ``dt_bias`` is
    ``dt/b``, its ``A_log`` ``a_log/bias``, its ``D`` ``d/scale``)."""
    d, f = cfg["d_model"], cfg["d_inner"]
    out = {"emb/word_emb": (cfg["vocab"], d), "final_norm/scale": (d,)}
    for i, kind in enumerate(cfg["layer_types"]):
        out.update({
            f"layer_{i}/mixer_norm/scale": (d,), f"layer_{i}/ffn_norm/scale": (d,),
            f"layer_{i}/ffn/fc1/w": (d, f), f"layer_{i}/ffn/gate/w": (d, f),
            f"layer_{i}/ffn/fc2/w": (f, d)})
        out.update(attention_param_shapes(cfg, f"layer_{i}/attn") if kind == ATTENTION
                   else mamba_param_shapes(cfg, f"layer_{i}/mamba"))
    return out


def attention_param_shapes(cfg: dict, a: str) -> dict:
    d, dh, Hq, Hkv = cfg["d_model"], cfg["head_dim"], cfg["num_heads"], kv_heads(cfg)
    return {f"{a}/q/w": (d, Hq * dh), f"{a}/k/w": (d, Hkv * dh),
            f"{a}/v/w": (d, Hkv * dh), f"{a}/out/w": (Hq * dh, d)}


def mamba_param_shapes(cfg: dict, m: str) -> dict:
    d = cfg["d_model"]
    D, _, H, _, ch = _dims(cfg)
    return {f"{m}/in/w": (d, D + ch + H), f"{m}/conv/w": (cfg["ssm_conv"], ch),
            f"{m}/conv/b": (ch,), f"{m}/dt/b": (H,), f"{m}/a_log/bias": (H,),
            f"{m}/d/scale": (H,), f"{m}/norm/scale": (D,), f"{m}/out/w": (D, d)}


def mamba_initializers(shapes: dict) -> dict:
    """Mamba-2's own starting points among ``shapes``: dt near 0.01, A = -1,
    taps of size 1/2."""
    from paddle_tpu import initializer as init

    own = {}
    for n in shapes:
        if n.endswith("/dt/b"):
            own[n] = init.Constant(float(np.log(np.expm1(0.01))))
        elif n.endswith("/a_log/bias") or n.endswith("/conv/b"):
            own[n] = init.Constant(0.0)
        elif n.endswith("/conv/w"):
            own[n] = init.Normal(0.0, 0.3)
    return own


def _check(cfg: dict) -> None:
    kinds = set(cfg["layer_types"])
    enforce(kinds <= {MAMBA, ATTENTION},
            f"hybrid_ssm_lm: layer_types may hold {MAMBA!r} and {ATTENTION!r}, got {sorted(kinds)}")
    enforce(MAMBA in kinds and ATTENTION in kinds,
            "hybrid_ssm_lm serves a model with layers of both kinds (its cache is pages "
            "and states); a stack of one kind is transformer_lm's or a state family's")
    check_mixers(cfg, "hybrid_ssm_lm")


def check_mixers(cfg: dict, family: str) -> None:
    """What the two mixers ask of a configuration, whichever family stacks them."""
    enforce(cfg["ssm_groups"] >= 1 and cfg["ssm_heads"] % cfg["ssm_groups"] == 0,
            f"{family}: ssm_heads {cfg['ssm_heads']} do not fall into "
            f"ssm_groups {cfg['ssm_groups']} equal groups")
    enforce(cfg["num_heads"] % kv_heads(cfg) == 0,
            f"num_heads {cfg['num_heads']} is not a multiple of num_kv_heads {kv_heads(cfg)}")


# -- training ---------------------------------------------------------------

def lm_forward(ids, labels, *, cfg):
    """Next-token training forward through the chunked form, differentiated
    by XLA: ``(loss, token count, logits)`` like ``transformer_lm``'s."""
    shapes = param_shapes(cfg)
    # the embedding is read twice (the head is tied): made once
    p = functools.lru_cache(maxsize=None)(_frame_params(cfg, shapes, mamba_initializers(shapes)))
    return _next_token_loss(_logits(p, _hidden(p, ids, cfg, _via_train(cfg)), cfg), labels)


# -- serving: the engine's two programs ------------------------------------

CACHE_ARGS = ("k_pages", "v_pages", "ssm_state", "conv_state")
STATE_ARGS = ("ssm_state", "conv_state")


def hybrid_cache_specs(cfg: dict, *, max_slots: int, num_pages: int, page_size: int,
                       dtype, **_):
    """What the engine allocates and owns for this model: the K and the V
    page array of the attention layers (``dtype``), the SSM states and the
    convolution tails of the Mamba-2 layers (float32), in ``CACHE_ARGS``'
    order."""
    D, N, _, _, ch = _dims(cfg)
    La, Lm = len(layers_of(cfg, ATTENTION)), len(layers_of(cfg, MAMBA))
    pages = jax.ShapeDtypeStruct(
        (La, num_pages, page_size, kv_heads(cfg) * cfg["head_dim"]), dtype)
    return (pages, pages,
            jax.ShapeDtypeStruct((Lm, max_slots, N, D), jnp.float32),
            jax.ShapeDtypeStruct((Lm, max_slots, (cfg["ssm_conv"] - 1) * ch), jnp.float32))


def _params_of(params):
    params = params.params if hasattr(params, "params") else params
    return params.__getitem__


def _cache_in(k_pages, v_pages, ssm_state, conv_state) -> dict:
    """The arrays as the ``_via_*`` read and rebind them: the page arrays as
    the list ``_paged_attend`` rebinds layer by layer."""
    return {"pages": [k_pages, v_pages], "ssm_state": ssm_state, "conv_state": conv_state}


def _cache_out(cache: dict):
    return (*cache["pages"], cache["ssm_state"], cache["conv_state"])


def hybrid_prefill_chunk(params, tokens, pos0, last_index, slot_ref, k_pages, v_pages,
                         ssm_state, conv_state, rng=None, *, cfg: dict, page_size: int,
                         temperature: float = 0.0, top_k: int | None = None,
                         top_p: float | None = None):
    """Prefill ONE sequence's chunk: ``tokens`` [C] at positions ``[pos0,
    pos0 + C)``, of which those up to chunk index ``last_index`` are real.
    ``slot_ref`` is ``(page_table [P], slot)``: the attention layers' rows
    go through the table to the slot's pages, the Mamba-2 layers read and
    write the slot's tails and states by its number. A padded position adds
    nothing to a state, decays nothing and is no input of a convolution; a
    chunk at ``pos0`` 0 starts from zeros whatever the slot held. Returns
    ``(next_token, k_pages, v_pages, ssm_state, conv_state, active [1])``;
    the token is sampled at ``last_index`` and means something on the final
    chunk only."""
    _enforce_sampling(temperature, rng, "hybrid decode")
    page_table, slot = slot_ref
    p = _params_of(params)
    (C,) = tokens.shape
    cache = _cache_in(k_pages, v_pages, ssm_state, conv_state)
    x = _hidden(p, tokens[None], cfg,
                _via_chunk(cfg, cache, page_table, slot, pos0, last_index, C, page_size))
    x_last = jax.lax.dynamic_index_in_dim(x[0], jnp.minimum(last_index, C - 1), 0)
    with jax.named_scope("sampling"):
        tok = sample_logits(_logits(p, x_last, cfg)[0], rng, temperature, top_k, top_p)
    return (tok, *_cache_out(cache), jnp.ones((1,), jnp.int32))


def hybrid_decode_step(params, tokens, positions, slot_refs, k_pages, v_pages,
                       ssm_state, conv_state, rng=None, *, cfg: dict, page_size: int,
                       temperature: float = 0.0, top_k: int | None = None,
                       top_p: float | None = None):
    """One decode iteration for ``S`` slots: ``tokens`` [S] at ``positions``
    [S]; ``slot_refs`` is ``(page_tables [S, P], active [S])``, ``active`` 1
    for a decoding slot. An idle or still-prefilling slot has a scratch table
    row, its tails and states are not changed (nor moved, on a TPU) and its
    output is garbage the engine ignores. Returns ``(next_tokens [S],
    k_pages, v_pages, ssm_state, conv_state, active [S])``."""
    _enforce_sampling(temperature, rng, "hybrid decode")
    page_tables, active = slot_refs
    p = _params_of(params)
    cache = _cache_in(k_pages, v_pages, ssm_state, conv_state)
    x = _hidden(p, tokens[:, None], cfg,
                _via_step(cfg, cache, page_tables, positions, active, page_size))
    with jax.named_scope("sampling"):
        nxt = sample_logits(_logits(p, x[:, 0], cfg), rng, temperature, top_k, top_p)
    return (nxt, *_cache_out(cache), active.astype(jnp.int32))


def span_attrs(cfg: dict, active: np.ndarray) -> dict:
    """What a call's ``active`` says, as the attributes its span carries: the
    slots whose SSM states the call updated, the Mamba-2 layers, and the
    bytes of state that had to cross HBM for it (each such slot's state of
    each layer in once and out once)."""
    n, layers = int((active != 0).sum()), len(layers_of(cfg, MAMBA))
    return {"ssm_active_slots": n, "ssm_layers": layers,
            "ssm_state_bytes_moved": 2 * n * layers * state_bytes_a_slot(cfg)}


def serving_programs() -> ServingPrograms:
    return ServingPrograms(
        cache="pages+state", cache_args=CACHE_ARGS, state_args=STATE_ARGS,
        cache_specs=hybrid_cache_specs, prefill_chunk=hybrid_prefill_chunk,
        decode_step=hybrid_decode_step, verify_step=None,
        mechanism="Mamba-2 layers with a recurrent state per slot beside attention "
                  "layers with KV pages",
        kv_heads=kv_heads, attends_in_kernel=kv_attends_in_kernel, extras=("active",),
        span_attrs=span_attrs,
        gauges=lambda cfg: {"ssm.layers": len(layers_of(cfg, MAMBA)),
                            "ssm.state_bytes_a_slot": (len(layers_of(cfg, MAMBA))
                                                       * state_bytes_a_slot(cfg))})


# -- registry ---------------------------------------------------------------

def get_model(seq_len: int = 1024, learning_rate: float = 1e-3, **overrides) -> ModelSpec:
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})
    cfg["layer_types"] = tuple(cfg["layer_types"])
    cfg["max_len"] = max(cfg["max_len"], seq_len)
    _check(cfg)
    model = pt.build(functools.partial(lm_forward, cfg=cfg), name="hybrid_ssm_lm")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        tok = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    return ModelSpec(
        name="hybrid_ssm_lm", model=model, synth_batch=synth_batch,
        optimizer=lambda: pt.optimizer.Adam(learning_rate=learning_rate),
        unit="tokens/sec", examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len})
