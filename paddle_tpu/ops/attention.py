"""Attention ops.

Reference: attention exists only as composed ops
(``python/paddle/fluid/nets.py:332`` scaled_dot_product_attention; the
Transformer model in ``benchmark/fluid/models/machine_translation.py``).
TPU-native: one fused-friendly function XLA lowers well; a Pallas
flash-attention kernel (``paddle_tpu.ops.pallas.flash_attention``) takes
over for long sequences when ``flags().use_flash_attention`` is set.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.enforce import enforce

__all__ = [
    "scaled_dot_product_attention", "split_heads", "combine_heads",
    "causal_mask", "rope_tables", "apply_rope", "yarn_inv_freq", "yarn_mscale",
]


def rope_tables(dim: int, t: int, base: float = 10000.0, pos0: int = 0,
                scaling: dict | None = None):
    """Rotary position embedding cos/sin tables: [t, dim//2] each.
    No reference counterpart (the reference era used additive sinusoid PE,
    ``models/transformer.py`` position_encoding_init); RoPE is the modern
    long-context scheme — relative-position attention scores, exact under
    sequence sharding since tables index GLOBAL positions via ``pos0``.

    ``scaling`` (a published config's ``rope_scaling`` group, or None):
    :func:`yarn_inv_freq` in place of the plain inverse frequencies."""
    half = dim // 2
    if scaling is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_inv_freq(dim, base, scaling))
    angles = (pos0 + jnp.arange(t, dtype=jnp.float32))[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> np.ndarray:
    """[dim // 2] float32 inverse frequencies under YaRN as DeepSeek-V2
    spells it (``rope_scaling.type`` ``deepseek_yarn`` or ``yarn``): a
    frequency that turns more than ``beta_fast`` times within the original
    context keeps its value, one that turns fewer than ``beta_slow`` times
    is divided by ``factor``, and a linear ramp over the dimensions between
    the two blends them. The tables' own magnitude factor is
    ``mscale / mscale_all_dim`` in that spelling; only 1 is supported (the
    softmax scale carries the rest: :func:`yarn_mscale`)."""
    kind = scaling.get("type", scaling.get("rope_type"))
    enforce(kind in ("deepseek_yarn", "yarn"),
            f"rope_tables: no rope_scaling of type {kind!r} (deepseek_yarn only)")
    enforce(scaling.get("mscale", 1.0) == scaling.get("mscale_all_dim", 1.0),
            "rope_tables: mscale != mscale_all_dim would scale the tables; "
            "not supported")
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    half = dim // 2

    def correction_dim(rotations: float) -> float:
        return dim * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(base))

    low = max(np.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(np.ceil(correction_dim(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low), 0, 1)
    extra = base ** (-np.arange(half, dtype=np.float32) / half)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``; the softmax scale of a latent
    attention under YaRN is ``head_dim ** -0.5 * yarn_mscale(factor,
    mscale_all_dim) ** 2``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate feature pairs of [..., T, d] by position angle (half-split
    pairing): out = (x1*cos - x2*sin, x1*sin + x2*cos)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos], axis=-1
    ).astype(x.dtype)


def causal_mask(t_q: int, t_k: int, dtype=jnp.float32) -> jax.Array:
    """[Tq, Tk] additive mask, -inf above the diagonal."""
    i = jnp.arange(t_q)[:, None]
    j = jnp.arange(t_k)[None, :]
    return jnp.where(j <= i + (t_k - t_q), 0.0, -jnp.inf).astype(dtype)


def split_heads(x: jax.Array, num_heads: int) -> jax.Array:
    """[B, T, H*D] → [B, num_heads, T, D]."""
    b, t, hd = x.shape
    return x.reshape(b, t, num_heads, hd // num_heads).transpose(0, 2, 1, 3)


def combine_heads(x: jax.Array) -> jax.Array:
    """[B, N, T, D] → [B, T, N*D]."""
    b, n, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n * d)


def _flash_block(t: int):
    """Largest MXU-friendly block size dividing t (None = no fit)."""
    for b in (128, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return None


def _flash_block_arg(t: int):
    """The block to hand the kernel for a length ``_flash_block`` fits:
    128-divisible lengths defer to the kernel's own per-kernel table or rule
    (None); shorter ones pin their largest divisor."""
    b = _flash_block(t)
    return None if b == 128 else b


def _flash_per_shard(q, k, v, kv_len, **kw):
    """The flash kernel under the ambient mesh (``jax.set_mesh`` —
    ``DataParallel.step`` runs under one). A Mosaic kernel cannot be
    partitioned automatically, so on a mesh of more than one device it
    runs per shard through ``shard_map``: the batch splits over ``data``
    and the heads over ``model``/``tp`` where the axis divides them, any
    other dim is replicated (the ragged-tail step feeds its batch
    replicated by design). With no mesh, or inside a ``shard_map`` that
    already made every axis manual (ring/ulysses/pipeline), the call is
    bare. There is no XLA-attention way out of here."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.core.compat import shard_map
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.parallel import mesh as mesh_mod

    mesh = jax.sharding.get_abstract_mesh()
    manual = set(mesh.manual_axes)
    auto = [a for a in mesh.axis_names
            if mesh.shape[a] > 1 and a not in manual]
    if not auto:
        return flash_attention(q, k, v, kv_len=kv_len, **kw)
    if manual:
        raise NotImplementedError(
            f"flash attention inside a shard_map over {sorted(manual)} with "
            f"mesh axes {auto} left automatic cannot be mapped; make those "
            "axes manual too or call it outside the shard_map")

    def pick(names, *sizes):
        for a in names:
            if a in auto and all(s % mesh.shape[a] == 0 for s in sizes):
                return a
        return None

    b_axis = pick((mesh_mod.DATA_AXIS,), q.shape[0])
    h_axis = pick((mesh_mod.MODEL_AXIS, mesh_mod.TP_AXIS), q.shape[1], k.shape[1])
    spec = P(b_axis, h_axis, None, None)

    def body(q_, k_, v_, *kl):
        return flash_attention(q_, k_, v_, kv_len=kl[0] if kl else None, **kw)

    args = (q, k, v) + ((kv_len,) if kv_len is not None else ())
    in_specs = (spec,) * 3 + ((P(b_axis),) if kv_len is not None else ())
    return shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    )(*args)


def scaled_dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    is_test: bool = True,
    dropout_key=None,
    scale: Optional[float] = None,
    causal: bool = False,
    kv_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Attention over [..., T, D] tensors (head dims lead). ``mask`` is an
    additive mask broadcastable to [..., Tq, Tk] (0 = keep, -inf = drop);
    ``causal=True`` applies the autoregressive mask structurally — prefer it
    over an additive causal mask, because the flash kernel then skips the
    masked blocks' compute entirely instead of materializing [Tq, Tk].
    ``kv_len`` ([B] int) masks key positions >= kv_len[b] structurally
    (suffix padding): variable-length batches ride the flash kernel with
    fully-padded tail blocks skipped, instead of an additive [Tq, Tk] mask.

    Softmax in fp32; QK^T and PV matmuls accumulate fp32 on the MXU.
    With ``flags().use_flash_attention``, the mask-free 4-D case routes
    through the Pallas flash kernel (``ops.pallas.flash_attention``) when
    block tiling divides the sequence lengths.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    if window is not None and not causal:
        # match flash_attention's contract on every path: a non-causal
        # window would silently mean "past-limited but future-visible"
        from paddle_tpu.core.enforce import enforce

        enforce(False, "window requires causal=True (sliding-window attention "
                       "is defined over the causal band)")

    from paddle_tpu.core import config as _cfg

    if (
        _cfg.flags().use_flash_attention
        and mask is None
        and (dropout_rate == 0.0 or is_test)
        and q.ndim == 4
        and k.shape == v.shape
        and q.shape[0] == k.shape[0]
        and q.shape[1] % k.shape[1] == 0  # equal heads or GQA/MQA grouping
        # the kernel's causal mask is top-left aligned (q_pos >= k_pos);
        # causal_mask below is bottom-right aligned for Tq != Tk — only
        # route equal-length causal calls so the two paths agree
        and (not causal or q.shape[-2] == k.shape[-2])
        and (window is None or causal)
    ):
        bq = _flash_block(q.shape[-2])
        bk = _flash_block(k.shape[-2])
        if bq and bk:
            from paddle_tpu.core.dtypes import mxu_operands

            out_dtype = q.dtype
            q, k, v = mxu_operands(q, k, v)  # bf16 halves K/V HBM traffic
            return _flash_per_shard(
                q, k, v, kv_len, causal=causal, sm_scale=scale,
                block_q=_flash_block_arg(q.shape[-2]),
                block_k=_flash_block_arg(k.shape[-2]),
                window=window,
            ).astype(out_dtype)
    if kv_len is not None:
        from paddle_tpu.core.dtypes import NEG_INF

        k_pos = jnp.arange(k.shape[-2])
        len_mask = jnp.where(
            k_pos[None, :] < kv_len[:, None], 0.0, NEG_INF
        ).astype(jnp.float32)
        len_mask = len_mask.reshape(
            (kv_len.shape[0],) + (1,) * (q.ndim - 2) + (k.shape[-2],)
        )
        mask = len_mask if mask is None else mask + len_mask
    if causal:
        mask_c = causal_mask(q.shape[-2], k.shape[-2])
        mask = mask_c if mask is None else mask + mask_c
    if window is not None:
        t_q, t_k = q.shape[-2], k.shape[-2]
        i = jnp.arange(t_q)[:, None] + (t_k - t_q)  # align ends for Tq != Tk
        jpos = jnp.arange(t_k)[None, :]
        wmask = jnp.where(i - jpos < window, 0.0, -jnp.inf).astype(jnp.float32)
        mask = wmask if mask is None else mask + wmask
    from paddle_tpu.core.dtypes import mxu_operands

    out_dtype = q.dtype
    q, k, v = mxu_operands(q, k, v)

    if q.ndim == 4 and k.ndim == 4 and k.shape[1] != q.shape[1]:
        # grouped-query attention: q has H heads, k/v have H_kv < H (MQA at
        # H_kv=1). Grouped einsums keep K/V at H_kv in HBM — no repeat
        # materialization, the point of GQA's KV-traffic savings.
        b, h, t_q, d_ = q.shape
        h_kv = k.shape[1]
        if h % h_kv:
            raise ValueError(f"GQA: {h} query heads not divisible by {h_kv} kv heads")
        if mask is not None and mask.ndim >= 3 and mask.shape[-3] not in (1, h_kv):
            raise ValueError("GQA: per-query-head masks are unsupported; use a "
                             "head-broadcastable mask (head dim 1)")
        g = h // h_kv
        qg = q.reshape(b, h_kv, g, t_q, d_)
        logits = jnp.einsum(
            "bkgqd,bktd->bkgqt", qg, k, preferred_element_type=jnp.float32
        ) * scale
        if mask is not None:
            m = mask.astype(jnp.float32)
            if m.ndim >= 3:  # insert the group dim after the (1|h_kv) head dim
                m = jnp.expand_dims(m, -3)
            logits = logits + m
        weights = jax.nn.softmax(logits, axis=-1)
        if dropout_rate > 0.0 and not is_test:
            from paddle_tpu.ops.nn import dropout as _dropout

            weights = _dropout(weights, dropout_rate, is_test=False, key=dropout_key)
        out = jnp.einsum(
            "bkgqt,bktd->bkgqd", weights.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, h, t_q, d_).astype(out_dtype)

    logits = jnp.matmul(q, jnp.swapaxes(k, -1, -2), preferred_element_type=jnp.float32)
    logits = logits * scale
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and not is_test:
        from paddle_tpu.ops.nn import dropout as _dropout

        weights = _dropout(weights, dropout_rate, is_test=False, key=dropout_key)
    out = jnp.matmul(weights.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.astype(out_dtype)
