"""Ring attention: exact attention over a sequence-sharded ICI ring.

No reference counterpart (SURVEY.md §5.7: the reference has no context/
sequence parallelism; its long-sequence story is LoD + DynamicRNN). This is
the TPU-native long-context path: Q/K/V are sharded over the ``seq`` mesh
axis; each device computes attention of its local Q block against one K/V
block at a time while K/V blocks rotate around the ring via ``ppermute``
(Liu et al., Ring Attention; blockwise online-softmax accumulation à la
FlashAttention so nothing materializes the full [T, T] score matrix).

Causal masking uses global position offsets derived from each block's ring
rank, skip-computing is left to XLA (all blocks are computed; masked ones
contribute -inf scores — static shapes beat dynamic skipping on TPU).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.core.compat import shard_map
from paddle_tpu.core.dtypes import NEG_INF
from paddle_tpu.core.enforce import enforce
from paddle_tpu.ops.pallas.flash_attention import _float0_like
from paddle_tpu.parallel import mesh as mesh_mod

__all__ = ["ring_attention", "ring_attention_sharded"]


def _block_attn(q, k, v, bias):
    """Scores + online-softmax partials for one (Q-block, KV-block) pair.
    q: [B, H, Tq, d]; k/v: [B, H_kv, Tk, d] (H_kv < H = GQA, repeated here —
    this composed body is the correctness/recompute path); bias
    broadcastable to [B, H, Tq, Tk]. Returns (m, l, o)."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = scores + bias
    m = jnp.max(scores, axis=-1)  # [B, H, Tq]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partial results."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None] + o2 * a2[..., None]
    return m, l, o


def _ring_composed(q, k, v, axis: str, causal: bool, window=None, kv_len=None) -> jax.Array:
    """Composed-einsum ring body — the always-differentiable reference path
    (scan + ppermute autodiff) and the recompute backward for the flash
    forward below. ``kv_len`` ([B] int, GLOBAL lengths) masks key positions
    >= kv_len[b] — ragged batches under sequence parallelism."""
    n_dev = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    t_local = q.shape[2]
    dtype = q.dtype
    q32, k0, v0 = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]

    q_pos = rank * t_local + jnp.arange(t_local)  # global positions of Q rows

    def block_bias(i):
        # kv block held at ring step i started at rank (rank - i) mod n_dev
        kv_rank = (rank - i) % n_dev
        k_pos = kv_rank * t_local + jnp.arange(t_local)
        if causal:
            keep = q_pos[:, None] >= k_pos[None, :]
            if window is not None:  # sliding window over GLOBAL positions
                keep = jnp.logical_and(keep, q_pos[:, None] - k_pos[None, :] < window)
            bias = jnp.where(keep, 0.0, NEG_INF)[None, None]
        else:
            bias = jnp.zeros((1, 1, t_local, t_local), jnp.float32)
        if kv_len is not None:  # suffix padding at GLOBAL positions
            lenm = jnp.where(k_pos[None, :] < kv_len[:, None], 0.0, NEG_INF)
            bias = bias + lenm[:, None, None, :]
        return bias

    # step 0 on the local block, then permute-then-compute for the remaining
    # n_dev-1 ring steps — no wasted final shift
    m, l, o = _block_attn(q32, k0, v0, block_bias(0))

    def step(carry, i):
        m, l, o, kk, vv = carry
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        bm, bl, bo = _block_attn(q32, kk, vv, block_bias(i))
        m, l, o = _merge(m, l, o, bm, bl, bo)
        return (m, l, o, kk, vv), None

    (m, l, o, _, _), _ = jax.lax.scan(
        step, (m, l, o, k0, v0), jnp.arange(1, n_dev)
    )
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(dtype)


def _ring_block_dead(causal: bool, window, q_off, k_off, t_local: int):
    """True when an entire (local-Q, ring-step-KV) block pair is masked —
    fully future under causal, or entirely left of every query's window.
    The offset kernels would skip all compute anyway, but their grids still
    STREAM the K/V tiles; callers lax.cond on this to skip even that HBM
    traffic (about half the ring steps under causal)."""
    if not causal:
        return jnp.bool_(False)
    dead = k_off > q_off + t_local - 1
    if window is not None:
        dead = jnp.logical_or(dead, k_off + t_local - 1 < q_off - (window - 1))
    return dead


def _merge_normalized(o1, lse1, o2, lse2):
    """Merge two NORMALIZED partials (o_i = softmax-weighted values over
    block i, lse_i = logsumexp of its scores, [B, H, T, 1])."""
    m = jnp.maximum(lse1, lse2)
    a1 = jnp.exp(lse1 - m)
    a2 = jnp.exp(lse2 - m)
    l = a1 + a2
    o = (o1 * a1 + o2 * a2) / l
    return o, m + jnp.log(l)


def _ring_flash_fwd(
    q, k, v, axis: str, causal: bool, window=None, kv_len=None,
) -> tuple[jax.Array, jax.Array]:
    """Flash-kernel ring body: each (local-Q, rotating-KV) block pair runs
    the fused Pallas kernel AT ITS GLOBAL OFFSETS (q_off = rank·T_local,
    k_off = kv_rank·T_local) and partials merge by logsumexp. The kernel's
    offset-aware causal/window/kv_len masking subsumes the ring-level
    bookkeeping: fully-future (or fully-out-of-window / fully-padded) K/V
    blocks are block-skipped inside the kernel and come back with
    lse ≈ NEG_INF, which the merge weights to zero — sliding-window cost
    stays O(T·W) through the FLASH path."""
    from paddle_tpu.ops.attention import _flash_block_arg
    from paddle_tpu.ops.pallas import flash_attention_with_lse

    n_dev = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    t_local = q.shape[-2]
    dtype = q.dtype
    # q in f32 (merge accumulates in its dtype); k/v keep the input dtype —
    # they rotate the ring, and bf16 halves the per-step ICI bytes (the
    # kernel widens them to q's dtype as it loads a tile)
    q32 = q.astype(jnp.float32)
    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
    bq = _flash_block_arg(t_local)
    bk = _flash_block_arg(k.shape[-2])
    q_off = rank * t_local

    o, lse = flash_attention_with_lse(
        q32, k, v, causal=causal, block_q=bq, block_k=bk,
        window=window, kv_len=kv_len, q_off=q_off, k_off=q_off,
    )

    def step(carry, i):
        o, lse, kk, vv = carry
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        k_off = ((rank - i) % n_dev) * t_local
        bo, blse = jax.lax.cond(
            _ring_block_dead(causal, window, q_off, k_off, t_local),
            lambda a, b, c: (
                jnp.zeros(a.shape, jnp.float32),
                jnp.full(a.shape[:-1] + (1,), NEG_INF, jnp.float32),
            ),
            lambda a, b, c: flash_attention_with_lse(
                a, b, c, causal=causal, block_q=bq, block_k=bk,
                window=window, kv_len=kv_len, q_off=q_off, k_off=k_off,
            ),
            q32, kk, vv,
        )
        o, lse = _merge_normalized(o, lse, bo, blse)
        return (o, lse, kk, vv), None

    (o, lse, _, _), _ = jax.lax.scan(step, (o, lse, k, v), jnp.arange(1, n_dev))
    return o.astype(dtype), lse


def _ring_flash_bwd_ring(q, k, v, out, lse, g, axis: str, causal: bool,
                         window=None, kv_len=None):
    """Fused-backward ring (Liu et al. ring attention, backward pass): each
    ring step runs the Pallas block backward AT ITS GLOBAL OFFSETS against
    the GLOBAL (out, lse) residuals — Δ and P need only final statistics,
    so per-block dQ/dK/dV contributions are exact and independent, and the
    kernel's offset masking zeroes dead (future / out-of-window / padded)
    blocks with p = exp(NEG_INF − lse) = 0. dQ accumulates locally; dK/dV
    accumulate in f32 carriers that rotate WITH k/v, so after the full
    cycle (n-1 scan steps + one final shift) each block's gradient arrives
    back at its home device. Nothing [T_local, T_local]-shaped ever hits
    HBM in the backward either."""
    from paddle_tpu.ops.attention import _flash_block_arg
    from paddle_tpu.ops.pallas import flash_attention_bwd_block

    n_dev = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    t_local = q.shape[-2]
    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
    bq = _flash_block_arg(t_local)
    bk = _flash_block_arg(k.shape[-2])
    q32 = q.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    out32 = out.astype(jnp.float32)
    q_off = rank * t_local

    # step 0: the diagonal block; f32 k/v so the gradient carriers start and
    # stay full-precision
    dq, dkk, dvv = flash_attention_bwd_block(
        q32, k.astype(jnp.float32), v.astype(jnp.float32), out32, lse, g32,
        causal=causal, block_q=bq, block_k=bk,
        window=window, kv_len=kv_len, q_off=q_off, k_off=q_off,
    )

    def step(carry, i):
        dq, dkk, dvv, kk, vv = carry
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        dkk = jax.lax.ppermute(dkk, axis, perm)
        dvv = jax.lax.ppermute(dvv, axis, perm)
        k_off = ((rank - i) % n_dev) * t_local
        # upcast the rotating K/V at the kernel call (ICI still moves the
        # input dtype): dk/dv then come back f32, so carrier accumulation
        # never rounds per step. Dead block pairs contribute exact zeros —
        # lax.cond skips even their K/V tile streaming.
        bdq, bdk, bdv = jax.lax.cond(
            _ring_block_dead(causal, window, q_off, k_off, t_local),
            lambda a, b, c: (
                jnp.zeros(a.shape, jnp.float32),
                jnp.zeros(b.shape, jnp.float32),
                jnp.zeros(c.shape, jnp.float32),
            ),
            lambda a, b, c: flash_attention_bwd_block(
                a, b.astype(jnp.float32), c.astype(jnp.float32), out32,
                lse, g32, causal=causal, block_q=bq, block_k=bk,
                window=window, kv_len=kv_len, q_off=q_off, k_off=k_off,
            ),
            q32, kk, vv,
        )
        dq = dq + bdq
        dkk = dkk + bdk
        dvv = dvv + bdv
        return (dq, dkk, dvv, kk, vv), None

    (dq, dkk, dvv, _, _), _ = jax.lax.scan(
        step, (dq, dkk, dvv, k, v), jnp.arange(1, n_dev)
    )
    # k/v have rotated n-1 steps; one more shift completes the cycle and
    # lands each block's accumulated gradient on its home device
    dkk = jax.lax.ppermute(dkk, axis, perm)
    dvv = jax.lax.ppermute(dvv, axis, perm)
    return dq.astype(q.dtype), dkk.astype(k.dtype), dvv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_flash(q, k, v, kv_len, axis, causal, window, has_kvlen):
    out, _ = _ring_flash_fwd(
        q, k, v, axis, causal, window, kv_len if has_kvlen else None
    )
    return out


def _ring_flash_vjp_fwd(q, k, v, kv_len, axis, causal, window, has_kvlen):
    out, lse = _ring_flash_fwd(
        q, k, v, axis, causal, window, kv_len if has_kvlen else None
    )
    return out, (q, k, v, kv_len, out, lse)


def _ring_flash_vjp_bwd(axis, causal, window, has_kvlen, res, g):
    q, k, v, kv_len, out, lse = res
    dq, dk, dv = _ring_flash_bwd_ring(
        q, k, v, out, lse, g, axis, causal, window,
        kv_len if has_kvlen else None,
    )
    return dq, dk, dv, _float0_like(kv_len)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    causal: bool = False,
    use_flash: Optional[bool] = None,
    window: Optional[int] = None,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-device body (call inside shard_map/pjit with ``axis`` a mesh axis
    over which the SEQUENCE dim is sharded). q/k/v: [B, H, T_local, d].
    Returns [B, H, T_local, d] — exact softmax(QK^T)V over the GLOBAL
    sequence.

    ``use_flash`` (default: ``flags().use_flash_attention``) computes each
    block pair with the fused Pallas kernel instead of composed einsums —
    forward AND backward (a second ring of fused block-backwards against
    the global (out, lse) residuals) — so nothing [T_local, T_local]-shaped
    materializes in HBM in either direction: long-context training memory
    stays O(T_local · d) per device. ``window`` (sliding-window, causal
    only) and ``kv_len`` ([B] GLOBAL lengths — ragged batches, the LoD
    replacement) both ride the flash path natively via the kernels' global
    position offsets. Note: gradients for queries at positions >= kv_len[b]
    are only exact when the incoming cotangent is zero there (the loss must
    mask pad positions — which defines them anyway)."""
    if use_flash is None:
        from paddle_tpu.core.config import flags

        use_flash = flags().use_flash_attention
    if window is not None:
        enforce(causal, "ring_attention: window requires causal=True")
    if use_flash and q.ndim == 4:
        from paddle_tpu.ops.attention import _flash_block

        if _flash_block(q.shape[-2]) and _flash_block(k.shape[-2]):
            has_kvlen = kv_len is not None
            if not has_kvlen:
                kv_len = jnp.zeros((q.shape[0],), jnp.int32)
            return _ring_flash(
                q, k, v, kv_len.astype(jnp.int32), axis, causal, window, has_kvlen
            )
    return _ring_composed(q, k, v, axis, causal, window, kv_len)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = mesh_mod.SEQ_AXIS,
    causal: bool = False,
    use_flash: Optional[bool] = None,
    batch_axis: Optional[str] = mesh_mod.DATA_AXIS,
    window: Optional[int] = None,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Convenience wrapper: q/k/v are GLOBAL [B, H, T, d] arrays; shards the
    T dim over ``axis`` (and the batch dim over ``batch_axis`` when the mesh
    has it — each data group then rings only its own batch shard instead of
    all-gathering and redundantly computing the full batch), runs
    :func:`ring_attention` under shard_map, and returns the global result.
    ``kv_len``: [B] GLOBAL sequence lengths (sharded with the batch)."""
    b_axis = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    if b_axis is not None and q.shape[0] % mesh.shape[b_axis] != 0:
        from paddle_tpu.core import logging as ptlog

        ptlog.warning(
            "ring_attention_sharded: batch %d not divisible by mesh axis "
            "%r (size %d) — replicating the batch across it (%dx redundant "
            "attention compute); pad the batch to restore data parallelism",
            q.shape[0], b_axis, mesh.shape[b_axis], mesh.shape[b_axis],
        )
        b_axis = None
    spec = P(b_axis, None, axis, None)

    def body(q_, k_, v_, *kl):
        return ring_attention(q_, k_, v_, axis=axis, causal=causal,
                              use_flash=use_flash, window=window,
                              kv_len=kl[0] if kl else None)

    args = (q, k, v) + ((kv_len,) if kv_len is not None else ())
    in_specs = (spec, spec, spec) + ((P(b_axis),) if kv_len is not None else ())
    return shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    )(*args)
