"""Flash attention forward kernel (Pallas TPU).

Blockwise attention with online-softmax accumulation: Q blocks stream down
the grid, K/V blocks stream through VMEM inside the kernel loop, and the
[T, T] score matrix never materializes in HBM — the classic
FlashAttention schedule laid out for the MXU (128-aligned blocks,
``preferred_element_type=f32`` accumulators).

The reference framework composed attention from softmax/matmul ops
(``python/paddle/fluid/nets.py:332`` scaled_dot_product_attention) and had
no fused kernel; this replaces that composition on the hot path.

Backward is a fused Pallas kernel pair (FlashAttention-2 schedule): the
forward additionally emits the per-row logsumexp, and the backward
recomputes P blockwise from (Q, K, LSE) — one kernel accumulates dK/dV
streaming over Q blocks, one accumulates dQ streaming over K/V blocks —
so the [T, T] probability matrix never hits HBM in either direction.
Set ``flags().flash_fused_bwd = False`` to fall back to the recomputed
XLA backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.core.dtypes import NEG_INF
from paddle_tpu.core.enforce import enforce

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "flash_attention_bwd_block",
    "fit_block",
    "resolve_blocks",
    "tuned_blocks",
]


def fit_block(block: int, total: int) -> int:
    """Largest block <= ``block`` that divides ``total``, preferring
    MXU/lane-aligned sizes (multiples of 128), then sublane-aligned ones
    (multiples of 8). A plain ``min(block, total)`` rejects perfectly
    servable shapes — T=192 with the 128 default used to hard-fail the
    divisibility enforce; this fits it to 96 instead."""
    total = int(total)
    block = max(1, min(int(block), total))
    if total % block == 0:
        return block
    best_8 = best_any = 0
    for b in range(block, 0, -1):
        if total % b:
            continue
        if b % 128 == 0:
            return b
        if not best_8 and b % 8 == 0:
            best_8 = b
        if not best_any:
            best_any = b
    return best_8 or best_any or 1


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, kvlen_ref, offs_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, block_q: int, block_k: int, causal: bool, sm_scale: float,
    has_kvlen: bool, window=None,
):
    """One (batch*head, q_block, kv_block) grid cell. Only the CURRENT
    [block_k, d] K/V tiles are VMEM-resident — long sequences stream through
    the innermost grid dimension with m/l/acc carried in VMEM scratch (the
    kv dim iterates sequentially per core, so scratch persists across j).

    ``offs_ref`` = [q_off, k_off] GLOBAL position offsets (SMEM scalars, may
    be traced — e.g. ring-rank dependent): causal/window/kv_len masking is
    applied at global positions, so an off-diagonal ring block pair runs this
    same kernel with full block skipping instead of a composed fallback."""
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    kv_limit = kvlen_ref[pl.program_id(0), 0] if has_kvlen else None
    q_start = offs_ref[0] + pl.program_id(1) * block_q
    k_start = offs_ref[1] + j * block_k

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: kv blocks fully above the diagonal contribute nothing — skip
    # their compute entirely (half the FLOPs on average); same for kv
    # blocks entirely past this row's kv_len (padded tails)
    live = (k_start <= q_start + block_q - 1) if causal else True
    if window is not None:
        # kv block entirely left of every query's window -> dead
        live = jnp.logical_and(live, k_start + block_k - 1 >= q_start - (window - 1))
    if has_kvlen:
        live = jnp.logical_and(live, k_start < kv_limit)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if has_kvlen:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < kv_limit, s, NEG_INF)

        m_prev, l_prev = m_ref[:], l_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_kv - 1)
    def _():
        l_safe = jnp.maximum(l_ref[:], 1e-20)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _flash_fwd_kernel_resident(
    q_ref, k_ref, v_ref, kvlen_ref, offs_ref, o_ref, lse_ref,
    *, block_k: int, causal: bool, sm_scale: float, has_kvlen: bool,
    window=None,
):
    """Fast path for K/V that fit in VMEM: one (batch*head, q_block) grid
    cell holds the whole K/V and loops kv blocks with a fori_loop — the
    causal loop bound halves the work and Q is fetched once. Global
    position offsets as in :func:`_flash_fwd_kernel` (the loop bounds are
    offset-shifted, so e.g. a fully-future ring block runs zero
    iterations)."""
    _, block_q, d = q_ref.shape
    t_kv = k_ref.shape[1]
    kv_limit = kvlen_ref[pl.program_id(0), 0] if has_kvlen else None
    q_off, k_off = offs_ref[0], offs_ref[1]
    q_start = q_off + pl.program_id(1) * block_q

    q = q_ref[0].astype(jnp.float32) * sm_scale

    def body(i, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        k_start = k_off + i * block_k
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if has_kvlen:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < kv_limit, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    n_kv = t_kv // block_k
    if causal:
        # keys with global pos <= q_start + block_q - 1 -> local idx bound
        hi = q_start + block_q - k_off
        n_kv_used = jnp.clip((hi + block_k - 1) // block_k, 0, n_kv)
    else:
        n_kv_used = n_kv
    if has_kvlen:  # fully-padded tail blocks contribute nothing — skip them
        n_kv_used = jnp.minimum(
            n_kv_used, jnp.maximum(0, (kv_limit - k_off + block_k - 1) // block_k)
        )
    lo = 0
    if window is not None:  # kv blocks left of every window: skip entirely
        lo = jnp.maximum(0, (q_start - k_off - (window - 1)) // block_k)
    init = (
        jnp.full((block_q, 1), NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
        jnp.zeros((block_q, d), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(lo, n_kv_used, body, init)
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


# K+V per (batch, head) beyond this stays in HBM and streams via the grid
_VMEM_RESIDENT_BYTES = 4 * 1024 * 1024

# Chip-measured (block_q, block_k) table, keyed by minimum sequence length;
# no sweep has run on a chip yet (ROADMAP A7), so it is empty.
# An empty or non-matching table -> the 128/128 MXU-aligned default. Rows are
# ascending by min_T; the last row whose min_T <= T and whose blocks divide
# the sequence lengths wins.
_TUNED_BLOCKS: list[tuple[int, int, int]] = []


def tuned_blocks(t_q: int, t_kv: int) -> tuple[int, int]:
    """Resolve default (block_q, block_k) for the given sequence lengths:
    the measured table when a row fits, else 128/128 (clamped by the
    callers' divisibility requirements)."""
    bq, bk = 128, 128
    for min_t, q_, k_ in _TUNED_BLOCKS:
        if t_q >= min_t and t_q % q_ == 0 and t_kv % k_ == 0:
            bq, bk = q_, k_
    return bq, bk


def resolve_blocks(t_q: int, t_kv: int, dtype=None, causal: bool = False,
                   window: Optional[int] = None) -> tuple[int, int]:
    """Default-block resolution order: autotune store (when
    ``flags().autotune`` is on — fingerprint-checked, process-memoized,
    counted under ``tune.cache.{hit,miss,stale}``), then the checked-in
    :data:`_TUNED_BLOCKS` table, then 128/128."""
    from paddle_tpu.core.config import flags

    if flags().autotune:
        from paddle_tpu.tune import autotune as _autotune

        tuned = _autotune.lookup_blocks(
            t_q, t_kv, dtype=dtype, causal=causal, window=window)
        if tuned is not None:
            return tuned
    return tuned_blocks(t_q, t_kv)


def _kvlen_rows(kv_len, B: int, H: int):
    """[B] lengths → [B*H, 1] i32 so the kernel grid's combined batch*head
    dim indexes it directly."""
    return jnp.repeat(kv_len.astype(jnp.int32), H).reshape(B * H, 1)


def _offs_arr(q_off, k_off):
    """[2] i32 SMEM scalars: global position offsets (ints or traced)."""
    return jnp.stack([
        jnp.asarray(q_off, jnp.int32), jnp.asarray(k_off, jnp.int32)
    ])


def _flash_fwd(q, k, v, causal: bool, sm_scale: float, block_q: int, block_k: int,
               interpret: bool, kv_len=None, window=None, q_off=0, k_off=0):
    """Returns ``(out [B,H,T,d], lse [B,H,T,1])`` — lse is the per-row
    logsumexp of the scaled scores, consumed by the fused backward.
    ``kv_len`` ([B] int) masks key positions >= kv_len[b] (suffix padding,
    the LoD-replacement layout). ``q_off``/``k_off`` (ints or traced
    scalars) shift causal/window/kv_len masking to GLOBAL positions — the
    ring-attention block pairs pass their rank-derived offsets here."""
    B, H, T, d = q.shape
    h_kv = k.shape[1]
    t_kv = k.shape[2]
    enforce(H % h_kv == 0, f"{H} query heads not divisible by {h_kv} kv heads")
    group = H // h_kv
    # fit rather than reject: a requested block that doesn't divide the
    # sequence falls back to the largest MXU-friendly divisor (T=192 with
    # the 128 default runs at 96 instead of hard-failing)
    block_q = fit_block(block_q, T)
    block_k = fit_block(block_k, t_kv)
    enforce(T % block_q == 0, f"seq len {T} not divisible by block_q {block_q}")
    enforce(t_kv % block_k == 0, f"kv len {t_kv} not divisible by block_k {block_k}")

    qr = q.reshape(B * H, T, d)
    kr = k.reshape(B * h_kv, t_kv, d)
    vr = v.reshape(B * h_kv, t_kv, d)
    has_kvlen = kv_len is not None
    lens = _kvlen_rows(kv_len, B, H) if has_kvlen else jnp.zeros((B * H, 1), jnp.int32)
    offs = _offs_arr(q_off, k_off)
    from jax.experimental.pallas import tpu as pltpu

    def kvrow(b):  # combined q row -> combined kv row (GQA head sharing)
        return (b // H) * h_kv + (b % H) // group

    out_shapes = [
        jax.ShapeDtypeStruct((B * H, T, d), q.dtype),
        jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
    ]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kv_bytes = 2 * t_kv * d * (4 if q.dtype == jnp.float32 else 2)
    if kv_bytes <= _VMEM_RESIDENT_BYTES:
        kernel = functools.partial(
            _flash_fwd_kernel_resident,
            block_k=block_k, causal=causal, sm_scale=sm_scale, has_kvlen=has_kvlen,
            window=window,
        )
        out, lse = pl.pallas_call(
            kernel,
            name="flash_fwd_resident",
            grid=(B * H, T // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, t_kv, d), lambda b, i: (kvrow(b), 0, 0)),
                pl.BlockSpec((1, t_kv, d), lambda b, i: (kvrow(b), 0, 0)),
                smem,
                smem,
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=out_shapes,
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(qr, kr, vr, lens, offs)
        return out.reshape(B, H, T, d), lse.reshape(B, H, T, 1)

    kernel = functools.partial(
        _flash_fwd_kernel,
        block_q=block_q, block_k=block_k, causal=causal, sm_scale=sm_scale,
        has_kvlen=has_kvlen, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B * H, T // block_q, t_kv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kvrow(b), j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kvrow(b), j, 0)),
            smem,
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr, lens, offs)
    return out.reshape(B, H, T, d), lse.reshape(B, H, T, 1)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvlen_ref, offs_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, block_q: int, block_k: int, causal: bool, sm_scale: float,
    has_kvlen: bool, n_qb: int, window=None,
):
    """dK/dV for one kv block, streaming q blocks through the innermost grid
    dim. P is recomputed from (Q, K, LSE) — FlashAttention-2 eq. (13-16):
    dV += P^T dO; dS = P ∘ (dO V^T − Δ); dK += dS^T Q·scale.
    Under GQA the innermost dim runs group * n_qb steps: all q blocks of
    every query head sharing this kv head accumulate into the same
    dk/dv block (``n_qb`` = T // block_q; the index maps route each step
    to its (head, q-block) pair)."""
    s_idx = pl.program_id(2)
    n_total = pl.num_programs(2)
    i = s_idx % n_qb  # q-block index within the current query head
    j = pl.program_id(1)
    kv_limit = kvlen_ref[pl.program_id(0), 0] if has_kvlen else None
    q_start = offs_ref[0] + i * block_q  # GLOBAL positions (ring offsets)
    k_start = offs_ref[1] + j * block_k

    @pl.when(s_idx == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: q blocks fully above this kv block's diagonal see none of it;
    # kv blocks fully past kv_len contribute zero grads — skip both
    live = (q_start + block_q - 1 >= k_start) if causal else True
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 >= q_start - (window - 1))
    if has_kvlen:
        live = jnp.logical_and(live, k_start < kv_limit)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]      # [block_q, 1]
        delta = delta_ref[0]  # [block_q, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if has_kvlen:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < kv_limit, s, NEG_INF)
        p = jnp.exp(s - lse)  # normalized probabilities, [block_q, block_k]
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # P^T dO -> [block_k, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # dO V^T -> [block_q, block_k]
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # dS^T (Q·scale) -> [block_k, d]

    @pl.when(s_idx == n_total - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvlen_ref, offs_ref,
    dq_ref, dq_acc,
    *, block_q: int, block_k: int, causal: bool, sm_scale: float,
    has_kvlen: bool, window=None,
):
    """dQ for one q block, streaming kv blocks: dQ += dS K·scale."""
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    i = pl.program_id(1)
    kv_limit = kvlen_ref[pl.program_id(0), 0] if has_kvlen else None
    q_start = offs_ref[0] + i * block_q  # GLOBAL positions (ring offsets)
    k_start = offs_ref[1] + j * block_k

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (k_start <= q_start + block_q - 1) if causal else True
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 >= q_start - (window - 1))
    if has_kvlen:
        live = jnp.logical_and(live, k_start < kv_limit)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if has_kvlen:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < kv_limit, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # dS K -> [block_q, d]

    @pl.when(j == n_kv - 1)
    def _():
        dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
               kv_len=None, window=None, q_off=0, k_off=0):
    """Fused backward: returns (dq, dk, dv), each the dtype of its primal
    (dk/dv at the kv head count under GQA). ``q_off``/``k_off``: global
    position offsets, as in :func:`_flash_fwd`."""
    B, H, T, d = q.shape
    h_kv = k.shape[1]
    group = H // h_kv
    t_kv = k.shape[2]
    # same divisor-fitting fallback as _flash_fwd (the pair must agree so
    # fwd and fused bwd run the same tiling for a given request)
    block_q = fit_block(block_q, T)
    block_k = fit_block(block_k, t_kv)
    enforce(T % block_q == 0, f"seq len {T} not divisible by block_q {block_q}")
    enforce(t_kv % block_k == 0, f"kv len {t_kv} not divisible by block_k {block_k}")
    n_qb = T // block_q

    qr = q.reshape(B * H, T, d)
    kr = k.reshape(B * h_kv, t_kv, d)
    vr = v.reshape(B * h_kv, t_kv, d)
    gr = g.reshape(B * H, T, d)
    lse_r = lse.reshape(B * H, T, 1)
    # Δ = rowsum(dO ∘ O): cheap elementwise+reduce, XLA fuses it
    delta = jnp.sum(
        gr.astype(jnp.float32) * out.reshape(B * H, T, d).astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    has_kvlen = kv_len is not None
    lens = _kvlen_rows(kv_len, B, H) if has_kvlen else jnp.zeros((B * H, 1), jnp.int32)
    lens_kv = (
        _kvlen_rows(kv_len, B, h_kv) if has_kvlen else jnp.zeros((B * h_kv, 1), jnp.int32)
    )
    offs = _offs_arr(q_off, k_off)
    from jax.experimental.pallas import tpu as pltpu

    def kvrow(b):  # combined q row -> combined kv row
        return (b // H) * h_kv + (b % H) // group

    def qrow(r, s):  # (combined kv row, grouped inner step) -> combined q row
        return (r // h_kv) * H + (r % h_kv) * group + s // n_qb

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel,
        block_q=block_q, block_k=block_k, causal=causal, sm_scale=sm_scale,
        has_kvlen=has_kvlen, n_qb=n_qb, window=window,
    )
    # grid: (group * q-blocks) innermost (sequential accumulate), kv parallel
    q_stream = pl.BlockSpec((1, block_q, d), lambda r, j, s: (qrow(r, s), s % n_qb, 0))
    row_stream = pl.BlockSpec((1, block_q, 1), lambda r, j, s: (qrow(r, s), s % n_qb, 0))
    kv_fixed = pl.BlockSpec((1, block_k, d), lambda r, j, s: (r, j, 0))
    len_spec3 = pl.BlockSpec(memory_space=pltpu.SMEM)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(B * h_kv, t_kv // block_k, group * n_qb),
        in_specs=[q_stream, kv_fixed, kv_fixed, q_stream, row_stream, row_stream,
                  len_spec3, len_spec3],
        out_specs=[kv_fixed, kv_fixed],
        out_shape=[
            jax.ShapeDtypeStruct((B * h_kv, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((B * h_kv, t_kv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr, gr, lse_r, delta, lens_kv, offs)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel,
        block_q=block_q, block_k=block_k, causal=causal, sm_scale=sm_scale,
        has_kvlen=has_kvlen, window=window,
    )
    # grid: kv innermost (sequential accumulate), q parallel
    q_fixed = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_fixed = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv_stream = pl.BlockSpec((1, block_k, d), lambda b, i, j: (kvrow(b), j, 0))
    (dq,) = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(B * H, T // block_q, t_kv // block_k),
        in_specs=[q_fixed, kv_stream, kv_stream, q_fixed, row_fixed, row_fixed,
                  len_spec3, len_spec3],
        out_specs=[q_fixed],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr, gr, lse_r, delta, lens, offs)

    return (
        dq.reshape(B, H, T, d),
        dk.reshape(B, h_kv, t_kv, d),
        dv.reshape(B, h_kv, t_kv, d),
    )


def _reference_attention(q, k, v, causal: bool, sm_scale: float, kv_len=None, window=None):
    # f32 accumulation in both einsums — bf16 inputs must not produce
    # bf16-precision scores in the recomputed backward. GQA: repeat kv heads
    # (correctness path only; repeat's VJP sums group grads back to h_kv)
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), bool))
        if window is not None:  # sliding window: keep only the last `window` keys
            mask = jnp.logical_and(mask, ~jnp.tril(jnp.ones((T, S), bool), -window))
        s = jnp.where(mask, s, NEG_INF)
    if kv_len is not None:
        k_pos = jnp.arange(s.shape[-1])
        s = jnp.where(k_pos[None, None, None, :] < kv_len[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(q.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def _float0_like(x):
    import numpy as _np

    return _np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, kv_len, causal, sm_scale, block_q, block_k, interpret, has_kvlen, window):
    out, _ = _flash_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        kv_len if has_kvlen else None, window,
    )
    return out


def _flash_vjp_fwd(q, k, v, kv_len, causal, sm_scale, block_q, block_k, interpret, has_kvlen, window):
    out, lse = _flash_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        kv_len if has_kvlen else None, window,
    )
    return out, (q, k, v, kv_len, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, has_kvlen, window, res, g):
    q, k, v, kv_len, out, lse = res
    from paddle_tpu.core.config import flags

    if flags().flash_fused_bwd:
        dq, dk, dv = _flash_bwd(
            q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
            kv_len if has_kvlen else None, window,
        )
    else:
        # recomputed XLA attention backward (activations were never stored)
        _, vjp = jax.vjp(
            lambda a, b, c: _reference_attention(
                a, b, c, causal, sm_scale, kv_len if has_kvlen else None,
                window=window,
            ),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
    return dq, dk, dv, _float0_like(kv_len)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    kv_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
    q_off=0,
    k_off=0,
):
    """Forward-only fused attention returning ``(out, lse)`` with lse
    [B, H, T, 1] — the building block for outer blockwise schedules that
    merge partials themselves (ring attention merges per-ring-step outputs
    by lse). NOT differentiable: callers wrap the whole schedule in their
    own ``jax.custom_vjp``.

    ``q_off``/``k_off`` (ints or traced scalars) place the Q and K/V blocks
    at GLOBAL sequence positions: causal, ``window`` (sliding band), and
    ``kv_len`` masking all act on global positions, and block skipping
    follows — a ring step whose K/V block is entirely future/out-of-window
    costs (near) nothing. Rows with no live key come back with
    lse ≈ NEG_INF, which the lse-merge weights to zero."""
    if window is not None:
        enforce(causal, "flash_attention_with_lse: window (sliding-window "
                        "attention) requires causal=True")
        enforce(window >= 1, f"window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None or block_k is None:
        tq, tk = resolve_blocks(q.shape[-2], k.shape[-2], q.dtype, causal, window)
        block_q, block_k = block_q or tq, block_k or tk
    return _flash_fwd(
        q, k, v, causal, float(sm_scale), block_q, block_k, interpret, kv_len,
        window, q_off, k_off,
    )


def flash_attention_bwd_block(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    g: jax.Array,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    kv_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
    q_off=0,
    k_off=0,
):
    """One block-pair backward against GLOBAL residuals: returns
    ``(dq, dk, dv)`` for this (Q, K/V) pair, where ``out``/``lse`` are the
    FINAL merged attention output and logsumexp over the whole sequence
    (FlashAttention-2: Δ = rowsum(dO ∘ O) and P = exp(S − lse) both use
    global statistics, so per-block backward contributions are independent
    and sum to the exact gradients). The ring-attention backward calls this
    per ring step, accumulating dK/dV in carriers that rotate with K/V.
    ``q_off``/``k_off``/``window``/``kv_len`` as in
    :func:`flash_attention_with_lse` — masked entries have p = exp(NEG_INF
    − lse) = 0, so dead blocks contribute exact zeros."""
    if window is not None:
        enforce(causal, "flash_attention_bwd_block: window (sliding-window "
                        "attention) requires causal=True")
        enforce(window >= 1, f"window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None or block_k is None:
        tq, tk = resolve_blocks(q.shape[-2], k.shape[-2], q.dtype, causal, window)
        block_q, block_k = block_q or tq, block_k or tk
    return _flash_bwd(
        q, k, v, out, lse, g, causal, float(sm_scale), block_q, block_k,
        interpret, kv_len, window, q_off, k_off,
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    kv_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused attention: ``softmax(QK^T * sm_scale) V``.

    q: [B, H, T, d]; k/v: [B, H_kv, T, d] with H % H_kv == 0 — H_kv < H is
    grouped-query attention (kv blocks are fetched once per shared head via
    the index maps; dK/dV accumulate over the query-head group in the fused
    backward). ``kv_len`` ([B] int, values >= 1) masks key positions >=
    kv_len[b] — suffix padding, the framework's LoD replacement — in
    forward AND fused backward, with fully-padded tail blocks skipped.
    ``window`` (with causal=True) restricts attention to the last ``window``
    keys — sliding-window attention; out-of-window kv blocks are skipped
    entirely, making compute O(T * window) instead of O(T^2/2).
    ``interpret`` defaults to True off-TPU so the same code path runs under
    the CPU test mesh. ``block_q``/``block_k`` default through
    :func:`resolve_blocks`: the ``paddle_tpu.tune`` autotune store when
    ``flags().autotune`` is on, else the chip-measured
    :func:`tuned_blocks` table, else 128/128 — always fitted to the
    largest MXU-friendly divisor of the sequence lengths."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None or block_k is None:
        tq, tk = resolve_blocks(q.shape[-2], k.shape[-2], q.dtype, causal, window)
        block_q, block_k = block_q or tq, block_k or tk
    if window is not None:
        enforce(causal, "flash_attention: window (sliding-window attention) "
                        "requires causal=True")
        enforce(window >= 1, f"window must be >= 1, got {window}")
    has_kvlen = kv_len is not None
    if not has_kvlen:
        kv_len = jnp.zeros((q.shape[0],), jnp.int32)
    return _flash(
        q, k, v, kv_len.astype(jnp.int32), causal, float(sm_scale),
        block_q, block_k, interpret, has_kvlen, window,
    )
