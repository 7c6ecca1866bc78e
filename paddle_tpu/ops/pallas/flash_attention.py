"""Flash attention, forward and fused backward (Pallas TPU).

Blockwise attention with online-softmax accumulation: the [T, T] score
matrix never materializes in HBM — the FlashAttention-2 schedule laid out
for the MXU. The reference framework composed attention from
softmax/matmul ops (``python/paddle/fluid/nets.py:332``
scaled_dot_product_attention) and had no fused kernel; this replaces that
composition on the hot path.

Three kernels, each in two forms chosen from what the call can observe:

* ``flash_fwd``: one q block per grid cell, kv blocks swept by an inner
  loop; emits the output and the per-row logsumexp.
* ``flash_bwd_dkv``: one kv block per grid cell, q blocks (of every query
  head sharing the kv head, under GQA) swept; accumulates dK and dV. It
  works on the transposed scores ``K Q^T`` so that ``P^T dO`` and
  ``dS^T Q`` are plain products and the row statistics broadcast along
  sublanes: no transpose runs inside the loop.
* ``flash_bwd_dq``: one q block per grid cell, kv blocks swept;
  accumulates dQ. P is recomputed from (Q, K, LSE) in both backward
  kernels.

*Resident* form (``flash_*_resident``): the swept side of one head (K and
V for fwd and dq, Q and dO for dkv) fits :data:`_VMEM_RESIDENT_BYTES`, is
fetched whole once per head and swept by a ``fori_loop`` whose bounds
follow the causal diagonal, the window and ``kv_len`` at global positions
(ring attention passes traced offsets): a dead block costs nothing.
*Streamed* form: the swept side moves through the innermost grid
dimension one block a step; a dead step's index maps are clamped to the
nearest live block, so Pallas fetches nothing new for it.

Matmul operands enter the MXU in the dtype they arrive in (bfloat16 on
the training path, P and dS rounded to it) and accumulate in float32;
max, exp, l, lse, delta and every accumulator are float32, and
``sm_scale`` multiplies the float32 scores. Blocks fully inside the live
region skip the mask arithmetic. The row statistics travel as
``[B*H, T/block, 1, block]`` (positions on the lane axis).

Block sizes are per kernel: a caller's explicit blocks, else the autotune
store (``flags().autotune``), else the chip-measured
:data:`_TUNED_BLOCKS` table, else the largest blocks under a VMEM budget.
Where they came from and which form ran is counted at trace time under
``flash.blocks.{caller,store,table,rule}`` and
``flash.form.{resident,streamed}`` (label ``kernel``), and
:func:`take_resolved` hands the resolved blocks to the ``executor.compile``
span. Set ``flags().flash_fused_bwd = False`` to fall back to the
recomputed XLA backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core import profiler as prof
from paddle_tpu.core.dtypes import NEG_INF
from paddle_tpu.core.enforce import enforce

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "flash_attention_bwd_block",
    "fit_block",
    "resolve_blocks",
    "tuned_blocks",
    "working_set_bytes",
    "take_resolved",
    "KERNELS",
]

# the three kernels, as the block table, the counters and the sweep name them
KERNELS = ("fwd", "dkv", "dq")
_KERNEL_NAMES = {"fwd": "flash_fwd", "dkv": "flash_bwd_dkv", "dq": "flash_bwd_dq"}


def fit_block(block: int, total: int) -> int:
    """Largest block <= ``block`` that divides ``total``, preferring
    MXU/lane-aligned sizes (multiples of 128), then sublane-aligned ones
    (multiples of 8). A plain ``min(block, total)`` rejects perfectly
    servable shapes — T=192 with a 128 block used to hard-fail the
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


# ---- which blocks of the swept side are live, and which need no mask -------


def _cdiv(a, b: int):
    """ceil(a / b) for a possibly negative, possibly traced ``a``."""
    return -((-a) // b)


def _ordered(lo, flo, fhi, hi, first, n):
    """Clip the live range [lo, hi) and the mask-free range [flo, fhi)
    inside it to the ``n`` blocks from ``first`` that this grid step holds."""
    lo = jnp.clip(lo, first, first + n)
    hi = jnp.clip(hi, lo, first + n)
    flo = jnp.clip(flo, lo, hi)
    fhi = jnp.clip(fhi, flo, hi)
    return lo, flo, fhi, hi


def _kv_ranges(q_start, block_q: int, k_off, block_k: int, n_kv, causal: bool,
               window, kv_limit):
    """For the q block at global rows [q_start, q_start + block_q): kv blocks
    [lo, hi) hold a live position, [flo, fhi) hold only live ones."""
    q_end = q_start + block_q - 1
    lo = flo = 0
    hi = fhi = n_kv
    if causal:
        hi = (q_end - k_off) // block_k + 1
        fhi = (q_start - k_off + 1) // block_k
    if window is not None:
        lo = (q_start - (window - 1) - k_off) // block_k
        flo = _cdiv(q_end - (window - 1) - k_off, block_k)
    if kv_limit is not None:  # fully padded tail blocks hold nothing live
        hi = jnp.minimum(hi, _cdiv(kv_limit - k_off, block_k))
        fhi = jnp.minimum(fhi, (kv_limit - k_off) // block_k)
    return lo, flo, fhi, hi


def _q_ranges(k_start, block_k: int, q_off, block_q: int, n_q, causal: bool,
              window, kv_limit):
    """For the kv block at global columns [k_start, k_start + block_k): the
    twin of :func:`_kv_ranges` over q blocks."""
    k_end = k_start + block_k - 1
    lo = flo = 0
    hi = fhi = n_q
    if causal:
        lo = (k_start - q_off) // block_q
        flo = _cdiv(k_end - q_off, block_q)
    if window is not None:
        hi = (k_end + (window - 1) - q_off) // block_q + 1
        fhi = (k_start + window - q_off) // block_q
    if kv_limit is not None:
        hi = jnp.where(k_start < kv_limit, hi, 0)
        fhi = jnp.where(k_end < kv_limit, fhi, 0)
    return lo, flo, fhi, hi


def _sweep(ranges, body, can_mask: bool):
    """Run ``body(block, masked)`` over the live blocks in ascending order;
    only those outside the mask-free range pay for the mask."""
    lo, flo, fhi, hi = ranges

    def run(a, b, masked):
        jax.lax.fori_loop(a, b, lambda t, c: (body(t, masked), c)[1], 0)

    if not can_mask:
        run(lo, hi, False)
        return
    run(lo, flo, True)
    run(flo, fhi, False)
    run(fhi, hi, True)


def _clamped(t, lo, hi, n: int):
    """Index-map clamp of a streamed block index (of ``n``) to the live range
    [lo, hi): a dead step names the block its neighbour fetched, so nothing
    moves."""
    lo = jnp.clip(lo, 0, n - 1)
    return jnp.clip(t, lo, jnp.maximum(jnp.minimum(hi, n) - 1, lo))


def _mask(s, q_start, k_start, causal: bool, window, kv_limit, q_axis: int):
    """NEG_INF where a (query, key) pair is dead. ``q_axis`` is the axis of
    ``s`` the queries lie on: 0 for Q K^T, 1 for the transposed K Q^T."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = None
    if causal:
        keep = q_pos >= k_pos
        if window is not None:
            keep = jnp.logical_and(keep, q_pos - k_pos < window)
    if kv_limit is not None:
        in_len = k_pos < kv_limit
        keep = in_len if keep is None else jnp.logical_and(keep, in_len)
    return jnp.where(keep, s, NEG_INF)


def _flip(x):
    """[n, 1] -> [1, n] or back, exact, from broadcasts, a select and one
    reduction only, so it lowers for any n (no transpose unit, no MXU
    rounding). Once per q block, outside the sweep."""
    n = max(x.shape)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, x, 0.0), axis=x.shape.index(n), keepdims=True)


def _rows(ref, t, block: int, n_held: int):
    """Block ``t`` (of ``n_held`` held in VMEM) of a [1, rows, d] ref."""
    if n_held == 1:
        return ref[0]
    return ref[0, pl.ds(pl.multiple_of(t * block, block), block), :]


_NT = (((1,), (1,)), ((), ()))  # A B^T: contract the last axis of both


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ---- the kernels -----------------------------------------------------------


def _flash_fwd_kernel(
    lens_ref, offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, block_k: int, n_held: int, heads: int, causal: bool, sm_scale: float,
    has_kvlen: bool, window=None,
):
    """One (batch*head, q block, kv step) grid cell. The kv step holds
    ``n_held`` kv blocks: all of them in the resident form (one step), one
    in the streamed form, where m/l/acc carry over the sequential kv steps
    in VMEM scratch. ``offs_ref`` = [q_off, k_off] GLOBAL position offsets
    (SMEM scalars, may be traced — e.g. ring-rank dependent):
    causal/window/kv_len masking is applied at global positions, so an
    off-diagonal ring block pair runs this same kernel with full block
    skipping instead of a composed fallback."""
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    q_start = offs_ref[0] + pl.program_id(1) * block_q
    k_off = offs_ref[1]
    kv_limit = lens_ref[pl.program_id(0) // heads] if has_kvlen else None
    first = j * n_held
    ranges = _ordered(
        *_kv_ranges(q_start, block_q, k_off, block_k, pl.num_programs(2) * n_held,
                    causal, window, kv_limit), first, n_held)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cd = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    q = q_ref[0].astype(cd)

    def body(t, masked):
        k = _rows(k_ref, t - first, block_k, n_held).astype(cd)
        v = _rows(v_ref, t - first, block_k, n_held).astype(cd)
        s = _dot(q, k, _NT) * sm_scale  # [block_q, block_k] float32
        if masked:
            s = _mask(s, q_start, k_off + t * block_k, causal, window, kv_limit, 0)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _dot(p.astype(cd), v)

    _sweep(ranges, body, causal or has_kvlen)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l_safe = jnp.maximum(l_ref[:], 1e-20)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = _flip(m_ref[:] + jnp.log(l_safe))


def _flash_bwd_dkv_kernel(
    lens_ref, offs_ref, q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, block_q: int, n_held: int, n_steps: int, h_kv: int, causal: bool,
    sm_scale: float, has_kvlen: bool, window=None,
):
    """dK/dV for one kv block; the innermost grid dim runs group * n_steps
    sequential steps: every query head sharing this kv head (GQA), and per
    head ``n_steps`` q steps of ``n_held`` q blocks each (all of a head's
    in the resident form). FlashAttention-2 eq. (13-16) on the transposed
    scores: S^T = K Q^T; dV += P^T dO; dS^T = P^T ∘ (V dO^T − Δ);
    dK += dS^T Q (scaled at the end)."""
    step = pl.program_id(2)
    block_k = k_ref.shape[1]
    k_start = offs_ref[1] + pl.program_id(1) * block_k
    q_off = offs_ref[0]
    kv_limit = lens_ref[pl.program_id(0) // h_kv] if has_kvlen else None
    first = (step % n_steps) * n_held
    ranges = _ordered(
        *_q_ranges(k_start, block_k, q_off, block_q, n_steps * n_held, causal,
                   window, kv_limit), first, n_held)

    @pl.when(step == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    cd = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    k = k_ref[0].astype(cd)
    v = v_ref[0].astype(cd)

    def body(t, masked):
        q = _rows(q_ref, t - first, block_q, n_held).astype(cd)
        do = _rows(do_ref, t - first, block_q, n_held).astype(cd)
        s = _dot(k, q, _NT) * sm_scale  # [block_k, block_q]: queries on lanes
        if masked:
            s = _mask(s, q_off + t * block_q, k_start, causal, window, kv_limit, 1)
        p = jnp.exp(s - lse_ref[0, t - first])  # [1, block_q] along sublanes
        dv_acc[:] += _dot(p.astype(cd), do)
        ds = p * (_dot(v, do, _NT) - delta_ref[0, t - first])
        dk_acc[:] += _dot(ds.astype(cd), q)

    _sweep(ranges, body, causal or has_kvlen)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    lens_ref, offs_ref, q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
    dq_ref, dq_acc, lse_col, delta_col,
    *, block_k: int, n_held: int, heads: int, causal: bool, sm_scale: float,
    has_kvlen: bool, window=None,
):
    """dQ for one q block, kv blocks swept as in the forward:
    dQ += dS K (scaled at the end)."""
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    q_start = offs_ref[0] + pl.program_id(1) * block_q
    k_off = offs_ref[1]
    kv_limit = lens_ref[pl.program_id(0) // heads] if has_kvlen else None
    first = j * n_held
    ranges = _ordered(
        *_kv_ranges(q_start, block_q, k_off, block_k, pl.num_programs(2) * n_held,
                    causal, window, kv_limit), first, n_held)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        lse_col[:] = _flip(lse_ref[0, 0])
        delta_col[:] = _flip(delta_ref[0, 0])

    cd = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    q = q_ref[0].astype(cd)
    do = do_ref[0].astype(cd)

    def body(t, masked):
        k = _rows(k_ref, t - first, block_k, n_held).astype(cd)
        v = _rows(v_ref, t - first, block_k, n_held).astype(cd)
        s = _dot(q, k, _NT) * sm_scale
        if masked:
            s = _mask(s, q_start, k_off + t * block_k, causal, window, kv_limit, 0)
        p = jnp.exp(s - lse_col[:])
        ds = p * (_dot(do, v, _NT) - delta_col[:])
        dq_acc[:] += _dot(ds.astype(cd), k)

    _sweep(ranges, body, causal or has_kvlen)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


# ---- block sizes and forms -------------------------------------------------

# the swept side of one (batch, head) beyond this stays in HBM and streams
# through the grid
_VMEM_RESIDENT_BYTES = 4 * 1024 * 1024

# Chip-measured blocks (TPU v5e, ``python -m paddle_tpu.tune.search``; ROADMAP
# A2), keyed by what a call can observe: (T, head size, operand itemsize),
# then the kernel -> (block_q, block_k). A self-attention call whose lengths,
# head size and itemsize match a row takes it; anything else takes
# :func:`rule_blocks`.
_TUNED_BLOCKS: dict[tuple[int, int, int], dict[str, tuple[int, int]]] = {
    # causal, B*H 64 (32 at head size 128); milliseconds a call in the sweep's
    # timing loop, the winner then the runner-up (PERF.md, PR 28)
    # fwd 1.236, 512x1024 1.241; dkv 1.572, 512x1024 1.686; dq 1.283, 1024x1024 1.379
    (2048, 64, 2): {"fwd": (1024, 1024), "dkv": (512, 512), "dq": (512, 512)},
    # fwd 2.607, 512x1024 2.724; dkv 3.838, 512x512 3.897; dq 3.025, 1024x512 3.074
    (8192, 64, 2): {"fwd": (1024, 1024), "dkv": (1024, 1024), "dq": (1024, 1024)},
    # fwd 0.644, 512x1024 0.670; dkv 0.804, 512x256 0.842; dq 0.655, 1024x512 0.688
    (2048, 128, 2): {"fwd": (1024, 1024), "dkv": (512, 512), "dq": (512, 512)},
}

# what the rule may pick from, and the working set it keeps a pick under. On
# the chip the swept-side block matters most and larger was never slower
# (PERF.md, PR 28), so the budget is what 1024 x 1024 needs at head sizes to
# 256, not the default scoped VMEM: :func:`_vmem_limit` raises the kernel's
# limit to its working set (a v5e core has 128 MiB).
_RULE_SIZES = (1024, 512, 256, 128)
_RULE_VMEM_BYTES = 40 * 1024 * 1024
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_LIMIT_MAX = 100 * 1024 * 1024


def _pad(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def working_set_bytes(kernel: str, block_q: int, block_k: int, d: int,
                      itemsize: int, swept_rows: Optional[int] = None) -> int:
    """VMEM one grid step of ``kernel`` needs, as the chip lays it out (the
    head axis padded to 128 lanes, pipelined operands double-buffered).
    ``swept_rows``: rows of the swept side held at once, the whole head in
    the resident form; defaults to one block (streamed)."""
    enforce(kernel in KERNELS, f"unknown flash kernel {kernel!r}")
    dl = _pad(d, 128)
    fixed, swept = (block_k, block_q) if kernel == "dkv" else (block_q, block_k)
    rows = swept if swept_rows is None else max(int(swept_rows), swept)
    tile = lambda r: 2 * _pad(r, 16) * dl * itemsize        # double-buffered
    stat = lambda n: 2 * n * 8 * _pad(block_q, 128) * 4     # [n, 1, block_q] f32
    scores = block_q * _pad(block_k, 128)
    if kernel == "fwd":  # q, o; k, v; lse; m, l, acc; s, p, mask, p cast
        return (2 * tile(fixed) + 2 * tile(rows) + stat(1)
                + 2 * fixed * 128 * 4 + fixed * dl * 4
                + scores * (12 + itemsize) + block_q * _pad(block_q, 128) * 8)
    if kernel == "dkv":  # k, v, dk, dv; q, do; lse, delta; accs; s, p, dp, ds
        return (4 * tile(fixed) + 2 * tile(rows) + 2 * stat(rows // swept)
                + 2 * fixed * dl * 4 + scores * (16 + 2 * itemsize))
    return (3 * tile(fixed) + 2 * tile(rows) + 2 * stat(1)  # q, do, dq; k, v
            + 2 * fixed * 128 * 4 + fixed * dl * 4
            + scores * (16 + 2 * itemsize) + block_q * _pad(block_q, 128) * 8)


def _vmem_limit(need: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a working set: None while the default scoped
    VMEM holds it with room to spare."""
    if need <= _SCOPED_VMEM_DEFAULT * 3 // 4:
        return None
    return min(max(_SCOPED_VMEM_DEFAULT, need * 3 // 2 + (8 << 20)), _VMEM_LIMIT_MAX)


def _resident(kernel: str, t_q: int, t_kv: int, d: int, q_itemsize: int,
              kv_itemsize: int) -> bool:
    """Whether the swept side of one head (K and V, or Q and dO) fits the
    resident bound."""
    if kernel == "dkv":
        return 2 * t_q * d * q_itemsize <= _VMEM_RESIDENT_BYTES
    return 2 * t_kv * d * kv_itemsize <= _VMEM_RESIDENT_BYTES


def rule_blocks(t_q: int, t_kv: int, d: int = 128, itemsize: int = 2,
                kernel: str = "fwd") -> tuple[int, int]:
    """Blocks for a shape the table does not hold: the largest fitted pair
    (by area, then by the swept side) whose working set stays under
    :data:`_RULE_VMEM_BYTES`; 128/128 fitted to the lengths when none does."""
    swept = None
    if _resident(kernel, t_q, t_kv, d, itemsize, itemsize):
        swept = t_q if kernel == "dkv" else t_kv
    best, best_key = None, None
    for bq in sorted({fit_block(c, t_q) for c in _RULE_SIZES}):
        for bk in sorted({fit_block(c, t_kv) for c in _RULE_SIZES}):
            if working_set_bytes(kernel, bq, bk, d, itemsize, swept) > _RULE_VMEM_BYTES:
                continue
            key = (bq * bk, bq if kernel == "dkv" else bk)
            if best_key is None or key > best_key:
                best, best_key = (bq, bk), key
    return best or (fit_block(128, t_q), fit_block(128, t_kv))


def _table_blocks(t_q, t_kv, d, itemsize, kernel) -> Optional[tuple[int, int]]:
    row = _TUNED_BLOCKS.get((t_q, d, itemsize)) if t_q == t_kv else None
    return row.get(kernel) if row else None


def tuned_blocks(t_q: int, t_kv: int, d: int = 128, itemsize: int = 2,
                 kernel: str = "fwd") -> tuple[int, int]:
    """Default (block_q, block_k) of ``kernel`` without the autotune store:
    the measured table when it holds the shape, else :func:`rule_blocks`."""
    return (_table_blocks(t_q, t_kv, d, itemsize, kernel)
            or rule_blocks(t_q, t_kv, d, itemsize, kernel))


def _resolve(kernel, t_q, t_kv, d, dtype, causal, window) -> tuple[int, int, str]:
    from paddle_tpu.core.config import flags

    if flags().autotune:
        from paddle_tpu.tune import autotune as _autotune

        tuned = _autotune.lookup_blocks(
            t_q, t_kv, dtype=dtype, causal=causal, window=window)
        if tuned is not None:
            return (*tuned, "store")
    itemsize = jnp.dtype(dtype).itemsize if dtype is not None else 2
    table = _table_blocks(t_q, t_kv, d, itemsize, kernel)
    if table is not None:
        return (*table, "table")
    return (*rule_blocks(t_q, t_kv, d, itemsize, kernel), "rule")


def resolve_blocks(t_q: int, t_kv: int, dtype=None, causal: bool = False,
                   window: Optional[int] = None, d: int = 128,
                   kernel: str = "fwd") -> tuple[int, int]:
    """Default-block resolution order of ``kernel``: autotune store (when
    ``flags().autotune`` is on — fingerprint-checked, process-memoized,
    counted under ``tune.cache.{hit,miss,stale}``; one pair for all three
    kernels), then the checked-in :data:`_TUNED_BLOCKS` table, then
    :func:`rule_blocks`."""
    return _resolve(kernel, t_q, t_kv, d, dtype, causal, window)[:2]


# what the kernels traced since the last take_resolved() ran with
_resolved: dict[str, str] = {}


def take_resolved() -> dict[str, str]:
    """``{kernel name: "<block_q>x<block_k> <source> <form>"}`` of the flash
    kernels traced since the last call, and forget them: the executor puts
    it on the ``executor.compile`` span of the compile that traced them."""
    out = dict(_resolved)
    _resolved.clear()
    return out


def _plan(kernel: str, q, k, block_q, block_k, causal, window):
    """Blocks, form and ``pallas_call`` name of one kernel for this call,
    counted where an operator can see them. A caller's explicit block wins;
    whatever the source, blocks are fitted to the lengths (T=192 runs at a
    divisor instead of hard-failing)."""
    t_q, d = q.shape[-2:]
    t_kv = k.shape[-2]
    source = "caller"
    if block_q is None or block_k is None:
        cd = jnp.promote_types(q.dtype, k.dtype)
        bq, bk, source = _resolve(kernel, t_q, t_kv, d, cd, causal, window)
        block_q, block_k = block_q or bq, block_k or bk
    block_q, block_k = fit_block(block_q, t_q), fit_block(block_k, t_kv)
    resident = _resident(kernel, t_q, t_kv, d, q.dtype.itemsize, k.dtype.itemsize)
    form = "resident" if resident else "streamed"
    name = _KERNEL_NAMES[kernel] + ("_resident" if resident else "")
    prof.inc_counter(f"flash.blocks.{source}", labels={"kernel": kernel})
    prof.inc_counter(f"flash.form.{form}", labels={"kernel": kernel})
    _resolved[name] = f"{block_q}x{block_k} {source} {form}"
    return block_q, block_k, resident, name


def _params(interpret: bool, need: int):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(need),
    )


def _scalars(kv_len, q_off, k_off):
    """The two scalar-prefetch operands: [B] key lengths (a single 0 when
    there are none) and [q_off, k_off] (ints or traced). 1-D, so SMEM holds
    them at any batch and head count."""
    lens = jnp.zeros((1,), jnp.int32) if kv_len is None else kv_len.astype(jnp.int32)
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32), jnp.asarray(k_off, jnp.int32)])
    return lens, offs


def _stat_blocks(x, block: int):
    """[B*H, T] row statistics -> [B*H, T/block, 1, block]: a block is one
    contiguous DMA with the positions on lanes, whatever the block size."""
    return x.reshape(x.shape[0], x.shape[1] // block, 1, block)


# ---- the three calls -------------------------------------------------------


def _kv_swept_specs(resident: bool, block_q: int, block_k: int, t_kv: int, d: int,
                    H: int, h_kv: int, causal: bool, window, has_kvlen: bool):
    """For the two kernels that hold a q block and sweep kv (fwd, dq):
    ``(q_spec, kv_spec, n_held, n_steps)`` over the grid (B*H, q blocks, kv
    steps). GQA: a query row's kv row is shared by its group."""
    group = H // h_kv
    n_held = t_kv // block_k if resident else 1
    n_steps = 1 if resident else t_kv // block_k

    def kv_map(b, i, j, lens, offs):
        if not resident:
            lo, _, _, hi = _kv_ranges(
                offs[0] + i * block_q, block_q, offs[1], block_k, n_steps, causal,
                window, lens[b // H] if has_kvlen else None)
            j = _clamped(j, lo, hi, n_steps)
        return ((b // H) * h_kv + (b % H) // group, j, 0)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0))
    return q_spec, pl.BlockSpec((1, n_held * block_k, d), kv_map), n_held, n_steps


def _stat_spec(block_q: int):
    """One q block's row statistics, for the same grid."""
    return pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j, *_: (b, i, 0, 0))


# The three calls below are jitted (inlined into whatever traces them) for the
# trace cache alone: a model that is not scanned meets the same call once a
# layer, and tracing a kernel costs a quarter of a second on a chip's host.
_CALL_STATICS = ("causal", "sm_scale", "block_q", "block_k", "resident", "name",
                 "interpret", "window")


def _flash_fwd(q, k, v, causal: bool, sm_scale: float, block_q, block_k,
               interpret: bool, kv_len=None, window=None, q_off=0, k_off=0):
    """Returns ``(out [B,H,T,d], lse [B,H,T,1])`` — lse is the per-row
    logsumexp of the scaled scores, consumed by the fused backward.
    ``kv_len`` ([B] int) masks key positions >= kv_len[b] (suffix padding,
    the LoD-replacement layout). ``q_off``/``k_off`` (ints or traced
    scalars) shift causal/window/kv_len masking to GLOBAL positions — the
    ring-attention block pairs pass their rank-derived offsets here."""
    enforce(q.shape[1] % k.shape[1] == 0,
            f"{q.shape[1]} query heads not divisible by {k.shape[1]} kv heads")
    block_q, block_k, resident, name = _plan("fwd", q, k, block_q, block_k, causal, window)
    return _fwd_call(q, k, v, kv_len, q_off, k_off, causal=causal, sm_scale=sm_scale,
                     block_q=block_q, block_k=block_k, resident=resident, name=name,
                     interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
def _fwd_call(q, k, v, kv_len, q_off, k_off, *, causal, sm_scale, block_q, block_k,
              resident, name, interpret, window):
    B, H, T, d = q.shape
    h_kv, t_kv = k.shape[1], k.shape[2]
    has_kvlen = kv_len is not None
    q_spec, kv_spec, n_held, n_steps = _kv_swept_specs(
        resident, block_q, block_k, t_kv, d, H, h_kv, causal, window, has_kvlen)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, block_k=block_k, n_held=n_held, heads=H,
            causal=causal, sm_scale=sm_scale, has_kvlen=has_kvlen, window=window),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, T // block_q, n_steps),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, _stat_spec(block_q)],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, d), q.dtype),
            jax.ShapeDtypeStruct((B * H, T // block_q, 1, block_q), jnp.float32),
        ],
        compiler_params=_params(interpret, working_set_bytes(
            "fwd", block_q, block_k, d, k.dtype.itemsize, n_held * block_k)),
        interpret=interpret,
    )(*_scalars(kv_len, q_off, k_off), q.reshape(B * H, T, d),
      k.reshape(B * h_kv, t_kv, d), v.reshape(B * h_kv, t_kv, d))
    return out.reshape(B, H, T, d), lse.reshape(B, H, T, 1)


def _flash_bwd_dkv(q, k, v, g, lse, delta, causal, sm_scale, block_q, block_k,
                   interpret, kv_len=None, window=None, q_off=0, k_off=0):
    """(dk, dv) at the kv head count; ``lse``/``delta`` are [B*H, T]."""
    block_q, block_k, resident, name = _plan("dkv", q, k, block_q, block_k, causal, window)
    return _dkv_call(q, k, v, g, lse, delta, kv_len, q_off, k_off, causal=causal,
                     sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                     resident=resident, name=name, interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
def _dkv_call(q, k, v, g, lse, delta, kv_len, q_off, k_off, *, causal, sm_scale,
              block_q, block_k, resident, name, interpret, window):
    B, H, T, d = q.shape
    h_kv, t_kv = k.shape[1], k.shape[2]
    group = H // h_kv
    has_kvlen = kv_len is not None
    n_held = T // block_q if resident else 1
    n_steps = 1 if resident else T // block_q

    def q_map(r, j, s, lens, offs):  # (kv row, grouped step) -> q row, q step
        t = s % n_steps
        if not resident:
            lo, _, _, hi = _q_ranges(
                offs[1] + j * block_k, block_k, offs[0], block_q, n_steps, causal,
                window, lens[r // h_kv] if has_kvlen else None)
            t = _clamped(t, lo, hi, n_steps)
        return ((r // h_kv) * H + (r % h_kv) * group + s // n_steps, t)

    q_spec = pl.BlockSpec((1, n_held * block_q, d), lambda *a: (*q_map(*a), 0))
    stat_spec = pl.BlockSpec((1, n_held, 1, block_q), lambda *a: (*q_map(*a), 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda r, j, s, *_: (r, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, n_held=n_held, n_steps=n_steps,
            h_kv=h_kv, causal=causal, sm_scale=sm_scale, has_kvlen=has_kvlen,
            window=window),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * h_kv, t_kv // block_k, group * n_steps),
            in_specs=[q_spec, q_spec, kv_spec, kv_spec, stat_spec, stat_spec],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * h_kv, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((B * h_kv, t_kv, d), v.dtype),
        ],
        compiler_params=_params(interpret, working_set_bytes(
            "dkv", block_q, block_k, d, q.dtype.itemsize, n_held * block_q)),
        interpret=interpret,
    )(*_scalars(kv_len, q_off, k_off), q.reshape(B * H, T, d), g.reshape(B * H, T, d),
      k.reshape(B * h_kv, t_kv, d), v.reshape(B * h_kv, t_kv, d),
      _stat_blocks(lse, block_q), _stat_blocks(delta, block_q))
    return dk.reshape(B, h_kv, t_kv, d), dv.reshape(B, h_kv, t_kv, d)


def _flash_bwd_dq(q, k, v, g, lse, delta, causal, sm_scale, block_q, block_k,
                  interpret, kv_len=None, window=None, q_off=0, k_off=0):
    """dq; ``lse``/``delta`` are [B*H, T]."""
    block_q, block_k, resident, name = _plan("dq", q, k, block_q, block_k, causal, window)
    return _dq_call(q, k, v, g, lse, delta, kv_len, q_off, k_off, causal=causal,
                    sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                    resident=resident, name=name, interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
def _dq_call(q, k, v, g, lse, delta, kv_len, q_off, k_off, *, causal, sm_scale,
             block_q, block_k, resident, name, interpret, window):
    B, H, T, d = q.shape
    h_kv, t_kv = k.shape[1], k.shape[2]
    has_kvlen = kv_len is not None
    q_spec, kv_spec, n_held, n_steps = _kv_swept_specs(
        resident, block_q, block_k, t_kv, d, H, h_kv, causal, window, has_kvlen)
    stat_spec = _stat_spec(block_q)
    (dq,) = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, n_held=n_held, heads=H,
            causal=causal, sm_scale=sm_scale, has_kvlen=has_kvlen, window=window),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, T // block_q, n_steps),
            in_specs=[q_spec, q_spec, kv_spec, kv_spec, stat_spec, stat_spec],
            out_specs=[q_spec],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B * H, T, d), q.dtype)],
        compiler_params=_params(interpret, working_set_bytes(
            "dq", block_q, block_k, d, k.dtype.itemsize, n_held * block_k)),
        interpret=interpret,
    )(*_scalars(kv_len, q_off, k_off), q.reshape(B * H, T, d), g.reshape(B * H, T, d),
      k.reshape(B * h_kv, t_kv, d), v.reshape(B * h_kv, t_kv, d),
      _stat_blocks(lse, block_q), _stat_blocks(delta, block_q))
    return dq.reshape(B, H, T, d)


def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
               kv_len=None, window=None, q_off=0, k_off=0):
    """Fused backward: returns (dq, dk, dv), each the dtype of its primal
    (dk/dv at the kv head count under GQA). ``q_off``/``k_off``: global
    position offsets, as in :func:`_flash_fwd`."""
    B, H, T, _ = q.shape
    lse = lse.reshape(B * H, T)
    # Δ = rowsum(dO ∘ O): cheap elementwise+reduce, XLA fuses it
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(B * H, T)
    args = (q, k, v, g, lse, delta, causal, sm_scale, block_q, block_k, interpret,
            kv_len, window, q_off, k_off)
    dk, dv = _flash_bwd_dkv(*args)
    return _flash_bwd_dq(*args), dk, dv


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


def _checked(name: str, q, causal, sm_scale, interpret, window):
    """The defaults and refusals the three public entries share."""
    if window is not None:
        enforce(causal, f"{name}: window (sliding-window attention) "
                        "requires causal=True")
        enforce(window >= 1, f"window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return float(sm_scale), interpret


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
    sm_scale, interpret = _checked(
        "flash_attention_with_lse", q, causal, sm_scale, interpret, window)
    return _flash_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, kv_len,
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
    sm_scale, interpret = _checked(
        "flash_attention_bwd_block", q, causal, sm_scale, interpret, window)
    return _flash_bwd(
        q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
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
    the CPU test mesh. ``block_q``/``block_k`` pin the blocks of all three
    kernels; left None, each kernel resolves its own (module docstring) —
    always fitted to the largest MXU-friendly divisor of the sequence
    lengths."""
    sm_scale, interpret = _checked("flash_attention", q, causal, sm_scale, interpret, window)
    has_kvlen = kv_len is not None
    if not has_kvlen:
        kv_len = jnp.zeros((q.shape[0],), jnp.int32)
    return _flash(
        q, k, v, kv_len.astype(jnp.int32), causal, sm_scale,
        block_q, block_k, interpret, has_kvlen, window,
    )
