"""Candidate generation + timing loop for the kernel autotuner.

One timing loop for everything: the in-framework autotuner
(:mod:`paddle_tpu.tune.autotune`) and the bench ``--tune`` leg both call
:func:`time_fn` and :func:`candidate_blocks`, so they cannot drift apart.

Candidates are constrained up front to what the kernel will accept —
every (block_q, block_k) pair divides the sequence lengths (via the
kernel's own :func:`fit_block` policy), is MXU/lane aligned, and fits
the VMEM tile budget — so no candidate can ever trip the divisibility
enforce mid-sweep.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

import jax

from paddle_tpu.ops.pallas.flash_attention import fit_block

__all__ = [
    "MXU_LANE",
    "CANDIDATE_SIZES",
    "candidate_blocks",
    "shape_bucket",
    "variant_tag",
    "time_fn",
    "fit_block",
]

MXU_LANE = 128
# the sizes worth sweeping on current TPUs: one MXU tile up to the VMEM
# comfort limit (tests/test_flash_blocks.py pins the same bounds)
CANDIDATE_SIZES = (128, 256, 512)
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_D_MAX = 256


def _tile_bytes(bq: int, bk: int, d: int = _D_MAX) -> int:
    """Fwd working set per grid step (q/k/v tiles bf16, scores + out
    accumulator f32) — mirrors tests/test_flash_blocks.py."""
    return bq * d * 2 + 2 * bk * d * 2 + bq * bk * 4 + bq * d * 4 + bq * 4


def candidate_blocks(t_q: int, t_kv: int, d: int = 128) -> List[Tuple[int, int]]:
    """Valid (block_q, block_k) candidates for the given sequence lengths:
    every pair divides (t_q, t_kv), stays lane-aligned where the length
    allows it, and fits the VMEM budget. Never empty — the fitted default
    (128/128 clamped by :func:`fit_block`) is always included."""
    qs = sorted({fit_block(c, t_q) for c in CANDIDATE_SIZES if c <= t_q} | {fit_block(MXU_LANE, t_q)})
    ks = sorted({fit_block(c, t_kv) for c in CANDIDATE_SIZES if c <= t_kv} | {fit_block(MXU_LANE, t_kv)})
    out = [
        (bq, bk)
        for bq in qs
        for bk in ks
        if _tile_bytes(bq, bk, max(d, MXU_LANE)) <= _VMEM_BUDGET_BYTES
    ]
    if not out:  # budget excluded everything exotic: keep the fitted default
        out = [(fit_block(MXU_LANE, t_q), fit_block(MXU_LANE, t_kv))]
    return out


def shape_bucket(t_q: int, t_kv: Optional[int] = None) -> str:
    """Bucket sequence lengths to the next power of two (floor 128) so one
    tuned entry covers the whole bucket instead of one exact shape."""
    def _b(t: int) -> int:
        b = MXU_LANE
        while b < t:
            b *= 2
        return b

    if t_kv is None or t_kv == t_q:
        return f"q{_b(t_q)}"
    return f"q{_b(t_q)}k{_b(t_kv)}"


def variant_tag(causal: bool, window: Optional[int] = None,
                fused_bwd: bool = True) -> str:
    """Masking/schedule variant: it changes the work per block, so tuned
    winners are keyed by it."""
    tag = "causal" if causal else "full"
    if window is not None:
        tag += f"_w{int(window)}"
    if not fused_bwd:
        tag += "_xlabwd"
    return tag


def time_fn(fn: Callable, *args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall-clock milliseconds per call — the timing loop every
    tune surface shares (framework autotuner, bench --tune), so they
    cannot drift apart."""
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]
