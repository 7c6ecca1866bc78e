"""Candidate generation + timing loop for the kernel autotuner, and the
per-kernel block sweep that fills the flash kernels' checked-in table.

One timing loop for everything: the in-framework autotuner
(:mod:`paddle_tpu.tune.autotune`), the bench ``--tune`` leg and
:func:`sweep_kernels` all call :func:`time_fn` and
:func:`candidate_blocks`, so they cannot drift apart.

Candidates are constrained up front to what the kernel will accept —
every (block_q, block_k) pair divides the sequence lengths (via the
kernel's own :func:`fit_block` policy), is MXU/lane aligned, and its
working set (the kernel's own :func:`working_set_bytes`, per kernel)
fits the VMEM budget — so no candidate can ever trip the divisibility
enforce mid-sweep.

    python -m paddle_tpu.tune.search --shape 4,16,2048,64 --dtype bfloat16

times the forward, dK/dV and dQ kernels one by one over their candidates
on the attached chip and prints the row for
``flash_attention._TUNED_BLOCKS`` with each kernel's runner-up.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (
    KERNELS,
    fit_block,
    working_set_bytes,
)

__all__ = [
    "MXU_LANE",
    "CANDIDATE_SIZES",
    "candidate_blocks",
    "shape_bucket",
    "variant_tag",
    "time_fn",
    "fit_block",
    "sweep_kernels",
    "table_row",
]

MXU_LANE = 128
# the sizes worth sweeping on current TPUs: one MXU tile up to what a v5e
# kernel's raised VMEM limit holds (tests/test_flash_blocks.py pins the bounds)
CANDIDATE_SIZES = (128, 256, 512, 1024)
# the kernels raise ``vmem_limit_bytes`` to their working set; a candidate
# past this is not worth the compile
_VMEM_BUDGET_BYTES = 48 * 1024 * 1024


def _tile_bytes(bq: int, bk: int, d: int = 128, kernel: str = "fwd",
                itemsize: int = 2) -> int:
    """Streamed working set of one grid step of ``kernel``: the kernels' own
    arithmetic (:func:`working_set_bytes`), not a copy of it."""
    return working_set_bytes(kernel, bq, bk, d, itemsize)


def candidate_blocks(t_q: int, t_kv: int, d: int = 128, kernel: str = "fwd",
                     itemsize: int = 2) -> List[Tuple[int, int]]:
    """Valid (block_q, block_k) candidates of ``kernel`` for the given
    sequence lengths: every pair divides (t_q, t_kv), stays lane-aligned
    where the length allows it, and fits the VMEM budget. Never empty — the
    fitted default (128/128 clamped by :func:`fit_block`) is always
    included."""
    qs = sorted({fit_block(c, t_q) for c in CANDIDATE_SIZES if c <= t_q} | {fit_block(MXU_LANE, t_q)})
    ks = sorted({fit_block(c, t_kv) for c in CANDIDATE_SIZES if c <= t_kv} | {fit_block(MXU_LANE, t_kv)})
    out = [
        (bq, bk)
        for bq in qs
        for bk in ks
        if _tile_bytes(bq, bk, d, kernel, itemsize) <= _VMEM_BUDGET_BYTES
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


# ---- the per-kernel sweep behind flash_attention._TUNED_BLOCKS -------------


def _kernel_call(kernel: str, shape, dtype, causal: bool, interpret: bool, reps: int):
    """``(make(block_q, block_k) -> jitted call, args)`` of one flash kernel
    alone at ``shape`` = (B, H, T, d): the forward, or one backward kernel on
    the forward's own residuals. A call runs the kernel ``reps`` times, one
    after the other over stacked copies of the operands, so that a kernel of
    a millisecond is not timed by its dispatch."""
    import importlib

    import numpy as np

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    B, H, T, d = shape
    rng = np.random.default_rng(0)
    q, k, v, g = (jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(4))
    scale = float(d) ** -0.5
    if kernel == "fwd":
        call = lambda bq, bk: lambda a, b, c: fa._flash_fwd(
            a, b, c, causal, scale, bq, bk, interpret)
        args = (q, k, v)
    else:
        out, lse = fa._flash_fwd(q, k, v, causal, scale, None, None, interpret)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)
        one = fa._flash_bwd_dkv if kernel == "dkv" else fa._flash_bwd_dq
        call = lambda bq, bk: lambda *a: one(*a, causal, scale, bq, bk, interpret)
        args = (q, k, v, g, lse.reshape(B * H, T), delta.reshape(B * H, T))
    stacked = tuple(jnp.broadcast_to(a, (reps,) + a.shape) + 0 for a in args)
    return (lambda bq, bk: jax.jit(
        lambda *xs: jax.lax.map(lambda x: call(bq, bk)(*x), xs))), stacked


def sweep_kernels(shape, dtype=jnp.bfloat16, causal: bool = True,
                  kernels=KERNELS, iters: int = 5, warmup: int = 2,
                  reps: int = 8, interpret: Optional[bool] = None,
                  progress=None) -> Dict[str, List[dict]]:
    """Time each flash kernel alone over its candidate blocks at ``shape`` =
    (B, H, T, d): milliseconds a kernel call, the median of ``iters`` timed
    runs of ``reps`` calls each. Returns per kernel the measured rows,
    fastest first; a candidate the compiler refuses keeps its ``error`` and
    sorts last."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, _, T, d = shape
    out: Dict[str, List[dict]] = {}
    for kernel in kernels:
        make, args = _kernel_call(kernel, shape, dtype, causal, interpret, reps)
        rows = []
        for bq, bk in candidate_blocks(T, T, d, kernel, jnp.dtype(dtype).itemsize):
            row = {"kernel": kernel, "shape": list(shape), "block_q": bq, "block_k": bk}
            try:
                ms = time_fn(make(bq, bk), *args, iters=iters, warmup=warmup)
                row["ms"] = round(ms / reps, 4)
            except Exception as e:  # one refused candidate must not end the sweep
                row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            rows.append(row)
            if progress is not None:
                progress(dict(row))
        out[kernel] = sorted(rows, key=lambda r: r.get("ms", float("inf")))
    return out


def table_row(shape, dtype, swept: Dict[str, List[dict]]) -> str:
    """The ``_TUNED_BLOCKS`` row a sweep's winners make, runners-up in a
    trailing comment."""
    _, _, T, d = shape
    best = {k: rows[0] for k, rows in swept.items() if rows and "ms" in rows[0]}
    entry = ", ".join(f'"{k}": ({r["block_q"]}, {r["block_k"]})' for k, r in best.items())
    second = "; ".join(
        f'{k} {r[0]["ms"]} ms, then {r[1]["block_q"]}x{r[1]["block_k"]} {r[1]["ms"]} ms'
        for k, r in swept.items() if len(r) > 1 and "ms" in r[1])
    return f"({T}, {d}, {jnp.dtype(dtype).itemsize}): {{{entry}}},  # {second}"


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[-1])
    ap.add_argument("--shape", required=True, help="B,H,T,d")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--reps", type=int, default=8, help="kernel calls a timed run")
    ap.add_argument("--kernels", default=",".join(KERNELS))
    args = ap.parse_args(argv)
    shape = tuple(int(x) for x in args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    swept = sweep_kernels(
        shape, dtype, bool(args.causal), tuple(args.kernels.split(",")),
        iters=args.iters, reps=args.reps, progress=lambda row: print(json.dumps(row), flush=True))
    print(table_row(shape, dtype, swept), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
