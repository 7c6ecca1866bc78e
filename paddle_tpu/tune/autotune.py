"""Flash-attention autotuner + the call-time tuned-config lookup.

The tuner benchmarks the forward + fused-backward pair over the
:func:`paddle_tpu.tune.search.candidate_blocks` grid and persists the
winner per ``(shape-bucket, dtype, variant, device_kind)`` to the
:class:`paddle_tpu.tune.store.TuneStore`, keyed by the *kernel
fingerprint* — a hash of the Pallas kernel sources + the config schema —
so a kernel edit silently retires every stale winner.

``flash_attention`` consults :func:`lookup_blocks` at call time (only
when ``flags().autotune`` is on). The lookup is process-level memoized:
the store file is read once, each (key, shape) resolution is computed
once, and ``tune.cache.{hit,miss,stale}`` counters plus a one-shot
``tune`` runlog event record what happened.

The sweep consults the roofline cost ledger
(:mod:`paddle_tpu.observability.roofline`): shapes classified
memory-bound run first — block-size choice moves bytes, not FLOPs, so
memory-bound buckets are where tuning pays and a cut time budget should
spend its window there. Each winner's measured time is compared against
the roofline-predicted device time; a >2x disagreement in either
direction bumps ``tune.cost_model_divergence_total`` (the cost model is
lying about this kernel), and the measurement is fed back into the
ledger so later sweeps and ``/roofline`` see tuned reality.
"""

from __future__ import annotations

import functools
import importlib
import inspect
import json
import threading
from typing import Dict, Optional, Sequence, Tuple

import os

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import locks
from paddle_tpu.core import config as cfg
from paddle_tpu.core import profiler as prof
from paddle_tpu.observability import runlog
from paddle_tpu.tune import search
from paddle_tpu.tune.store import TuneKey, TuneStore, kernel_fingerprint

__all__ = [
    "KERNEL",
    "flash_fingerprint",
    "device_kind",
    "default_store_path",
    "get_store",
    "lookup_blocks",
    "reset_lookup_cache",
    "autotune_flash_attention",
]

KERNEL = "flash_attention"

# part of the fingerprint: if the tunable parameter space or key layout
# changes shape, old entries no longer mean what they say
_CONFIG_SCHEMA = {
    "params": ["block_q", "block_k"],
    "key": ["kernel", "shape_bucket", "dtype", "variant", "device_kind"],
}


@functools.lru_cache(maxsize=1)
def flash_fingerprint() -> str:
    """Fingerprint of the flash-attention kernel pair: forward kernels,
    fused-backward kernels, and the wrappers that pick grids/specs —
    any edit to them invalidates tuned entries."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    srcs = [
        inspect.getsource(f)
        for f in (
            fa._flash_fwd_kernel,
            fa._flash_bwd_dkv_kernel,
            fa._flash_bwd_dq_kernel,
            fa._fwd_call,
            fa._dkv_call,
            fa._dq_call,
            fa._flash_bwd,
        )
    ]
    return kernel_fingerprint(*srcs, json.dumps(_CONFIG_SCHEMA, sort_keys=True))


def device_kind() -> str:
    try:
        return str(jax.devices()[0].device_kind).replace(" ", "_").replace(
            TuneKey.SEP, "_")
    except Exception:
        return "unknown"


def default_store_path() -> Optional[str]:
    """Store location: ``flags().tune_cache_dir``, else a ``tune/``
    subdir next to the persistent compilation cache, else None (tuning
    disabled by configuration)."""
    fl = cfg.flags()
    d = fl.tune_cache_dir or (
        os.path.join(fl.compilation_cache_dir, "tune")
        if fl.compilation_cache_dir else "")
    return os.path.join(d, "kernel_tune.json") if d else None


_store_lock = locks.Lock("tune.autotune_store")
_stores: Dict[Optional[str], TuneStore] = {}
_lookup_cache: Dict[tuple, Optional[Tuple[int, int]]] = {}
_announced = False


def get_store(path: Optional[str] = None) -> TuneStore:
    """Process-level store cache — one disk read per path per process."""
    path = path or default_store_path()
    with _store_lock:
        st = _stores.get(path)
        if st is None:
            st = _stores[path] = TuneStore(path)
        return st


def reset_lookup_cache() -> None:
    """Drop memoized lookups + cached stores (after an autotune run or a
    flag change, so fresh winners are visible in-process)."""
    with _store_lock:
        _stores.clear()
        _lookup_cache.clear()
    global _announced
    _announced = False


def lookup_blocks(t_q: int, t_kv: int, dtype=None, causal: bool = False,
                  window: Optional[int] = None,
                  store: Optional[TuneStore] = None) -> Optional[Tuple[int, int]]:
    """Tuned (block_q, block_k) for this call — or None when autotuning is
    off, no entry exists, the entry is stale (kernel fingerprint changed),
    or the stored blocks don't divide these exact lengths (bucket
    neighbors). Memoized per (key, shape) so the hot call path costs one
    dict probe after the first resolution."""
    if not cfg.flags().autotune:
        return None
    dt = jnp.dtype(dtype).name if dtype is not None else "-"
    key = TuneKey.render(
        KERNEL, search.shape_bucket(t_q, t_kv), dt,
        search.variant_tag(causal, window), device_kind())
    memo_key = (key, t_q, t_kv, id(store) if store is not None else None)
    if memo_key in _lookup_cache:
        return _lookup_cache[memo_key]
    st = store if store is not None else get_store()
    fp = flash_fingerprint()
    result: Optional[Tuple[int, int]] = None
    ent = st.get(key, fingerprint=fp)
    if ent is None:
        if st.is_stale(key, fp):
            prof.inc_counter("tune.cache.stale")
        else:
            prof.inc_counter("tune.cache.miss")
    else:
        bq = int(ent["config"].get("block_q", 0))
        bk = int(ent["config"].get("block_k", 0))
        if bq > 0 and bk > 0 and t_q % bq == 0 and t_kv % bk == 0:
            prof.inc_counter("tune.cache.hit")
            result = (bq, bk)
        else:
            prof.inc_counter("tune.cache.miss")
    global _announced
    if not _announced:
        _announced = True
        runlog.emit("tune", kernel=KERNEL, key=key, hit=result is not None,
                    fingerprint=fp, store=str(st.path))
    _lookup_cache[memo_key] = result
    return result


# measured/predicted outside [1/x, x] means the cost model and the chip
# disagree about this kernel — worth a counter, not worth failing a sweep
COST_MODEL_DIVERGENCE_RATIO = 2.0


def _flash_flops_bytes(B: int, H: int, T: int, d: int,
                       itemsize: int) -> Tuple[float, float]:
    """Analytic fwd-attention cost: QK^T + PV are ``2*T*T*d`` MACs each
    per head; bytes are the q/k/v/o tensor traffic. Coarse on purpose —
    only the compute-vs-memory *side* matters for sweep ordering."""
    flops = 4.0 * B * H * T * T * d
    bytes_ = 4.0 * B * H * T * d * float(itemsize)
    return flops, bytes_


def _sweep_order(
    shapes: Sequence[Tuple[int, int, int, int]], dtype, dk: str,
) -> Sequence[Tuple[int, int, int, int]]:
    """Memory-bound-first sweep order. A shape whose bucket already has a
    measured flash-attention row in the roofline ledger uses that row's
    verdict; otherwise the analytic flash cost against the device peaks
    decides which roofline slope it sits under. Stable within each class,
    so caller-specified priority survives."""
    from paddle_tpu.observability import mfu as obs_mfu
    from paddle_tpu.observability import roofline

    ledger_verdicts: Dict[str, str] = {}
    try:
        for row in roofline.snapshot():
            if row["kernel"] == KERNEL and row["device_kind"] == dk:
                ledger_verdicts[row["shape_bucket"]] = row["verdict"]
    except Exception:
        pass
    peak_f = obs_mfu.peak_flops_for_kind(dk)
    peak_b = obs_mfu.peak_hbm_bw_for_kind(dk)
    itemsize = jnp.dtype(dtype).itemsize

    def memory_bound(shape: Tuple[int, int, int, int]) -> bool:
        B, H, T, d = shape
        verdict = ledger_verdicts.get(search.shape_bucket(T, T))
        if verdict is not None:
            return verdict == roofline.MEMORY_BOUND
        if not peak_f or not peak_b:
            return False
        flops, bytes_ = _flash_flops_bytes(B, H, T, d, itemsize)
        return bytes_ / peak_b > flops / peak_f

    return sorted(shapes, key=lambda s: 0 if memory_bound(s) else 1)


def autotune_flash_attention(
    shapes: Sequence[Tuple[int, int, int, int]] = ((1, 4, 1024, 128),),
    causal: bool = True,
    window: Optional[int] = None,
    dtype=jnp.float32,
    include_bwd: bool = True,
    iters: int = 3,
    warmup: int = 1,
    store: Optional[TuneStore] = None,
    save: bool = True,
    interpret: Optional[bool] = None,
    progress=None,
    should_stop=None,
) -> Dict[str, dict]:
    """Sweep the candidate grid for each ``(B, H, T, d)`` shape and persist
    the per-bucket winner. Returns per-key results including every row
    measured and the winner's speedup over the fitted 128/128 default.
    ``progress(row_dict)`` fires after every measurement — the manual TPU
    sweep script uses it for incremental JSON output. ``should_stop()``
    (e.g. a time-budget check) cuts the sweep: a cut or a failing
    candidate marks the key ``partial`` and a partial winner is NEVER
    persisted — it must not be mistaken for a tuned default. A single
    candidate failure is recorded on its row and excluded from the
    winner, not fatal to the sweep."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    st = store if store is not None else get_store()
    fp = flash_fingerprint()
    st.prune_stale(KERNEL, fp)
    dk = device_kind()
    dt = jnp.dtype(dtype).name
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    results: Dict[str, dict] = {}
    for (B, H, T, d) in _sweep_order(shapes, dtype, dk):
        key = TuneKey.render(KERNEL, search.shape_bucket(T, T), dt,
                             search.variant_tag(causal, window), dk)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, H, T, d)), dtype)
        k = jnp.asarray(rng.standard_normal((B, H, T, d)), dtype)
        v = jnp.asarray(rng.standard_normal((B, H, T, d)), dtype)

        def make_fn(bq: int, bk: int):
            def loss(q_, k_, v_):
                return fa.flash_attention(
                    q_, k_, v_, causal=causal, window=window,
                    block_q=bq, block_k=bk, interpret=interpret).sum()

            if include_bwd:  # fwd + fused bwd pair (dkv + dq kernels)
                return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            return jax.jit(loss)

        default_cfg = (fa.fit_block(128, T), fa.fit_block(128, T))
        rows = []
        partial = False
        for (bq, bk) in search.candidate_blocks(T, T, d):
            if should_stop is not None and should_stop():
                partial = True
                break
            row = {"key": key, "shape": [B, H, T, d], "block_q": bq,
                   "block_k": bk}
            try:
                ms = search.time_fn(make_fn(bq, bk), q, k, v,
                                    iters=iters, warmup=warmup)
                row["ms"] = round(ms, 4)
            except Exception as e:  # one bad candidate must not end a
                # scarce chip window — record it, keep sweeping
                row["error"] = f"{type(e).__name__}: {str(e)[:120]}"
                partial = True
            rows.append(row)
            if progress is not None:
                progress(dict(row))
        ok_rows = [r for r in rows if "ms" in r]
        entry: dict = {"rows": rows, "partial": partial}
        if ok_rows:
            best = min(ok_rows, key=lambda r: r["ms"])
            default_ms = next(
                (r["ms"] for r in ok_rows
                 if (r["block_q"], r["block_k"]) == default_cfg), best["ms"])
            entry["best"] = {"block_q": best["block_q"],
                             "block_k": best["block_k"], "ms": best["ms"]}
            entry["default_ms"] = default_ms
            entry["speedup_vs_default"] = round(
                default_ms / max(best["ms"], 1e-9), 4)
            # measured vs. roofline-predicted for the winner: feed the
            # ledger, and count when the cost model diverges from the chip
            try:
                from paddle_tpu.observability import mfu as obs_mfu
                from paddle_tpu.observability import roofline

                lowered = make_fn(best["block_q"],
                                  best["block_k"]).lower(q, k, v)
                totals = obs_mfu.cost_analysis_totals(lowered)
                ledger_key = roofline.SEP.join(
                    (KERNEL, search.shape_bucket(T, T), dt, dk))
                roofline.note_compile(
                    ledger_key, flops=totals["flops"],
                    bytes_accessed=totals["bytes"],
                    transcendentals=totals["transcendentals"])
                roofline.observe_call(ledger_key, best["ms"] / 1e3)
                pred = roofline.predicted_seconds(
                    totals["flops"], totals["bytes"], kind=dk)
                if pred and pred > 0:
                    entry["predicted_ms"] = round(pred * 1e3, 4)
                    ratio = best["ms"] / (pred * 1e3)
                    entry["cost_model_ratio"] = round(ratio, 4)
                    if (ratio > COST_MODEL_DIVERGENCE_RATIO
                            or ratio < 1.0 / COST_MODEL_DIVERGENCE_RATIO):
                        prof.inc_counter("tune.cost_model_divergence_total")
            except Exception:
                pass  # cost attribution must never fail a sweep
            if not partial:  # a cut sweep's winner is not a tuned default
                st.put(key, fp,
                       {"block_q": best["block_q"],
                        "block_k": best["block_k"]},
                       ms=best["ms"], candidates=len(ok_rows))
                prof.inc_counter("tune.autotune_keys_total")
        results[key] = entry
    if save and st.path:
        st.save()
    reset_lookup_cache()
    runlog.emit("tune", phase="autotune", keys=len(results),
                fingerprint=fp, store=str(st.path))
    return results
