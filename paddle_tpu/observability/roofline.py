"""Roofline cost-attribution ledger: FLOPs, bytes, and verdicts per kernel.

The reference's DeviceTracer streamed per-op CUDA kernel timings out of
CUPTI; under XLA a "kernel" is a whole compiled executable, and its cost
comes from the compiler, not a driver callback. This module keeps a
process-wide **ledger** of every instrumented executable:

- at compile time it captures ``cost_analysis()`` FLOPs / bytes-accessed
  (through the shared :func:`~paddle_tpu.observability.mfu.cost_analysis_totals`
  accessor, so jax's dict-vs-list drift is absorbed in one place) and —
  best effort — ``memory_analysis()`` peak HBM for the executable;
- at call time it books wall seconds per entry (the compiling call itself
  is excluded: its wall is trace + compile + run, not a kernel sample);
- on read it derives arithmetic intensity (FLOPs/byte), achieved vs. peak
  FLOP/s and bytes/s against ``mfu.PEAK_FLOPS_TABLE`` /
  ``mfu.PEAK_HBM_BW_TABLE``, and a **roofline verdict**:

  - ``compute_bound``  — the FLOP side of max(F/P_f, B/P_b) dominates;
  - ``memory_bound``   — the byte side dominates;
  - ``overhead_bound`` — measured wall exceeds the predicted device time
    by more than ``OVERHEAD_FRAC_THRESHOLD`` (dispatch / host overhead
    dominates the kernel itself).

Entries are keyed ``kernel|shape_bucket|dtype|device_kind`` — the same
``|``-separated scheme as :class:`~paddle_tpu.tune.store.TuneKey`, with the
shape bucket rendered by :func:`paddle_tpu.tune.search.shape_bucket` — so
ledger rows and autotune rows about the same kernel land next to each
other. ``tune.autotune`` orders its sweep memory-bound-first from this
ledger, the exporter serves it at ``/roofline``, the Chrome-trace export
emits its counter tracks, and flight-recorder bundles embed a snapshot.

Everything is best-effort and bounded: capture failures never take down
the instrumented call, and the ledger holds at most ``MAX_ENTRIES`` keys
(oldest evicted). Disable with ``PADDLE_TPU_ROOFLINE=0``; the
``memory_analysis()`` capture (a duplicate AOT compile per executable) is
``PADDLE_TPU_ROOFLINE_MEMORY=auto|on|off`` — ``auto`` skips it on CPU,
where PJRT reports no real peak and compile time would double for
nothing.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from paddle_tpu.core import locks
from paddle_tpu.observability import mfu

__all__ = [
    "COMPUTE_BOUND",
    "MEMORY_BOUND",
    "OVERHEAD_BOUND",
    "OVERHEAD_FRAC_THRESHOLD",
    "RooflineLedger",
    "default_ledger",
    "reset_ledger",
    "enabled",
    "call_key",
    "device_kind",
    "instrument",
    "capture_costs",
    "memory_capture_enabled",
    "note_compile",
    "observe_call",
    "snapshot",
    "summary",
    "history",
    "predicted_seconds",
]

SEP = "|"  # TuneKey.SEP — kernel|shape_bucket|dtype|device_kind

COMPUTE_BOUND = "compute_bound"
MEMORY_BOUND = "memory_bound"
OVERHEAD_BOUND = "overhead_bound"

# wall time more than this fraction above the roofline-predicted device
# time means dispatch/host overhead, not the kernel, is the bottleneck
OVERHEAD_FRAC_THRESHOLD = 0.5

MAX_ENTRIES = 4096

# bounded achieved-rate time series feeding the Chrome-trace counter
# tracks (tracing.export); oldest half dropped on overflow
MAX_HISTORY = 4096


def enabled() -> bool:
    from paddle_tpu.core import config

    return bool(getattr(config.flags(), "roofline", True))


def device_kind() -> str:
    """Sanitized device-kind key segment (same discipline as
    ``tune.autotune.device_kind``: no spaces, no key separator)."""
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:
        return "unknown"
    return str(kind).replace(" ", "_").replace(SEP, "_")


def _bucket_token(args: tuple, kwargs: dict) -> Tuple[str, str]:
    """(shape_bucket, dtype) segments from one call's argument tree: the
    bucket of the largest axis across all array leaves (pow2 bucketing via
    ``tune.search.shape_bucket`` keeps key cardinality bounded under
    ragged traffic) and the first floating dtype seen."""
    from paddle_tpu.tune import search as tune_search

    try:
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:
        leaves = []
    max_dim = 1
    dtype = "-"
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape:
            try:
                max_dim = max(max_dim, max(int(d) for d in shape))
            except (TypeError, ValueError):
                pass
        if dtype == "-":
            dt = getattr(leaf, "dtype", None)
            if dt is not None and "float" in str(dt):
                dtype = str(dt)
    return tune_search.shape_bucket(max_dim), dtype


def call_key(kernel: str, args: tuple = (), kwargs: Optional[dict] = None,
             kind: Optional[str] = None) -> str:
    """Render the 4-part ledger key for one call signature."""
    bucket, dtype = _bucket_token(args, kwargs or {})
    kernel = str(kernel).replace(SEP, "_")
    return SEP.join((kernel, bucket, dtype, kind or device_kind()))


class _Entry:
    __slots__ = ("key", "flops", "bytes", "transcendentals",
                 "peak_hbm_bytes", "arg_bytes", "out_bytes", "bytes_source",
                 "calls", "total_s", "min_s", "last_s")

    def __init__(self, key: str):
        self.key = key
        self.flops = 0.0
        self.bytes = 0.0
        self.transcendentals = 0.0
        self.peak_hbm_bytes: Optional[int] = None
        self.arg_bytes = 0
        self.out_bytes = 0
        self.bytes_source = "cost_analysis"
        self.calls = 0
        self.total_s = 0.0
        self.min_s: Optional[float] = None
        self.last_s: Optional[float] = None


class RooflineLedger:
    """Thread-safe ledger of per-executable static costs + measured walls."""

    def __init__(self, max_entries: int = MAX_ENTRIES):
        self._lock = locks.Lock("observability.roofline")
        self._entries: Dict[str, _Entry] = {}
        self._max = max_entries
        # (t_pc_us, kernel, achieved_flops_per_s, achieved_bytes_per_s)
        self._history: List[Tuple[float, str, float, float]] = []

    def _entry(self, key: str) -> _Entry:
        # caller holds the lock
        e = self._entries.get(key)
        if e is None:
            if len(self._entries) >= self._max:
                self._entries.pop(next(iter(self._entries)))
            e = self._entries[key] = _Entry(key)
        return e

    def note_compile(self, key: str, flops: float, bytes_accessed: float,
                     transcendentals: float = 0.0,
                     peak_hbm_bytes: Optional[int] = None,
                     arg_bytes: int = 0, out_bytes: int = 0) -> None:
        """Record one executable's static costs. A zero bytes-accessed
        (backends without a byte model) falls back to argument + output
        sizes so arithmetic intensity stays finite, with the source
        labeled honestly."""
        with self._lock:
            e = self._entry(key)
            e.flops = float(flops)
            e.transcendentals = float(transcendentals)
            e.arg_bytes = int(arg_bytes)
            e.out_bytes = int(out_bytes)
            if bytes_accessed and bytes_accessed > 0:
                e.bytes = float(bytes_accessed)
                e.bytes_source = "cost_analysis"
            else:
                e.bytes = float(max(arg_bytes + out_bytes, 1))
                e.bytes_source = "arg_out_estimate"
            if peak_hbm_bytes:
                e.peak_hbm_bytes = int(peak_hbm_bytes)

    def observe(self, key: str, wall_s: float) -> None:
        """Book one non-compiling call's wall seconds against an entry."""
        if wall_s <= 0:
            return
        with self._lock:
            e = self._entry(key)
            e.calls += 1
            e.total_s += wall_s
            e.last_s = wall_s
            e.min_s = wall_s if e.min_s is None else min(e.min_s, wall_s)
            if e.flops > 0 or e.bytes > 0:
                if len(self._history) >= MAX_HISTORY:
                    del self._history[: MAX_HISTORY // 2]
                self._history.append(
                    (time.perf_counter() * 1e6, key.split(SEP, 1)[0],
                     e.flops / wall_s, e.bytes / wall_s))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self) -> List[str]:
        with self._lock:
            return list(self._entries)

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            del self._history[:]

    def history(self) -> List[Tuple[float, str, float, float]]:
        """Achieved-rate samples ``(t_pc_us, kernel, flops_per_s,
        bytes_per_s)``, oldest first — the Chrome counter-track feed."""
        with self._lock:
            return list(self._history)

    def snapshot(self) -> List[dict]:
        """Derived rows: intensity, achieved vs. peak, verdicts. Pure
        read; every row carries a verdict (the acceptance contract for
        ``/roofline``)."""
        with self._lock:
            entries = [(e.key, e.flops, e.bytes, e.transcendentals,
                        e.peak_hbm_bytes, e.bytes_source,
                        e.calls, e.total_s, e.min_s, e.last_s)
                       for e in self._entries.values()]
        rows = []
        for (key, flops, bytes_, transc, peak_hbm, bytes_source,
             calls, total_s, min_s, last_s) in entries:
            parts = key.split(SEP)
            kind = parts[3] if len(parts) == 4 else device_kind()
            peak_f = mfu.peak_flops_for_kind(kind)
            peak_b = mfu.peak_hbm_bw_for_kind(kind)
            intensity = flops / bytes_ if bytes_ > 0 else 0.0
            t_flops = flops / peak_f if peak_f else 0.0
            t_bytes = bytes_ / peak_b if peak_b else 0.0
            t_pred = max(t_flops, t_bytes)
            row = {
                "key": key,
                "kernel": parts[0] if parts else key,
                "shape_bucket": parts[1] if len(parts) > 1 else "-",
                "dtype": parts[2] if len(parts) > 2 else "-",
                "device_kind": kind,
                "flops": flops,
                "bytes": bytes_,
                "transcendentals": transc,
                "bytes_source": bytes_source,
                "peak_hbm_bytes": peak_hbm,
                "arithmetic_intensity": intensity,
                "predicted_device_s": t_pred,
                "calls": calls,
                "total_s": total_s,
                "min_s": min_s,
                "last_s": last_s,
            }
            # static classification: which roofline slope the kernel sits
            # under at this intensity
            static = (COMPUTE_BOUND if t_flops >= t_bytes and flops > 0
                      else MEMORY_BOUND)
            wall = min_s  # best wall strips scheduler noise
            if wall and wall > 0:
                row["achieved_flops_per_s"] = flops / wall
                row["achieved_bytes_per_s"] = bytes_ / wall
                row["flops_frac_of_peak"] = (
                    flops / wall / peak_f if peak_f else None)
                row["bw_frac_of_peak"] = (
                    bytes_ / wall / peak_b if peak_b else None)
                overhead = max(0.0, (wall - t_pred) / wall)
                row["overhead_frac"] = overhead
                row["verdict"] = (OVERHEAD_BOUND
                                  if overhead > OVERHEAD_FRAC_THRESHOLD
                                  else static)
            else:
                # compiled but never re-called: classify on the static
                # sides alone; there is no honest overhead number yet
                row["achieved_flops_per_s"] = None
                row["achieved_bytes_per_s"] = None
                row["flops_frac_of_peak"] = None
                row["bw_frac_of_peak"] = None
                row["overhead_frac"] = 0.0
                row["verdict"] = static
            rows.append(row)
        rows.sort(key=lambda r: r["key"])
        return rows

    def summary(self) -> dict:
        """Verdict histogram + totals for bench JSON / flight bundles."""
        rows = self.snapshot()
        verdicts: Dict[str, int] = {}
        for r in rows:
            verdicts[r["verdict"]] = verdicts.get(r["verdict"], 0) + 1
        return {
            "entries": len(rows),
            "verdicts": verdicts,
            "total_flops": sum(r["flops"] for r in rows),
            "total_bytes": sum(r["bytes"] for r in rows),
            "calls": sum(r["calls"] for r in rows),
        }


_default = RooflineLedger()


def default_ledger() -> RooflineLedger:
    return _default


def reset_ledger() -> None:
    _default.reset()


def snapshot() -> List[dict]:
    return _default.snapshot()


def summary() -> dict:
    return _default.summary()


def history() -> List[Tuple[float, str, float, float]]:
    return _default.history()


def note_compile(key: str, **kw) -> None:
    _default.note_compile(key, **kw)


def observe_call(key: str, wall_s: float) -> None:
    _default.observe(key, wall_s)


def predicted_seconds(flops: float, bytes_accessed: float,
                      kind: Optional[str] = None) -> Optional[float]:
    """Roofline-predicted device seconds max(F/P_f, B/P_b); None when
    neither peak is known for the device kind."""
    kind = kind or device_kind()
    peak_f = mfu.peak_flops_for_kind(kind)
    peak_b = mfu.peak_hbm_bw_for_kind(kind)
    t_f = flops / peak_f if peak_f else None
    t_b = bytes_accessed / peak_b if peak_b else None
    if t_f is None and t_b is None:
        return None
    return max(t_f or 0.0, t_b or 0.0)


def memory_capture_enabled() -> bool:
    """Whether :func:`capture_costs` should AOT-compile for
    ``memory_analysis()``. The duplicate compile is the price of the peak
    number; ``flags().roofline_memory`` is ``auto`` (pay it only on
    backends that report a real device peak — CPU PJRT reports none and
    we estimate sizes anyway), ``on``, or ``off``."""
    from paddle_tpu.core import config

    v = str(getattr(config.flags(), "roofline_memory", "auto")).lower()
    if v in ("1", "on", "true", "yes"):
        return True
    if v in ("0", "off", "false", "no"):
        return False
    try:
        import jax

        return jax.default_backend() != "cpu"
    except Exception:
        return False


def _arg_nbytes(args: tuple, kwargs: dict) -> int:
    try:
        import jax

        return sum(int(getattr(leaf, "nbytes", 0) or 0)
                   for leaf in jax.tree_util.tree_leaves((args, kwargs)))
    except Exception:
        return 0


def capture_costs(jitted, key: str, args: tuple, kwargs: dict) -> Optional[dict]:
    """Capture static costs for the executable a jit call just compiled:
    re-lower for ``cost_analysis()`` (a trace, no compile) and — when
    :func:`memory_capture_enabled` — AOT-compile for ``memory_analysis()``
    peak HBM. The AOT compile normally hits the persistent compilation
    cache (``flags().compilation_cache_dir``); when it does not, the
    duplicate compile is the price of the peak number — which is why the
    ``auto`` policy skips it on CPU, where there is no real peak to buy.
    Failures and absent analyses degrade to a cost-only entry, never an
    error. Returns ``{"alias_bytes": n}`` where the compile was made: the
    bytes of outputs that take a donated input's buffer (``args`` may be
    arrays the call consumed: lowering reads their shapes only)."""
    try:
        lowered = jitted.lower(*args, **kwargs)
    except Exception:
        return None
    totals = mfu.cost_analysis_totals(lowered)
    peak_hbm = None
    out_bytes = 0
    span_attrs = None
    if memory_capture_enabled():
        try:
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            if mem is not None:
                def _get(attr):
                    v = getattr(mem, attr, None)
                    try:
                        return int(v) if v is not None else 0
                    except (TypeError, ValueError):
                        return 0

                out_bytes = _get("output_size_in_bytes")
                span_attrs = {"alias_bytes": _get("alias_size_in_bytes")}
                peak_hbm = _get("peak_memory_in_bytes")
                if not peak_hbm:
                    # backends reporting no peak: reconstruct like
                    # tracing.memory.record_executable_memory does
                    peak_hbm = (_get("argument_size_in_bytes") + out_bytes
                                + _get("temp_size_in_bytes"))
        except Exception:
            pass
    note_compile(
        key,
        flops=totals["flops"],
        bytes_accessed=totals["bytes"],
        transcendentals=totals["transcendentals"],
        peak_hbm_bytes=peak_hbm or None,
        arg_bytes=_arg_nbytes(args, kwargs),
        out_bytes=out_bytes,
    )
    return span_attrs


class InstrumentedJit:
    """Wrap a ``jax.jit`` callable so every compile lands its costs in the
    ledger and every subsequent call books wall seconds. The decode
    engine's directly-jitted step functions use this; ``Executor``'s
    ``_InstrumentedCompiled`` calls the same hooks for everything routed
    through ``prepare()``. Transparent otherwise (``lower``,
    ``_cache_size``, ... delegate)."""

    __slots__ = ("_fn", "_kernel", "_tracked", "_kind")

    def __init__(self, fn: Callable, kernel: str):
        self._fn = fn
        self._kernel = kernel
        self._tracked = hasattr(fn, "_cache_size")
        self._kind: Optional[str] = None

    def __call__(self, *args, **kwargs):
        if not (self._tracked and enabled()):
            return self._fn(*args, **kwargs)
        before = self._fn._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        t1 = time.perf_counter()
        try:
            if self._kind is None:
                self._kind = device_kind()
            key = call_key(self._kernel, args, kwargs, kind=self._kind)
            if self._fn._cache_size() > before:
                capture_costs(self._fn, key, args, kwargs)
            else:
                observe_call(key, t1 - t0)
        except Exception:
            pass  # telemetry must never take the step down
        return out

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)


def instrument(kernel: str, fn: Callable) -> Callable:
    """Ledger-instrument one jitted callable (no-op wrapper for objects
    without a ``_cache_size``)."""
    return InstrumentedJit(fn, kernel)
