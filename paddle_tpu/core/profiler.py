"""Profiling: host-side RAII annotations + jax.profiler device traces.

Reference: ``platform/profiler.h:73-91`` (RecordEvent/RecordBlock RAII),
``platform/profiler.cc:476`` aggregation tables, CUPTI DeviceTracer
(``platform/device_tracer.h:49-103``), Python context managers
``python/paddle/fluid/profiler.py:125-221``.

TPU-native mapping: device-side tracing is jax.profiler (XPlane/Perfetto,
viewable in TensorBoard/xprof); host-side step breakdown keeps the RAII
annotation idiom via ``record_event``, which feeds a host aggregation
table and opens a ``paddle_tpu.tracing`` span: that span is the one place
that writes a TraceAnnotation into device traces and the one store that
holds host spans (``tracing.export_chrome_trace`` writes the timeline).
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Iterator, Optional

import jax

_events: dict[str, list[float]] = defaultdict(list)
_enabled: bool = False

# -- counters/gauges: monotonically-increasing totals and last-value gauges
# for long-running services (the serving engine's queue depth, batch
# occupancy, timeout totals). Unlike record_event these are always on,
# and since paddle_tpu.observability they are thin delegates into the
# typed labeled registry (observability/metrics.py) that the Prometheus
# exporter scrapes — the flat counters()/gauges() dicts remain as the
# legacy aggregate view (labeled children summed / last-write).


_the_registry = None


def _registry():
    # looked up once: an ``import`` statement a write is a third of the write
    global _the_registry
    if _the_registry is None:
        from paddle_tpu.observability import metrics as obs_metrics

        _the_registry = obs_metrics.default_registry()
    return _the_registry


def inc_counter(name: str, value: float = 1.0, labels: dict | None = None) -> None:
    """Add to a named monotonic counter (thread-safe)."""
    _registry().inc(name, value, labels=labels)


def set_gauge(name: str, value: float, labels: dict | None = None) -> None:
    """Set a named gauge to its latest value (thread-safe)."""
    _registry().set(name, value, labels=labels)


def observe(name: str, value: float, labels: dict | None = None) -> None:
    """Record one observation into a named histogram (thread-safe).
    Declare non-default bucket edges up front via
    ``observability.default_registry().histogram(name, buckets=...)``."""
    _registry().observe(name, value, labels=labels)


def counters() -> dict[str, float]:
    """Snapshot of all counters (labeled children summed per family)."""
    return _registry().flat_counters()


def gauges() -> dict[str, float]:
    """Snapshot of all gauges (most recent write per family)."""
    return _registry().flat_gauges()


def reset_metrics() -> None:
    """Clear counters, gauges, and histograms (test isolation)."""
    _registry().reset()


@contextlib.contextmanager
def record_event(name: str) -> Iterator[None]:
    """RAII host annotation (RecordEvent parity): a ``tracing`` span of that
    name, and a row of the aggregation table while the profiler is enabled."""
    from paddle_tpu.tracing import context as spans  # it imports this module

    t0 = time.perf_counter()
    with spans.start_span(name):
        yield
    if _enabled:
        _events[name].append(time.perf_counter() - t0)


def enable_profiler() -> None:
    global _enabled
    _enabled = True
    _events.clear()


def disable_profiler() -> dict[str, dict[str, float]]:
    """Stop host profiling and return the aggregation table
    (name → {calls, total_s, mean_s, min_s, max_s}), mirroring the sorted
    summary of reference ``profiler.cc:476``. Clears the recorded events so
    the next profiling window starts empty."""
    global _enabled
    _enabled = False
    table = {}
    for name, times in _events.items():
        table[name] = {
            "calls": len(times),
            "total_s": sum(times),
            "mean_s": sum(times) / len(times),
            "min_s": min(times),
            "max_s": max(times),
        }
    _events.clear()
    return table


def summary_string(table: Optional[dict] = None) -> str:
    table = table if table is not None else disable_profiler()
    rows = sorted(table.items(), key=lambda kv: -kv[1]["total_s"])
    lines = [f"{'Event':40s} {'Calls':>8s} {'Total(s)':>10s} {'Mean(ms)':>10s}"]
    for name, s in rows:
        lines.append(f"{name:40s} {s['calls']:8d} {s['total_s']:10.4f} {s['mean_s'] * 1e3:10.3f}")
    return "\n".join(lines)


def step_breakdown(table: Optional[dict] = None) -> dict[str, float]:
    """Mean seconds per phase for the canonical step phases
    (feed/compute/fetch/...), for the benchmark's per-step breakdown
    table (reference ``fluid_benchmark.py`` profile output)."""
    table = table if table is not None else {
        name: {"mean_s": sum(ts) / len(ts)} for name, ts in _events.items() if ts
    }
    return {name: s["mean_s"] for name, s in table.items()}


@contextlib.contextmanager
def profiler(log_dir: Optional[str] = None) -> Iterator[None]:
    """Device-trace context manager (fluid.profiler.profiler parity):
    captures a jax.profiler trace (XPlane) into ``log_dir`` and host events."""
    from paddle_tpu.core import config

    log_dir = log_dir or config.flags().profile_dir
    enable_profiler()
    with jax.profiler.trace(log_dir):
        yield
    from paddle_tpu.core import logging as ptlog
    from paddle_tpu.tracing import export

    timeline = export.export_chrome_trace(os.path.join(log_dir, "timeline.chrome.json"))

    ptlog.info(
        "profiler trace written to %s (host timeline: %s)\n%s",
        log_dir, timeline, summary_string(),
    )


def start_profiler(log_dir: Optional[str] = None) -> None:
    from paddle_tpu.core import config

    enable_profiler()
    jax.profiler.start_trace(log_dir or config.flags().profile_dir)


def stop_profiler() -> dict:
    jax.profiler.stop_trace()
    return disable_profiler()


def reset_profiler() -> None:
    """Clear recorded host events (reference ``profiler.py:104`` — works
    for start/stop/``profiler``, not the CUDA runtime profiler). The spans
    live in ``paddle_tpu.tracing``: ``tracing.reset_tracing()`` clears them."""
    _events.clear()


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Reference ``profiler.py:39`` is a thin shim over the CUDA runtime
    profiler — there is no CUDA on TPU, so this delegates to the host/XLA
    profiler (``profiler(log_dir=...)``) and warns once, keeping ported
    scripts running with equivalent (better: device-aware) tracing."""
    import warnings

    warnings.warn(
        "cuda_profiler: no CUDA runtime on TPU; delegating to the XLA "
        "profiler (see paddle_tpu.core.profiler.profiler)",
        stacklevel=2,
    )
    with profiler(log_dir=output_file):
        yield
