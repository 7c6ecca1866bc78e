"""Executor — the compiled-program runtime.

Reference: ``paddle/fluid/framework/executor.cc:50-490`` (per-op interpreter
loop with Prepare/RunPreparedContext caching) and the Python wrapper
``python/paddle/fluid/executor.py:256`` (feed/fetch injection, prepared-
program cache).

TPU-native: "preparing" a program = tracing + XLA-compiling it once per
(function, shapes, dtypes); "running" = dispatching the cached executable.
There is no op loop, no scope creation per step, no garbage collector — XLA
buffer assignment plus argument donation replaces the reference's eager
ref-count GC (``executor.cc:336-397``) and the memory_optimize transpiler.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import numpy as np

from paddle_tpu.core import config as cfg
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.observability import runlog


class _InstrumentedCompiled:
    """Wraps a ``jax.jit`` callable to detect executable-cache growth — a
    growth across one call means XLA compiled for a new (shape, dtype)
    signature, so that call's wall time is (approximately) trace + compile
    + first run. Emits ``executor.compiles_total`` / the
    ``executor.compile_seconds`` histogram and a ``compile`` runlog event,
    and feeds the roofline cost ledger (observability/roofline.py): the
    compiling call captures the executable's ``cost_analysis()`` /
    ``memory_analysis()``, every later call books its wall seconds.
    Transparent otherwise: attribute access (``lower``, ``_cache_size``,
    ...) delegates to the wrapped jit object."""

    __slots__ = ("_fn", "_label", "_tracked")

    def __init__(self, fn: Callable, label: str):
        self._fn = fn
        self._label = label
        self._tracked = hasattr(fn, "_cache_size")

    def __call__(self, *args, **kwargs):
        if not self._tracked:
            return self._fn(*args, **kwargs)
        from paddle_tpu.observability import roofline

        ledger_on = roofline.enabled()
        before = self._fn._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if self._fn._cache_size() > before:
            t1 = time.perf_counter()
            dt = t1 - t0
            prof.inc_counter("executor.compiles_total")
            prof.observe("executor.compile_seconds", dt)
            runlog.emit("compile", target=self._label, seconds=round(dt, 6))
            from paddle_tpu.tune import warmup as tune_warmup

            # persist the compiled (label, signature) key so restart
            # tooling knows what to prewarm (no-op when no manifest dir
            # is configured; see paddle_tpu.tune.warmup)
            tune_warmup.record_compile(
                "executor", "executor", target=self._label,
                signature=tune_warmup.tree_signature((args, kwargs)))
            from paddle_tpu import tracing

            # parents under the caller's active span (a trainer step, a
            # serving warmup), so compiles show up inside the step trace
            # the flash kernels this compile traced, with the blocks and the
            # form each resolved to ("512x1024 table resident"), and the
            # expert kernel's calls with their column block ("512 table")
            from paddle_tpu.ops.pallas import moe as moe_kernel
            from paddle_tpu.ops.pallas.flash_attention import take_resolved

            # the ledger's capture compiles ahead of time where the backend
            # reports memory; what the program aliases (a donated state's
            # bytes) goes on the span beside the kernels
            costs = {}
            if ledger_on:
                try:
                    costs = roofline.capture_costs(
                        self._fn, roofline.call_key(self._label, args, kwargs),
                        args, kwargs) or {}
                except Exception:
                    pass
            tracing.record_span("executor.compile", t0, t1, target=self._label,
                                **take_resolved(), **moe_kernel.take_resolved(), **costs)
        elif ledger_on:
            try:
                roofline.observe_call(
                    roofline.call_key(self._label, args, kwargs),
                    time.perf_counter() - t0)
            except Exception:
                pass
        return out

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)


class Executor:
    """Compile-and-run driver bound to a Place.

    Usage (mirrors ``exe = fluid.Executor(place); exe.run(...)``):

        exe = Executor()                       # default: TPU if present
        out = exe.run(step_fn, variables, opt_state, batch)   # jits + caches
    """

    def __init__(self, place: Optional[cfg.Place] = None, max_cache: int = 64):
        self.place = place or cfg.default_place()
        self._device = self.place.device()
        self._cache: Dict[Any, Callable] = {}
        cfg.apply_compile_cache()
        self._max_cache = max_cache

    @property
    def device(self):
        return self._device

    def prepare(
        self,
        fn: Callable,
        donate_argnums: Sequence[int] = (),
        static_argnums: Sequence[int] = (),
        key: Any = None,
    ) -> Callable:
        """Compile-cache a function for this executor's device
        (Executor::Prepare parity)."""
        # key on the function object itself (kept alive by the cache) — an
        # id() key could collide after GC recycles the address
        cache_key = key if key is not None else (fn, tuple(donate_argnums), tuple(static_argnums))
        if cache_key in self._cache:
            # LRU: refresh on hit so hot entries (serving buckets) are never
            # evicted by a burst of cold one-off shapes
            self._cache[cache_key] = self._cache.pop(cache_key)
        else:
            if len(self._cache) >= self._max_cache:
                # LRU eviction: callers passing fresh closures per step would
                # otherwise leak a compiled executable per call
                self._cache.pop(next(iter(self._cache)))
            prof.inc_counter("executor.cache_misses_total")
            label = (str(key[0]) if isinstance(key, tuple) and key
                     else getattr(fn, "__name__", "fn"))
            self._cache[cache_key] = _InstrumentedCompiled(
                jax.jit(
                    fn,
                    donate_argnums=tuple(donate_argnums),
                    static_argnums=tuple(static_argnums),
                    device=self._device,
                ),
                label,
            )
            prof.set_gauge("executor.cache_size", len(self._cache))
        return self._cache[cache_key]

    def run(
        self,
        fn: Callable,
        *args,
        donate_argnums: Sequence[int] = (),
        static_argnums: Sequence[int] = (),
        fetch: bool = False,
        **kwargs,
    ):
        """Run a (cached) compiled function. With ``fetch=True`` outputs are
        device_get'ed to numpy (FetchOpHandle parity) and NaN/Inf-checked when
        flags().check_nan_inf is set (FLAGS_check_nan_inf,
        reference operator.cc:725-737)."""
        compiled = self.prepare(
            fn, donate_argnums=donate_argnums, static_argnums=static_argnums
        )
        with prof.record_event(f"executor.run.{getattr(fn, '__name__', 'fn')}"):
            out = compiled(*args, **kwargs)
        if fetch:
            out = jax.device_get(out)
            if cfg.flags().check_nan_inf:
                self._check_nan_inf(out)
        return out

    @staticmethod
    def _check_nan_inf(tree):
        for leaf in jax.tree_util.tree_leaves(tree):
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating) and not np.all(np.isfinite(arr)):
                raise EnforceError("NaN/Inf detected in fetched output (check_nan_inf)")

    def put(self, tree):
        """Place host arrays on this executor's device (feed parity)."""
        return jax.device_put(tree, self._device)

    def close(self):
        self._cache.clear()
