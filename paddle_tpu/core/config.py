"""Typed flags/config and Place abstraction.

Reference: scattered gflags (``framework/scope.cc:23-34``,
``platform/gpu_info.cc:22``, ``operator.cc:28`` check_nan_inf, etc.), Python
``core.init_gflags`` passthrough, and the Place variant
(``platform/place.h:134`` CPUPlace/CUDAPlace/CUDAPinnedPlace).

TPU-native design: one frozen-ish dataclass of flags, settable from env vars
(``PADDLE_TPU_<NAME>``) or programmatically; Places reduce to CPU vs TPU and
resolve to jax devices.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass
class Flags:
    """Global runtime flags (gflags parity, typed)."""

    # verbosity for vlog()
    v: int = 0
    # numeric sanitizer: check NaN/Inf on fetched outputs (FLAGS_check_nan_inf,
    # reference operator.cc:28,725-737). In-graph via jax_debug_nans is separate.
    check_nan_inf: bool = False
    # what the Trainer does with a non-finite step when check_nan_inf is on:
    # "raise" | "skip_step" | "rollback" (see resilience.ResilienceConfig)
    check_nan_inf_policy: str = "raise"
    # consecutive bad steps before the "rollback" policy restores the last
    # good checkpoint
    nan_rollback_after: int = 3
    # print per-step timing/memory like FLAGS_benchmark (executor.cc:399-401)
    benchmark: bool = False
    # mixed precision: bf16 compute for matmul/conv (MXU-native)
    use_bf16_compute: bool = False
    # route unmasked/causal attention through the Pallas flash kernel
    use_flash_attention: bool = False
    # fused Pallas backward for flash attention (False = recomputed XLA vjp)
    flash_fused_bwd: bool = True
    # run the IR verifier between native-program passes (always on under
    # pytest; see paddle_tpu.analysis.verifier / native.passes.PassManager)
    verify_passes: bool = False
    # default seed for program-level RNG when none is given
    seed: int = 0
    # host data pipeline: prefetch depth of the device double-buffer
    # (reference double_buffer reader, operators/reader/buffered_reader.cc)
    prefetch_depth: int = 2
    # directory for profiler traces
    profile_dir: str = "/tmp/paddle_tpu_profile"
    # persistent XLA compilation cache (big TPU compile-time win across
    # runs); empty = disabled. Applied at first Executor/jit use.
    compilation_cache_dir: str = ""
    # kernel autotune store + warmup manifests (paddle_tpu.tune); empty =
    # derived from compilation_cache_dir (<dir>/tune) when that is set
    tune_cache_dir: str = ""
    # consult the autotune store for Pallas kernel block configs
    autotune: bool = False
    # replay the persistent warmup manifest before admitting traffic
    # (serving engines) so a restarted server never compiles under load
    prewarm: bool = False
    # observability: Prometheus exporter bind port (-1 = disabled, 0 = pick
    # an ephemeral port; see paddle_tpu.observability.ObservabilityConfig)
    metrics_port: int = -1
    metrics_host: str = "127.0.0.1"
    # append-only JSONL run-event log path; empty = disabled
    runlog_path: str = ""
    # size-based runlog rollover: rotate the active file when it would
    # exceed this many bytes (0 = never rotate), keeping runlog_keep
    # rotated segments (path.1 .. path.N, oldest dropped)
    runlog_max_bytes: int = 0
    runlog_keep: int = 3
    # per-device peak FLOP/s override for MFU accounting (0 = use the
    # device-kind table in observability/mfu.py)
    peak_flops: float = 0.0
    # peak HBM bandwidth (bytes/s) override for roofline classification
    # (0 = use the device-kind table in observability/mfu.py)
    peak_hbm_bw: float = 0.0
    # roofline cost ledger: capture per-executable cost_analysis() /
    # memory_analysis() at compile time and per-call wall times
    # (observability/roofline.py; /roofline on the exporter)
    roofline: bool = True
    # memory_analysis() peak-HBM capture costs a duplicate AOT compile
    # per executable. "auto" pays it only where the number is a real
    # device peak (non-CPU backends; on TPU the persistent compile cache
    # absorbs the cost); "on"/"off" force it
    roofline_memory: str = "auto"
    # tracing: bounded in-memory span store size (oldest spans evicted;
    # evictions counted under tracing.spans_evicted)
    trace_max_spans: int = 200_000
    # straggler detector: flag a replica/step whose duration exceeds the
    # group median by this ratio (see paddle_tpu.tracing.straggler)
    straggler_ratio: float = 2.5
    # elastic training (see paddle_tpu.resilience.elastic): shrink the mesh
    # past lost devices and keep training instead of crashing
    elastic: bool = False
    # refuse to shrink below this many surviving devices
    elastic_min_devices: int = 1
    # re-expand the mesh at a checkpoint boundary when lost devices return
    elastic_regrow: bool = True
    # consecutive watchdog stalls that escalate to a device-liveness probe
    elastic_escalate_stalls: int = 2
    # serving multi-tenancy defaults (paddle_tpu.serving.admission): a
    # TenantConfig field left None resolves from these
    # per-tenant queued-request quota
    tenant_queue_capacity: int = 64
    # per-tenant queued-payload byte quota (0 = unlimited)
    tenant_byte_quota: int = 0
    # priority class for requests that don't specify one
    tenant_default_class: str = "interactive"
    # lock-order deadlock detection for core.locks instrumented wrappers
    # (always on under pytest and tools/chaos_smoke.py; this flag turns it
    # on elsewhere — env PADDLE_TPU_LOCK_CHECK=1)
    lock_check: bool = False
    # guaranteed batch-class drain share under interactive overload
    tenant_batch_min_share: float = 0.1

    @staticmethod
    def _coerce(value: str, typ):
        if typ is bool:
            return value.lower() in ("1", "true", "yes", "on")
        return typ(value)

    def load_env(self) -> "Flags":
        """Override fields from PADDLE_TPU_<UPPERNAME> env vars."""
        for f in dataclasses.fields(self):
            env = os.environ.get(f"PADDLE_TPU_{f.name.upper()}")
            if env is not None:
                setattr(self, f.name, self._coerce(env, f.type if isinstance(f.type, type) else type(getattr(self, f.name))))
        return self


_flags = Flags().load_env()


def flags() -> Flags:
    return _flags


def set_flags(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_flags, k):
            raise AttributeError(f"unknown flag {k!r}")
        setattr(_flags, k, v)
    if kwargs.get("compilation_cache_dir"):
        apply_compile_cache()


_compile_cache_applied = False


def apply_compile_cache(default_dir: str = "") -> str:
    """Turn on JAX's persistent compilation cache — repeat runs then skip
    XLA compilation (the 15-40 s per program TPU compile cost; the
    reference's op-loop executor had no compile step to cache). The one
    place that sets the directory, and returns the one in effect:
    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself, nothing is set in code), then ``flags().compilation_cache_dir``,
    then ``default_dir`` (``benchmarks/harness.py`` passes one under the
    checkout: the path is part of the cache key, so it is fixed relative
    to the code); with none of them there is no cache.
    Called from set_flags and from every framework entry that jits
    (Executor, Inferencer, DataParallel), so direct-jit workloads honor
    the flag too."""
    global _compile_cache_applied
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    dir_ = env or _flags.compilation_cache_dir or default_dir
    if _compile_cache_applied or not dir_:
        return dir_
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not env:
        jax.config.update("jax_compilation_cache_dir", dir_)
    # jax latches "cache unused" on the first compile it sees; any jit
    # before this call would otherwise disable the cache for the rest of
    # the process
    compilation_cache.reset_cache()
    _compile_cache_applied = True
    return dir_


# ---------------------------------------------------------------------------
# Places. On TPU the real device topology is owned by jax/PJRT; Place is a
# thin user-facing selector kept for API parity with fluid.CPUPlace()/
# fluid.CUDAPlace(i) call sites.
# ---------------------------------------------------------------------------


class Place:
    platform: str = "cpu"

    def device(self):
        import jax

        devs = [d for d in jax.devices() if d.platform == self.platform]
        k = getattr(self, "device_id", 0)
        if not 0 <= k < len(devs):
            # never a silent stand-in: TPUPlace(k) on a host without chip k
            # must not train on the CPU (or on another chip) unannounced
            raise RuntimeError(
                f"{self!r}: no {self.platform} device {k} here — jax.devices() "
                f"is {jax.devices()}")
        return devs[k]

    def __repr__(self):
        return f"{type(self).__name__}()"

    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == getattr(other, "device_id", 0)

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "device_id", 0)))


class CPUPlace(Place):
    platform = "cpu"


class TPUPlace(Place):
    platform = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


def default_place() -> Place:
    """TPU if available, else CPU — mirrors fluid's cuda-if-compiled default."""
    import jax

    if any(d.platform == "tpu" for d in jax.devices()):
        return TPUPlace(0)
    return CPUPlace()
