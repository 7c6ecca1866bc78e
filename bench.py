"""Benchmark entry — ALWAYS prints exactly one JSON line on stdout.

Headline: ResNet-50 training throughput (images/sec) on synthetic 224x224
data — the ``benchmark/fluid`` ResNet config (reference
``benchmark/fluid/models/resnet.py``; examples/sec metric discipline at
``fluid_benchmark.py:295-301``). The JSON also carries Transformer training
tokens/sec and computed MFU for both (model FLOPs from the compiled
executable's cost analysis / chip peak).

``vs_baseline`` is against the strongest published in-tree reference number
for ResNet-50 training: 84.08 img/s (2S Xeon 6148,
``benchmark/IntelOptimizedPaddle.md:41-45``; no GPU Fluid ResNet-50 number is
published in-tree — see BASELINE.md).

The parent process imports no JAX: it runs the measurement once in a child
subprocess under a wall-clock budget and prints the child's JSON line. There
is no retry on another backend and no smaller config: a child that yields no
result makes the exit code non-zero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC = 84.08  # ResNet-50 train bs256, 2S Xeon 6148 (in-tree)

# The MFU-representative LM config (the 512-wide default underfills the MXU).
# Single-sourced: chip_smoke.py and the chip-compile tests run THIS config —
# retune it here and every artifact stays comparable.
LM_LARGE_KWARGS = dict(
    seq_len=2048, d_model=1024, d_inner=4096, num_heads=16, n_layers=12,
    max_len=2048,
    # one scanned body -> one Mosaic flash fwd+bwd compile instead of 12
    scan_layers=True,
)
# North-star anchor (an estimate, not a measurement): 0.8x of one V100's
# share of an 8xV100 fluid ResNet-50 run ~= 240-265 img/s/chip; midpoint
# used for self-grading.
V100_TARGET_IMG_PER_SEC = 252.0

_GOODPUT = None


def _goodput_tracker():
    """Process-wide goodput split: _bench_step charges measured train time
    as good, failed sections as bad (lazy so --cpu children configure jax
    before any paddle_tpu import)."""
    global _GOODPUT
    if _GOODPUT is None:
        from paddle_tpu.observability.mfu import GoodputTracker

        _GOODPUT = GoodputTracker()
    return _GOODPUT


def _peak_flops(device_kind: str):
    """Peak bf16 FLOP/s for a device kind — single-sourced from
    observability.mfu (one table for bench, trainer MFU gauge, exporter)."""
    from paddle_tpu.observability import mfu as obs_mfu

    return obs_mfu.peak_flops_for_kind(device_kind)


def _cost_flops(compiled) -> float:
    """Per-step model FLOPs from the compiled executable's cost analysis."""
    from paddle_tpu.observability.mfu import cost_flops

    return cost_flops(compiled)


def _mem_stats(compiled):
    """Peak-HBM + donation stats from the compiled executable
    (VERDICT r4 #2; reference logs memory per iteration under
    FLAGS_benchmark, ``paddle/fluid/framework/executor.cc:399-401``).
    ``alias_size_in_bytes`` > 0 proves argument donation took effect —
    without it a train step holds params + opt state twice."""
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0]
        return {
            "peak_hbm_bytes": int(ma.peak_memory_in_bytes),
            "argument_size_bytes": int(ma.argument_size_in_bytes),
            "temp_size_bytes": int(ma.temp_size_in_bytes),
            "donated_alias_bytes": int(ma.alias_size_in_bytes),
        }
    except Exception:
        return None


def _bench_step(spec, batch_size: int, warmup: int, iters: int, rng_seed: int = 0):
    """Compile + time one model's train step; returns
    (sec/step, flops/step, mem_stats_dict_or_None). Feeds the metric
    registry (bench.* families) and the goodput tracker as it goes, so the
    JSON telemetry fields come from the same source the exporter scrapes."""
    t_begin = time.perf_counter()
    try:
        return _bench_step_inner(spec, batch_size, warmup, iters, rng_seed)
    except Exception:
        # the wall time burned by a failing section is badput, not silence
        _goodput_tracker().record_bad(
            time.perf_counter() - t_begin, "bench_failure")
        raise


def _bench_step_inner(spec, batch_size: int, warmup: int, iters: int,
                      rng_seed: int = 0):
    import jax
    import numpy as np

    from paddle_tpu import tracing
    from paddle_tpu.core import profiler as prof

    rng = np.random.RandomState(rng_seed)
    with tracing.start_span("bench.data_wait", model=spec.name):
        batch = spec.synth_batch(batch_size, rng)
    variables = spec.model.init(0, *batch)
    opt = spec.optimizer()
    opt_state = opt.create_state(variables.params)
    step = jax.jit(opt.minimize(spec.model), donate_argnums=(0, 1))
    with tracing.start_span("bench.h2d", model=spec.name):
        dev_batch = tuple(jax.device_put(np.asarray(b)) for b in batch)
    key = jax.random.PRNGKey(rng_seed)  # dropout etc. in train mode

    lowered = step.lower(variables, opt_state, *dev_batch, rng=key)
    t_c = time.perf_counter()
    with tracing.start_span("bench.compile", model=spec.name):
        compiled = lowered.compile()
    dt_c = time.perf_counter() - t_c
    prof.inc_counter("bench.compiles_total")
    prof.inc_counter("bench.compile_seconds_total", dt_c)
    prof.observe("bench.compile_seconds", dt_c)
    flops = _cost_flops(compiled)
    mem = _mem_stats(compiled)
    # compile-time HBM plan into device.hbm.executable_* gauges
    tracing.record_executable_memory(compiled, f"bench.{spec.name}")

    v, o = variables, opt_state
    out = None
    with tracing.start_span("bench.step", model=spec.name, warmup=True):
        for _ in range(warmup):
            out = compiled(v, o, *dev_batch, rng=key)
            v, o = out.variables, out.opt_state
        if out is not None:
            jax.block_until_ready(out)

    t0 = time.perf_counter()
    with tracing.start_span("bench.step", model=spec.name):
        for _ in range(iters):
            out = compiled(v, o, *dev_batch, rng=key)
            v, o = out.variables, out.opt_state
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    prof.inc_counter("bench.examples_total", batch_size * iters)
    prof.inc_counter("bench.train_seconds_total", dt * iters)
    prof.observe("bench.step_seconds", dt)
    _goodput_tracker().record_good(dt * iters)
    return dt, flops, mem


def child_main(tiny: bool, force_cpu: bool = False) -> None:
    """Runs measurements, prints ONE JSON line on stdout."""
    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")

    from paddle_tpu import models
    from paddle_tpu.core.config import apply_compile_cache, set_flags

    apply_compile_cache(default_dir=os.path.join(_REPO, ".jax_cache"))

    deadline = time.monotonic() + float(os.environ.get("PT_BENCH_CHILD_BUDGET_S", "420"))
    dev = jax.devices()[0]
    if dev.platform != "cpu":
        # TPU-native training mode: bf16 matmul/conv on the MXU + the Pallas
        # flash kernel wherever attention is mask-free/causal
        set_flags(use_bf16_compute=True, use_flash_attention=True)
    peak = _peak_flops(dev.device_kind)
    result = {
        "metric": "resnet50_train_images_per_sec",
        "value": 0.0,
        "unit": "images/sec",
        "vs_baseline": 0.0,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "notes": [],
    }
    if tiny:
        result["notes"].append("cpu_fallback_tiny_config")

    def refresh_telemetry():
        """Registry-sourced run accounting (same counters the Prometheus
        exporter scrapes): aggregate examples/sec over every timed section,
        total compile seconds, goodput split, and the best model MFU."""
        from paddle_tpu.core import profiler as prof

        c = prof.counters()
        train_s = c.get("bench.train_seconds_total", 0.0)
        if train_s > 0:
            result["examples_per_sec"] = round(
                c.get("bench.examples_total", 0.0) / train_s, 2)
        result["compile_seconds"] = round(
            c.get("bench.compile_seconds_total", 0.0), 3)
        result["goodput_frac"] = round(_goodput_tracker().goodput_frac(), 4)
        mfus = [v for k, v in result.items()
                if k.endswith("_mfu") and isinstance(v, (int, float))]
        if mfus:
            result["mfu"] = max(mfus)
        # where the wall time went, from the tracing spans the timed
        # sections open (bench.* phases, cumulative across all models)
        from paddle_tpu import tracing

        totals = tracing.phase_totals(
            ("bench.data_wait", "bench.h2d", "bench.compile", "bench.step"))
        result["phase_breakdown"] = {
            "data_wait_s": round(totals.get("bench.data_wait", 0.0), 3),
            "h2d_s": round(totals.get("bench.h2d", 0.0), 3),
            "compile_s": round(totals.get("bench.compile", 0.0), 3),
            "step_s": round(totals.get("bench.step", 0.0), 3),
        }

    def checkpoint_result():
        """Interim JSON after each section: if the wall-clock budget kills
        this child mid-run, the parent still salvages the newest line."""
        refresh_telemetry()
        print(json.dumps(result), flush=True)

    # --- ResNet-50 (sweep bs; report the best stable throughput) ---
    sweep = (16,) if tiny else tuple(
        int(b) for b in os.environ.get("PT_BENCH_RESNET_BS", "64,128,256").split(",")
    )
    iters = 3 if tiny else 10
    try:
        spec = models.get_model("resnet", dataset="flowers", depth=50, class_dim=1000)
        best = None
        for bs in sweep:
            if best is not None and time.monotonic() > deadline - 60:
                result["notes"].append(f"resnet_bs{bs}_skipped_budget")
                continue
            try:
                dt, flops, mem = _bench_step(spec, bs, warmup=1, iters=iters)
            except Exception as e:  # OOM at large bs ends the sweep
                result["notes"].append(f"resnet_bs{bs}_failed: {type(e).__name__}"[:120])
                break
            ips = bs / dt
            result[f"resnet_imgs_per_sec_bs{bs}"] = round(ips, 2)
            if mem:
                result[f"resnet_peak_hbm_bytes_bs{bs}"] = mem["peak_hbm_bytes"]
                result[f"resnet_donated_alias_bytes_bs{bs}"] = mem["donated_alias_bytes"]
            if best is None or ips > best[0]:
                best = (ips, bs, dt, flops)
                result["value"] = round(ips, 2)
                result["resnet_batch_size"] = bs
                result["vs_baseline"] = round(ips / BASELINE_IMG_PER_SEC, 3)
                result["vs_v100_target"] = round(ips / V100_TARGET_IMG_PER_SEC, 3)
                if peak and flops:
                    result["resnet_mfu"] = round(flops / dt / peak, 4)
            checkpoint_result()
        if best is None:
            raise RuntimeError("resnet sweep produced no result")
        ips, bs, dt, flops = best
        result["value"] = round(ips, 2)
        result["resnet_batch_size"] = bs
        result["vs_baseline"] = round(ips / BASELINE_IMG_PER_SEC, 3)
        result["vs_v100_target"] = round(ips / V100_TARGET_IMG_PER_SEC, 3)
        if peak and flops:
            result["resnet_mfu"] = round(flops / dt / peak, 4)
        print(f"resnet50: {result['value']} img/s (bs={bs})", file=sys.stderr)
    except Exception as e:  # keep going — transformer number still valuable
        result["notes"].append(f"resnet_failed: {type(e).__name__}: {e}"[:300])
    checkpoint_result()

    # --- larger LM (d_model=1024, the MFU-representative config: the
    # default 512-wide LM is too small to fill the MXU). Second in value
    # order. ---
    if dev.platform != "cpu" and not tiny and time.monotonic() < deadline:
        try:
            lspec = models.get_model("transformer_lm", **LM_LARGE_KWARGS)
            dt, flops, mem = _bench_step(lspec, 4, warmup=1, iters=6)
            result["lm_large_tokens_per_sec"] = round(4 * 2048 / dt, 1)
            if peak and flops:
                result["lm_large_mfu"] = round(flops / dt / peak, 4)
            if mem:
                result["lm_large_peak_hbm_bytes"] = mem["peak_hbm_bytes"]
                result["lm_large_donated_alias_bytes"] = mem["donated_alias_bytes"]
            print(f"lm_large: {result['lm_large_tokens_per_sec']} tok/s", file=sys.stderr)
        except Exception as e:
            result["notes"].append(f"lm_large_failed: {type(e).__name__}: {e}"[:300])
        checkpoint_result()

    # --- Flash attention A/B (fused Pallas fwd+bwd vs composed XLA) ---
    def bench_flash(T: int, iters: int = 8):
        import jax.numpy as jnp
        import numpy as np

        from paddle_tpu.ops.pallas import flash_attention
        from paddle_tpu.ops.pallas.flash_attention import _reference_attention

        B, H, d2 = (4, 16, 64) if T <= 2048 else (1, 16, 64)
        rng = np.random.RandomState(0)
        mk = lambda: jax.device_put(
            jnp.asarray(rng.randn(B, H, T, d2).astype(np.float32)).astype(jnp.bfloat16)
        )
        q, k, v = mk(), mk(), mk()

        def time_grad(fn):
            g = jax.jit(jax.grad(lambda a, b, c: fn(a, b, c).astype(jnp.float32).sum(), (0, 1, 2)))
            out = g(q, k, v)
            float(jax.device_get(out[0][0, 0, 0, 0]))  # real sync (see _bench_step)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = g(q, k, v)
            float(jax.device_get(out[0][0, 0, 0, 0]))
            return (time.perf_counter() - t0) / iters

        t_flash = time_grad(lambda a, b, c: flash_attention(a, b, c, causal=True))
        result[f"flash_fwdbwd_ms_t{T}"] = round(t_flash * 1e3, 3)
        # the composed reference materializes [B,H,T,T] and can OOM at long
        # T — the flash number above must survive that
        t_xla = time_grad(lambda a, b, c: _reference_attention(a, b, c, True, d2 ** -0.5))
        return t_flash, t_xla

    if dev.platform != "cpu" and not tiny:
        for T in (1024, 8192):
            if time.monotonic() > deadline:
                result["notes"].append(f"flash_t{T}_skipped_budget")
                continue
            try:
                t_flash, t_xla = bench_flash(T)
                result[f"flash_speedup_vs_xla_t{T}"] = round(t_xla / t_flash, 3)
                print(f"flash T={T}: {t_flash*1e3:.2f}ms vs xla {t_xla*1e3:.2f}ms", file=sys.stderr)
            except Exception as e:
                result["notes"].append(f"flash_t{T}_failed: {type(e).__name__}: {e}"[:300])
        checkpoint_result()

    # --- decode path: generate() tokens/s, prefill vs decode split.
    # generate(mnt=1) ~= prefill-only; generate(mnt=1+N) adds N scan steps —
    # the difference isolates steady-state decode (reference metric
    # discipline: examples/sec, fluid_benchmark.py:295-301). The tiny (CPU
    # fallback) variant keeps the key contract alive at toy sizes. ---
    if time.monotonic() < deadline:
        try:
            import functools

            import jax.numpy as jnp
            import numpy as np

            from paddle_tpu.models import transformer_lm

            if tiny:
                dspec = models.get_model(
                    "transformer_lm", seq_len=64, vocab=512, d_model=64,
                    d_inner=128, num_heads=4, n_layers=2,
                )
                Tp, N, bss = 16, 8, (1, 2)
            else:
                # scan_layers: the decode jit compiles one scanned layer
                # body instead of L unrolled ones per (bs, mnt) variant
                dspec = models.get_model("transformer_lm", seq_len=512,
                                         scan_layers=True)
                Tp, N, bss = 128, 64, (1, 8, 32)
            dcfg = dspec.extra["cfg"]
            drng = np.random.RandomState(0)
            dvars = dspec.model.init(0, *dspec.synth_batch(1, drng))
            # artifacts stay self-describing: the decode config changed to
            # scan_layers in r4 — numbers are not comparable across the flag
            result["decode_scan_layers"] = bool(dcfg.get("scan_layers"))
            # stack once outside jit (closed over as a constant): per-call
            # re-stacking would copy the full parameter set per decode
            dstacked = (
                transformer_lm.stack_decode_params(dvars, dcfg)
                if dcfg.get("scan_layers") else None
            )

            def time_fn(fn, fetch, reps=3):
                """Shared timing discipline for every decode-path variant:
                warmup call, then reps timed calls, device_get sync via
                ``fetch`` (see _bench_step for why not block_until_ready)."""
                o = fn()
                fetch(o)
                t0 = time.perf_counter()
                for _ in range(reps):
                    o = fn()
                fetch(o)
                return (time.perf_counter() - t0) / reps

            def time_gen(bs, mnt, **gen_kw):
                prompt = jnp.asarray(
                    drng.randint(1, dcfg["vocab"], size=(bs, Tp)).astype(np.int32)
                )
                fn = jax.jit(functools.partial(
                    transformer_lm.generate, max_new_tokens=mnt, cfg=dcfg,
                    stacked_params=dstacked, **gen_kw,
                ))
                return time_fn(
                    lambda: fn(dvars, prompt),
                    lambda o: int(jax.device_get(o[0, -1])),
                )

            for bs in bss:
                if time.monotonic() > deadline - 30:
                    result["notes"].append(f"decode_bs{bs}_skipped_budget")
                    continue
                t_prefill = time_gen(bs, 1)
                t_full = time_gen(bs, 1 + N)
                t_dec = t_full - t_prefill
                if t_dec <= t_prefill * 0.05:
                    # decode delta is inside the prefill timing noise —
                    # an absurd tok/s here would pollute the artifact
                    result["notes"].append(f"decode_bs{bs}_noise_dominated")
                    continue
                result[f"decode_tok_per_sec_bs{bs}"] = round(bs * N / t_dec, 1)
                result[f"prefill_ms_bs{bs}"] = round(t_prefill * 1e3, 2)
                print(
                    f"decode bs={bs}: {result[f'decode_tok_per_sec_bs{bs}']} tok/s "
                    f"(prefill {result[f'prefill_ms_bs{bs}']} ms)", file=sys.stderr,
                )
            # bf16-cache A/B at bs=8: decode streams the whole cache per
            # step, so halving its bytes is the decode-throughput lever
            if not tiny and time.monotonic() < deadline - 30:
                t_p16 = time_gen(8, 1, cache_dtype=jnp.bfloat16)
                t_f16 = time_gen(8, 1 + N, cache_dtype=jnp.bfloat16)
                if t_f16 - t_p16 > t_p16 * 0.05:
                    result["decode_tok_per_sec_bs8_bf16cache"] = round(
                        8 * N / (t_f16 - t_p16), 1
                    )
                else:
                    result["notes"].append("decode_bf16cache_noise_dominated")
            elif not tiny:
                result["notes"].append("decode_bf16cache_skipped_budget")
            # beam decode (first-class path, scanned layer loop r5): same
            # prefill-subtraction discipline as the decode rows — the rate
            # covers only the beam scan steps, comparable to decode_tok_*
            if not tiny and time.monotonic() < deadline - 30:
                beam_bs, beam_mnt = 2, 16
                bprompt = jnp.asarray(
                    drng.randint(1, dcfg["vocab"], size=(beam_bs, Tp)).astype(np.int32)
                )

                def time_beam(mnt):
                    fn = jax.jit(functools.partial(
                        transformer_lm.generate_beam, max_new_tokens=mnt,
                        cfg=dcfg, beam_size=4, stacked_params=dstacked,
                    ))
                    return time_fn(
                        lambda: fn(dvars, bprompt),
                        lambda o: int(jax.device_get(o[0][0, 0, -1])),
                    )

                t_bpre = time_beam(1)
                t_bfull = time_beam(1 + beam_mnt)
                if t_bfull - t_bpre > t_bpre * 0.05:
                    result["beam_tok_per_sec_bs2_beam4"] = round(
                        beam_bs * beam_mnt / (t_bfull - t_bpre), 1
                    )
                    print(f"beam decode: {result['beam_tok_per_sec_bs2_beam4']} tok/s",
                          file=sys.stderr)
                else:
                    result["notes"].append("beam_noise_dominated")
            elif not tiny:
                result["notes"].append("beam_skipped_budget")
        except Exception as e:
            result["notes"].append(f"decode_failed: {type(e).__name__}: {e}"[:300])
        checkpoint_result()

    # --- Transformer ---
    if time.monotonic() < deadline:
        tbs, tseq = (4, 64) if tiny else (32, 256)
        titers = 3 if tiny else 10
        try:
            # scan_layers: one body compile per stack (see lm_large note)
            tspec = models.get_model("transformer", seq_len=tseq,
                                     scan_layers=not tiny)
            dt, flops, mem = _bench_step(tspec, tbs, warmup=1, iters=titers)
            if mem:
                result["transformer_peak_hbm_bytes"] = mem["peak_hbm_bytes"]
            result["transformer_tokens_per_sec"] = round(tbs * tseq / dt, 1)
            if peak and flops:
                result["transformer_mfu"] = round(flops / dt / peak, 4)
            print(f"transformer: {result['transformer_tokens_per_sec']} tok/s", file=sys.stderr)
        except Exception as e:
            result["notes"].append(f"transformer_failed: {type(e).__name__}: {e}"[:300])
        checkpoint_result()
    else:
        result["notes"].append("transformer_skipped_budget")

    # --- decoder-only LM (flash + bf16 path, the long-context flagship) ---
    if time.monotonic() < deadline:
        lbs, lseq = (2, 128) if tiny else (8, 1024)
        try:
            lspec = models.get_model("transformer_lm", seq_len=lseq)
            dt, flops, mem = _bench_step(lspec, lbs, warmup=1, iters=3 if tiny else 10)
            if mem:
                result["lm_peak_hbm_bytes"] = mem["peak_hbm_bytes"]
            result["lm_tokens_per_sec"] = round(lbs * lseq / dt, 1)
            if peak and flops:
                result["lm_mfu"] = round(flops / dt / peak, 4)
            print(f"transformer_lm: {result['lm_tokens_per_sec']} tok/s", file=sys.stderr)
        except Exception as e:
            result["notes"].append(f"lm_failed: {type(e).__name__}: {e}"[:300])
        checkpoint_result()
    else:
        result["notes"].append("lm_skipped_budget")

    # --- input pipeline: host reader + DevicePrefetcher feed rate vs the
    # measured resnet step rate (SURVEY hard part (d): at 800+ img/s the
    # Python reader can become the bottleneck; reference leaned on C++
    # double-buffer readers, operators/reader/buffered_reader.cc). ---
    if time.monotonic() < deadline:
        try:
            import numpy as np

            from paddle_tpu import reader as rdr

            fbs = result.get("resnet_batch_size", 64)
            n_batches = 4 if tiny else 16
            side = 64 if tiny else 224

            def synth_source():
                # flowers-shaped samples, synthesized host-side per row: the
                # measurement covers per-sample python cost + batching +
                # host->device transfer (not disk/network)
                r = np.random.RandomState(0)
                for _ in range(fbs * n_batches):
                    yield (r.rand(side, side, 3).astype(np.float32), 1)

            batched = rdr.stack_batch(lambda: synth_source(), fbs)
            # t0 BEFORE construction: the prefetcher's fill thread starts
            # synthesizing + transferring immediately
            t0 = time.perf_counter()
            pref = rdr.DevicePrefetcher(batched())
            n = 0
            for imgs, labels in pref:
                n += int(imgs.shape[0])
            # device_get, NOT block_until_ready: same early-return hazard as
            # the step timing loops (see _bench_step)
            float(jax.device_get(imgs.ravel()[0]))
            dt_feed = time.perf_counter() - t0
            feed_ips = n / dt_feed
            result["feed_imgs_per_sec"] = round(feed_ips, 1)
            step_ips = result.get("value", 0.0)
            if step_ips and not tiny:
                # fraction of each step the device would wait on the host;
                # only meaningful when feed and step use the same image size
                # (tiny feeds 64x64 against a 224x224 step — skip it there)
                result["feed_stall_frac"] = round(
                    max(0.0, 1.0 - feed_ips / step_ips), 3
                )
            print(f"feed: {feed_ips:.1f} img/s", file=sys.stderr)
        except Exception as e:
            result["notes"].append(f"feed_failed: {type(e).__name__}: {e}"[:300])

    # physics check: MFU cannot exceed 1.0 — if it does, the timing loop is
    # not actually synchronizing with the device
    for k, val in list(result.items()):
        if k.endswith("_mfu") and isinstance(val, float) and val > 1.0:
            result["notes"].append(f"timing_suspect_{k}={val}")
    refresh_telemetry()
    print(json.dumps(result))


def serve_main(duration_s: float = 3.0, tenant_mix: bool = False) -> dict:
    """Serving-engine benchmark (``bench.py --serve``): closed-loop client
    threads against ``paddle_tpu.serving.ServingEngine`` on CPU JAX.
    Prints ONE JSON line: throughput (req/s), mean batch occupancy, and
    p50/p99 request latency — the three numbers that tell whether dynamic
    batching is doing its job (occupancy > 1 at sane tail latency).

    With ``--tenants`` (or ``PT_BENCH_TENANT_MIX=1``) the run goes through
    admission control with a 4:1 interactive/batch tenant pair and reports
    per-tenant throughput plus shed counts — the overload-protection view."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import threading

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import (
        AdmissionRejected,
        ServingConfig,
        ServingEngine,
        TenantConfig,
    )

    d_in, n_clients = 32, 8
    result = {
        "metric": "serving_requests_per_sec",
        "value": 0.0,
        "unit": "req/s",
        "notes": [],
    }
    try:
        def net(x):
            h = pt.layers.fc(x, size=64, act="relu", name="fc1")
            return pt.layers.fc(h, size=8, name="fc2")

        model = pt.build(net)
        rng = np.random.RandomState(0)
        variables = model.init(0, rng.randn(4, d_in).astype(np.float32))
        tenants = None
        if tenant_mix:
            tenants = [
                TenantConfig("interactive", weight=4.0, queue_capacity=64),
                TenantConfig("batch", weight=1.0, queue_capacity=64,
                             default_class="batch"),
            ]
        engine = ServingEngine(
            model,
            variables,
            [FeedSpec("x", (d_in,), "float32")],
            config=ServingConfig(
                max_batch_size=16,
                max_queue_delay_s=0.002,
                queue_capacity=256,
                num_replicas=2,
                tenants=tenants,
            ),
        )
        stop = time.monotonic() + duration_s
        counts = [0] * n_clients
        sheds = [0] * n_clients
        # 3 of 4 clients drive the interactive tenant: sustained overload
        # on one side so the fairness/shed numbers mean something
        tenant_of = [
            "interactive" if ci % 4 else "batch" for ci in range(n_clients)
        ]

        def client(ci):
            r = np.random.RandomState(ci)
            while time.monotonic() < stop:
                n = 1 + r.randint(4)  # mixed request sizes keep buckets honest
                x = r.randn(n, d_in).astype(np.float32)
                if tenant_mix:
                    try:
                        engine.infer({"x": x}, tenant=tenant_of[ci],
                                     retries=2, backoff=0.002)
                    except AdmissionRejected:
                        sheds[ci] += 1
                        continue
                else:
                    engine.infer({"x": x})
                counts[ci] += 1

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=duration_s + 60)
        dt = time.perf_counter() - t0
        engine.close()
        snap = engine.metrics.snapshot()
        result["value"] = round(sum(counts) / dt, 1)
        result["rows_per_sec"] = round(snap["rows_total"] / dt, 1)
        result["batch_occupancy_mean"] = round(snap["mean_batch_occupancy"], 2)
        # histogram-interpolated quantiles over EVERY response (the same
        # estimator the SLO engine uses), not the bounded reservoir's
        # nearest-rank points; fall back to the reservoir if empty
        p50 = engine.metrics.latency_quantile(0.5)
        p99 = engine.metrics.latency_quantile(0.99)
        result["p50_ms"] = round((p50 * 1e3) if p50 is not None else snap["p50_ms"], 3)
        result["p99_ms"] = round((p99 * 1e3) if p99 is not None else snap["p99_ms"], 3)
        result["batches_total"] = snap["batches_total"]
        result["timeouts_total"] = snap["timeouts_total"]
        result["errors_total"] = snap["errors_total"]
        result["warmup_executables"] = snap["warmup_executables"]
        result["distinct_dispatch_shapes"] = snap["distinct_dispatch_shapes"]
        if tenant_mix:
            per_tenant = {}
            for name in ("interactive", "batch"):
                cis = [ci for ci in range(n_clients) if tenant_of[ci] == name]
                per_tenant[name] = {
                    "req_per_sec": round(sum(counts[ci] for ci in cis) / dt, 1),
                    "shed": sum(sheds[ci] for ci in cis),
                    "admitted_total": engine.metrics.tenant_admitted(name),
                    "shed_by_reason": engine.metrics.tenant_shed(name),
                }
            result["tenants"] = per_tenant
            result["retries_total"] = snap["retries_total"]
    except Exception as e:  # same robustness contract as main(): always JSON
        result["notes"].append(f"serve_failed: {type(e).__name__}: {e}"[:300])
    print(json.dumps(result))
    return result


def serve_decode_main(n_requests: int = 24) -> dict:
    """Continuous-batching decode benchmark (``bench.py --serve-decode``):
    a seeded mixed-length request set served two ways on CPU JAX —

    - **continuous**: ``serving.DecodeEngine`` (paged KV cache, iteration-
      level admission; a finished request's slot refills next step);
    - **continuous + lock check**: the same engine with the ``core.locks``
      order detector forced on (``lock_check_overhead_pct`` — the
      detector's whole tax, gated so leaving it on under test/chaos stays
      cheap);
    - **continuous + journal**: the same engine with the durable token
      journal enabled (``decode_serve_journal_tok_per_sec``) — the delta
      against the first leg is the zero-loss WAL overhead, gated so it
      stays a tax and never becomes a regression;
    - **static**: the ``generate()`` path batched ``max_slots`` at a time,
      prompts padded to a 16-token bucket and every batch member running
      to the slowest member's budget — the pre-PR serving discipline;
    - **speculative**: the same traffic through draft-and-verify
      (``spec_vs_plain_tok_per_sec``, plus the per-slot mean accepted
      tokens per verify step — > 1.0 means each verify iteration lands
      more than a plain step's single token);
    - **prefix**: shared-system-prompt traffic with the radix prefix
      cache on (``prefix_prefill_tokens_saved_frac`` — the fraction of
      admitted prompt tokens whose prefill the tree absorbed).

    Prints ONE JSON line: generated tokens/sec for both paths, the ratio,
    mean step occupancy, preemption count, and whether the jitted decode
    step stayed compile-flat under the mixed traffic. Compile time is
    excluded from both sides (engine warmup / per-shape prewarm), so the
    ratio isolates the scheduling win, not recompile overhead. Also
    carries the continuous leg's token-latency percentiles from the
    waterfall docs (``ttft_p50/p99``, ``tpot_p50/p99`` in ms;
    ``decode_tpot_p99_ms`` is the gated lower-better entry) and a
    ``roofline_summary`` block from the kernel cost ledger."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.models.transformer_lm import generate
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    result = {
        "metric": "decode_serve_cont_tok_per_sec",
        "value": 0.0,
        "unit": "tok/s",
        "notes": [],
    }
    try:
        result["device_kind"] = jax.devices()[0].device_kind
        vocab, slots = 512, 4
        spec = models.get_model("transformer_lm", seq_len=128, vocab=vocab,
                                d_model=64, d_inner=128, num_heads=4,
                                n_layers=2)
        cfg = spec.extra["cfg"]
        rng = np.random.RandomState(0)
        variables = spec.model.init(0, *spec.synth_batch(2, rng))
        reqs = []
        for _ in range(n_requests):
            tp = int(rng.randint(4, 25))
            mnt = int(rng.randint(8, 49))
            reqs.append((rng.randint(1, vocab, size=(tp,)).astype(np.int32),
                         mnt))
        total_tokens = sum(mnt for _, mnt in reqs)

        # -- continuous: one engine, all requests submitted up front ------
        # lock-order checking forced OFF for this leg: it is the baseline
        # side of lock_check_overhead_pct below (and the production
        # default)
        from paddle_tpu.core import locks as _locks
        from paddle_tpu.observability import roofline as _roofline
        from paddle_tpu.tracing import waterfall as _waterfall
        _locks.set_enabled(False)
        # fresh cost ledger + waterfall store: the roofline summary and
        # the token-latency percentiles below describe THIS run only
        _roofline.reset_ledger()
        _waterfall.reset()
        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(
            max_slots=slots, page_size=16, max_context=128,
            prefill_chunk=16))
        t0 = time.perf_counter()
        handles = [eng.submit(p, mnt) for p, mnt in reqs]
        outs = [h.result(timeout=600) for h in handles]
        dt_cont = time.perf_counter() - t0
        gen_cont = sum(len(o.tokens) for o in outs)
        snap = eng.metrics.snapshot()
        compile_flat = (eng.decode_step_cache_size() == 1
                        and eng.prefill_cache_size() == 1)
        eng.close()
        eng.kv.assert_no_leaks()
        # token-latency samples from the continuous leg's waterfall docs
        # (exact per-request TTFT + per-token TPOT, not bucket estimates)
        ttfts, tpots = [], []
        for rid in _waterfall.rids(finished_only=True):
            d = _waterfall.doc(rid)
            if d is None:
                continue
            if d["ttft_s"] is not None:
                ttfts.append(d["ttft_s"])
            tpots.extend(d["tpot_s"])

        # -- continuous + lock-order detector: same traffic with
        # core.locks checking forced ON; the delta vs the leg above is the
        # whole detector tax (per-acquire bookkeeping + edge checks),
        # gated so the "cheap enough to leave on under test/chaos" claim
        # stays true
        try:
            _locks.set_enabled(True)
            eng = DecodeEngine(variables, cfg, decode=DecodeConfig(
                max_slots=slots, page_size=16, max_context=128,
                prefill_chunk=16))
            t0 = time.perf_counter()
            handles = [eng.submit(p, mnt) for p, mnt in reqs]
            outs_l = [h.result(timeout=600) for h in handles]
            dt_lock = time.perf_counter() - t0
            gen_lock = sum(len(o.tokens) for o in outs_l)
            eng.close()
            eng.kv.assert_no_leaks()
            lock_violations = len(_locks.violations())
        finally:
            _locks.set_enabled(None)  # back to flag/pytest resolution

        # -- continuous + durable journal: same traffic with the WAL on --
        # the delta vs the leg above is the whole journaling tax (CRC +
        # buffered append + batched fsync, all off the jitted step path)
        import shutil
        import tempfile
        jdir = tempfile.mkdtemp(prefix="paddle_tpu_bench_wal_")
        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(
            max_slots=slots, page_size=16, max_context=128,
            prefill_chunk=16,
            journal_path=os.path.join(jdir, "decode.wal")))
        t0 = time.perf_counter()
        handles = [eng.submit(p, mnt) for p, mnt in reqs]
        outs_j = [h.result(timeout=600) for h in handles]
        dt_journal = time.perf_counter() - t0
        gen_journal = sum(len(o.tokens) for o in outs_j)
        journal_records = eng.metrics.snapshot()["journal_records_total"]
        eng.close()
        eng.kv.assert_no_leaks()
        shutil.rmtree(jdir, ignore_errors=True)

        # -- static: generate() in admission-order batches of `slots` -----
        def bucket(n, q=16):
            return -(-n // q) * q

        batches = []
        for i in range(0, len(reqs), slots):
            group = reqs[i:i + slots]
            tp_pad = bucket(max(len(p) for p, _ in group))
            mnt_max = max(mnt for _, mnt in group)
            prompts = np.ones((len(group), tp_pad), np.int32)  # pad tok 1
            for j, (p, _) in enumerate(group):
                prompts[j, tp_pad - len(p):] = p  # right-align real tokens
            batches.append((jnp.asarray(prompts), mnt_max))
        for prompts, mnt_max in batches:  # prewarm each (B, Tp, N) shape
            np.asarray(generate(variables, prompts, mnt_max, cfg))
        t0 = time.perf_counter()
        for prompts, mnt_max in batches:
            np.asarray(generate(variables, prompts, mnt_max, cfg))
        dt_static = time.perf_counter() - t0

        # -- speculative: same traffic, draft-and-verify (self-draft) -----
        # the ratio vs the plain continuous leg is the rolling baseline;
        # the per-slot accepted-tokens-per-verify-step mean is the
        # acceptance criterion (> 1.0 means speculation lands more than
        # the one token a plain step would)
        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(
            max_slots=slots, page_size=16, max_context=128,
            prefill_chunk=16, spec_tokens=4),
            draft_variables=variables, draft_cfg=cfg)
        t0 = time.perf_counter()
        handles = [eng.submit(p, mnt) for p, mnt in reqs]
        outs_s = [h.result(timeout=600) for h in handles]
        dt_spec = time.perf_counter() - t0
        gen_spec = sum(len(o.tokens) for o in outs_s)
        snap_s = eng.metrics.snapshot()
        spec_exact = all(np.array_equal(a.tokens, b.tokens)
                         for a, b in zip(outs, outs_s))
        spec_compile_flat = eng.verify_step_cache_size() == 1
        k = eng.spec_tokens
        eng.close()
        eng.kv.assert_no_leaks()

        # -- prefix cache: shared-system-prompt traffic, hot vs cold ------
        # every prompt opens with the same 48-token (3-page) preamble;
        # after the first prefill the radix tree serves those pages and
        # the saved fraction of prompt tokens is the rolling baseline
        preamble = rng.randint(1, vocab, size=(48,)).astype(np.int32)
        preqs = []
        for _ in range(n_requests):
            tail = rng.randint(
                1, vocab, size=(int(rng.randint(4, 17)),)).astype(np.int32)
            preqs.append((np.concatenate([preamble, tail]),
                          int(rng.randint(8, 33))))
        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(
            max_slots=slots, page_size=16, max_context=128,
            prefill_chunk=16, prefix_cache=True))
        handles = [eng.submit(p, mnt) for p, mnt in preqs]
        for h in handles:
            h.result(timeout=600)
        snap_p = eng.metrics.snapshot()
        prefix_saved = eng.metrics.prefix_saved_frac()
        eng.close()
        eng.kv.assert_no_leaks()

        result["value"] = round(gen_cont / dt_cont, 1)
        # token-latency percentiles (milliseconds) for the continuous
        # leg; decode_tpot_p99_ms is the gated lower-better entry
        if ttfts:
            result["ttft_p50"] = round(float(np.percentile(ttfts, 50)) * 1e3, 3)
            result["ttft_p99"] = round(float(np.percentile(ttfts, 99)) * 1e3, 3)
        if tpots:
            result["tpot_p50"] = round(float(np.percentile(tpots, 50)) * 1e3, 3)
            result["tpot_p99"] = round(float(np.percentile(tpots, 99)) * 1e3, 3)
            result["decode_tpot_p99_ms"] = result["tpot_p99"]
        result["roofline_summary"] = _roofline.summary()
        result["decode_serve_lockcheck_tok_per_sec"] = round(
            gen_lock / dt_lock, 1)
        result["lock_check_overhead_pct"] = round(
            100.0 * (1.0 - (gen_lock / dt_lock)
                     / max(gen_cont / dt_cont, 1e-9)), 1)
        if lock_violations:
            result["notes"].append(
                f"lock-order violations under bench traffic: "
                f"{lock_violations}")
        result["decode_serve_journal_tok_per_sec"] = round(
            gen_journal / dt_journal, 1)
        result["journal_overhead_pct"] = round(
            100.0 * (1.0 - (gen_journal / dt_journal)
                     / max(gen_cont / dt_cont, 1e-9)), 1)
        result["journal_records_total"] = journal_records
        result["decode_serve_static_tok_per_sec"] = round(
            total_tokens / dt_static, 1)
        result["speedup_vs_static"] = round(
            (gen_cont / dt_cont) / max(total_tokens / dt_static, 1e-9), 2)
        result["decode_serve_spec_tok_per_sec"] = round(
            gen_spec / dt_spec, 1)
        result["spec_vs_plain_tok_per_sec"] = round(
            (gen_spec / dt_spec) / max(gen_cont / dt_cont, 1e-9), 3)
        # per-slot mean: tokens landed per (slot, verify step) pair — the
        # aggregate gauge can exceed K+1 when several slots verify at once
        slot_steps = snap_s["spec_drafts_proposed_total"] / max(k, 1)
        result["spec_accepted_tokens_per_verify_step"] = round(
            snap_s["spec_tokens_total"] / max(slot_steps, 1e-9), 2)
        result["spec_accept_rate"] = round(snap_s["spec_accept_rate"], 3)
        result["prefix_prefill_tokens_saved_frac"] = round(prefix_saved, 3)
        result["prefix_hit_tokens_total"] = snap_p["prefix_hit_tokens_total"]
        result["cow_copies_total"] = snap_p["cow_copies_total"]
        result["requests"] = len(reqs)
        result["tokens_generated"] = gen_cont
        result["mean_step_occupancy"] = round(snap["mean_step_occupancy"], 2)
        result["preempted_total"] = snap["preempted_total"]
        result["compile_flat"] = compile_flat
        if not compile_flat:
            result["notes"].append("decode step recompiled under traffic")
        if not spec_compile_flat:
            result["notes"].append("verify step recompiled under traffic")
        if not spec_exact:
            result["notes"].append("speculative tokens diverged from plain")
    except Exception as e:  # same robustness contract as main(): always JSON
        result["notes"].append(
            f"serve_decode_failed: {type(e).__name__}: {e}"[:300])
    print(json.dumps(result))
    return result


def serve_group_main(n_requests: int = 16) -> dict:
    """Tensor-parallel replica-group benchmark (``bench.py --serve-group``):
    a seeded mixed-length request set served two ways on CPU JAX —

    - **single**: one ``DecodeEngine`` on one device (the PR 16 baseline
      discipline: the dispatch unit is a device);
    - **group**: the same engine backed by a tp=2 ``ReplicaGroup`` — one
      pjit'd step over a two-device submesh, params and paged KV sharded
      per ``GroupLayout``, the per-member canary probing every loop.

    Headline metric: group-mode generated tokens/sec. The ratio
    ``group_vs_single_tok_per_sec`` is the rolling baseline — on a CPU
    host both "devices" share the same cores, so the ratio measures the
    partitioning + collective overhead (< 1.0 expected; on a real pod the
    ICI collectives overlap and the win is HBM: half the params and KV
    per chip). ``group_probe_overhead_pct`` is the whole per-member
    canary tax (timed host→device probes + skew bookkeeping), gated so
    the always-on health check stays cheap. Both legs must agree
    token-for-token and stay compile-flat. Prints ONE JSON line."""
    # the tp=2 submesh needs two devices BEFORE jax initializes
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.shardgroup import make_groups, probe_members

    result = {
        "metric": "group_serve_tok_per_sec",
        "value": 0.0,
        "unit": "tok/s",
        "notes": [],
    }
    try:
        result["device_kind"] = jax.devices()[0].device_kind
        from paddle_tpu.core import locks as _locks
        _locks.set_enabled(False)  # production default; measured elsewhere
        vocab, slots = 512, 4
        spec = models.get_model("transformer_lm", seq_len=128, vocab=vocab,
                                d_model=64, d_inner=128, num_heads=4,
                                n_layers=2)
        cfg = spec.extra["cfg"]
        rng = np.random.RandomState(0)
        variables = spec.model.init(0, *spec.synth_batch(2, rng))
        reqs = []
        for _ in range(n_requests):
            tp = int(rng.randint(4, 25))
            mnt = int(rng.randint(8, 49))
            reqs.append((rng.randint(1, vocab, size=(tp,)).astype(np.int32),
                         mnt))
        dconf = dict(max_slots=slots, page_size=16, max_context=128,
                     prefill_chunk=16)

        def run(group, probe_every):
            eng = DecodeEngine(variables, cfg, decode=DecodeConfig(
                group_probe_every_s=probe_every, **dconf), group=group)
            t0 = time.perf_counter()
            handles = [eng.submit(p, mnt) for p, mnt in reqs]
            outs = [h.result(timeout=600) for h in handles]
            dt = time.perf_counter() - t0
            gen = sum(len(o.tokens) for o in outs)
            flat = (eng.decode_step_cache_size() == 1
                    and eng.prefill_cache_size() == 1)
            eng.close()
            eng.kv.assert_no_leaks()
            return outs, gen / dt, flat

        group = make_groups(2)[0]
        outs_single, tps_single, flat_single = run(None, 0.05)
        # group leg 1: probes at the production cadence
        outs_group, tps_group, flat_group = run(group, 0.05)
        # group leg 2: canary on EVERY loop iteration — the delta against
        # the cadenced leg bounds the probe tax from above
        _, tps_probe, _ = run(group, 0.0)

        exact = all(np.array_equal(a.tokens, b.tokens)
                    for a, b in zip(outs_single, outs_group))
        # standalone probe cost, for the notes: one full member sweep
        t0 = time.perf_counter()
        for _ in range(50):
            probe_members(group)
        probe_ms = (time.perf_counter() - t0) / 50 * 1e3

        result["value"] = round(tps_group, 1)
        result["group_single_tok_per_sec"] = round(tps_single, 1)
        result["group_vs_single_tok_per_sec"] = round(
            tps_group / max(tps_single, 1e-9), 3)
        result["group_probe_overhead_pct"] = round(
            100.0 * (1.0 - tps_probe / max(tps_group, 1e-9)), 1)
        result["group_probe_ms"] = round(probe_ms, 3)
        result["tp_degree"] = 2
        result["requests"] = len(reqs)
        result["compile_flat"] = flat_single and flat_group
        if not (flat_single and flat_group):
            result["notes"].append("decode step recompiled under traffic")
        if not exact:
            result["notes"].append("group tokens diverged from single")
    except Exception as e:  # same robustness contract as main(): always JSON
        result["notes"].append(
            f"serve_group_failed: {type(e).__name__}: {e}"[:300])
    print(json.dumps(result))
    return result


def serve_disagg_main(n_rounds: int = 4) -> dict:
    """Disaggregated prefill/decode benchmark (``bench.py --serve-disagg``):
    the same storm-under-decode workload served two ways on CPU JAX —

    - **single**: one ``DecodeEngine`` runs prefill AND decode; a storm of
      long-prompt requests steals loop iterations from in-flight decodes
      (the pre-PR-15 discipline: chunked prefill bounds the stall but the
      roles still share a worker);
    - **disagg**: a ``DisaggRouter`` over one prefill-role and one
      decode-role worker; the storm's prefill chunks all land on the
      prefill worker and in-flight decodes never see them.

    Headline metric: p99 completion latency of steady interactive
    generations submitted just before the storm
    (``disagg_decode_p99_storm_ms``, lower is better), with the
    single-engine number alongside. ``handoff_quiet_throughput_frac``
    is the storm-free throughput cost of crossing the handoff boundary
    (page gather + payload + adoption) versus decoding in place, as a
    fraction of the single-engine rate (~1.0 = free) — gated so the
    disaggregation never becomes a steady-state regression.
    ``trace_overhead_pct`` is the quiet-throughput cost of recording the
    per-request span tree (queue_wait/prefill/handoff/decode, fleet
    observability) versus tracing disabled — gated ≈0 so trace
    propagation never becomes a serving tax. Note: on a
    single shared-core CPU host both roles compete for the same compute,
    so the p99 isolation win is structural (decode workers never run
    prefill chunks) rather than visible in wall-clock. Prints ONE JSON
    line."""
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.serving import DecodeConfig, DecodeEngine, DisaggRouter
    from paddle_tpu.serving.disagg import DECODE, PREFILL

    result = {
        "metric": "disagg_decode_p99_storm_ms",
        "value": 0.0,
        "unit": "ms",
        "notes": [],
    }
    try:
        result["device_kind"] = jax.devices()[0].device_kind
        from paddle_tpu.core import locks as _locks
        _locks.set_enabled(False)  # production default; measured elsewhere
        vocab, slots = 512, 4
        spec = models.get_model(
            "transformer_lm", seq_len=128, vocab=vocab, d_model=64,
            d_inner=128, num_heads=4, n_layers=2)
        cfg = spec.extra["cfg"]
        rng = np.random.RandomState(0)
        variables = spec.model.init(0, *spec.synth_batch(2, rng))
        dconf = dict(max_slots=slots, page_size=16, max_context=128,
                     prefill_chunk=16, num_pages=48)
        # steady fills only half the slots: the storm gets admitted
        # alongside it, so on the single engine its prefill chunks steal
        # loop iterations from live decodes (that contention is exactly
        # what the role split removes)
        steady = [(rng.randint(1, vocab,
                               size=(int(rng.randint(8, 17)),)
                               ).astype(np.int32), 64)
                  for _ in range(slots // 2)]
        storm = [rng.randint(1, vocab, size=(96,)).astype(np.int32)
                 for _ in range(8)]
        steady_tokens = sum(mnt for _, mnt in steady)

        def timed_wave(submit, with_storm):
            """Submit the steady set, optionally unleash the storm right
            behind it, and return (per-request latencies, wall seconds)."""
            lats = [0.0] * len(steady)
            t_sub = []
            handles = []
            t_wave = time.perf_counter()
            for p, mnt in steady:
                handles.append(submit(p, mnt))
                t_sub.append(time.perf_counter())
            storm_handles = ([submit(p, 2) for p in storm]
                             if with_storm else [])

            def waiter(i):
                handles[i].result(timeout=600)
                lats[i] = time.perf_counter() - t_sub[i]

            threads = [threading.Thread(target=waiter, args=(i,))
                       for i in range(len(handles))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t_wave
            for h in storm_handles:
                h.result(timeout=600)
            return lats, wall

        def measure(submit):
            timed_wave(submit, False)  # warm the jits off the clock
            # median-of-waves: a single ~70ms wave swings ±30% on one
            # scheduler hiccup, which is noise, not handoff cost
            quiet_walls = sorted(
                timed_wave(submit, False)[1] for _ in range(5))
            quiet_wall = quiet_walls[len(quiet_walls) // 2]
            storm_lats = []
            for _ in range(n_rounds):
                lats, _ = timed_wave(submit, True)
                storm_lats.extend(lats)
            return quiet_wall, storm_lats

        # -- single engine: prefill and decode share one worker -----------
        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(**dconf))
        single_quiet_wall, single_storm = measure(eng.submit)
        eng.close()
        eng.kv.assert_no_leaks()

        # -- disaggregated: the storm lands on the prefill worker ---------
        pre = DecodeEngine(variables, cfg, decode=DecodeConfig(**dconf))
        dec = DecodeEngine(variables, cfg, decode=DecodeConfig(**dconf))
        router = DisaggRouter([pre, dec], [PREFILL, DECODE])
        disagg_quiet_wall, disagg_storm = measure(router.submit)
        handoffs = router.handoffs_total
        rejects = router.handoff_rejects_total
        dec_prefills = dec.metrics.snapshot()["prefill_chunks_total"]

        # -- tracing tax: the same quiet wave with spans on vs off --------
        # every request now records queue_wait/prefill/handoff/decode spans
        # (fleet observability); gate that the bookkeeping stays ~free. The
        # jits are warm from the legs above, so two short median-of-3 runs
        # on the live router isolate the span-recording cost.
        from paddle_tpu import tracing as _tracing
        was_tracing = _tracing.tracing_enabled()
        try:
            trace_on_walls, trace_off_walls = [], []
            for _ in range(5):  # interleave on/off: drift hits both sides
                _tracing.enable_tracing()
                trace_on_walls.append(timed_wave(router.submit, False)[1])
                _tracing.disable_tracing()
                trace_off_walls.append(timed_wave(router.submit, False)[1])
            trace_on_walls.sort()
            trace_off_walls.sort()
        finally:
            if was_tracing:
                _tracing.enable_tracing()
            else:
                _tracing.disable_tracing()
        # best-of-5 per side: a single wave is ~80ms on a shared CPU box,
        # so medians still carry ±20% scheduler noise; the fastest wave on
        # each side strips the hiccups and leaves the systematic span cost
        tps_trace_on = steady_tokens / trace_on_walls[0]
        tps_trace_off = steady_tokens / trace_off_walls[0]
        result["trace_overhead_pct"] = round(
            100.0 * (1.0 - tps_trace_on / max(tps_trace_off, 1e-9)), 1)

        router.close(60)
        pre.kv.assert_no_leaks()
        dec.kv.assert_no_leaks()

        tps_single = steady_tokens / single_quiet_wall
        tps_disagg = steady_tokens / disagg_quiet_wall
        result["value"] = round(
            float(np.percentile(disagg_storm, 99)) * 1e3, 1)
        result["single_decode_p99_storm_ms"] = round(
            float(np.percentile(single_storm, 99)) * 1e3, 1)
        result["disagg_vs_single_p99_frac"] = round(
            result["value"] / max(result["single_decode_p99_storm_ms"],
                                  1e-9), 3)
        # handoff tax, gated as a fraction of single-engine quiet
        # throughput: ~1.0 when crossing the boundary is free; a relative
        # band around a near-zero "overhead pct" would flap on noise
        result["handoff_quiet_throughput_frac"] = round(
            tps_disagg / max(tps_single, 1e-9), 3)
        result["notes"].append(
            "handoff overhead "
            f"{100.0 * (1.0 - tps_disagg / max(tps_single, 1e-9)):+.1f}% "
            "of quiet steady-state throughput")
        result["disagg_quiet_tok_per_sec"] = round(tps_disagg, 1)
        result["single_quiet_tok_per_sec"] = round(tps_single, 1)
        result["handoffs_total"] = handoffs
        result["requests"] = (len(steady) * (n_rounds + 6)
                              + len(storm) * n_rounds)
        if rejects:
            result["notes"].append(f"unforced handoff rejects: {rejects}")
        if dec_prefills:
            result["notes"].append(
                f"decode worker ran {dec_prefills} prefill chunks")
    except Exception as e:  # same robustness contract as main(): always JSON
        result["notes"].append(
            f"serve_disagg_failed: {type(e).__name__}: {e}"[:300])
    print(json.dumps(result))
    return result


def serve_host_tier_main(n_rounds: int = 3) -> dict:
    """Hierarchical KV host tier benchmark (``bench.py --serve-host-tier``):
    the same two-tenant shared-system-prompt workload served by a
    two-engine ``DecodeFleet`` two ways on CPU JAX —

    - **no tier**: radix prefix caches only, capped small enough that ONE
      engine's tree holds one tenant's working set; least-loaded routing
      interleaves both tenants onto both engines, so the shared prefixes
      churn out of the trees and most prompt tokens re-pay prefill;
    - **tiered**: a shared ``HostPagePool`` behind both engines plus
      prefix-digest routing — each tenant's traffic converges on the
      engine already holding its prefix, and pages the capped trees do
      evict demote to host RAM and promote back instead of re-prefilling.

    Headline metric: fleet-wide prefix-cache hit fraction of prompt
    tokens with the tier+routing on (``host_tier_prefix_hit_frac``,
    higher is better, gated), with the untiered fraction alongside — the
    gap is the tier's effective-capacity win. The promote path runs on
    the decode loop thread, so the leg also storms the warm tiered fleet
    with prefix traffic while interactive decodes are in flight and
    reports their p99 (``host_tier_decode_p99_storm_ms``, lower is
    better, gated) against the untiered fleet's number — promotion must
    stay decode-p99-neutral. Prints ONE JSON line."""
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.serving import (DecodeConfig, DecodeEngine, DecodeFleet,
                                    HostPagePool)

    result = {
        "metric": "host_tier_prefix_hit_frac",
        "value": 0.0,
        "unit": "frac",
        "notes": [],
    }
    try:
        result["device_kind"] = jax.devices()[0].device_kind
        from paddle_tpu.core import locks as _locks
        _locks.set_enabled(False)  # production default; measured elsewhere
        vocab, ps = 512, 8
        spec = models.get_model(
            "transformer_lm", seq_len=128, vocab=vocab, d_model=64,
            d_inner=128, num_heads=4, n_layers=2)
        cfg = spec.extra["cfg"]
        rng = np.random.RandomState(0)
        variables = spec.model.init(0, *spec.synth_batch(2, rng))
        # the radix budget (8 pages) holds ONE tenant's 6-page system
        # prompt plus tails — not both tenants'. That cap is the whole
        # experiment: without the tier, whatever routing interleaves onto
        # an engine churns; with it, evictions come back as promotes.
        dconf = dict(max_slots=4, page_size=ps, max_context=128,
                     prefill_chunk=16, num_pages=64, prefix_cache=True,
                     prefix_cache_pages=8)
        prefixes = [rng.randint(1, vocab, size=(48,)).astype(np.int32)
                    for _ in range(2)]
        reqs = []
        for i in range(12):  # six requests per tenant
            tail = rng.randint(1, vocab,
                               size=(int(rng.randint(4, 9)),)
                               ).astype(np.int32)
            reqs.append((np.concatenate([prefixes[i % 2], tail]), 8))
        # shuffled submit order per wave: least-loaded placement then
        # lands an arbitrary tenant mix on each engine (the fleet-wide
        # working set, ~14 pages, overflows any one 8-page tree), while
        # digest routing keeps each tenant pinned to its warm engine
        # regardless of order
        orders = [rng.permutation(len(reqs)) for _ in range(n_rounds)]
        steady = [(rng.randint(1, vocab,
                               size=(int(rng.randint(8, 13)),)
                               ).astype(np.int32), 48)
                  for _ in range(3)]

        def storm_wave(fleet):
            """Interactive decodes in flight, then the prefix storm lands
            on top (demotes + promotes on the tiered fleet); returns the
            interactive requests' completion latencies."""
            lats = [0.0] * len(steady)
            t_sub = []
            handles = []
            for p, mnt in steady:
                handles.append(fleet.submit(p, mnt))
                t_sub.append(time.perf_counter())
            storm_handles = [fleet.submit(p, 2) for p, _ in reqs]

            def waiter(i):
                handles[i].result(timeout=600)
                lats[i] = time.perf_counter() - t_sub[i]

            threads = [threading.Thread(target=waiter, args=(i,))
                       for i in range(len(handles))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for h in storm_handles:
                h.result(timeout=600)
            return lats

        def run_config(with_tier):
            pool = (HostPagePool(max_bytes=8 << 20, page_size=ps)
                    if with_tier else None)
            kw = dict(dconf, prefix_digest=with_tier)
            engines = [DecodeEngine(variables, cfg,
                                    decode=DecodeConfig(**kw),
                                    host_tier=pool)
                       for _ in range(2)]
            fleet = DecodeFleet(engines)

            def counts():
                tot = {"prompt_tokens_total": 0, "prefix_hit_tokens_total": 0,
                       "host_promoted_pages_total": 0}
                for e in engines:
                    snap = e.metrics.snapshot()
                    for k in tot:
                        tot[k] += snap[k]
                return tot

            # warm: jits + seed each tenant's prefix once, off the clock
            for pfx in prefixes:
                fleet.submit(pfx, 4).result(timeout=600)
            before = counts()
            for r in range(n_rounds):
                handles = [fleet.submit(*reqs[i]) for i in orders[r]]
                for h in handles:
                    h.result(timeout=600)
            after = counts()
            prompt_toks = (after["prompt_tokens_total"]
                           - before["prompt_tokens_total"])
            hit_toks = (after["prefix_hit_tokens_total"]
                        - before["prefix_hit_tokens_total"])
            promoted = counts()["host_promoted_pages_total"]
            # p99 probe on the warm fleet: storms re-touch both tenants'
            # prefixes, so the tiered loop threads interleave demote +
            # promote work with the live decodes being timed
            storm_lats = []
            for _ in range(n_rounds):
                storm_lats.extend(storm_wave(fleet))
            fleet.close(timeout=120)
            for e in engines:
                e.kv.assert_no_leaks()
            p99 = float(np.percentile(storm_lats, 99)) * 1e3
            return hit_toks / max(prompt_toks, 1), p99, promoted

        no_tier_frac, no_tier_p99, _ = run_config(False)
        tier_frac, tier_p99, promoted = run_config(True)

        result["value"] = round(tier_frac, 3)
        result["no_tier_prefix_hit_frac"] = round(no_tier_frac, 3)
        result["host_tier_decode_p99_storm_ms"] = round(tier_p99, 1)
        result["no_tier_decode_p99_storm_ms"] = round(no_tier_p99, 1)
        result["host_tier_promoted_pages"] = promoted
        result["requests"] = 2 * (1 + n_rounds * (len(reqs) + len(steady)
                                                  + len(reqs)))
        result["notes"].append(
            "tier+routing prefix hit frac "
            f"{tier_frac:.3f} vs {no_tier_frac:.3f} untiered "
            f"({promoted} pages promoted from host RAM)")
        if tier_frac <= no_tier_frac:
            result["notes"].append(
                "WARNING: host tier + digest routing did not raise the "
                "fleet prefix hit fraction")
    except Exception as e:  # same robustness contract as main(): always JSON
        result["notes"].append(
            f"serve_host_tier_failed: {type(e).__name__}: {e}"[:300])
    print(json.dumps(result))
    return result


def tune_child_main(cache_dir: str, mode: str) -> dict:
    """``bench.py --tune-child <cache_dir> <cold|warm>``: construct the
    warm-restart probe engine against a shared persistent compile cache +
    warmup manifest and print ONE JSON line with the construction compile
    seconds. ``cold`` pays full warmup (and records the manifest); ``warm``
    restarts with ``warmup=False, prewarm=True`` — manifest replay through
    the persistent XLA cache, the restart path this PR is buying down."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    pt.core.config.set_flags(
        compilation_cache_dir=os.path.join(cache_dir, "xla"),
        tune_cache_dir=os.path.join(cache_dir, "tune"))

    import jax.numpy as jnp

    # a long shared-weight matmul chain: LLVM codegen cost scales with the
    # op count while tracing 48 jnp calls stays ~15ms, so the cold/warm
    # ratio measures the persistent cache instead of shared retrace time
    def net(x):
        h = pt.layers.fc(x, size=256, act="tanh", name="in")
        w = pt.layers.create_parameter([256, 256], h.dtype, name="chain_w")
        for _ in range(48):
            h = jnp.tanh(h @ w)
        return pt.layers.fc(h, size=8, name="out")

    model = pt.build(net)
    variables = model.init(0, np.zeros((2, 64), np.float32))
    spec = [FeedSpec("x", (64,), "float32")]
    conf = dict(max_batch_size=8, num_replicas=1, lint_model=False)
    t0 = time.perf_counter()
    if mode == "cold":
        eng = ServingEngine(model, variables, spec,
                            config=ServingConfig(**conf))
    else:
        eng = ServingEngine(model, variables, spec,
                            config=ServingConfig(warmup=False, prewarm=True,
                                                 **conf))
    dt = time.perf_counter() - t0
    result = {
        "metric": "warm_restart_child",
        "mode": mode,
        "compile_seconds": round(dt, 3),
        "aot_cache_sizes": eng.aot_cache_sizes(),
    }
    eng.close()
    print(json.dumps(result))
    return result


def tune_main() -> dict:
    """``bench.py --tune``: the two numbers this PR's perf story rests on,
    as ONE gated JSON line —

    - **tuned_vs_default_speedup** (headline): sweep the flash-attention
      candidate grid through ``paddle_tpu.tune.autotune_flash_attention``
      and report winner-vs-fitted-128/128-default (>= 1.0 by construction:
      the default is in the candidate set);
    - **warm_restart_compile_seconds** / **warm_restart_compile_speedup**:
      a cold child pays full engine warmup into a fresh persistent compile
      cache + warmup manifest; a warm child restarts from both
      (``prewarm``) — the acceptance criterion is the warm restart landing
      >= 5x cheaper, pinned by the baseline band."""
    import shutil
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.tune import autotune as tune_autotune

    result = {
        "metric": "tuned_vs_default_speedup",
        "value": 0.0,
        "unit": "x",
        "notes": [],
    }
    tmp = tempfile.mkdtemp(prefix="pt_tune_bench_")
    try:
        result["device_kind"] = jax.devices()[0].device_kind
        pt.core.config.set_flags(tune_cache_dir=os.path.join(tmp, "tune"),
                                 autotune=True)
        tune_autotune.reset_lookup_cache()
        try:
            res = tune_autotune.autotune_flash_attention(
                shapes=((1, 4, 512, 64),), causal=True, dtype=jnp.float32,
                include_bwd=True, iters=3, warmup=1)
            info = next(iter(res.values()))
            if "best" in info:
                result["value"] = info["speedup_vs_default"]
                result["tuned_block_q"] = info["best"]["block_q"]
                result["tuned_block_k"] = info["best"]["block_k"]
                result["tune_candidates"] = len(info["rows"])
            if info.get("partial"):
                result["notes"].append("autotune_sweep_partial")
        except Exception as e:
            result["notes"].append(
                f"autotune_failed: {type(e).__name__}: {e}"[:300])
        finally:
            pt.core.config.set_flags(tune_cache_dir="", autotune=False)
            tune_autotune.reset_lookup_cache()

        # -- warm restart: cold child populates cache+manifest, warm replays
        cache_dir = os.path.join(tmp, "restart")
        times = {}
        for mode in ("cold", "warm"):
            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--tune-child", cache_dir, mode],
                    timeout=300, capture_output=True, text=True, cwd=_REPO,
                    env=dict(os.environ),
                )
                sys.stderr.write(proc.stderr[-1500:])
                for line in reversed(proc.stdout.strip().splitlines()):
                    try:
                        parsed = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if parsed.get("metric") == "warm_restart_child":
                        times[mode] = parsed
                        break
            except subprocess.TimeoutExpired:
                result["notes"].append(f"tune_child_{mode}_timed_out")
        if "cold" in times and "warm" in times:
            cold_s = times["cold"]["compile_seconds"]
            warm_s = times["warm"]["compile_seconds"]
            result["cold_compile_seconds"] = cold_s
            result["warm_restart_compile_seconds"] = warm_s
            result["warm_restart_compile_speedup"] = round(
                cold_s / max(warm_s, 1e-9), 2)
            if times["cold"]["aot_cache_sizes"] != times["warm"]["aot_cache_sizes"]:
                result["notes"].append("prewarm_aot_set_mismatch")
        else:
            result["notes"].append("warm_restart_children_incomplete")
    except Exception as e:  # same robustness contract as main(): always JSON
        result["notes"].append(f"tune_failed: {type(e).__name__}: {e}"[:300])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(result))
    return result


_REPO = os.path.dirname(os.path.abspath(__file__))


def _run_child(extra_env: dict, timeout: float):
    """Run a measurement child; returns parsed JSON dict or None."""
    env = {**os.environ, **extra_env}
    stdout, stderr = "", ""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            env=env,
            cwd=_REPO,
            timeout=timeout,
            capture_output=True,
            text=True,
        )
        stdout, stderr = proc.stdout, proc.stderr
        rc = proc.returncode
    except subprocess.TimeoutExpired as te:
        # the child prints interim JSON after every section — salvage the
        # newest line instead of discarding the whole (possibly TPU!) run
        print(f"bench child timed out after {timeout:.0f}s (salvaging)", file=sys.stderr)
        stdout = te.stdout.decode() if isinstance(te.stdout, bytes) else (te.stdout or "")
        stderr = te.stderr.decode() if isinstance(te.stderr, bytes) else (te.stderr or "")
        rc = -1
    sys.stderr.write(stderr[-2000:])
    for line in reversed(stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
            if isinstance(parsed, dict) and "metric" in parsed:
                return parsed
        except (json.JSONDecodeError, ValueError):
            continue
    print(f"bench child rc={rc}, no JSON found", file=sys.stderr)
    return None


def main() -> dict:
    budget = float(os.environ.get("PT_BENCH_BUDGET_S", "900"))
    child_budget = min(float(os.environ.get("PT_BENCH_CHILD_CAP_S", "480")), budget * 0.75)
    result = _run_child(
        {"PT_BENCH_CHILD_BUDGET_S": str(child_budget * 0.85)}, timeout=child_budget
    )
    if result is None:
        sys.exit(1)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    if "--child" in sys.argv:
        child_main(tiny="--tiny" in sys.argv, force_cpu="--cpu" in sys.argv)
    elif "--tune-child" in sys.argv:
        i = sys.argv.index("--tune-child")
        tune_child_main(sys.argv[i + 1], sys.argv[i + 2])
    elif "--tune" in sys.argv:
        tune_main()
    elif "--serve-group" in sys.argv:
        serve_group_main(
            n_requests=int(os.environ.get("PT_BENCH_GROUP_REQS", "16")))
    elif "--serve-disagg" in sys.argv:
        serve_disagg_main(
            n_rounds=int(os.environ.get("PT_BENCH_DISAGG_ROUNDS", "4")))
    elif "--serve-host-tier" in sys.argv:
        serve_host_tier_main(
            n_rounds=int(os.environ.get("PT_BENCH_HOST_TIER_ROUNDS", "3")))
    elif "--serve-decode" in sys.argv:
        serve_decode_main(
            n_requests=int(os.environ.get("PT_BENCH_DECODE_REQS", "24")))
    elif "--serve" in sys.argv:
        serve_main(
            duration_s=float(os.environ.get("PT_BENCH_SERVE_S", "3")),
            tenant_mix=("--tenants" in sys.argv
                        or os.environ.get("PT_BENCH_TENANT_MIX") == "1"),
        )
    else:
        main()
