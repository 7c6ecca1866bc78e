"""Benchmark driver CLI — the ``fluid_benchmark.py`` equivalent.

Reference: ``benchmark/fluid/fluid_benchmark.py:310`` (main: get_model,
train loop printing examples/sec per pass at ``:295-301``) and
``benchmark/fluid/args.py`` (flag surface). Flags kept with the same names
where they still make sense; GPU-count flags map to chip counts on the mesh
(``--gpus`` → data-parallel devices via DataParallel instead of
ParallelExecutor), ``--update_method nccl2`` maps to multi-host mesh
initialization, and ``--profile`` wraps the timed region in a jax.profiler
trace instead of nvprof.

Usage:
    python -m paddle_tpu.benchmark --model resnet --batch_size 64 \
        --iterations 20 --pass_num 2
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

BENCHMARK_MODELS = [
    "machine_translation",
    "resnet",
    "se_resnext",
    "vgg",
    "mnist",
    "stacked_dynamic_lstm",
    "transformer",
    # decoder-only LM: the long-context flagship (not in the reference's
    # benchmark set — its list ends at the NMT transformer)
    "transformer_lm",
]


def parse_args(argv=None):
    parser = argparse.ArgumentParser("paddle_tpu model benchmarks.")
    parser.add_argument("--model", type=str, choices=BENCHMARK_MODELS, default="resnet")
    parser.add_argument("--batch_size", type=int, default=32, help="per-step GLOBAL batch")
    parser.add_argument("--learning_rate", type=float, default=0.001)
    parser.add_argument("--skip_batch_num", type=int, default=5,
                        help="warmup steps excluded from timing (compile amortization)")
    parser.add_argument("--iterations", type=int, default=80, help="steps per pass")
    parser.add_argument("--pass_num", type=int, default=1)
    parser.add_argument("--device", type=str, default="TPU", choices=["CPU", "TPU"],
                        help="backend to place the benchmark on")
    parser.add_argument("--chips", "--gpus", dest="chips", type=int, default=1,
                        help="data-parallel chips; >1 uses the mesh DataParallel path")
    parser.add_argument("--data_set", type=str, default="flowers",
                        choices=["cifar10", "flowers", "mnist"],
                        help="real-data source for image models (with --use_real_data)")
    parser.add_argument("--infer_only", action="store_true", help="forward only")
    parser.add_argument("--use_real_data", action="store_true",
                        help="feed from paddle_tpu.dataset readers instead of one "
                        "synthetic device-resident batch (the reference's default; "
                        "its --use_fake_data flag is inverted here because fake "
                        "data is the honest default for kernel benchmarking)")
    parser.add_argument("--profile", action="store_true",
                        help="emit a jax.profiler trace for a few steps")
    parser.add_argument("--profile_dir", type=str, default="/tmp/paddle_tpu_profile")
    parser.add_argument("--update_method", type=str, default="local",
                        choices=["local", "collective", "nccl2"],
                        help="'collective'/'nccl2': initialize multi-host distributed mesh")
    parser.add_argument("--no_random", action="store_true")
    parser.add_argument("--json", action="store_true", help="print one JSON line per pass")
    parser.add_argument("--scan_layers", action="store_true",
                        help="transformer/transformer_lm: compile the layer "
                             "stack as one lax.scan body (O(1)-in-depth "
                             "compile; see models.transformer_lm)")
    parser.add_argument("--moe_experts", type=int, default=0,
                        help="transformer_lm: expert-parallel MoE FFN with "
                             "this many experts (0 = dense)")
    return parser.parse_args(argv)


def _make_batch(args, spec, rng):
    """One benchmark batch: synthetic by default; with --use_real_data, drawn
    from the dataset readers (the batch is still device-resident and reused —
    the metric isolates step compute, as the reference's fake-data mode did;
    the full streaming input path lives in paddle_tpu.reader)."""
    if not args.use_real_data:
        return spec.synth_batch(args.batch_size, rng)

    from paddle_tpu import dataset, reader

    def image_batch(creator, reshape):
        r = reader.stack_batch(creator, args.batch_size)
        imgs, labels = next(iter(r()))
        return reshape(imgs), labels.astype(np.int32)

    if args.model == "mnist":
        return image_batch(
            dataset.mnist.train(), lambda im: im.reshape(-1, 28, 28, 1)
        )
    if args.model in ("resnet", "vgg", "se_resnext") and args.data_set == "cifar10":
        return image_batch(
            dataset.cifar.train10(),
            lambda im: im.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1),
        )
    if args.model in ("resnet", "vgg", "se_resnext") and args.data_set == "flowers":
        return image_batch(
            dataset.flowers.train(), lambda im: im.transpose(0, 2, 3, 1)
        )
    if args.model == "machine_translation":
        # the reference NMT benchmark feeds from wmt14
        # (benchmark/fluid/models/machine_translation.py:212); pad the ragged
        # (src, trg_in, trg_next) triples to the model's static layout
        seq_len = 50
        rows = []
        # dict sized to the model's vocab: larger ids would index past the
        # embedding table
        for i, ex in enumerate(dataset.wmt14.train(10000)()):
            if i >= args.batch_size:
                break
            rows.append(ex)
        n = len(rows)
        src = np.zeros((n, seq_len), np.int32)
        trg = np.zeros((n, seq_len), np.int32)
        lab = np.zeros((n, seq_len), np.int32)
        src_lens = np.zeros((n,), np.int32)
        trg_lens = np.zeros((n,), np.int32)
        for i, (s, t, tn) in enumerate(rows):
            s, t, tn = s[:seq_len], t[:seq_len], tn[:seq_len]
            src[i, : len(s)] = s
            trg[i, : len(t)] = t
            lab[i, : len(tn)] = tn
            src_lens[i], trg_lens[i] = len(s), len(t)
        return src, src_lens, trg, lab, trg_lens
    print(
        f"WARNING: no real-data mapping for model={args.model} "
        f"data_set={args.data_set}; using synthetic batches"
    )
    return spec.synth_batch(args.batch_size, rng)


def run_benchmark(args) -> dict:
    import jax

    from paddle_tpu import models, optimizer as opt_mod, tracing
    from paddle_tpu.core import profiler as prof

    if args.update_method in ("collective", "nccl2"):
        from paddle_tpu.parallel.mesh import initialize_distributed

        initialize_distributed()

    model_cfg = {"learning_rate": args.learning_rate}
    if args.model in ("resnet", "vgg", "se_resnext"):
        model_cfg["dataset"] = args.data_set
        if args.data_set == "cifar10":
            model_cfg.update(image_size=32, class_dim=10)
        elif args.data_set == "flowers":
            model_cfg.update(image_size=224, class_dim=102)
    if getattr(args, "scan_layers", False) and args.model in (
        "transformer", "transformer_lm"
    ):
        model_cfg["scan_layers"] = True
    if getattr(args, "moe_experts", 0) and args.model == "transformer_lm":
        model_cfg["moe_experts"] = args.moe_experts
    spec = models.get_model(args.model, **model_cfg)
    rng = np.random.RandomState(0 if args.no_random else None)
    batch = _make_batch(args, spec, rng)
    backend = args.device.lower() if args.device != "TPU" else None
    devices = jax.devices(backend) if backend else jax.devices()

    class _FwdOut:  # step-protocol shim for the forward-only path
        def __init__(self, v, o, loss):
            self.variables, self.opt_state, self.loss = v, o, loss

    if args.chips > 1:
        from paddle_tpu.parallel import DataParallel
        from paddle_tpu.parallel.mesh import make_mesh

        dp = DataParallel(
            spec.model,
            spec.optimizer(),
            mesh=make_mesh({"data": args.chips}, devices=devices[: args.chips]),
        )
        variables, opt_state = dp.init(0, *batch)
        dev_batch = dp.put_batch(*batch)
        if args.infer_only:
            def step(v, o):
                out = dp.eval_step(v, *dev_batch)
                loss = out[0] if isinstance(out, (tuple, list)) else out
                return _FwdOut(v, o, loss)
        else:
            step = lambda v, o: dp.step(v, o, *dev_batch)
    else:
        dev_batch = tuple(jax.device_put(b, devices[0]) for b in batch)
        variables = spec.model.init(0, *batch)
        variables = jax.device_put(variables, devices[0])
        optimizer = spec.optimizer()
        opt_state = optimizer.create_state(variables.params)
        if args.infer_only:
            fwd = jax.jit(lambda v, *b: spec.model.apply(v, *b, is_train=False)[0])

            def step(v, o):
                out = fwd(v, *dev_batch)
                loss = out[0] if isinstance(out, (tuple, list)) else out
                return _FwdOut(v, o, loss)
        else:
            step_fn = jax.jit(optimizer.minimize(spec.model), donate_argnums=(0, 1))
            step = lambda v, o: step_fn(v, o, *dev_batch)

    results = []
    for pass_id in range(args.pass_num):
        out = None
        for _ in range(max(1, args.skip_batch_num)):  # ≥1 warmup to compile
            out = step(variables, opt_state)
            variables, opt_state = out.variables, out.opt_state
        jax.block_until_ready(out)

        profiled = args.profile and pass_id == 0
        ctx = (
            jax.profiler.trace(args.profile_dir)
            if profiled
            else prof.record_event(f"benchmark.pass_{pass_id}")
        )
        t0 = time.perf_counter()
        with ctx:
            if profiled:
                # instrumented loop: per-step host dispatch vs device wait,
                # synced each step so the phases are attributable (reference
                # device_tracer correlated kernel/memcpy timeline)
                prof.enable_profiler()
                for _ in range(args.iterations):
                    with prof.record_event("benchmark.step_dispatch"):
                        out = step(variables, opt_state)
                        variables, opt_state = out.variables, out.opt_state
                    with prof.record_event("benchmark.device_wait"):
                        float(jax.device_get(out.loss))
            else:
                for _ in range(args.iterations):
                    out = step(variables, opt_state)
                    variables, opt_state = out.variables, out.opt_state
                float(jax.device_get(out.loss))
        dt = time.perf_counter() - t0
        if profiled:
            timeline = tracing.export_chrome_trace(
                os.path.join(args.profile_dir, "timeline.chrome.json")
            )
            breakdown = prof.step_breakdown()
            print(f"timeline: {timeline}")
            for phase, mean_s in sorted(breakdown.items(), key=lambda kv: -kv[1]):
                print(f"  {phase:24s} {mean_s * 1e3:9.3f} ms/step")
        examples_per_sec = args.batch_size * args.iterations / dt
        record = {
            "pass": pass_id,
            "model": args.model,
            "batch_size": args.batch_size,
            "chips": args.chips,
            "examples_per_sec": round(examples_per_sec * spec.examples_per_row, 2),
            "unit": spec.unit,
            "last_loss": float(out.loss),
            "elapsed_sec": round(dt, 3),
        }
        results.append(record)
        if args.json:
            print(json.dumps(record))
        else:
            print(
                f"Pass: {pass_id}, Loss: {record['last_loss']:.5f}, "
                f"Speed: {record['examples_per_sec']:.2f} {spec.unit}"
            )
    return results[-1]


def main(argv=None):
    return run_benchmark(parse_args(argv))


if __name__ == "__main__":
    main()
