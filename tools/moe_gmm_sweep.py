"""Sweep the ``moe_gmm`` kernel's column block on the attached chip and time
the two serving programs of a ``latent_moe_lm`` cell around it.

    python tools/moe_gmm_sweep.py [--workload sarvam_105b.serve_docs32] [--programs 1]

Prints, per (rows an expert, K, N), milliseconds a call for every candidate
``tn`` and for XLA's ragged dot over the same rows: the winner is the row for
``ops/pallas/moe.py::_TUNED_BLOCKS``. With ``--programs 1`` it also compiles
the cell's decode step and prefill chunk on seeded weights and times each
(the chunk in both forms of the attention, heads a block). Results also go to
``chiprun_out/moe_gmm_sweep.json``. On the chip only: there is no CPU
fallback."""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, n=20):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def sweep_kernel(out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import moe as pmoe

    E, per_token = 32, 8
    key = jax.random.PRNGKey(0)
    for tokens, tm in ((32, 16), (512, 32), (512, 64)):
        for k, n in ((4096, 2048), (2048, 4096)):
            # the cell's routing: each token 8 of 128, a quarter of them held
            rng = np.random.default_rng(tokens)
            experts = jnp.asarray(np.stack([rng.choice(128, per_token, replace=False)
                                            for _ in range(tokens)]).astype(np.int32))
            lay = moe.share_layout(experts, (0, E), tm)
            rows = lay.src.shape[0]
            x = jax.random.normal(key, (rows, k), jnp.bfloat16)
            w = (jax.random.normal(key, (E, k, n), jnp.float32) * 0.02).astype(jnp.bfloat16)
            exact = jax.jit(lambda x, w, g: jax.lax.ragged_dot(
                x.astype(jnp.float32), w.astype(jnp.float32), g,
                precision=jax.lax.Precision.HIGHEST))(x, w, lay.padded)
            valid = np.arange(rows) < int(lay.padded.sum())
            row = {"tokens": tokens, "tm": tm, "k": k, "n": n, "rows": rows,
                   "pairs": int(lay.load.sum()), "hit": int((lay.load > 0).sum()), "tn": {}}
            for tn in (256, 512, 1024, 2048, 4096):
                if n % tn or k * tn * 2 > 16 * 2**20:
                    continue
                fn = jax.jit(functools.partial(pmoe.moe_gmm, tm=tm, tn=tn))
                got = np.asarray(fn(x, w, lay.tile_expert, lay.used))
                err = float(np.abs(got[valid] - np.asarray(exact)[valid]).max())
                row["tn"][tn] = {"ms": timed(fn, x, w, lay.tile_expert, lay.used), "max_err": err}
            row["ragged_dot_ms"] = timed(jax.jit(pmoe.moe_gmm_xla), x, w, lay.padded)
            row["least_ms"] = (row["hit"] * k * n * 2 + row["pairs"] * (k * 2 + n * 4)) / 819e9 * 1e3
            print(json.dumps(row), flush=True)
            out.append(row)


def time_programs(workload, out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, weights
    from benchmarks.drivers import serve_closed
    from paddle_tpu import models

    loaded = harness.load_cell(workload)
    run = harness.Run(loaded, jax.devices()[:1], 7, 0.0, False, time.perf_counter())
    family, _, shapes = serve_closed.prepare(run)
    params = weights.make_weights(shapes, 7)
    _, base = family.build_model(run.config, run.config["model"]["max_len"], "serve")
    eng = run.mix["engine"]
    S, page, C = eng["max_slots"], eng["page_size"], eng["prefill_chunk"]
    P = eng["max_context"] // page
    progs = models.serving_programs(base)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(1 + np.arange(S * P, dtype=np.int32).reshape(S, P))
    positions = jnp.asarray(rng.integers(1024, 13000, S).astype(np.int32))
    tokens = jnp.asarray(rng.integers(1, base["vocab"], S).astype(np.int32))
    chunk = jnp.asarray(rng.integers(1, base["vocab"], C).astype(np.int32))
    # score_mib: the scores a head block may hold (head_block_for's constant,
    # which this sweep is there to set): 2048 is all 64 heads of a chunk at once
    variants = [("step", {}), ("chunk", {}), ("chunk", {"score_mib": 2048}),
                ("chunk", {"score_mib": 256}), ("chunk", {"form": "expanded"}),
                ("chunk", {"form": "expanded", "score_mib": 256})]
    from paddle_tpu.models import latent_moe_lm

    cfg, rule = base, latent_moe_lm._SCORE_BYTES
    for which, over in variants:
        latent_moe_lm._SCORE_BYTES = over.get("score_mib", rule >> 20) << 20  # read at the trace
        (spec,) = progs.cache_specs(cfg, max_slots=S, num_pages=1 + S * P, page_size=page,
                                    dtype=jnp.bfloat16)
        pages = jnp.zeros(spec.shape, spec.dtype)
        fn = (progs.decode_step if which == "step"
              else functools.partial(progs.prefill_chunk, form=over.get("form", "absorbed")))
        jitted = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                         donate_argnames=progs.cache_args)
        args = ((tokens, positions, tables) if which == "step"
                else (chunk, jnp.int32(8192), jnp.int32(C - 1), tables[0]))
        t0 = time.perf_counter()
        try:
            tok, pages, load = jitted(params, *args, pages, None)
            jax.block_until_ready(tok)
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(10):
                tok, pages, load = jitted(params, *args, pages, None)
            jax.block_until_ready(tok)
        except Exception as e:  # a variant that does not fit is a finding, not a stop
            row = {"program": which, "over": over, "error": f"{type(e).__name__}: {e}"[:400]}
            print(json.dumps(row), flush=True)
            out.append(row)
            del pages, jitted
            continue
        row = {"program": which, "over": over, "ms": (time.perf_counter() - t0) / 10 * 1e3,
               "compile_s": compile_s, "pairs": int(np.asarray(load).sum()),
               "hit": int(np.count_nonzero(np.asarray(load))),
               "peak_bytes": (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")}
        print(json.dumps(row), flush=True)
        out.append(row)
        del pages, jitted


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="sarvam_105b.serve_docs32")
    ap.add_argument("--programs", type=int, default=0)
    ap.add_argument("--kernel", type=int, default=1)
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU: this sweep times the chip", file=sys.stderr)
        return 3
    out = []
    if args.kernel:
        sweep_kernel(out)
    if args.programs:
        time_programs(args.workload, out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_gmm_sweep.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
