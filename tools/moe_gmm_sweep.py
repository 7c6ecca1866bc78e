"""Sweep the ``moe_gmm`` kernel's column block on the attached chip and time
the two serving programs of a ``latent_moe_lm`` cell around it.

    python tools/moe_gmm_sweep.py [--workload sarvam_105b.serve_docs32] [--programs 1]
    python tools/moe_gmm_sweep.py --shapes latent    (128 of 512 held, 22 a token,
                                                      1024 x 2688 and 2688 x 1024)

Prints, per (rows an expert, K, N), milliseconds a call for every candidate
``tn`` and for XLA's ragged dot over the same rows: the winner is the row for
``ops/pallas/moe.py::_TUNED_BLOCKS``. With ``--programs 1`` it also compiles
the cell's decode step and prefill chunk on seeded weights and times each
(through the kernels over live pages and with the table gathered; the chunk
at three positions and in its expanded form). Results also go to
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


# (experts held, the router's width, experts a token, (tokens, row tile) of a
# step and of a chunk, the two products' (K, N)): a cell's calls
SHAPES = {
    "swiglu": (32, 128, 8, ((32, 16), (512, 32), (512, 64)), ((4096, 2048), (2048, 4096))),
    "latent": (128, 512, 22, ((64, 16), (512, 32), (512, 64)), ((1024, 2688), (2688, 1024))),
}


def sweep_kernel(out, shapes="swiglu"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import moe as pmoe

    E, width, per_token, calls, products = SHAPES[shapes]
    key = jax.random.PRNGKey(0)
    for tokens, tm in calls:
        for k, n in products:
            # the cell's routing: each token its share of the router's width, a quarter held
            rng = np.random.default_rng(tokens)
            experts = jnp.asarray(np.stack([rng.choice(width, per_token, replace=False)
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
            for tn in (128, 256, 384, 512, 896, 1024, 2048, 2688, 4096):
                if n % tn or k * tn * 2 > 16 * 2**20 or (tn < 256 and shapes == "swiglu"):
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
    # the cell's programs as a TPU lowers them (the absorbed core through the
    # kernels over live pages), then with the kernels' rule answered no: the
    # table gathered and scored, 16 heads a block in the chunk (what the cell
    # ran until PR 44), and the chunk's expanded form, which has no kernel
    variants = [("step", {}), ("step", {"gather": 1}),
                ("chunk", {"at": (3072, 8192, 12288 - C)}), ("chunk", {"gather": 1}),
                ("chunk", {"form": "expanded"})]
    from paddle_tpu.models import transformer_lm

    cfg, rule = base, transformer_lm.step_attends_in_kernel
    for which, over in variants:
        # read at the trace
        transformer_lm.step_attends_in_kernel = (lambda *a: False) if over.get("gather") else rule
        (spec,) = progs.cache_specs(cfg, max_slots=S, num_pages=1 + S * P, page_size=page,
                                    dtype=jnp.bfloat16)
        pages = jnp.zeros(spec.shape, spec.dtype)
        fn = (progs.decode_step if which == "step"
              else functools.partial(progs.prefill_chunk, form=over.get("form", "absorbed")))
        jitted = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                         donate_argnames=progs.cache_args)
        for pos0 in over.get("at", (8192,)):  # one program: a chunk's position is an argument
            args = ((tokens, positions, tables) if which == "step"
                    else (chunk, jnp.int32(pos0), jnp.int32(C - 1), tables[0]))
            row = {"program": which, "over": {k: v for k, v in over.items() if k != "at"}}
            if which == "chunk":
                row["pos0"] = pos0
            t0 = time.perf_counter()
            try:
                tok, pages, load = jitted(params, *args, pages, None)
                jax.block_until_ready(tok)
                row["first_call_s"] = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _ in range(10):
                    tok, pages, load = jitted(params, *args, pages, None)
                jax.block_until_ready(tok)
            except Exception as e:  # a variant that does not fit is a finding, not a stop
                row["error"] = f"{type(e).__name__}: {e}"[:400]
                print(json.dumps(row), flush=True)
                out.append(row)
                break
            row.update(ms=(time.perf_counter() - t0) / 10 * 1e3, pairs=int(np.asarray(load).sum()),
                       hit=int(np.count_nonzero(np.asarray(load))),
                       peak_bytes=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))
            print(json.dumps(row), flush=True)
            out.append(row)
        del pages, jitted
    transformer_lm.step_attends_in_kernel = rule


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="sarvam_105b.serve_docs32")
    ap.add_argument("--programs", type=int, default=0)
    ap.add_argument("--kernel", type=int, default=1)
    ap.add_argument("--shapes", choices=sorted(SHAPES), default="swiglu")
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU: this sweep times the chip", file=sys.stderr)
        return 3
    out = []
    if args.kernel:
        sweep_kernel(out, args.shapes)
    if args.programs:
        time_programs(args.workload, out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_gmm_sweep.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
