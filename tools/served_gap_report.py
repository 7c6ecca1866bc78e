"""How far an expert cell's served tokens lie from the plain reference, as a
distribution and not only the two numbers that decide ``correct``, for sound
runs, for the fp8 control, and for a fault planted in the held experts: what
the cell's limits are set from. ``benchmarks/tools/calibrate_layerwise.py``'s
procedure (the cell's own traffic, a window of ``--seconds``, the sample and
the layerwise reference of a benchmark run) under the ``serve_closed_experts``
driver's weights.

    python tools/served_gap_report.py --workload sarvam_105b.serve_docs32 \\
        --seeds 11,12,13 [--control-seeds 11] [--fault-seeds 11,12 --fault drop:5,drop1:5]

``--fault`` plants one fault in the program's weights after they are loaded
(the reference keeps the sound ones), in every expert layer, or with a ``1``
after the kind in the first only: ``drop:E`` zeroes held expert ``E``'s last
projection (its term is lost: a row tile the kernel skipped), ``swap:E``
exchanges held experts ``E`` and ``E + 1`` (each token's term comes from the
neighbour's weights: a wrong ``tile_expert``). One JSON line per seed and
variant, with the device it ran on, also appended to ``--out``. On the chip
only. Not part of a benchmark run."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def quantiles(gaps) -> dict:
    import numpy as np

    from benchmarks.drivers import serve_closed_experts

    g = np.sort(np.asarray(gaps))
    pick = lambda q: float(g[min(int(q * len(g)), len(g) - 1)])
    return {"tokens": len(g), "served_far_share": serve_closed_experts.far_share(gaps),
            "served_gap_sigmas": float(g[-1]), "off_best": int((g > 0).sum()),
            "p50": pick(0.5), "p90": pick(0.9), "p99": pick(0.99),
            "over_1": int((g > 1.0).sum())}


def planted(params: dict, cfg: dict, fault: str) -> dict:
    import jax.numpy as jnp

    kind, e = fault.split(":")
    e = int(e)
    layers = range(cfg["first_dense"], cfg["n_layers"])
    for i in (layers[:1] if kind.endswith("1") else layers):
        m = f"layer_{i}/moe/experts"
        if kind.rstrip("1") == "drop":
            params[f"{m}/fc2/w"] = params[f"{m}/fc2/w"].at[e].set(0)
        elif kind.rstrip("1") == "swap":
            for w in ("gate", "fc1", "fc2"):
                a = params[f"{m}/{w}/w"]
                params[f"{m}/{w}/w"] = a.at[jnp.array([e, e + 1])].set(a[jnp.array([e + 1, e])])
        else:
            raise ValueError(f"unknown fault {fault!r}")
    return params


def serve_and_compare(run, fault, control: bool) -> dict:
    """{variant: quantiles} of one pass of the cell's traffic."""
    from benchmarks.drivers import serve_closed, serve_closed_experts, serve_closed_layerwise
    from paddle_tpu.models import latent_moe_lm

    with serve_closed_experts.checkpoint_weights():
        family, per_client, shapes = serve_closed.prepare(run)
    load = latent_moe_lm.stack_experts
    if fault:
        latent_moe_lm.stack_experts = lambda params, cfg: planted(load(params, cfg), cfg, fault)
    try:
        seen = serve_closed.serve(run, family, per_client, shapes)
    finally:
        latent_moe_lm.stack_experts = load
    sample = serve_closed.sample_requests(seen["finished"], run.mix["check_requests"], run.seed)
    gaps = serve_closed_layerwise.served_gaps(run, family, shapes, sample,
                                              ("f32", "fp8") if control else ("f32",))
    out = {f"fault {fault}" if fault else "sound": dict(
        quantiles(gaps["f32"]), finished=len(seen["finished"]), failed=seen["failed"])}
    if control:
        out["fp8"] = quantiles(gaps["fp8"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fault", default="drop:5")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="chiprun_out/served_gap_report.jsonl")
    args = ap.parse_args()
    from benchmarks import harness
    from paddle_tpu.core.config import apply_compile_cache

    loaded = harness.load_cell(args.workload)
    devices = harness.require_devices(loaded["cell"]["chips"])
    apply_compile_cache(default_dir=os.path.join(harness.CACHE_DIR, "jax"))
    ints = lambda s: [int(x) for x in s.split(",") if x]
    for seed in ints(args.seeds):
        for fault in [None] + (args.fault.split(",") if seed in ints(args.fault_seeds) else []):
            t0 = time.perf_counter()
            run = harness.Run(loaded, devices, seed, args.seconds, False, t0)
            control = fault is None and seed in ints(args.control_seeds)
            for variant, q in serve_and_compare(run, fault, control).items():
                rec = dict(q, workload=args.workload, seed=seed, variant=variant,
                           platform=devices[0].platform, device_kind=devices[0].device_kind,
                           took_s=time.perf_counter() - t0)
                print(json.dumps(rec), flush=True)
                os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
