"""``tools/calibrate.py`` for a cell of the ``serve_closed_layerwise``
driver: over ``--seeds`` what sound runs of the program give against the
reference walked by layer, and over ``--control-seeds`` the gap of the token
the fp8 reference puts first. Not part of a benchmark run. One JSON line per
seed to ``--out``.

    python3 benchmarks/tools/calibrate_layerwise.py --workload brumby_14b.serve_docs16 \\
        --seeds 101,102,103 --control-seeds 101 --out chiprun_out/calib.jsonl
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def serve_seed(run, control: bool) -> dict:
    from benchmarks.drivers import serve_closed, serve_closed_layerwise

    family, per_client, shapes = serve_closed.prepare(run)
    seen = serve_closed.serve(run, family, per_client, shapes)
    sample = serve_closed.sample_requests(seen["finished"], run.mix["check_requests"], run.seed)
    gaps = serve_closed_layerwise.served_gaps(run, family, shapes, sample,
                                              ("f32", "fp8") if control else ("f32",))
    out = {"program": {"served_gap_sigmas": max(gaps["f32"]),
                       "exact": sum(g == 0 for g in gaps["f32"]), "tokens": len(gaps["f32"])},
           "finished": len(seen["finished"]), "failed": seen["failed"], "leaks": seen["leaks"]}
    if control:
        out["control"] = {"served_gap_sigmas": max(gaps["fp8"]),
                          "exact": sum(g == 0 for g in gaps["fp8"])}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from benchmarks import harness

    loaded = harness.load_cell(args.workload)
    devices = harness.require_devices(loaded["cell"]["chips"])
    from paddle_tpu.core.config import apply_compile_cache

    apply_compile_cache(default_dir=os.path.join(harness.CACHE_DIR, "jax"))
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = harness.Run(loaded, devices, seed, args.seconds, False, t0)
        run.listen_for_compiles()
        rec = dict(serve_seed(run, seed in controls), workload=args.workload, seed=seed,
                   took_s=time.perf_counter() - t0)
        print(json.dumps(rec), flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
