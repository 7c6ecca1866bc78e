"""Read, on the chip and in one process, the numbers a cell's limits are set
from: over ``--seeds`` what sound runs of the program give against the plain
reference, and over ``--control-seeds`` what the control gives: the reference
in the program's place, one precision lower (fp8 operands). Not part of a
benchmark run. Writes one JSON line per seed to ``--out``.

    python3 benchmarks/tools/calibrate.py --workload lm_big.train_2k \\
        --seeds 101,102,103 --control-seeds 101,102,103 --out chiprun_out/calib.jsonl
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def train_seed(run, control: bool) -> dict:
    from benchmarks import check
    from benchmarks.drivers import train_pool

    family, pool, model, shapes = train_pool.prepare(run)
    st, _ = train_pool.program_walk(run, family, pool, model, shapes)
    prog = train_pool.program_readings(st)
    ref = train_pool.reference_walk(run, family, pool, shapes, keep_first_grad=control,
                                    compare_first_grad=st.pop("first_grad"))
    first = ref.pop("first_grad", None)
    out = {"program": check.train_readings(prog, ref), "losses": [prog["losses"], ref["losses"]]}
    if control:
        low, diff = train_pool.control_walk(run, family, pool, shapes, first)
        out["control"] = check.train_readings(low, dict(ref, grad_diff_norms=diff))
        out["losses"].append(low["losses"])
    return out


def serve_seed(run, control: bool) -> dict:
    from benchmarks.drivers import serve_closed

    family, per_client, shapes = serve_closed.prepare(run)
    seen = serve_closed.serve(run, family, per_client, shapes)
    sample = serve_closed.sample_requests(seen["finished"], run.mix["check_requests"], run.seed)
    gaps = serve_closed.served_gaps(run, family, shapes, sample,
                                    ("f32", "fp8") if control else ("f32",))
    out = {"program": {"served_gap_sigmas": max(gaps["f32"]),
                       "exact": sum(g == 0 for g in gaps["f32"]), "tokens": len(gaps["f32"])},
           "finished": len(seen["finished"])}
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
    one = train_seed if loaded["mix"]["driver"] == "train_pool" else serve_seed
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = harness.Run(loaded, devices, seed, args.seconds, False, t0)
        run.listen_for_compiles()
        rec = dict(one(run, seed in controls), workload=args.workload, seed=seed,
                   took_s=time.perf_counter() - t0)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
