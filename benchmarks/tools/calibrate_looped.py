"""``tools/calibrate_layerwise.py`` for a cell of the ``serve_closed_looped``
driver: over ``--seeds`` what sound runs of the program give against the
reference walked pass by pass, over ``--control-seeds`` the gap of the token
the fp8 reference puts first, and over ``--fault-seeds`` what a program with
a fault of the mechanism planted in it gives (the reference stays sound):

* ``passes``: the program runs one pass fewer than the configuration says;
* ``planes``: pass ``r`` writes and attends the planes of pass ``r - 1``
  (pass 0 its own), so two passes share their keys and values.

Every variant is held to the cell's own limits, as a run of the cell holds
the program (``correct``: the control and both faults must read false). Not
part of a benchmark run. One JSON line per seed and variant to ``--out``.

    python3 benchmarks/tools/calibrate_looped.py --workload ouro_2_6b.serve_reason8 \\
        --seeds 101,102,103 --control-seeds 101 --fault-seeds 101 --faults passes,planes
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@contextlib.contextmanager
def planted(family, fault):
    """The program with ``fault`` in it while this is open."""
    import jax.numpy as jnp

    from paddle_tpu.models import looped_lm

    build, plane = family.build_model, looped_lm._plane
    if fault == "passes":
        def fewer(config, seq_len, mode):
            return build(dict(config, total_ut_steps=config["total_ut_steps"] - 1), seq_len, mode)

        family.build_model = fewer
    elif fault == "planes":
        looped_lm._plane = lambda r, i, n_layers: plane(jnp.maximum(r - 1, 0), i, n_layers)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        family.build_model, looped_lm._plane = build, plane


def summary(gaps, limits: dict) -> dict:
    """What a run of the cell compares, each beside the cell's limit, and
    the quantiles of the gaps."""
    import numpy as np

    from benchmarks import check
    from benchmarks.drivers.serve_closed_experts import far_share

    g = np.sort(np.asarray(gaps))
    pick = lambda q: float(g[min(int(q * len(g)), len(g) - 1)])
    checks = [check.compared(n, v, limits[n]) for n, v in (
        ("served_gap_sigmas", float(g[-1])), ("served_far_share", far_share(gaps)))]
    out = {c["name"]: c["value"] for c in checks}
    out.update(correct=all(c["ok"] for c in checks), limits={c["name"]: c["limit"] for c in checks},
               tokens=len(g), off_best=int((g > 0).sum()), p50=pick(0.5), p90=pick(0.9),
               p99=pick(0.99))
    return out


def serve_seed(run, control: bool, fault) -> dict:
    """{variant: summary} of one pass of the cell's traffic."""
    from benchmarks.drivers import serve_closed, serve_closed_experts, serve_closed_looped

    with serve_closed_experts.checkpoint_weights():
        family, per_client, shapes = serve_closed.prepare(run)
    with planted(family, fault):
        seen = serve_closed.serve(run, family, per_client, shapes)
    sample = serve_closed.sample_requests(seen["finished"], run.mix["check_requests"], run.seed)
    gaps = serve_closed_looped.served_gaps(run, family, shapes, sample,
                                           ("f32", "fp8") if control else ("f32",))
    out = {f"fault {fault}" if fault else "sound": dict(
        summary(gaps["f32"], run.limits), finished=len(seen["finished"]), failed=seen["failed"],
        leaks=seen["leaks"])}
    if control:
        out["fp8"] = summary(gaps["fp8"], run.limits)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="passes,planes")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="chiprun_out/calibrate_looped.jsonl")
    args = ap.parse_args()
    from benchmarks import harness

    loaded = harness.load_cell(args.workload)
    devices = harness.require_devices(loaded["cell"]["chips"])
    from paddle_tpu.core.config import apply_compile_cache

    apply_compile_cache(default_dir=os.path.join(harness.CACHE_DIR, "jax"))
    ints = lambda s: [int(x) for x in s.split(",") if x]
    for seed in ints(args.seeds):
        for fault in [None] + (args.faults.split(",") if seed in ints(args.fault_seeds) else []):
            t0 = time.perf_counter()
            run = harness.Run(loaded, devices, seed, args.seconds, False, t0)
            run.listen_for_compiles()
            control = fault is None and seed in ints(args.control_seeds)
            for variant, q in serve_seed(run, control, fault).items():
                rec = dict(q, workload=args.workload, seed=seed, variant=variant,
                           took_s=time.perf_counter() - t0)
                print(json.dumps(rec), flush=True)
                os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
