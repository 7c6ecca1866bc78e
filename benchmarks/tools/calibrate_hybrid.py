"""``tools/calibrate_layerwise.py`` for a cell of the ``serve_closed_hybrid``
driver: over ``--seeds`` what sound runs of the program give against the
reference walked layer by layer, over ``--control-seeds`` the gap of the token
the fp8 reference puts first, and over ``--fault-seeds`` what a program with a
fault of the mechanism planted in it gives (the reference stays sound):

* ``state``: a prefill chunk drops the SSM state it was handed (starts every
  chunk from zeros);
* ``tail``: a prefill chunk drops the convolution tail it was handed;
* ``reset``: a slot is not started over on admission (a chunk at position 0
  reads the tails and states the slot's last request left);
* ``planes``: attention layer ``j`` writes and attends the pages of layer
  ``j - 1`` (layer 0 its own), so two layers share their keys and values;
* ``scale``: the scores are scaled by ``1 / sqrt(head_dim)``, not by the
  configuration's ``attention_multiplier``.

Every variant is held to the cell's own limits, as a run of the cell holds
the program (``correct``: the control and every fault must read false). Not
part of a benchmark run. One JSON line per seed and variant to ``--out``.

    python3 benchmarks/tools/calibrate_hybrid.py --workload granite_4_0_h_micro.serve_chat64 \\
        --seeds 101,102 --control-seeds 101 --fault-seeds 101 --seconds 10
"""

import argparse
import contextlib
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

FAULTS = ("state", "tail", "reset", "planes", "scale")


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` in it while this is open."""
    import jax.numpy as jnp

    from paddle_tpu.models import hybrid_ssm_lm as hm

    real = {n: getattr(hm, n) for n in ("ssm_chunked", "_via_chunk", "_via_step", "_score_scale")}
    shared = lambda via: types.SimpleNamespace(
        window=via.window, scan=via.scan,
        attend=lambda j, *a: via.attend(max(j - 1, 0), *a))
    if fault == "state":
        hm.ssm_chunked = lambda x, dt, a, b, c, h0, **kw: real["ssm_chunked"](
            x, dt, a, b, c, jnp.zeros_like(h0), **kw)
    elif fault == "tail":
        def via_chunk(cfg, *args):
            via = real["_via_chunk"](cfg, *args)
            cut = lambda j, xbc: via.window(j, xbc).at[:, :cfg["ssm_conv"] - 1].set(0.0)
            return types.SimpleNamespace(window=cut, scan=via.scan, attend=via.attend)

        hm._via_chunk = via_chunk
    elif fault == "reset":
        def via_chunk(cfg, cache, table, slot, pos0, *rest):
            via = real["_via_chunk"](cfg, cache, table, slot, pos0, *rest)
            # the tails and states as a chunk that opens no sequence reads them
            later = real["_via_chunk"](cfg, cache, table, slot, pos0 + 1, *rest)
            return types.SimpleNamespace(window=later.window, scan=later.scan, attend=via.attend)

        hm._via_chunk = via_chunk
    elif fault == "planes":
        hm._via_chunk = lambda *a: shared(real["_via_chunk"](*a))
        hm._via_step = lambda *a: shared(real["_via_step"](*a))
    elif fault == "scale":
        hm._score_scale = lambda cfg: real["_score_scale"](dict(cfg, attention_multiplier=None))
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    try:
        yield
    finally:
        for n, f in real.items():
            setattr(hm, n, f)


def summary(gaps, limits: dict) -> dict:
    """What a run of the cell compares, beside the cell's limit, and the
    quantiles of the gaps."""
    import numpy as np

    from benchmarks import check

    g = np.sort(np.asarray(gaps))
    pick = lambda q: float(g[min(int(q * len(g)), len(g) - 1)])
    c = check.compared("served_gap_sigmas", float(g[-1]), limits["served_gap_sigmas"])
    return {"served_gap_sigmas": c["value"], "limit": c["limit"], "correct": c["ok"],
            "tokens": len(g), "off_best": int((g > 0).sum()), "p50": pick(0.5),
            "p90": pick(0.9), "p99": pick(0.99)}


def serve_seed(run, control: bool, fault) -> dict:
    """{variant: summary} of one pass of the cell's traffic."""
    from benchmarks.drivers import serve_closed, serve_closed_hybrid

    family, per_client, shapes = serve_closed.prepare(run)
    with planted(fault):
        seen = serve_closed.serve(run, family, per_client, shapes)
    sample = serve_closed.sample_requests(seen["finished"], run.mix["check_requests"], run.seed)
    gaps = serve_closed_hybrid.served_gaps(run, family, shapes, sample,
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
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="chiprun_out/calibrate_hybrid.jsonl")
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
