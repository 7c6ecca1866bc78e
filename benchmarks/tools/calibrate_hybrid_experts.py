"""``tools/calibrate_hybrid.py`` for a cell of the ``serve_closed_hybrid_experts``
driver: over ``--seeds`` what sound runs of the program give against the
reference walked layer by layer, over ``--control-seeds`` the gap of the token
the fp8 reference puts first, and over ``--fault-seeds`` what a program with a
fault of the mechanism planted in it gives (the reference stays sound):

* ``groups``: every head reads group 0's ``B`` and ``C`` (prefill and decode);
* ``norm``: the gated norm runs over all channels, not by group;
* ``state``: a prefill chunk drops the SSM state it was handed
  (``calibrate_hybrid``'s, the Mamba-2 body is shared);
* ``relu``: a routed expert applies relu, not relu^2;
* ``unnormalised``: the selected experts' scores are not normalised to sum 1;
* ``held``: the held range is off by one expert (every row meets the weights
  of the expert before its own).

Every variant is held to the cell's own limits, as a run of the cell holds
the program (``correct``: the control and every fault must read false). Not
part of a benchmark run. One JSON line per seed and variant to ``--out``
(default ``chiprun_out/calibrate_hybrid.jsonl``, ``calibrate_hybrid``'s: the
command line is that tool's).

    python3 benchmarks/tools/calibrate_hybrid_experts.py \\
        --workload nemotron_3_super_120b_a12b.serve_chat64_moe \\
        --seeds 101,102 --control-seeds 101 --fault-seeds 101 --seconds 10
"""

import contextlib
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

FAULTS = ("groups", "norm", "state", "relu", "unnormalised", "held")


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` in it while this is open."""
    import jax
    import jax.numpy as jnp

    from benchmarks.tools import calibrate_hybrid
    from paddle_tpu.models import hybrid_ssm_lm as hm
    from paddle_tpu.ops import moe

    if fault == "state" or fault is None:
        with calibrate_hybrid.planted(fault):
            yield
        return
    real = {(hm, n): getattr(hm, n) for n in ("_via_chunk", "_via_step", "group_rms_norm")}
    real.update({(moe, n): getattr(moe, n) for n in ("topk_route", "expert_share_ffn")})
    body = moe.BODIES["relu2"]
    if fault == "groups":
        first = lambda v: jnp.repeat(v[..., :1, :], v.shape[-2], axis=-2)

        def of_group_0(make):
            def via_of(*args):
                via = make(*args)
                return types.SimpleNamespace(
                    window=via.window, attend=via.attend,
                    scan=lambda j, x, dt, a, b, c: via.scan(j, x, dt, a, first(b), first(c)))

            return via_of

        hm._via_chunk, hm._via_step = of_group_0(hm._via_chunk), of_group_0(hm._via_step)
    elif fault == "norm":
        hm.group_rms_norm = lambda x, scale, eps, groups: real[hm, "group_rms_norm"](
            x, scale, eps, 1)
    elif fault == "relu":
        moe.BODIES["relu2"] = lambda gmm, rows, w: gmm(jax.nn.relu(gmm(rows, w["fc1"])), w["fc2"])
    elif fault == "unnormalised":
        def topk_route(scores, bias, k, scaling=1.0):
            route = real[moe, "topk_route"](scores, bias, k, scaling)
            chosen = jnp.take_along_axis(scores.astype(jnp.float32), route.experts, axis=-1)
            return route._replace(weights=chosen * scaling)

        moe.topk_route = topk_route
    elif fault == "held":
        moe.expert_share_ffn = lambda x, route, experts, held, **kw: real[
            moe, "expert_share_ffn"](x, route, experts, (held[0] + 1, held[1]), **kw)
    else:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    try:
        yield
    finally:
        for (module, n), f in real.items():
            setattr(module, n, f)
        moe.BODIES["relu2"] = body


def summary(gaps, limits: dict) -> dict:
    """What a run of the cell compares, each beside the cell's limit, and the
    quantiles of the gaps."""
    import numpy as np

    from benchmarks import check
    from benchmarks.drivers.serve_closed_experts import far_share

    g = np.sort(np.asarray(gaps))
    pick = lambda q: float(g[min(int(q * len(g)), len(g) - 1)])
    checks = [check.compared("served_gap_sigmas", float(g[-1]), limits["served_gap_sigmas"]),
              check.compared("served_far_share", far_share(gaps), limits["served_far_share"])]
    out = {c["name"]: c["value"] for c in checks}
    out.update(limits={c["name"]: c["limit"] for c in checks},
               correct=all(c["ok"] for c in checks), tokens=len(g),
               off_best=int((g > 0).sum()), p50=pick(0.5), p90=pick(0.9), p99=pick(0.99))
    return out


def serve_seed(run, control: bool, fault) -> dict:
    """{variant: summary} of one pass of the cell's traffic."""
    from benchmarks.drivers import (serve_closed, serve_closed_experts,
                                    serve_closed_hybrid_experts, serve_closed_layerwise)

    with serve_closed_experts.checkpoint_weights():
        family, per_client, shapes = serve_closed.prepare(run)
    with serve_closed_hybrid_experts.leaf_at_a_time():
        with planted(fault):
            seen = serve_closed.serve(run, family, per_client, shapes)
        sample = serve_closed.sample_requests(seen["finished"], run.mix["check_requests"],
                                              run.seed)
        gaps = serve_closed_layerwise.served_gaps(run, family, shapes, sample,
                                                  ("f32", "fp8") if control else ("f32",))
    steps, chunks = len(seen["log"]["steps"]), len(seen["log"]["chunks"])
    out = {f"fault {fault}" if fault else "sound": dict(
        summary(gaps["f32"], run.limits), finished=len(seen["finished"]), failed=seen["failed"],
        leaks=seen["leaks"], steps=steps, chunks=chunks)}
    if control:
        out["fp8"] = summary(gaps["fp8"], run.limits)
    return out


def main() -> int:
    """``calibrate_hybrid``'s command line and loop over seeds and variants,
    with this family's pass of the traffic and its faults."""
    from benchmarks.tools import calibrate_hybrid

    calibrate_hybrid.serve_seed, calibrate_hybrid.FAULTS = serve_seed, FAULTS
    calibrate_hybrid.__doc__ = __doc__  # the command's description
    return calibrate_hybrid.main()


if __name__ == "__main__":
    sys.exit(main())
