"""Least time the chip could take for the step's flash forward and backward
calls (the larger of operations over peak FLOP/s and bytes over peak bytes/s,
from ``benchmarks/flops.py``) over the kernels' summed device time per step.
The bound that holds is printed."""

import re

from benchmarks import flops

# the trace names a Mosaic kernel only by its call target; the flash forward and
# the two backward kernels are the only ones in these steps
FLASH_OP = re.compile(r"tpu_custom_call")


def flash_seconds(trace) -> float:
    return sum(s for name, s in trace["ops"].items() if FLASH_OP.search(name))


def read(view):
    t, calls = view["trace"], view["counters"].get("flash_calls")
    if not t or not calls or not view["peaks"]:
        return None
    sec = flash_seconds(t)
    if not sec:
        return None
    steps = t["window_s"] / (view["counters"]["step_ms"] / 1e3)
    shape = (calls["b"] // view["chips"], calls["heads"], calls["t"], calls["dh"])
    ops = calls["calls"] * (flops.flash_fwd_flops(*shape, calls["causal"])
                            + flops.flash_bwd_flops(*shape, calls["causal"]))
    byts = calls["calls"] * (flops.flash_fwd_bytes(*shape) + flops.flash_bwd_bytes(*shape))
    t_flops = ops / view["peaks"]["bf16_flops"]
    t_bytes = byts / view["peaks"]["hbm_bytes_per_s"]
    print(f"flash roofline: {ops:.3e} FLOPs ({t_flops * 1e3:.3f} ms) and {byts:.3e} bytes "
          f"({t_bytes * 1e3:.3f} ms) a step; bound by "
          f"{'compute' if t_flops >= t_bytes else 'memory'}; kernels {sec / steps * 1e3:.3f} ms a step",
          flush=True)
    return 100.0 * max(t_flops, t_bytes) / (sec / steps)
