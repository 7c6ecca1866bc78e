"""Least time the chip could take for the bytes the decode steps' ``ssm_step``
calls require (``benchmarks/ssm_bytes.py`` over ``peaks.json``'s
``hbm_bytes_per_s``, at the window's mean number of active slots) over the
kernels' summed device time per step. A share of the memory roofline only: the
kernel's arithmetic is float32 on the vector unit, for which ``peaks.json`` has
no peak, so no compute bound is taken; the operations a step and per byte are
printed beside it (0.6 a byte at the published shapes). None where the cell
runs no Mamba-2 layer or the trace names no such kernel."""

import re

from benchmarks import ssm_bytes

# a Mosaic kernel's ``name=`` heads its device-op name
STEP_OP = re.compile(r"^ssm_step")


def read(view):
    t, c = view["trace"], view["counters"]
    calls, occupancy = c.get("ssm_calls"), c.get("step_occupancy")
    if not t or not calls or not occupancy or not view["peaks"]:
        return None
    sec = sum(s for name, s in t["ops"].items() if STEP_OP.search(name))
    if not sec:
        return None
    steps = len(c["step_seconds"])  # the traced window's steps: the engine's own count
    slots = c["max_slots"] * sum(occupancy) / len(occupancy)
    n_bytes = ssm_bytes.ssm_step_bytes(calls, slots)
    t_bytes = n_bytes / view["peaks"]["hbm_bytes_per_s"]
    ops = ssm_bytes.ssm_step_flops(calls, slots)
    print(f"ssm_step roofline: {t_bytes * 1e3:.3f} ms of bytes a step at {slots:.2f} active "
          f"slots ({ops / 1e9:.2f} GFLOP of float32 vector work, {ops / n_bytes:.2f} a byte); "
          f"kernels {sec / steps * 1e3:.3f} ms a step over {steps} steps", flush=True)
    return 100.0 * t_bytes / (sec / steps)
