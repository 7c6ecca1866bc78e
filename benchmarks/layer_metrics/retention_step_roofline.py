"""Least time the chip could take for the bytes the decode steps'
``retention_step`` calls require (``benchmarks/retention_bytes.py`` over
``peaks.json``'s ``hbm_bytes_per_s``, at the window's mean number of active
slots) over the kernels' summed device time per step. A share of the memory
roofline only: the kernel's arithmetic is float32 on the vector unit, for
which ``peaks.json`` has no peak (its ``bf16_flops`` is the matrix unit's), so
no compute bound is taken; the operations a step and per byte are printed
beside it (1.5 a byte at the published shapes). None where the cell runs no
retention layer or the trace names no such kernel."""

import re

from benchmarks import retention_bytes

# a Mosaic kernel's ``name=`` heads its device-op name
STEP_OP = re.compile(r"^retention_step")


def step_seconds(trace) -> float:
    return sum(s for name, s in trace["ops"].items() if STEP_OP.search(name))


def read(view):
    t, c = view["trace"], view["counters"]
    calls, occupancy = c.get("retention_calls"), c.get("step_occupancy")
    if not t or not calls or not occupancy or not view["peaks"]:
        return None
    sec = step_seconds(t)
    if not sec:
        return None
    # the traced window's steps: the engine's own count of them
    steps = len(c["step_seconds"])
    slots = c["max_slots"] * sum(occupancy) / len(occupancy)
    shape = (slots, calls["layers"], calls["kv_heads"], calls["q_heads"], calls["d"],
             calls["value_width"])
    n_bytes = retention_bytes.retention_step_bytes(*shape)
    t_bytes = n_bytes / view["peaks"]["hbm_bytes_per_s"]
    ops = retention_bytes.retention_step_flops(*shape)
    print(f"retention_step roofline: {t_bytes * 1e3:.3f} ms of bytes a step at {slots:.2f} "
          f"active slots ({ops / 1e9:.2f} GFLOP of float32 vector work, {ops / n_bytes:.2f} a "
          f"byte); kernels {sec / steps * 1e3:.3f} ms a step over {steps} steps", flush=True)
    return 100.0 * t_bytes / (sec / steps)
