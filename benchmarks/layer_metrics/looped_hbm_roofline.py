"""Least time the chip could take for the traced window's decode steps and
prefill chunks of a looped decoder over the device's busy time in that
window. A call's least time is the larger of its required bytes over
``peaks.json``'s ``hbm_bytes_per_s`` and its operations over ``bf16_flops``
(``benchmarks/loop_bytes.py``), at the counts the program's spans carry:
every ``serving.decode.model_step`` span of the window says how many slots
decoded (``active``) and how many cache positions they attended
(``live_rows``), every ``serving.decode.prefill`` span of the loop which
chunk it was and up to which position it attended. Only what is required is
counted, so the share cannot pass 100 %. None where the cell runs no looped
model, the spans carry no counts or nothing was traced."""

from benchmarks import loop_bytes


def window_calls(view):
    """{"steps": [(active, live_rows)], "chunks": [(pos0, live_rows)]} of the
    window: the steps are the run ``loop_iteration_ms`` finds, the chunks the
    loop's own between the first of them and the last."""
    from paddle_tpu import tracing

    want = view["counters"].get("step_seconds")
    if not want:
        return None
    spans = tracing.spans()
    model = sorted((s for s in spans if s.name == "serving.decode.model_step"
                    and "seconds" in s.attrs), key=lambda s: s.t0_us)
    got = [s.attrs["seconds"] for s in model]
    starts = [i for i in range(len(got) - len(want) + 1) if got[i:i + len(want)] == want]
    if len(starts) != 1:
        return None
    model = model[starts[0]:starts[0] + len(want)]
    if not all("live_rows" in s.attrs for s in model):
        return None
    t0, t1, loop = model[0].t0_us, model[-1].t1_us, model[0].context.trace_id
    chunks = [s for s in spans if s.name == "serving.decode.prefill"
              and s.context.trace_id == loop and t0 <= s.t0_us <= t1 and "live_rows" in s.attrs]
    size = view["counters"]["prefill_chunk"]
    return {"steps": [(s.attrs["active"], s.attrs["live_rows"]) for s in model],
            "chunks": [(s.attrs["chunk"] * size, s.attrs["live_rows"]) for s in chunks]}


def read(view):
    t, calls = view["trace"], view["counters"].get("loop_calls")
    if not t or not calls or not view["peaks"] or not t.get("busy_s"):
        return None
    found = window_calls(view)
    if not found:
        return None
    peaks = view["peaks"]
    steps = sum(loop_bytes.step_least_seconds(calls, a, rows, peaks) for a, rows in found["steps"])
    chunks = sum(loop_bytes.chunk_least_seconds(calls, p0, rows, peaks)
                 for p0, rows in found["chunks"])
    print(f"looped roofline: least {steps * 1e3:.1f} ms over {len(found['steps'])} steps and "
          f"{chunks * 1e3:.1f} ms over {len(found['chunks'])} chunks; device busy "
          f"{t['busy_s'] * 1e3:.1f} ms", flush=True)
    return 100.0 * (steps + chunks) / t["busy_s"]
