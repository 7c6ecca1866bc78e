"""What the two span readers of the expert layer share: the window's decode
steps and prefill chunks with the counts their spans carry."""

from __future__ import annotations

COUNTS = ("moe_pairs", "moe_experts_hit", "moe_max_load")


def window_calls(view):
    """{"steps": [...], "chunks": [...]}, each the ``moe_*`` attributes of a
    ``serving.decode.model_step`` or ``serving.decode.prefill`` span of the
    window: the steps are the run ``loop_iteration_ms`` finds, the chunks the
    loop's own between the first of them and the last. None where the window
    cannot be found or its steps carry no counts."""
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
    if not all(k in s.attrs for s in model for k in COUNTS):
        return None
    t0, t1, loop = model[0].t0_us, model[-1].t1_us, model[0].context.trace_id
    chunks = [s for s in spans if s.name == "serving.decode.prefill"
              and s.context.trace_id == loop and t0 <= s.t0_us <= t1 and "moe_pairs" in s.attrs]
    pick = lambda s: {k: s.attrs[k] for k in COUNTS}
    return {"steps": [pick(s) for s in model], "chunks": [pick(s) for s in chunks]}

