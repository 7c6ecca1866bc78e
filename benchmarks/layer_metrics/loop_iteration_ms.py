"""One turn of the serving loop, which is the gap between a request's tokens:
median, over the window's iterations that held a model step, of the time from
the end of the previous ``serving.decode.step`` span to the end of this one.
Read from the program's own spans; the inside twin of ``decode_step_ms``.

The window's iterations are the one contiguous run of
``serving.decode.model_step`` spans whose ``seconds`` attributes equal
``counters["step_seconds"]`` element for element: after the window the engine
keeps turning while the harness reads its trace, so a count from the end would
take the drain for the window. No such run, or more than one, reads None."""

from benchmarks import stats


def window_iterations(view):
    """[(seconds of the turn, seconds of it spent in ``.wait`` spans)] of the
    window's iterations; None where the window cannot be found."""
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
    trace_id = model[0].context.trace_id  # the engine's loop
    loop = [s for s in spans if s.context.trace_id == trace_id]
    turns = sorted((s for s in loop if s.name == "serving.decode.step"), key=lambda s: s.t1_us)
    at = {s.context.span_id: i for i, s in enumerate(turns)}
    waits = [s for s in loop if s.name.endswith(".wait")]
    out = []
    for m in model:
        i = at.get(m.context.parent_id)
        if not i:  # not under a step span, or no turn before it to measure from
            return None
        t0, t1 = turns[i - 1].t1_us, turns[i].t1_us
        blocked = sum(w.t1_us - w.t0_us for w in waits if t0 <= w.t0_us and w.t1_us <= t1)
        out.append(((t1 - t0) / 1e6, blocked / 1e6))
    return out


def read(view):
    turns = window_iterations(view)
    return 1e3 * stats.median([t for t, _ in turns]) if turns else None
