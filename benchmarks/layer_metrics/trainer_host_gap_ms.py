"""Host time of a training step with nothing queued on the device: median, over
the window's steps, of ``trainer.step``'s duration less the stretch from the
start of ``trainer.step_compute`` (the enqueue) to the end of ``trainer.fetch``
(the wait for the loss). Read from the program's own spans, in the process
that ran the window. The inside twin of ``device_idle_share.train`` x step
time, which also holds the enqueue's latency and the gaps inside the program.

The window's steps are the last ``counters["steps"]`` ``trainer.step`` roots in
the span store: every step after the window opened is counted there, and the
run ends with the window. A store that holds fewer, or a step without the
children a metric needs (a program from before these spans), reads None."""

from benchmarks import stats


def window_steps(view, needed):
    """[{child name: span}] of each of the window's steps, with the root under
    ``"trainer.step"``; None where the window cannot be found whole."""
    from paddle_tpu import tracing

    n = view["counters"].get("steps")
    spans = tracing.spans()
    roots = [s for s in spans if s.name == "trainer.step" and s.context.parent_id is None]
    if not n or len(roots) < n:
        return None
    roots = {s.context.span_id: {"trainer.step": s} for s in roots[-n:]}
    for s in spans:
        step = roots.get(s.context.parent_id)
        if step is not None:
            step[s.name] = s
    steps = list(roots.values())
    if any(name not in step for step in steps for name in needed):
        return None
    return steps


def median_ms(view, needed, seconds_of):
    steps = window_steps(view, needed)
    if steps is None:
        return None
    return 1e3 * stats.median([seconds_of(step) for step in steps])


def read(view):
    def gap(step):
        queued = step["trainer.fetch"].t1_us - step["trainer.step_compute"].t0_us
        return step["trainer.step"].duration_s - queued / 1e6

    return median_ms(view, ("trainer.step_compute", "trainer.fetch"), gap)
