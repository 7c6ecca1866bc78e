"""The serving loop's turns of the measured window, found once from the
program's own spans, for every per-layer reader that reads a turn.

A turn is what ``loop_iteration_ms`` measures: from the close of the
``serving.decode.step`` span before to the close of this one, so it holds the
last turn's ``serving.decode.publish``, this turn's ``serving.decode.admit``
and the ``serving.decode.step`` with everything under it. The window's turns
are those of the one contiguous run of ``serving.decode.model_step`` spans
whose ``seconds`` attributes equal ``counters["step_seconds"]`` element for
element (after the window the engine keeps turning while the harness reads its
trace, so a count from the end would take the drain for the window). No such
run, or more than one, or a step without a turn before it: None.

``loop_iteration_ms.window_iterations``, ``moe_spans.window_calls`` and
``looped_hbm_roofline`` each make this search themselves; they are the
accepted benchmark's and stay as they are until a ``benchmark`` PR points them
here (``tests/benchmarks/test_loop_readers.py`` holds this one equal to the
first, turn for turn)."""

from __future__ import annotations

import bisect
from typing import List, NamedTuple, Optional

from benchmarks import stats


class Turn(NamedTuple):
    step: object          # the ``serving.decode.step`` span that ends the turn
    model_step: object    # the ``serving.decode.model_step`` span it holds
    t0_us: float          # the close of the step span before: where the turn starts
    inside: list          # the loop's spans that lie in the turn, by start

    @property
    def seconds(self) -> float:
        return (self.step.t1_us - self.t0_us) / 1e6

    def seconds_in(self, *names: str, suffix: str = "") -> float:
        """Summed durations of the turn's spans of these names, or, with
        ``suffix``, of every span whose name ends so."""
        return sum(s.t1_us - s.t0_us for s in self.inside
                   if s.name in names or (suffix and s.name.endswith(suffix))) / 1e6

    @property
    def wait_seconds(self) -> float:
        """Blocked on the device: the ``.wait`` spans."""
        return self.seconds_in(suffix=".wait")

    @property
    def host_seconds(self) -> float:
        """``loop_host_ms``'s stretch: the turn less its ``.wait`` spans."""
        return self.seconds - self.wait_seconds


def window_model_steps(view, spans) -> Optional[list]:
    """The window's ``serving.decode.model_step`` spans, in order."""
    want = view["counters"].get("step_seconds")
    if not want:
        return None
    model = sorted((s for s in spans if s.name == "serving.decode.model_step"
                    and "seconds" in s.attrs), key=lambda s: s.t0_us)
    got = [s.attrs["seconds"] for s in model]
    starts = [i for i in range(len(got) - len(want) + 1) if got[i:i + len(want)] == want]
    if len(starts) != 1:
        return None
    return model[starts[0]:starts[0] + len(want)]


def turns(spans) -> List[Turn]:
    """Every turn among ``spans`` that held a model step and has a turn
    before it, of any engine's loop (a loop is one trace), loop by loop in
    the order the turns ended."""
    model = {s.context.parent_id: s for s in spans if s.name == "serving.decode.model_step"}
    loops = {}
    for s in spans:
        if s.t1_us is not None:
            loops.setdefault(s.context.trace_id, []).append(s)
    out = []
    for loop in loops.values():
        steps = sorted((s for s in loop if s.name == "serving.decode.step"), key=lambda s: s.t1_us)
        if len(steps) < 2:
            continue
        loop.sort(key=lambda s: s.t0_us)
        starts = [s.t0_us for s in loop]
        for before, step in zip(steps, steps[1:]):
            if step.context.span_id in model:
                t0, t1 = before.t1_us, step.t1_us
                inside = [s for s in loop[bisect.bisect_left(starts, t0):
                                          bisect.bisect_right(starts, t1)] if s.t1_us <= t1]
                out.append(Turn(step, model[step.context.span_id], t0, inside))
    return out


def window_turns(view) -> Optional[List[Turn]]:
    """The window's turns, one a model step of the window; None where the
    window cannot be found, or one of its steps is not under a step span or
    has no turn before it to measure from."""
    from paddle_tpu import tracing

    spans = tracing.spans()
    model = window_model_steps(view, spans)
    if model is None:
        return None
    trace_id = model[0].context.trace_id  # the engine's loop
    by_step = {id(t.model_step): t for t in turns(
        [s for s in spans if s.context.trace_id == trace_id])}
    found = [by_step.get(id(m)) for m in model]
    return None if any(t is None for t in found) else found


def median_ms(view, seconds_of) -> Optional[float]:
    """Median over the window's turns of ``seconds_of(turn)``, in ms; None
    where the window cannot be found or ``seconds_of`` finds nothing to read
    in some turn (it returns None: a program from before the attribute)."""
    turns = window_turns(view)
    if not turns:
        return None
    values = [seconds_of(t) for t in turns]
    if any(v is None for v in values):
        return None
    return 1e3 * stats.median(values)
