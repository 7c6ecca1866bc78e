"""What the program's own bookings cost a serving turn: median over the
window's turns of ``telemetry_seconds`` on the turn's ``serving.decode.step``
span (registry writes, the waterfall, the cost model, the gauges of
``.publish``, timed by the engine in the few stretches it gathers them into).
The serving twin of ``trainer_telemetry_ms``; the window is found by
``loop_spans``. A program whose spans lack the attribute reads None."""

from benchmarks import loop_spans


def read(view):
    return loop_spans.median_ms(view, lambda turn: turn.step.attrs.get("telemetry_seconds"))
