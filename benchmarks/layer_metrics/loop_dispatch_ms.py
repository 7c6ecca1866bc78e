"""What handing the device a turn's programs costs the serving host: median
over the window's turns of the durations, summed a turn, of
``serving.decode.model_step.dispatch`` (the enqueue of the step's one program)
and of the loop's own ``serving.decode.prefill`` spans (packing and enqueuing
the chunk behind the step; nothing in them waits). The serving twin of
``trainer_dispatch_ms``; the window is found by ``loop_spans``."""

from benchmarks import loop_spans


def read(view):
    return loop_spans.median_ms(view, lambda turn: turn.seconds_in(
        "serving.decode.model_step.dispatch", "serving.decode.prefill"))
