"""What it costs the host to hand the device a step: median over the window's
steps of ``trainer.h2d`` (the batch to device arrays) plus
``trainer.step_compute`` (the enqueue of the step's one program). The window is
found as ``trainer_host_gap_ms`` finds it."""

from benchmarks.layer_metrics import trainer_host_gap_ms as gap


def read(view):
    return gap.median_ms(
        view, ("trainer.h2d", "trainer.step_compute"),
        lambda step: step["trainer.h2d"].duration_s + step["trainer.step_compute"].duration_s)
