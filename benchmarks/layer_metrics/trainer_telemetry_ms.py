"""What the program's own instrumentation costs a step: median over the
window's steps of ``trainer.record_step`` (registry writes, the run log, the
per-device memory sample, MFU, the straggler watch). The window is found as
``trainer_host_gap_ms`` finds it."""

from benchmarks.layer_metrics import trainer_host_gap_ms as gap


def read(view):
    return gap.median_ms(view, ("trainer.record_step",),
                         lambda step: step["trainer.record_step"].duration_s)
