"""Time of one turn of the serving loop in which the loop thread was not
blocked on the device: the stretch ``loop_iteration_ms`` measures less the
``.wait`` spans inside it (``serving.decode.model_step.wait``,
``serving.decode.prefill.wait``); median over the window's iterations. The
inside twin of ``serve_host_share``."""

from benchmarks import stats
from benchmarks.layer_metrics import loop_iteration_ms as loop


def read(view):
    turns = loop.window_iterations(view)
    return 1e3 * stats.median([t - w for t, w in turns]) if turns else None
