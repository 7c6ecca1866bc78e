"""Median seconds of the engine's ``record_step`` calls inside the window. The
step ends in a host sync and the prefill chunk enqueued before it does not, so
this is one iteration of the loop: the chunk's device time is inside it."""

from benchmarks import stats


def read(view):
    steps = view["counters"].get("step_seconds")
    return 1e3 * stats.median(steps) if steps else None
