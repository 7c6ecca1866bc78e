"""Time of a serving turn in which the loop thread neither waited for the
device nor ran: the median turn's host part (``loop_host_ms``'s stretch, the
turn less its ``.wait`` spans) times the share of the window's host time that
the thread was off the CPU, ``1 - sum(cpu_seconds) / sum(host parts)`` floored
at 0, with ``cpu_seconds`` the thread's CPU time over the turn as the engine
put it on the turn's ``serving.decode.step`` span. What is left is the
interpreter lock in another thread's hands, a named lock, or the scheduler.

The share is taken over the window and not a turn at a time because a host
may tick its thread clocks coarsely: the chip's host advances
``time.thread_time()`` in steps of 10 ms, twice a turn of 5 ms, so a single
turn reads 0 or 10 ms and only the sum over the window is a measurement (it
is off by one tick at most). The window is found by ``loop_spans``. A program
whose spans lack the attribute reads None."""

from benchmarks import loop_spans, stats


def read(view):
    turns = loop_spans.window_turns(view)
    if not turns or any("cpu_seconds" not in t.step.attrs for t in turns):
        return None
    host = [t.host_seconds for t in turns]
    cpu = sum(t.step.attrs["cpu_seconds"] for t in turns)
    return 1e3 * stats.median(host) * max(0.0, 1.0 - cpu / sum(host))
