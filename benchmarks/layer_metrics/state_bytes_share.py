"""Share of a decode step's required bytes that is SSM state: the bytes of
state the window's steps say they moved (``ssm_state_bytes_moved`` on the
``serving.decode.model_step`` spans: each active slot's state of each Mamba-2
layer in once and out once) over what those steps had to move in all
(``benchmarks/ssm_bytes.py::step_bytes``: weights, states, convolution tails,
the live K and V rows), summed over the window. Read from the program's own
spans; None where they carry no such counts."""

from benchmarks import loop_spans, ssm_bytes

COUNTS = ("ssm_active_slots", "ssm_state_bytes_moved", "attend_live_pages")


def read(view):
    from paddle_tpu import tracing

    calls, page = view["counters"].get("ssm_calls"), view["counters"].get("page_size")
    if not calls or not page:
        return None
    steps = loop_spans.window_model_steps(view, tracing.spans())
    if not steps or not all(k in s.attrs for s in steps for k in COUNTS):
        return None  # no window, or a program from before the counts
    moved = sum(s.attrs["ssm_state_bytes_moved"] for s in steps)
    needed = sum(ssm_bytes.step_bytes(calls, s.attrs["ssm_active_slots"],
                                      s.attrs["attend_live_pages"] * page) for s in steps)
    return 100.0 * moved / needed if needed else None
