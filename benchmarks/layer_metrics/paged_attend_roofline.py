"""Least time the chip could take for the rows the decode steps'
``paged_attend_step`` calls have to read over the kernels' summed device time
(PERF.md, Open question 26 as PR 36 left it). A step's required bytes are the
pages its decoding slots hold rows in (``attend_live_pages`` on the window's
``serving.decode.model_step`` spans) times the bytes of one page over every
plane the step attends and both arrays (``attend_page_bytes`` there: page
size x a K and a V row x planes), over ``peaks.json``'s ``hbm_bytes_per_s``.
The kernel copies a page whole, so a slot's last page counts whole. A share
of the memory roofline only: the block-diagonal matmuls multiply ``H_kv``
times what attention needs, on a matrix unit the copies leave idle. None
where the step attends through the gather (the trace names no such kernel),
the spans carry no such counts (a program from before them), or nothing was
traced."""

import re

STEP_OP = re.compile(r"^paged_attend_step")
COUNTS = ("attend_live_pages", "attend_page_bytes")


def read(view):
    from paddle_tpu import tracing

    from benchmarks import loop_spans

    t, peaks = view["trace"], view["peaks"]
    if not t or not peaks:
        return None
    sec = sum(s for name, s in t["ops"].items() if STEP_OP.search(name))
    model = loop_spans.window_model_steps(view, tracing.spans()) if sec else None
    if not model or not all(k in s.attrs for s in model for k in COUNTS):
        return None
    n_bytes = sum(s.attrs["attend_live_pages"] * s.attrs["attend_page_bytes"] for s in model)
    t_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    print(f"paged_attend_step roofline: {t_bytes * 1e3:.3f} ms of bytes over {len(model)} "
          f"steps ({n_bytes / len(model) / 1e6:.1f} MB a step); kernels {sec * 1e3:.3f} ms",
          flush=True)
    return 100.0 * t_bytes / sec
