"""Share of the window the serving loop spent outside its model calls:
1 - (summed step and prefill-chunk seconds) / window."""


def read(view):
    c = view["counters"]
    if "step_seconds" not in c:
        return None
    inside = sum(c["step_seconds"]) + sum(c["chunk_seconds"])
    return 100.0 * (1.0 - inside / c["window_s"])
