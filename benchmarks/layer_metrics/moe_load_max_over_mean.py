"""How unevenly the router loads the experts held here: mean, over the
window's decode steps, of the most tokens any held expert took in any layer
(``moe_max_load`` on the ``serving.decode.model_step`` span) over the mean
load of the held experts in that step (``moe_pairs`` over experts held times
expert layers). 1 is an even spread. Read from the program's own spans; None
where they carry no such counts."""

from benchmarks.moe_spans import window_calls


def read(view):
    calls = view["counters"].get("moe_calls")
    found = window_calls(view) if calls else None
    if not found:
        return None
    ratios = [s["moe_max_load"] * calls["held"] * calls["layers"] / s["moe_pairs"]
              for s in found["steps"] if s["moe_pairs"]]
    return sum(ratios) / len(ratios) if ratios else None
