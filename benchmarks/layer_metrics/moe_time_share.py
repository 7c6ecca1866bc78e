"""Device time of the operations named ``moe_gmm*`` (the expert layer's
grouped matmul kernel) over device busy time. None where the cell runs no
expert layer or the trace names no such kernel."""


def read(view):
    t = view["trace"]
    if not t or not view["counters"].get("moe_calls"):
        return None
    sec = sum(s for name, s in t["ops"].items() if name.startswith("moe_gmm"))
    return 100.0 * sec / t["busy_s"] if sec and t["busy_s"] else None
