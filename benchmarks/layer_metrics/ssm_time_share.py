"""Device time of the operations named ``ssm_*`` (the decode step's kernel,
and the chunk's scan once it is one) over device busy time."""


def read(view):
    t = view["trace"]
    if not t or not view["counters"].get("ssm_calls"):
        return None
    sec = sum(s for name, s in t["ops"].items() if name.startswith("ssm_"))
    return 100.0 * sec / t["busy_s"] if sec and t["busy_s"] else None
