"""Device time of the operations named ``retention_*`` (the decode step's
kernel, and the chunk's once it is one) over device busy time."""


def read(view):
    t = view["trace"]
    if not t or not view["counters"].get("retention_calls"):
        return None
    sec = sum(s for name, s in t["ops"].items() if name.startswith("retention_"))
    return 100.0 * sec / t["busy_s"] if sec and t["busy_s"] else None
