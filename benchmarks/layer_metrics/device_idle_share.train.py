"""1 - (union of device-op intervals) / traced window, averaged over chips."""


def read(view):
    t = view["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
