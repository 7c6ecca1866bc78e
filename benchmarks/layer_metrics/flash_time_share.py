"""The flash attention kernels' device time over device busy time."""

from benchmarks.layer_metrics import flash_roofline as fr


def read(view):
    t = view["trace"]
    if not t or not view["counters"].get("flash_calls"):
        return None
    sec = fr.flash_seconds(t)
    return 100.0 * sec / t["busy_s"] if sec else None
