"""Median, over the first tokens that landed inside the window, of the time
from the client's sending the request (a closed loop's due time) to its first
token. A handful of samples a window today: reported, not bounded."""

from benchmarks import stats


def read(view):
    ttft = view["counters"].get("ttft_seconds")
    return 1e3 * stats.median(ttft) if ttft else None
