"""Output tokens that landed inside the window (waterfall events of every
request, finished or not) over its seconds. Not an end-to-end metric: in a
window of about one request's life it moves with the order the seed deals."""


def read(view):
    c = view["counters"]
    if "out_tokens" not in c:
        return None
    return c["out_tokens"] / c["window_s"]
