"""Mean decoding slots over ``max_slots`` per decode step in the window."""


def read(view):
    occ = view["counters"].get("step_occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
